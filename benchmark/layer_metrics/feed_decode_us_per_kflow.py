"""Native frame decode as the program times it on the feed thread:
microseconds per thousand flows, over the window. The inside twin of
decode_us_per_kflow. Source: the program's decode span [rows]."""

from benchmark import program_spans


def read(run):
    w = program_spans.window(run)
    spans = w.named("decode") if w else []
    rows = sum(s[5].get("rows", 0) for s in spans)
    return None if not rows else sum(s[2] - s[1] for s in spans) * 1e9 / rows
