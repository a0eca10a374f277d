"""Native frame decode (FlowBatch.from_wire on the prefetch thread):
microseconds per thousand flows, over the window. Source: span."""


def read(run):
    spans = run.in_window("decode")
    rows = sum(s[4] for s in spans)
    return None if not rows else sum(s[2] - s[1] for s in spans) * 1e9 / rows
