"""Rows whose address lanes hold an IPv4 address (etype 0x0800, left-padded
into the four lanes) over the rows built into device lanes, in per cent,
over the window. Source: lane_build's v4_rows and rows; a program whose
lane_build does not say reads nothing."""

from benchmark import program_spans


def read(run):
    w = program_spans.window(run)
    spans = [s[5] for s in w.named("lane_build")
             if "v4_rows" in s[5]] if w else []
    rows = sum(a["rows"] for a in spans)
    return 100.0 * sum(a["v4_rows"] for a in spans) / rows if rows else None
