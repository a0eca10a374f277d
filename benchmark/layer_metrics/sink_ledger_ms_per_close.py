"""The serve surface's RangeLedger as a sink (it keeps the closed flows_5m
rows for /query/range), summed over the tables one chunk flushed; median
over the window's chunks that flushed. Source: the program's sink_put span
[sink = RangeLedger]."""

from benchmark import inside_spans


def read(run):
    return inside_spans.flush_ms_per_chunk(
        run, "sink_put", lambda s: s[5].get("sink") == "RangeLedger")
