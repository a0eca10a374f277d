"""Rows one slide extracts for the sinks, all ranked tables together: median
over the window's slides. Source: slide_close [window_end, rows]."""

from benchmark import slide_spans


def read(run):
    return slide_spans.p50_per_slide(
        run, "slide_close", "window_end", lambda s: s[5].get("rows", 0))
