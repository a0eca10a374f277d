"""One slide's folds and extractions on the worker thread: the slide_close
spans of the ranked tables that share a window end, summed; median over the
window's slides. Source: the program's slide_close span [window_end]."""

from benchmark import slide_spans


def read(run):
    return slide_spans.p50_per_slide(
        run, "slide_close", "window_end", lambda s: (s[2] - s[1]) * 1e3)
