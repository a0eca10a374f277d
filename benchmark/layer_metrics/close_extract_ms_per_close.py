"""A tumbling close's extraction on the worker thread, at the roll inside the
pipeline's update: the window_close spans of the ranked tables that share a
slot (model.top(k), five tables; the first waits for the step in flight),
summed; median over the window's closes. The twin of slide_close_ms_p50.
Source: the program's window_close span [model, slot, rows]."""

from benchmark import inside_spans, slide_spans


def read(run):
    return slide_spans.p50_per_slide(run, "window_close", "slot",
                                     inside_spans.ms)
