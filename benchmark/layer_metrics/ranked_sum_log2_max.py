"""log2 of the largest sum a ranked table emitted at a close of the window:
past 24 a float32 plane no longer holds every integer, so the sums that
are ranked carry a relative rounding error. Source: window_close's
bytes_max; a program whose window_close does not say reads nothing."""

import math

from benchmark import program_spans


def read(run):
    w = program_spans.window(run)
    largest = max(w.args("window_close", "bytes_max"), default=0) if w else 0
    return math.log2(largest) if largest > 0 else None
