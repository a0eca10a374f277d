"""Wall time from the roll that held a unit to its deferred close: median
over the window's held_close spans (the tables' windows and the detector's
sub-windows alike; -window.lateness of event time at the rate the backlog
drains). Source: held_close's held_ms; a program without the span reads
nothing."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_arg(run, "held_close", "held_ms")
