"""Rows over padded rows of the window's device steps, in per cent: what
share of each padded batch was flows. Source: step_dispatch's rows and
padded."""

from benchmark import program_spans


def read(run):
    w = program_spans.window(run)
    padded = sum(w.args("step_dispatch", "padded")) if w else 0
    return (100.0 * sum(w.args("step_dispatch", "rows")) / padded
            if padded else None)
