"""The least time one slide's fold programs could take (HBM bytes from shapes,
slide_roofline.py: K states read, one written, a table) over their measured
device time (one execution of each, summed), in per cent. Source: profiler
trace, XLA Modules by program name."""

from benchmark import slide_roofline, slide_trace


def read(run):
    ms = slide_trace.fold_ms(run)
    if not ms:
        return None
    least_s, _bound = slide_roofline.fold_least_seconds(
        run.cell.config, run.device["kind"])
    return 100.0 * least_s / (ms / 1e3)
