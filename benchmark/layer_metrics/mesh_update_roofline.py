"""The least time one chip's update programs of one global step could take
(HBM bytes from shapes, mesh_roofline.py, over the chip's HBM peak) over
their measured device time (one execution of each, summed), in per cent.
Source: profiler trace, XLA Modules by program name."""

from benchmark import mesh_roofline, mesh_trace


def read(run):
    ms = mesh_trace.execution_ms(run, mesh_trace.is_update)
    if not ms:
        return None
    least_s, _bound = mesh_roofline.update_least_seconds(
        run.cell.config, run.device["kind"])
    return 100.0 * least_s / (ms / 1e3)
