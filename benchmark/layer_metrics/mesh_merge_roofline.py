"""The least time one close's merge programs could take on a chip (bytes
over ICI and over HBM from shapes, mesh_roofline.py; the slower path
bounds) over their measured device time (one execution of each, summed),
in per cent. Source: profiler trace, XLA Modules by program name."""

from benchmark import mesh_roofline, mesh_trace


def read(run):
    ms = mesh_trace.execution_ms(run, mesh_trace.is_merge)
    if not ms:
        return None
    least_s, _bound = mesh_roofline.merge_least_seconds(
        run.cell.config, run.device["kind"])
    return 100.0 * least_s / (ms / 1e3)
