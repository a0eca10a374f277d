"""Device ms a global batch, on one chip (mean over the chips), in
the two port tables' update programs (mesh_dense_update_<model>: the dense scatters).
Source: profiler trace, XLA Modules by program name (mesh_trace.py)."""

from benchmark import mesh_trace


def read(run):
    return mesh_trace.family_ms_per_batch(run, "dense")
