"""Device ms a global batch, on one chip (mean over the chips), in
the flows_5m partial's program (mesh_wagg_update: the per-chip group-by).
Source: profiler trace, XLA Modules by program name (mesh_trace.py)."""

from benchmark import mesh_trace


def read(run):
    return mesh_trace.family_ms_per_batch(run, "wagg")
