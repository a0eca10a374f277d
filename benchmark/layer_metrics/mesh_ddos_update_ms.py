"""Device ms a global batch, on one chip (mean over the chips), in
the detector's programs (mesh_ddos_update, and mesh_ddos_close at each sub-window).
Source: profiler trace, XLA Modules by program name (mesh_trace.py)."""

from benchmark import mesh_trace


def read(run):
    return mesh_trace.family_ms_per_batch(run, "ddos")
