"""Device ms a global batch, on one chip (mean over the chips), in
the three heavy-hitter families' update programs (mesh_hh_update_<model>: CMS update, prefilter, admission merge).
Source: profiler trace, XLA Modules by program name (mesh_trace.py)."""

from benchmark import mesh_trace


def read(run):
    return mesh_trace.family_ms_per_batch(run, "hh")
