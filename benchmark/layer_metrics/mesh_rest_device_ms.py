"""Device ms a global batch, on one chip, outside the four update families:
the merges of closes and publishes, top-K extraction, resets, slices and
whatever carries no mesh name. With the four mesh_*_update_ms it sums to
the programs' device time a batch. Source: profiler trace, XLA Modules by
program name (mesh_trace.py)."""

from benchmark import mesh_trace


def read(run):
    return mesh_trace.rest_ms_per_batch(run)
