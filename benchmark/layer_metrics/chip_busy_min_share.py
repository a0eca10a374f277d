"""The least busy chip's busy time over the busiest chip's, in the traced
part of the window, in per cent. Source: profiler trace, the union of each
device plane's XLA Ops (mesh_trace.py)."""

from benchmark import mesh_trace


def read(run):
    busy = mesh_trace.chip_busy_s(run)
    return 100.0 * min(busy) / max(busy) if busy and max(busy) else None
