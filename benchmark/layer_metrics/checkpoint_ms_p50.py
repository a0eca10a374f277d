"""StreamWorker.snapshot_and_commit (state to disk, then offsets):
median duration in the window. Source: span."""

from benchmark import reduce


def read(run):
    return reduce.p50(reduce.window_ms(run, "snapshot_and_commit"))
