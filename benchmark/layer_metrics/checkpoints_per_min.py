"""snapshot_and_commit calls per minute of the window: tied to the batch
count (-flush.count) and to every flush that emitted rows."""

from benchmark import reduce


def read(run):
    return len(run.in_window("snapshot_and_commit")) \
        / reduce.window_minutes(run)
