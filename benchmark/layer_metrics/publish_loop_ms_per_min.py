"""What the serve publishes take from the dispatch loop: total time inside
snapshot_publish per minute of window (read beside checkpoint_ms_p50 x
checkpoints_per_min). Source: the program's snapshot_publish span."""

from benchmark import program_spans, reduce


def read(run):
    w = program_spans.window(run)
    spent = w.ms("snapshot_publish") if w else []
    return sum(spent) / reduce.window_minutes(run) if spent else None
