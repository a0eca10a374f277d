"""Seconds a minute the dispatch loop spends in flush_closed (when it
emitted) and snapshot_and_commit: what the live tail is made of."""

from benchmark import reduce


def read(run):
    ms = sum(reduce.window_ms(run, "flush_closed", bool)) \
        + sum(reduce.window_ms(run, "snapshot_and_commit"))
    return ms / 1e3 / reduce.window_minutes(run)
