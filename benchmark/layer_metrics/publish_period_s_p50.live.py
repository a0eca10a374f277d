"""The publish sawtooth's true period: start to start of consecutive
snapshot_publish spans in the window, in seconds; median. Half of it is the
mean age the cadence alone adds to what a reader sees. Source: the
program's snapshot_publish span."""

import statistics

from benchmark import program_spans


def read(run):
    w = program_spans.window(run)
    starts = sorted(s[1] for s in (w.named("snapshot_publish") if w
                                   else []))
    return (statistics.median(b - a for a, b in zip(starts, starts[1:]))
            if len(starts) > 1 else None)
