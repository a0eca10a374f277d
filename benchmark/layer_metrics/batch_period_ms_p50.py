"""Start-to-start interval of consecutive batches in the dispatch loop:
median. Against step_device_ms_p50 x device_steps_per_batch it says whether
the loop runs at the device step's pace (the host's work hidden under the
step) or adds its own to every batch. In the as64k cell that is what the
host fold of ~9x10^3 groups adds; in the sliding cell whether slides
stretch the common batch (they should not: a slide is one batch in ~58).
Source: the program's apply spans on the worker thread."""

import statistics

from benchmark import program_spans


def read(run):
    w = program_spans.window(run)
    if not w:
        return None
    thread = w.worker_thread()
    starts = sorted(s[1] for s in w.named("apply") if s[3] == thread)
    periods = [(b - a) * 1e3 for a, b in zip(starts, starts[1:])]
    return statistics.median(periods) if periods else None
