"""Event-time latency of a window's rows: from the moment the last flow
of the closed slot was due at the generator to the end of the flush that
wrote its rows. Median over the closes in the window, each matched to the
slot roll it follows. Source: spans + the generator's schedule."""

from benchmark import reduce


def read(run):
    if run.t0_schedule is None:
        return None
    lo = run.plan.window_start_flow
    rolls = [run.due(c - 1) for c in
             run.spec.close_flows(lo, lo + run.plan.window_flows)]
    out = []
    for f in reduce.close_spans(run):
        before = [t for t in rolls if t <= f[1]]
        if before:
            out.append(f[2] - before[-1])
    return reduce.p50(out)
