"""Device time of one fused step under the spread detectors' register
updates (the scopes spread_regs_<detector>, both summed: the element
hashes and the scatter-max into each detector's flat register plane):
median over the step's executions in the traced window. Source: profiler
trace, XLA Ops self times by scope (spread_scopes.py). A program whose
step holds no spread detector reads nothing."""

from benchmark import spread_scopes


def read(run):
    return spread_scopes.scope_ms_p50(run, "spread_regs_")
