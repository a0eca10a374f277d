"""Device time of one execution of the step program (the fused step, or
under a mesh the sharded updates of one batch): median over the traced
window. Source: profiler trace, device plane."""

from benchmark import reduce


def read(run):
    return None if run.trace is None else reduce.p50(run.trace.step_ms)
