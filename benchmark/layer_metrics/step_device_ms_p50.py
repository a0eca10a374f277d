"""Device time of one execution of the step program (the fused step, or
under a mesh the sharded updates of one batch): median over the traced
window. In the as64k and the sliding cell the program and its shapes are
estate-catchup's: the AS labels change no shape, and under a slide the
step holds one sub-window's state (the ring is not in it). Source:
profiler trace, device plane."""

from benchmark import reduce


def read(run):
    return None if run.trace is None else reduce.p50(run.trace.step_ms)
