"""Device time of one execution of the fused step (estate-catchup's shapes:
the step holds one sub-window's state, the ring is not in it): median over
the traced window. Source: profiler trace, device plane, as
step_device_ms_p50 reads it."""

from benchmark import reduce


def read(run):
    return None if run.trace is None else reduce.p50(run.trace.step_ms)
