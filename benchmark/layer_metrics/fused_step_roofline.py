"""The fused step's least possible time over its measured device time, in
per cent. Bytes and operations come from shapes (roofline.py), peaks from
the table there keyed by device_kind. Source: profiler trace. In the
as64k and the sliding cell no kernel is new and roofline.py's bytes serve
unchanged: the AS labels change no shape, and the step's bytes are one
sub-window's state, not ten (the ring never enters the step)."""

from benchmark import reduce, roofline


def read(run):
    if run.trace is None or not run.trace.step_ms:
        return None
    least_s, _bound = roofline.fused_step_least_seconds(
        run.cell.config, run.device["kind"])
    return 100.0 * least_s / (reduce.p50(run.trace.step_ms) / 1e3)
