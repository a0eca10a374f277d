"""The fused step's least possible time over its measured device time, in
per cent. Bytes and operations come from shapes (roofline.py), peaks from
the table there keyed by device_kind. Source: profiler trace."""

from benchmark import reduce, roofline


def read(run):
    if run.trace is None or not run.trace.step_ms:
        return None
    least_s, _bound = roofline.fused_step_least_seconds(
        run.cell.config, run.device["kind"])
    return 100.0 * least_s / (reduce.p50(run.trace.step_ms) / 1e3)
