"""The fused step's least possible time over its measured device time, in
per cent, for a configuration whose ranked families and sketch width are
its own: bytes from shapes (backbone_roofline.py: every family the flags
build, the shared sort's members, a count-min row of -sketch.width
cells), peaks from roofline.py's table keyed by device_kind. Source:
profiler trace."""

from benchmark import backbone_roofline, reduce


def read(run):
    if run.trace is None or not run.trace.step_ms:
        return None
    least_s, _bound = backbone_roofline.hh_step_least_seconds(
        run.cell.config, run.device["kind"])
    return 100.0 * least_s / (reduce.p50(run.trace.step_ms) / 1e3)
