"""The fused step's least possible time over its measured device time, in per
cent. The step's bytes are one sub-window's state, not ten: the ring never
enters the step, so roofline.py's bytes serve unchanged. The reader is
fused_step_roofline's own."""

from benchmark.layer_metrics.fused_step_roofline import read  # noqa: F401
