"""The fused step's least possible time over its measured device time, in per
cent. No kernel is new: the held state has the open one's shapes, so
roofline.py's bytes serve unchanged. The reader is fused_step_roofline's
own."""

from benchmark.layer_metrics.fused_step_roofline import read  # noqa: F401
