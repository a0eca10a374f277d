"""Device time of one execution of the fused step (estate-catchup's shapes and
program: a late group runs the same step with a family's held state in the
tuple): median over the traced window, over full and late steps alike.
The reader is step_device_ms_p50's own."""

from benchmark.layer_metrics.step_device_ms_p50 import read  # noqa: F401
