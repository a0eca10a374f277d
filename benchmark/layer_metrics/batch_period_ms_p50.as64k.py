"""Start-to-start interval of consecutive batches in the dispatch loop: median.
Against step_device_ms_p50.as64k it says what the host fold adds to every
batch. The reader is batch_period_ms_p50's own."""

from benchmark.layer_metrics.batch_period_ms_p50 import read  # noqa: F401
