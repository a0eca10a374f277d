"""Start-to-start interval of consecutive batches in the dispatch loop: median.
Against step_device_ms_p50.2part x device_steps_per_batch.2part it says
whether the device still sets the pace when a third of the batches run two
steps. The reader is batch_period_ms_p50's own."""

from benchmark.layer_metrics.batch_period_ms_p50 import read  # noqa: F401
