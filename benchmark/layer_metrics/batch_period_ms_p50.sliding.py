"""Start-to-start interval of consecutive batches in the dispatch loop: median.
Against step_device_ms_p50.sliding it says whether slides stretch the common
batch (they should not: a slide is one batch in ~58). The reader is
batch_period_ms_p50's own."""

from benchmark.layer_metrics.batch_period_ms_p50 import read  # noqa: F401
