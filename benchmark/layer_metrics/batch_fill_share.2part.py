"""Rows over padded rows of the window's device steps, in per cent: a late
group is a whole padded step for a few hundred to a few thousand rows, so
this falls as device_steps_per_batch.2part rises. The reader is
batch_fill_share's own."""

from benchmark.layer_metrics.batch_fill_share import read  # noqa: F401
