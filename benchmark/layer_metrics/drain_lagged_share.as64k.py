"""Share of the window's flows_5m drains that left the newest partial on the
device, in per cent. The reader is drain_lagged_share's own."""

from benchmark.layer_metrics.drain_lagged_share import read  # noqa: F401
