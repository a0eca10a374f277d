"""Share of the window's backlog still on the bus when the window closed: at
share s the cell holds 1 / (1 - s / 100) times the rate it ran at. The
reader is backlog_left_share's own."""

from benchmark.layer_metrics.backlog_left_share import read  # noqa: F401
