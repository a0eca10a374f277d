"""Bytes of the arrays one checkpoint serializes, in MB: median. A checkpoint
taken while a unit is held carries the held state beside the open one (the
detector holds a sub-window 7 s in every 10, the tables a window 7 s in
300). The reader is checkpoint_raw_mb_p50's own."""

from benchmark.layer_metrics.checkpoint_raw_mb_p50 import read  # noqa: F401
