"""Device steps run for one polled batch on two partitions with delay: after
each 10 s sub-window boundary, for the few seconds of event time in which
late flows still come, a poll holds rows of two sub-windows and is cut into
two groups, each a padded step (the late one against the held state). The
reader is device_steps_per_batch's own."""

from benchmark.layer_metrics.device_steps_per_batch import read  # noqa: F401
