"""Host bytes one publish captures (the families' rows and the count-min
planes' device->host copy; under a mesh the stacked per-chip planes), in MB:
median over the window's publishes. Source: the program's publish_view span
[bytes], summed a snapshot_publish."""

import statistics

from benchmark import inside_spans


def read(run):
    sums = inside_spans.per_publish(run, "publish_view",
                                    lambda s: s[5].get("bytes", 0) / 1e6)
    return statistics.median(sums) if sums else None
