"""Freezing the ranked families for one publish: m.top(depth) (the first
blocks on the step in flight) and the capture of the count-min planes, the
publish_view spans of one snapshot_publish summed; median over the window's
publishes. Source: the program's publish_view span [model, rows, bytes]."""

import statistics

from benchmark import inside_spans


def read(run):
    sums = inside_spans.per_publish(run, "publish_view", inside_spans.ms)
    return statistics.median(sums) if sums else None
