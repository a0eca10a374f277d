"""How long a decoded batch waits in the prefetch queue before the dispatch
loop picks it up: by chunk, the end of its decode span to the start of its
apply span; median. Part of a flow's age in the snapshot that first holds
it. Source: the program's decode and apply spans (one chunk id)."""

import statistics

from benchmark import inside_spans


def read(run):
    waits = inside_spans.queue_wait_ms(run)
    return statistics.median(waits) if waits else None
