"""StreamWorker._flush_closed calls in the window that closed a slot
(window extraction + sink writes, inline in the dispatch loop): median
duration. Source: span."""

from benchmark import reduce


def read(run):
    return reduce.p50(reduce.span_ms(reduce.close_spans(run)))
