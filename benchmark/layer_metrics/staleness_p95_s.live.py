"""The same function of time as query_staleness_p50_s; the value it is
under for 95 % of the window's time. Per-layer, not end to end: over 51 s
it is made of a dozen stall episodes and repeats within ~10 %, which no
bound the contract allows can hold (PERF.md, PR 23)."""

from benchmark import reduce, staleness


def read(run):
    ps = reduce.staleness_pieces(run)
    return None if ps is None else staleness.quantile(ps, 0.95)
