"""Time-weighted mean of the staleness function over the window: steadier
than a quantile, beside the end-to-end median."""

from benchmark import reduce, staleness


def read(run):
    ps = reduce.staleness_pieces(run)
    return None if ps is None else staleness.mean(ps)
