"""Time-weighted median over the window of: now minus the time the newest
flow in the visible snapshot was due at the generator (staleness.py)."""

from benchmark import reduce, staleness


def read(run):
    ps = reduce.staleness_pieces(run)
    return None if ps is None else staleness.quantile(ps, 0.5)
