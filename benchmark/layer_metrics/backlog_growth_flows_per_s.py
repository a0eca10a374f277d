"""Open loop: growth of (flows offered - flows fetched) from one edge of
the window to the other, per second. Backlog cells: how fast the lag
shrinks, as a negative growth. Source: bus fetch positions."""


def read(run):
    (t_a, pos_a), (t_b, pos_b) = run.rate_edges
    took = (pos_b - pos_a) / (t_b - t_a)
    offered = run.plan.rate if run.plan.mode == "open_loop" else 0.0
    return offered - took
