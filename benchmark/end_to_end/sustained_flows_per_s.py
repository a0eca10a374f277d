"""Flows the worker took from the bus per second of the window, read at
the bus (the fetch position) between two fetches: the first that returns
at or after each edge of the window. Nothing is cut mid-batch, and right
after a fetch the bus holds only what the worker has not kept up with.
Backlog cells: capacity. Open-loop cells: the offered rate for as long as
the system keeps up; it falls when the backlog grows over the window."""


def read(run):
    (t_a, pos_a), (t_b, pos_b) = run.rate_edges
    return (pos_b - pos_a) / (t_b - t_a)
