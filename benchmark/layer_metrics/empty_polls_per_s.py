"""Bus fetches per second that found nothing to take. Backlog cells: 0
unless the generator fell behind the worker. Source: span."""


def read(run):
    empty = sum(1 for s in run.in_window("bus_fetch") if s[4] is None)
    return empty / (run.t_b - run.t_a)
