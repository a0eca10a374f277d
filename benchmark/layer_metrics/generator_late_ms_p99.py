"""How late the benchmark's own generator handed flows over: actual
minus scheduled time of each hand-over tick in the window, 99th
percentile. Source: the generator's tick log (host clock)."""

from benchmark import reduce


def read(run):
    late = [(actual - sched) * 1e3 for sched, actual, _n in run.ticks
            if run.t_a <= sched < run.t_b]
    return reduce.quantile(late, 0.99)
