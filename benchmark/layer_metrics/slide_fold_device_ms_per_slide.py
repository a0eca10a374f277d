"""Device ms of one slide's fold programs: the sum over slide_fold_<table> of
one execution (the median over its executions; a publish's kept fold runs
the same programs). Source: profiler trace, XLA Modules by program name."""

from benchmark import slide_trace


def read(run):
    return slide_trace.fold_ms(run)
