"""Device time of one fused step under the kernel scope hh_chain_sort (the
nested-family sort: lax.sort over the chained hash lanes, its gathers and
the per-level hash grouping): median over the step's executions in the
traced window. Source: profiler trace, XLA Ops self times by scope
(kernel_scopes.py)."""

from benchmark import kernel_scopes


def read(run):
    return kernel_scopes.scope_ms_p50(run, "hh_chain_sort")
