"""Device time of one fused step under the kernel scope dst_sort (the shared
dst-keyed hash sort, its segment sums and what the top-dst-IP sketch and
the detector take from it): median over the step's executions in the traced
window. Source: profiler trace, XLA Ops self times by scope
(kernel_scopes.py)."""

from benchmark import kernel_scopes


def read(run):
    return kernel_scopes.scope_ms_p50(run, "dst_sort")
