"""Device time of one fused step under the kernel scope hh_table_merge (the
heavy-hitter table merges (CMS update, prefilter, admission merge), all
families summed): median over the step's executions in the traced window.
Source: profiler trace, XLA Ops self times by scope (kernel_scopes.py)."""

from benchmark import kernel_scopes


def read(run):
    return kernel_scopes.scope_ms_p50(run, "hh_table_merge")
