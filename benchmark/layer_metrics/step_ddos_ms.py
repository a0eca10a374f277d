"""Device time of one fused step under the kernel scope ddos_accumulate (the
detector's per-dst accumulate): median over the step's executions in the
traced window. Source: profiler trace, XLA Ops self times by scope
(kernel_scopes.py)."""

from benchmark import kernel_scopes


def read(run):
    return kernel_scopes.scope_ms_p50(run, "ddos_accumulate")
