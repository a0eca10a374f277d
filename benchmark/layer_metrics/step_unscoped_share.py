"""Device time of the fused step's executions in the traced window that
maps to no kernel scope, in per cent of all of it (layout copies of the
state at the step's edges; anything a later change leaves outside a
scope). Source: profiler trace (kernel_scopes.py)."""

from benchmark import kernel_scopes


def read(run):
    return kernel_scopes.unscoped_share(run)
