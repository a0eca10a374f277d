"""Of the device's idle time in the traced window, the share under no
program span of the dispatch loop's thread, in per cent: idle time the
program's own trace cannot explain. Source: profiler trace, the device's
ops against the program's TraceAnnotation events (kernel_scopes.py)."""

from benchmark import kernel_scopes


def read(run):
    return kernel_scopes.idle_unowned_share(run)
