"""Device time of the sliding window's fold programs, from the traced run's
``.xplane.pb``.

Each ranked table's ring folds its K sub-window states in one jitted
program named for it, ``slide_fold_<table>`` (``engine/windowed.py``,
``models/heavy_hitter.py::hh_fold_program``), as the mesh programs are
named; a ``/device:TPU`` plane's ``XLA Modules`` line holds one event per
execution, ``jit_slide_fold_<table>(<id>)``. Returns None, and raises
nothing, where there is no trace, no device plane (the CPU dry run: a CPU
number never goes under a device metric's name) or no such program (a
tumbling cell, a parent commit from before them).
"""

from __future__ import annotations

import statistics

from benchmark import kernel_scopes, mesh_trace

PREFIX = "slide_fold_"


def fold_executions(run):
    """{program: [device ms of each execution]} of the traced part."""
    if not hasattr(run, "_slide_fold_executions"):
        found: dict = {}
        if run.trace is not None:
            programs = mesh_trace._device_programs(
                kernel_scopes._planes(run))
            for events in programs.values():
                for name, _start, dur in events:
                    if name.startswith(PREFIX):
                        found.setdefault(name, []).append(dur / 1e6)
        run._slide_fold_executions = found or None
    return run._slide_fold_executions


def fold_ms(run):
    """Device ms of one slide's folds: one execution of each table's
    program (its median), summed."""
    found = fold_executions(run)
    if not found:
        return None
    return sum(statistics.median(v) for v in found.values())
