"""Sums of the program's mesh spans by polled batch (``program_spans.py``
counts children by parent; the mesh readers need their time)."""

from __future__ import annotations

import bisect

from benchmark import program_spans


def ms_per_apply(run, child: str):
    """For each ``apply`` span of the window, the summed ms of the
    ``child`` spans on its thread that started inside it; None where the
    program's spans cannot be read, [] where there is no batch."""
    w = program_spans.window(run)
    if not w:
        return None
    parents = sorted(w.named("apply"), key=lambda s: s[1])
    starts = [p[1] for p in parents]
    sums = [0.0] * len(parents)
    for c in w.spans:
        if c[0] != child:
            continue
        i = bisect.bisect_right(starts, c[1]) - 1
        if i >= 0 and c[1] < parents[i][2] and c[3] == parents[i][3]:
            sums[i] += (c[2] - c[1]) * 1e3
    return sums
