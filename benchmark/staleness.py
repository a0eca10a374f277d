"""Exact staleness quantiles from a log of version changes.

A reader outside the worker polls ``/query/version`` and keeps
``(t_seen, version, flows_seen)`` at every change. The generator is open
loop on one partition, so flow ``i`` was due at ``due(i)``; while a
snapshot holding ``flows_seen`` flows is visible, its newest flow is
``flows_seen - 1`` and staleness at time ``t`` is ``t - due(flows_seen-1)``.
Between two changes it rises at one second a second, so over a window it
is a known piecewise-linear function of time and its distribution is a
mixture of uniform pieces: piece ``k`` is uniform on
``[s_k, s_k + d_k]`` with weight ``d_k``. Quantiles are read exactly
from that mixture; nothing is sampled.
"""

from __future__ import annotations

from typing import Callable, Sequence


def pieces(log: Sequence[tuple], due: Callable[[int], float],
           t_a: float, t_b: float) -> list[tuple[float, float]]:
    """[(staleness at the piece's start, piece length)] over [t_a, t_b].

    ``log``: (t_seen, version, flows_seen) at every change of version,
    ascending in time. The window must start with a snapshot visible:
    the last change at or before ``t_a`` opens the first piece."""
    changes = [(float(t), int(f)) for t, _v, f in log]
    if not changes or changes[0][0] > t_a:
        raise ValueError("no snapshot was visible when the window began")
    out = []
    for k, (t, flows) in enumerate(changes):
        nxt = changes[k + 1][0] if k + 1 < len(changes) else float("inf")
        lo, hi = max(t, t_a), min(nxt, t_b)
        if hi <= lo:
            continue
        out.append((lo - due(flows - 1), hi - lo))
    return out


def quantile(ps: Sequence[tuple[float, float]], q: float) -> float:
    """The ``q``-quantile of the mixture of uniform pieces ``ps``: the
    least ``s`` with (time spent at staleness <= s) >= q * (total time)."""
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"q must lie in [0, 1], got {q}")
    total = sum(d for _s, d in ps)
    if total <= 0:
        raise ValueError("empty window")
    target = q * total
    pts = sorted({s for s, _d in ps} | {s + d for s, d in ps})

    def below(x: float) -> float:
        return sum(min(max(x - s, 0.0), d) for s, d in ps)

    prev_x, prev_c = pts[0], 0.0
    for x in pts[1:]:
        c = below(x)
        if c >= target:
            if c == prev_c:
                return prev_x
            return prev_x + (x - prev_x) * (target - prev_c) / (c - prev_c)
        prev_x, prev_c = x, c
    return pts[-1]


def mean(ps: Sequence[tuple[float, float]]) -> float:
    total = sum(d for _s, d in ps)
    return sum((s + d / 2.0) * d for s, d in ps) / total
