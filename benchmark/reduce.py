"""Reductions shared by the metric readers: quantiles of span durations,
sums over the window. A reader that finds nothing to read returns None."""

from __future__ import annotations

import statistics


def p50(values):
    return statistics.median(values) if values else None


def quantile(values, q: float):
    """Nearest-rank quantile of a list; None when it is empty."""
    if not values:
        return None
    s = sorted(values)
    return s[min(len(s) - 1, int(q * len(s)))]


def window_ms(run, name: str, keep=None) -> list:
    """Durations (ms) of the spans of ``name`` that started inside the
    measured window, optionally only those whose kept value passes."""
    return [(s[2] - s[1]) * 1e3 for s in run.in_window(name)
            if keep is None or keep(s[4])]


def window_minutes(run) -> float:
    return (run.t_b - run.t_a) / 60.0


def staleness_pieces(run):
    """The reader's publish log as pieces of the staleness function over
    the window, or None where the cell has no reader or no schedule."""
    from . import staleness

    if not run.publish_log or run.t0_schedule is None:
        return None
    return staleness.pieces(run.publish_log, run.due, run.t_a, run.t_b)


def close_spans(run) -> list:
    """The flush_closed spans of the window that closed a window slot:
    those inside which the configuration's ``close_table`` was written. (A
    flush can also emit without a close: detector alerts.)"""
    table = run.cell.config["close_table"]
    writes = [s for s in run.in_window("sink_write") if s[4] == table]
    return [f for f in run.in_window("flush_closed")
            if f[4] and any(f[1] <= w[1] and w[2] <= f[2] for w in writes)]


def span_ms(spans) -> list:
    return [(s[2] - s[1]) * 1e3 for s in spans]
