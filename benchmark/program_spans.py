"""The program's own spans, read after the run.

The processor ran in this process, so its tracer
(``flow_pipeline_tpu.obs.trace.TRACER``: one ring of ``(name, t0, t1,
thread, chunk, args)`` on the wall clock) still holds what the dispatch
loop recorded from inside: ``poll_wait``, ``apply`` and, nested in it,
``lane_build``, ``h2d``, ``step_dispatch``, ``wagg_wait|d2h|fold``,
``ckpt_state|d2h|serialize|write|commit`` (docs/OBSERVABILITY.md has the
catalogue). The run's window is on ``time.monotonic()``; the offset
between the two clocks is sampled when this module is loaded
(``manifest.load_cell``, before the run) and again at read time.

``window(run)`` returns None, with a line on standard error, where the
spans cannot be trusted or are not there: the two offsets differ by more
than 1 ms (the wall clock was stepped during the run), the ring no longer
holds the start of the window, or the program has no such tracer (a
parent commit from before these spans). A reader built on it then
returns None and the result line leaves its metric out.
"""

from __future__ import annotations

import bisect
import statistics
import sys
import time

from benchmark.trace_reduce import _union

MAX_CLOCK_STEP_S = 1e-3


def _wall_minus_monotonic() -> float:
    return time.time() - time.monotonic()


_OFFSET_AT_LOAD = _wall_minus_monotonic()


def _refuse(why: str) -> None:
    print(f"benchmark: program spans not read: {why}", file=sys.stderr)


def held_spans(tracer, t_a: float, offset_before: float,
               offset_now: float):
    """Every span ``tracer`` holds, on the monotonic clock, or None if
    they cannot place a window that opened at monotonic ``t_a``."""
    if abs(offset_now - offset_before) > MAX_CLOCK_STEP_S:
        _refuse(f"wall clock moved {offset_now - offset_before:+.6f} s "
                f"against the monotonic clock during the run")
        return None
    whole_since = getattr(tracer, "whole_since", None)
    if whole_since is None:
        _refuse("this program's tracer cannot say whether it still holds "
                "the window")
        return None
    snapshot = tracer.snapshot()  # before the check: the ring only loses
    if not whole_since(t_a + offset_now):
        _refuse("the ring has overwritten the start of the window")
        return None
    return [(name, t0 - offset_now, t1 - offset_now, thread, chunk,
             args or {})
            for name, t0, t1, thread, chunk, args in snapshot]


class Window:
    """The held spans and the run's window [t_a, t_b) among them."""

    def __init__(self, spans: list, t_a: float, t_b: float):
        self.spans, self.t_a, self.t_b = spans, t_a, t_b

    def named(self, name: str) -> list:
        """Spans of ``name`` that START inside the window."""
        return [s for s in self.spans
                if s[0] == name and self.t_a <= s[1] < self.t_b]

    def ms(self, name: str) -> list:
        return [(s[2] - s[1]) * 1e3 for s in self.named(name)]

    def args(self, name: str, key: str) -> list:
        return [s[5][key] for s in self.named(name) if key in s[5]]

    def worker_thread(self):
        """The dispatch loop's thread: the one that records ``apply``."""
        applies = self.named("apply")
        return applies[0][3] if applies else None


def window(run):
    """The run's ``Window``, read once; None where it cannot be."""
    if not hasattr(run, "_program_spans"):
        from flow_pipeline_tpu.obs.trace import TRACER

        spans = held_spans(TRACER, run.t_a, _OFFSET_AT_LOAD,
                           _wall_minus_monotonic())
        run._program_spans = (None if spans is None
                              else Window(spans, run.t_a, run.t_b))
    return run._program_spans


def p50_ms(run, name: str):
    """Median duration of the window's spans of ``name``; None where
    there is none."""
    w = window(run)
    values = w.ms(name) if w else []
    return statistics.median(values) if values else None


def p50_arg(run, name: str, key: str, scale: float = 1.0):
    w = window(run)
    values = w.args(name, key) if w else []
    return statistics.median(values) * scale if values else None


def per_parent(w: Window, parent: str, child: str) -> list:
    """For each ``parent`` span of the window, how many ``child`` spans
    on its thread started inside it."""
    parents = sorted(w.named(parent), key=lambda s: s[1])
    starts = [p[1] for p in parents]
    counts = [0] * len(parents)
    for c in w.spans:
        if c[0] != child:
            continue
        i = bisect.bisect_right(starts, c[1]) - 1
        if i >= 0 and c[1] < parents[i][2] and c[3] == parents[i][3]:
            counts[i] += 1
    return counts


def covered_s(intervals: list, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    return sum(max(0.0, min(b, hi) - max(a, lo))
               for a, b in _union(intervals))
