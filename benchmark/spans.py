"""Spans recorded from outside the program, around the calls into a layer.

``SpanLog.wrap(owner, "method", "span_name")`` replaces a method of a
program class by a wrapper that times the call on ``time.monotonic()``
and appends ``(name, t0, t1, thread, meta)`` to an in-memory list; with
``annotate`` on (the traced run) the call also runs inside a
``jax.profiler.TraceAnnotation`` of the same name, so that the profiler's
own trace holds the span on the device's clock and an idle gap has an
owner. ``restore()`` puts every method back.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional


class SpanLog:
    def __init__(self):
        self.spans: list[tuple] = []
        self.annotate = False
        self._restore: list[tuple] = []

    def wrap(self, owner, attr: str, name: str,
             meta: Optional[Callable] = None) -> None:
        """Time ``owner.attr``. ``meta(args, kwargs, result)`` may return
        anything to keep with the span (a row count, a flag)."""
        real = owner.__dict__[attr]
        kind = type(real)
        fn = real.__func__ if kind in (staticmethod, classmethod) else real
        log = self

        def timed(*args, **kwargs):
            if log.annotate:
                import jax

                with jax.profiler.TraceAnnotation(name):
                    return run(args, kwargs)
            return run(args, kwargs)

        def run(args, kwargs):
            t0 = time.monotonic()
            result = None
            try:
                result = fn(*args, **kwargs)
                return result
            finally:
                t1 = time.monotonic()
                log.spans.append((
                    name, t0, t1, threading.get_ident(),
                    meta(args, kwargs, result) if meta else None))

        timed.__wrapped__ = fn
        setattr(owner, attr,
                kind(timed) if kind in (staticmethod, classmethod) else timed)
        self._restore.append((owner, attr, real))

    def restore(self) -> None:
        for owner, attr, real in reversed(self._restore):
            setattr(owner, attr, real)
        self._restore.clear()

    # ---- reading ----------------------------------------------------------

    def named(self, name: str, t_a: float = float("-inf"),
              t_b: float = float("inf")) -> list[tuple]:
        """Spans of ``name`` that START inside [t_a, t_b)."""
        return [s for s in self.spans if s[0] == name and t_a <= s[1] < t_b]
