"""flowtrace: per-chunk structured tracing with a flight recorder.

The pipelined dataplane spreads one chunk's life across four threads —
feed (fetch+decode), group (prepare), worker (apply), flusher (sink
writes) — and the aggregate stage summaries cannot answer "why was
THIS window slow" after the fact. This module records per-chunk spans
(name, chunk id, thread, wall interval) into a fixed-size lock-safe
ring buffer, so the last ~seconds of pipeline causality are always
reconstructible: from a live process via the metrics server's
``/debug/trace`` endpoint, or post-mortem from the dump the worker
writes on an unhandled error.

Modes (``-obs.trace``, env fallback ``FLOWTPU_TRACE``):

- ``off``    — recording disabled; ``span()`` costs one attribute read
               and enters no profiler annotation.
- ``ring``   — the production default: spans land in the bounded ring
               (``RING_CAPACITY``), oldest overwritten (the
               flight-recorder contract). On the chip its cost cannot
               be told from ``off`` (PERF.md §6, PR 24).
- ``always`` — every span is retained (unbounded list): full traces for
               CI parity legs and short diagnostic runs, NOT for
               production streams.

Export is Chrome trace-event JSON (the ``traceEvents`` array of ``ph:
"X"`` complete events) — load the dump in Perfetto (ui.perfetto.dev)
or chrome://tracing; spans carrying the same ``chunk`` arg line up
across thread tracks, which is exactly the cross-thread causality the
aggregate summaries erase.

A ``span()`` is also a ``jax.profiler.TraceAnnotation`` of the same
name, so under any profiler session (an operator's, the benchmark's
traced run) the program's spans sit in the ``.xplane.pb`` on the device
trace's clock, beside the device ops. JAX is never imported from here: a
process that has not loaded it (the mesh coordinator) has no profiler to
annotate.
"""

from __future__ import annotations

# flowlint: lock-checked
# (spans are recorded from every pipeline thread; the ring state is
# guarded by one lock per recorder, and the mode latch is a
# single-writer configure() read by GIL-atomic loads on the hot path)

import itertools
import json
import os
import sys
import threading
import time
from typing import Optional

TRACE_MODES = ("off", "ring", "always")

# A 51 s window at four times the dispatch loop's span rate (~15 spans a
# batch, ~20 batches/s) with room to spare; ~10 MB when full.
RING_CAPACITY = 65536

# flowlint: unguarded -- idempotent latch: every writer stores the same class object
_ANNOTATION = None


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` once this process has loaded JAX,
    else None."""
    global _ANNOTATION
    if _ANNOTATION is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _ANNOTATION = getattr(profiler, "TraceAnnotation", None)
    return _ANNOTATION

# One process-wide chunk-id mint: Consumer.poll stamps every decoded
# FlowBatch, and the id rides PreparedBatch -> executor queue -> worker
# apply -> flush jobs, tying one chunk's spans together across threads.
_CHUNK_IDS = itertools.count(1)


def next_chunk_id() -> int:
    return next(_CHUNK_IDS)


class _Span:
    """One ``TraceRecorder.span()`` in flight (a class, not a generator:
    the dispatch loop enters ~15 of these a batch)."""

    __slots__ = ("_recorder", "_name", "_chunk", "_args", "_t0",
                 "_annotation")

    def __init__(self, recorder, name, chunk, args):
        self._recorder = recorder
        self._name = name
        self._chunk = chunk
        self._args = args
        # flowlint: unguarded -- a span belongs to the one thread that enters it
        self._t0 = None
        # flowlint: unguarded -- a span belongs to the one thread that enters it
        self._annotation = None

    def __enter__(self) -> dict:
        recorder = self._recorder
        if recorder._mode == "off" or recorder.paused:
            return self._args
        annotation = _trace_annotation()
        self._t0 = time.time()
        if annotation is not None:
            self._annotation = annotation(self._name)
            self._annotation.__enter__()
        return self._args

    def __exit__(self, *exc) -> bool:
        if self._t0 is not None:
            if self._annotation is not None:
                self._annotation.__exit__(*exc)
            self._recorder.record(self._name, self._t0, time.time(),
                                  self._chunk, **self._args)
        return False


class TraceRecorder:
    """Fixed-size span ring buffer (mode "ring") or unbounded span list
    (mode "always"), safe to record into from any thread."""

    def __init__(self, capacity: int = RING_CAPACITY,
                 mode: Optional[str] = None):
        if capacity < 1:
            raise ValueError("trace ring capacity must be >= 1")
        self.capacity = capacity
        self._lock = threading.Lock()
        self._ring: list = [None] * capacity  # guarded-by: _lock
        self._next = 0          # guarded-by: _lock
        self._dropped = 0       # guarded-by: _lock
        self._always: list = []  # guarded-by: _lock
        # flowlint: unguarded -- single-writer latch (configure at startup / test setup); hot-path readers take a GIL-atomic snapshot
        self._mode = "off"
        # flowguard: level >= 1 pauses recording — the flight recorder
        # is optional work, dropped before any DATA is. Pausing keeps
        # the ring's existing spans (a post-mortem still sees the lead-up
        # to the overload); configure() resets it.
        # flowlint: unguarded -- racy-but-monotone bool flipped by the guard's observe path; a stale read records/skips one span
        self.paused = False
        self.configure(mode if mode is not None
                       else os.environ.get("FLOWTPU_TRACE", "ring"))

    # ---- configuration ----------------------------------------------------

    def configure(self, mode: str) -> "TraceRecorder":
        if mode not in TRACE_MODES:
            raise ValueError(
                f"obs.trace must be one of {'|'.join(TRACE_MODES)}, "
                f"got {mode!r}")
        with self._lock:
            self._mode = mode
            self._ring = [None] * self.capacity
            self._next = 0
            self._dropped = 0
            self._always = []
            self.paused = False
        return self

    @property
    def mode(self) -> str:
        return self._mode

    @property
    def recording(self) -> bool:
        """True iff a span entered now would be kept: what a caller asks
        before it computes a span arg that costs more than a ``len``."""
        return self._mode != "off" and not self.paused

    # ---- recording --------------------------------------------------------

    def record(self, name: str, t0: float, t1: float,
               chunk: Optional[int] = None, **args) -> None:
        """One completed span. t0/t1 are time.time() seconds (wall clock
        — the Chrome format's ``ts`` is an absolute microsecond epoch);
        extra kwargs land in the event's ``args``."""
        if self._mode == "off" or self.paused:
            return
        ev = (name, t0, t1, threading.current_thread().name, chunk,
              args or None)
        with self._lock:
            if self._mode == "always":
                self._always.append(ev)
                return
            if self._ring[self._next] is not None:
                self._dropped += 1
            self._ring[self._next] = ev
            self._next = (self._next + 1) % self.capacity

    def span(self, name: str, chunk: Optional[int] = None, **args):
        """Context manager: record the wrapped block as one span, and as
        a profiler annotation of the same name. ``with ... as a`` binds
        the span's ``args`` dict: the block may add what it knows only at
        its end (``bytes``, ``rows``). Near-free when off."""
        return _Span(self, name, chunk, args)

    # ---- export -----------------------------------------------------------

    def snapshot(self) -> list:
        """Recorded spans, oldest first."""
        with self._lock:
            if self._mode == "always":
                return list(self._always)
            out = self._ring[self._next:] + self._ring[:self._next]
        return [ev for ev in out if ev is not None]

    def whole_since(self, t: float) -> bool:
        """True iff every span recorded since wall-clock ``t`` is still
        held. Spans land in the order they END, so once the ring has
        overwritten any, the window is whole only if the oldest span
        still held ended at or before ``t``."""
        with self._lock:
            if self._dropped == 0:
                return True
            return self._ring[self._next][2] <= t

    def chrome_trace(self) -> dict:
        """The Chrome trace-event JSON object (Perfetto-loadable):
        complete ("ph": "X") events with microsecond timestamps, one
        ``tid`` per recording thread, chunk ids under ``args.chunk``."""
        events = []
        pid = os.getpid()
        snap = self.snapshot()
        for name, t0, t1, thread, chunk, args in snap:
            ev = {
                "name": name,
                "ph": "X",
                "ts": round(t0 * 1e6, 1),
                "dur": round((t1 - t0) * 1e6, 1),
                "pid": pid,
                "tid": thread,
            }
            a = dict(args) if args else {}
            if chunk is not None:
                a["chunk"] = chunk
            if a:
                ev["args"] = a
            events.append(ev)
        with self._lock:
            dropped = self._dropped
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "source": "flow-pipeline-tpu flowtrace",
                "mode": self._mode,
                "dropped_spans": dropped,
                # whole_since(t) says whether a window is still held
                "oldest_span_start": snap[0][1] if snap else None,
            },
        }

    def dump(self, path: str) -> str:
        """Write the Chrome trace JSON to ``path``; returns the path."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def dump_on_error(self, tag: str = "worker") -> Optional[str]:
        """Best-effort flight-recorder dump for an unhandled error —
        never raises (the original exception must win), returns the
        written path or None. The dump goes next to the system tempdir
        so a crash-looping worker leaves a breadcrumb per process."""
        if self._mode == "off":
            return None
        import tempfile

        path = os.path.join(
            tempfile.gettempdir(),
            f"flowtrace-{tag}-{os.getpid()}.json")
        try:
            return self.dump(path)
        except Exception:  # noqa: BLE001 — the original error must win
            return None


# The process-wide recorder every pipeline stage records into. Tests
# and bench legs reconfigure it per leg (configure() resets the ring).
TRACER = TraceRecorder()
