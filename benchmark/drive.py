"""What every traffic mode drives a run with: the clock, handing frames
to the bus, waiting on the worker, the profiler inside the window.

A mode (``modes/<mode>.py``) imports this module and nothing of
``run.py``; ``run.py`` imports it first, so ``T_PROCESS`` is read as
early as the process can read a clock.
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import time

T_PROCESS = time.monotonic()
RUN_LIMIT_S = 330.0      # a run must exit within 360 s


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - T_PROCESS:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


class Abort(Exception):
    """The run cannot produce a result; exit non-zero, print none."""


def produce(run, lo: int, hi: int) -> None:
    """Hand flows [lo, hi) to the bus, in one call (one lock, one stamp):
    frames encoded during set-up."""
    c = run.spec.chunk_flows
    parts = []
    while lo < hi:
        ci, off = divmod(lo, c)
        n = min(hi - lo, c - off)
        parts.append(run.frames[ci] if n == c
                     else run.frames[ci][off:off + n])
        if off + n == c:
            run.frames[ci] = None  # the bus holds them now
        lo += n
    run.sut.bus.produce_many(
        run.sut.topic, itertools.chain.from_iterable(parts), partition=0)


def generate(run, chunks, early_hi: int) -> None:
    """Take every chunk as the worker processes make it; flows below
    ``early_hi`` go to the bus as they come (warm-up), the rest wait for
    their schedule."""
    c = run.spec.chunk_flows
    n_chunks = run.plan.total_flows // c
    sent = 0
    for ci, blob, offs, draws in chunks:
        o = offs.tolist()
        run.frames.append([blob[a:b] for a, b in zip(o[:-1], o[1:])])
        run.draws.append(draws)
        if len(run.frames[-1]) != c:
            raise Abort(f"chunk {ci} encoded {len(run.frames[-1])} frames")
        upto = min((ci + 1) * c, early_hi)
        if upto > sent:
            run.sut.bus_ready.wait()
            produce(run, sent, upto)
            sent = upto
    log(f"stream made: {n_chunks} chunks, {run.plan.total_flows} flows")


def wait(run, cond, what: str, poll: float = 0.005) -> None:
    while not cond():
        if time.monotonic() - T_PROCESS > RUN_LIMIT_S:
            raise Abort(f"timed out waiting for {what}")
        if run.error is not None:
            raise Abort("the processor failed")
        time.sleep(poll)


def tracing(run, t_open: float):
    """(start, stop) monotonic times of the profiler inside the window,
    or None when this run is not traced."""
    if not run.traced:
        return None
    tr = run.cell.traffic["trace"]
    start = t_open + float(tr["start_s"])
    return start, start + min(float(tr["seconds"]),
                              run.plan.seconds - float(tr["start_s"]))


def profiler(run, on: bool) -> None:
    """Start or stop the profiler on a thread of its own: stop_trace
    writes the trace out for seconds, and the generator's schedule must
    not wait for it."""
    import jax

    def start():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1  # TraceAnnotation spans, little else
        run.spans.annotate = True
        jax.profiler.start_trace(os.path.join(run.rundir, "trace"),
                                 profiler_options=opts)
        run.trace_span[0] = time.monotonic()

    started = run.trace_thread

    def stop():
        started.join()  # start_trace has returned
        run.spans.annotate = False
        jax.profiler.stop_trace()

    if on:
        run.trace_span = [time.monotonic(), None]
    else:
        run.trace_span[1] = time.monotonic()  # collection ends here
    run.trace_thread = threading.Thread(
        target=start if on else stop, name="bench-profiler", daemon=True)
    run.trace_thread.start()


def drive_profiler(run, times, now: float) -> None:
    """Start and stop the profiler at its times inside the window."""
    if not times:
        return
    if run.trace_span is None and now >= times[0]:
        profiler(run, True)
    elif run.trace_span and run.trace_span[1] is None and now >= times[1]:
        profiler(run, False)


class FetchScan:
    """Fetches that took flows, each seen once, in order."""

    def __init__(self, run):
        self.spans, self.i = run.spans.spans, 0

    def new(self) -> list:
        n = len(self.spans)
        out = [(s[2], s[4][0], s[4][1]) for s in self.spans[self.i:n]
               if s[0] == "bus_fetch" and s[4] is not None]
        self.i = n
        return out
