"""What every traffic mode drives a run with: the clock, handing frames
to the bus, waiting on the worker, the profiler inside the window.

A mode (``modes/<mode>.py``) imports this module and nothing of
``run.py``; ``run.py`` imports it first, so ``T_PROCESS`` is read as
early as the process can read a clock.
"""

from __future__ import annotations

import gc
import itertools
import os
import struct
import sys
import threading
import time

import numpy as np

T_PROCESS = time.monotonic()
RUN_LIMIT_S = 330.0      # a run must exit within 360 s


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - T_PROCESS:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


class Abort(Exception):
    """The run cannot produce a result; exit non-zero, print none."""


class Deal:
    """Where every flow went: the partition that the stream's kind deals
    each position to, and its offset there. The stream goes to the bus in
    position order, so a partition's log holds its positions in ascending
    order and a flow's offset is its index among them. On one partition
    a position is its own offset and nothing is kept; on several, one
    int64 a flow, made once for the whole stream."""

    def __init__(self, spec, partitions: int, total_flows: int):
        self.partitions = partitions
        self._positions = None
        if partitions > 1:
            part = np.asarray(spec.partition_of(
                np.arange(total_flows, dtype=np.int64), partitions))
            if part.min() < 0 or part.max() >= partitions:
                raise Abort(f"the stream deals to partitions {part.min()}.."
                            f"{part.max()}; the bus has {partitions}")
            self._positions = [np.flatnonzero(part == p)
                               for p in range(partitions)]

    def positions(self, partition: int, first: int, n: int) -> np.ndarray:
        """Positions of the flows at offsets [first, first + n) of
        ``partition``, ascending."""
        if self._positions is None:
            return np.arange(first, first + n, dtype=np.int64)
        return self._positions[partition][first:first + n]

    def offsets(self, partition: int, lo: int, hi: int) -> tuple:
        """[a, b): the offsets of ``partition`` whose flows lie at
        positions [lo, hi)."""
        if self._positions is None:
            return lo, hi
        a, b = np.searchsorted(self._positions[partition], [lo, hi])
        return int(a), int(b)

    def split(self, lo: int, hi: int) -> list:
        """For each partition, where among the flows [lo, hi) its own
        lie: indices into [0, hi - lo), ascending."""
        out = []
        for p in range(self.partitions):
            a, b = self.offsets(p, lo, hi)
            out.append(self.positions(p, a, b - a) - lo)
        return out

    def consumed(self, folded: list) -> np.ndarray:
        """Positions of the flows below offset ``folded[p]`` of every
        partition ``p``, ascending: the flows consumed."""
        if self._positions is None:
            return np.arange(sum(folded), dtype=np.int64)
        return np.sort(np.concatenate(
            [self.positions(p, 0, n) for p, n in enumerate(folded)]))

    def beyond(self, offsets: list, lo: int, hi: int) -> int:
        """How many flows at positions [lo, hi) lie at or past
        ``offsets[p]`` in their partition ``p``."""
        total = 0
        for p in range(self.partitions):
            a, b = self.offsets(p, lo, hi)
            total += max(0, b - max(offsets[p], a))
        return total


def produce(run, lo: int, hi: int) -> None:
    """Hand flows [lo, hi) to the bus, one call a partition (one lock, one
    stamp): frames cut during set-up, each to the partition its position
    is dealt to."""
    c = run.spec.chunk_flows
    parts, at = [], lo
    while at < hi:
        ci, off = divmod(at, c)
        n = min(hi - at, c - off)
        parts.append(run.frames[ci] if n == c
                     else run.frames[ci][off:off + n])
        if off + n == c:
            run.frames[ci] = None  # the bus holds them now
        at += n
    frames = itertools.chain.from_iterable(parts)
    deal = run.deal
    if deal.partitions == 1:
        split = [frames]
    else:
        flat = np.empty(hi - lo, object)
        flat[:] = list(frames)
        split = [flat[places].tolist() for places in deal.split(lo, hi)]
    for p, part in enumerate(split):
        run.sut.bus.produce_many(run.sut.topic, part, partition=p)


def folded(run) -> list:
    """A partition: the offset the worker has folded up to. Its own count
    of what its state covers, not the fetch position: the feed thread
    fetches ahead of it."""
    covered = run.sut.worker._covered
    return [int(covered.get(p, 0)) for p in range(run.deal.partitions)]


def freeze_heap() -> None:
    """Called by a mode once its warm-up is folded, before its window
    opens. The bus is a list inside the worker's process, 2x10^7 to 10^8
    frames long by the window's end, and every full collection walks it
    end to end (1.3 s for 6x10^7 entries here): a stall that a bus outside
    the process, which is what the cells stand for, would not cost the
    program. ``gc.freeze()`` moves everything alive now, that list and the
    program's own warmed-up objects with it, to the collector's permanent
    generation; what the program makes from here on is collected as ever.
    Every cell's window runs so (PERF.md 2 and 6, PR 29)."""
    gc.freeze()


class Stream:
    """The stream as the worker processes make it, cut into frames on a
    thread of its own from the moment the pool starts, not from when the
    device is up ~12 s later: what the cutting costs (7-8 ms of the
    interpreter lock a chunk on the cells' hosts) adds to what JAX's own
    start costs whenever it is done, but not the wait for it (PERF.md 6,
    PR 29).

    One ``Struct.unpack_from`` by the format the worker process sent
    makes a chunk's 32,768 ``bytes`` in one call, from the slot of the
    ring (``flowgen.Ring``) that the worker put the blob into.
    ``run.frames`` holds every chunk's frames as the tuple ``unpack``
    returned, until they are sent: the collector stops tracking a tuple
    of ``bytes`` the first time it meets it, where one list of every frame
    is walked end to end by each full collection, of which a starting JAX
    makes many (1.3 s for 6x10^7 entries here). ``run.draws`` holds every
    chunk's draws; ``made`` says how far both reach."""

    def __init__(self, run, chunks, ring):
        self.run, self.chunks, self.ring = run, chunks, ring
        self.made = 0            # flows cut so far
        self.done = self.stopped = False
        self.error = None
        self.thread = threading.Thread(target=self._cut, name="bench-cut",
                                       daemon=True)
        self.thread.start()

    def _cut(self) -> None:
        run, c = self.run, self.run.spec.chunk_flows
        n_chunks = run.plan.total_flows // c
        cut_s = made_s = 0.0
        t_first = None
        try:
            for ci, nbytes, fmt, draws, seconds in self.chunks:
                if self.stopped:
                    return
                t0 = time.monotonic()
                t_first = t_first or t0
                frames = self.ring.cut(ci, nbytes, struct.Struct(fmt))
                if len(frames) != c:
                    raise Abort(f"chunk {ci} encoded {len(frames)} frames")
                run.frames.append(frames)
                run.draws.append(draws)
                cut_s += time.monotonic() - t0
                made_s += seconds
                self.made = (ci + 1) * c
            log(f"stream made: {n_chunks} chunks, {run.plan.total_flows} "
                f"flows; first chunk at {t_first - T_PROCESS:.2f} s, "
                f"{made_s / n_chunks * 1e3:.1f} ms a chunk in a worker "
                f"process, {cut_s / n_chunks * 1e3:.2f} ms a chunk to cut")
        except Exception as e:  # noqa: BLE001 -- raised by generate()
            self.error = e
        finally:
            self.done = True

    def stop(self) -> None:
        """End the cutting at its next chunk and wait for it, while the
        pool still makes chunks: the ring may not be closed under a cut."""
        self.stopped = True
        self.thread.join(timeout=10)


def generate(run, stream: Stream, early_hi: int) -> None:
    """Wait until the whole stream is made; flows below ``early_hi`` go
    to the bus as they come (warm-up), the rest wait for their
    schedule."""
    sent = 0
    run.sut.bus_ready.wait()
    while not stream.done or sent < min(stream.made, early_hi):
        wait(run, lambda: stream.done
             or sent < min(stream.made, early_hi),
             "the stream to be made")
        if stream.error is not None:
            raise stream.error
        upto = min(stream.made, early_hi)
        if upto > sent:
            produce(run, sent, upto)
            sent = upto


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
    """Fetches that took flows, each seen once, in order: (time it
    returned, partition, first offset, flows, position). The position is
    the count of flows fetched over all partitions once it had returned:
    what "the fetch position" means on any number of them."""

    def __init__(self, run):
        self.spans, self.i = run.spans.spans, 0
        self.upto: dict = {}     # partition -> offset fetched up to

    def new(self) -> list:
        n, out = len(self.spans), []
        for s in self.spans[self.i:n]:
            if s[0] == "bus_fetch" and s[4] is not None:
                p, first, k = s[4]
                self.upto[p] = max(self.upto.get(p, 0), first + k)
                out.append((s[2], p, first, k, sum(self.upto.values())))
        self.i = n
        return out
