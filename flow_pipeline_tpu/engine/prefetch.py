"""Prefetching consumer: overlap host fetch+decode with the device step.

At the north-star rate the host path (bus fetch, wire decode,
columnarization) and the device path (jitted model updates) each take a
meaningful fraction of the batch budget; run serially they add up
(SURVEY.md §7 hard part (b): "double-buffered host->HBM feed"). This
wrapper runs the wrapped consumer on a dedicated thread, keeping a small
bounded queue of decoded batches ready, so the worker's device step for
batch i overlaps the host work for batch i+1 (JAX's async dispatch then
overlaps the device work itself with the NEXT poll).

Threading contract: the wrapped consumer is owned ENTIRELY by the
prefetch thread after start — kafka-python consumers are not thread-safe,
so commits are routed to that thread through a command queue and executed
between polls. ``flush_commits()`` blocks until queued commits have hit
the broker; the worker calls it after each snapshot so the at-least-once
protocol (state durable -> offsets committed) keeps its ordering.
"""

from __future__ import annotations

# flowlint: lock-checked
# (shared attributes declare their lock / single-writer story below;
# `make lint` verifies write sites — see docs/STATIC_ANALYSIS.md)

import queue
import threading
from typing import Optional

from ..guard import register_guard_metrics
from ..obs import get_logger
from ..obs.trace import TRACER

log = get_logger("prefetch")


class PrefetchConsumer:
    """Wraps a transport consumer with a fetch-ahead thread.

    depth is the max decoded batches held ready (2 = classic double
    buffering). The wrapper exposes the consumer surface the worker uses:
    poll / commit / committed / lag / positions.
    """

    def __init__(self, consumer, depth: int = 2, poll_max: int = 8192,
                 idle_sleep: float = 0.02):
        self.inner = consumer
        self.depth = depth
        # flowlint: unguarded -- worker writes, feed thread reads; stale sizes are tolerated by the documented poll() contract
        self.poll_max = poll_max
        self.idle_sleep = idle_sleep
        self._batches: queue.Queue = queue.Queue(maxsize=depth)
        self._commits: queue.Queue = queue.Queue()
        # pending-commit accounting: incremented on enqueue, decremented
        # after execution on the owner thread; a bare "queue empty" test
        # would race with a commit that is cleared-but-not-yet-enqueued
        self._pending = 0  # guarded-by: _cv
        # flowlint: unguarded -- the lock itself; bound once, never rebound
        self._cv = threading.Condition()
        self._stop = threading.Event()
        self._idle = threading.Event()  # last inner.poll returned nothing
        # freshness accounting for poll(): _started counts rounds begun,
        # _completed_start is the start-number of the last finished round
        # flowlint: unguarded -- feed thread is the sole writer; worker reads a monotonic int
        self._started = 0
        # flowlint: unguarded -- feed thread is the sole writer; worker reads a monotonic int
        self._completed_start = 0
        # first error from the feed thread; surfaced to the caller so a
        # poison message / dead broker crashes the worker (supervisor
        # restart semantics) instead of hanging or silently looping
        self._error: Optional[BaseException] = None  # guarded-by: _cv
        # flowlint: unguarded -- worker-thread lifecycle only (poll()/stop() run on the one owner thread)
        self._thread: Optional[threading.Thread] = None
        # flowguard occupancy: live bytes resident in the decoded-batch
        # queue (guard_buffer_bytes{stage="feed"}) — bounded at depth
        # batches by construction; this makes the occupancy observable
        self.m_bytes = register_guard_metrics()["buffer_bytes"]
        self._bytes = 0  # guarded-by: _cv

    def _track_bytes(self, delta: int) -> None:
        with self._cv:
            self._bytes += delta
            b = self._bytes
        self.m_bytes.set(b, stage="feed")

    # ---- consumer surface --------------------------------------------------

    def poll(self, max_messages: int = 8192):
        """Next prefetched batch, or None when the UNDERLYING consumer is
        idle. Blocks briefly while a fetch is in flight — returning None
        mid-fetch would make stop_when_idle callers quit a non-empty
        stream just because the thread hadn't finished its first poll.

        Contract drift from the wrapped consumer: ``max_messages`` applies
        to FUTURE feed rounds only — up to ``depth`` batches already
        fetched at the previous size are returned as-is. The worker passes
        a constant poll_max, so this is benign there; callers that vary
        the size mid-stream must tolerate a few stale-sized batches."""
        self.poll_max = max_messages  # picked up by the next feed round
        if self._thread is None:
            self._start()
        # Return None only after a poll round that STARTED after this call
        # came back empty: the sticky idle flag alone could be stale (a
        # producer may have published while the feed thread slept — or
        # while an in-flight round was already past its fetch), and a
        # premature None makes stop_when_idle callers abandon the tail.
        started_before = self._started
        # depth 0 when asked = the feed is the bottleneck; a full queue =
        # the worker is
        with TRACER.span("poll_wait", depth=self._batches.qsize()):
            while True:
                if self._error is not None:
                    raise self._error
                try:
                    batch = self._batches.get(timeout=self.idle_sleep)
                    self._track_bytes(-batch.nbytes())
                    return batch
                except queue.Empty:
                    if not self._thread.is_alive():
                        # the thread may have died DURING our get() —
                        # re-check the error before calling it
                        # end-of-stream, or the crash-the-worker semantics
                        # silently become a clean exit for stop_when_idle
                        # callers
                        if self._error is not None:
                            raise self._error
                        return None
                    if self._idle.is_set() and \
                            self._completed_start > started_before:
                        return None

    def commit(self, partition: int, next_offset: int) -> None:
        """Queue the commit for the owner thread (kafka-python consumers
        are not thread-safe). flush_commits() awaits execution."""
        if self._thread is None or not self._thread.is_alive():
            # no live thread owns the consumer (nothing polled yet, or the
            # feed died after surfacing its error): commit directly — an
            # enqueued commit would never drain and flush_commits would
            # stall for its full timeout
            self.inner.commit(partition, next_offset)
            return
        with self._cv:
            self._pending += 1
        self._commits.put((partition, next_offset))

    def flush_commits(self, timeout: float = 30.0) -> None:
        """Block until every queued commit has executed on the consumer."""
        if self._thread is None:
            return
        with self._cv:
            done = self._cv.wait_for(
                lambda: self._pending == 0 or self._error is not None,
                timeout,
            )
        if self._error is not None:
            # the real failure, not a misleading timeout: the exiting
            # thread's final drain still executes any queued commits
            raise self._error
        if not done:
            raise TimeoutError("prefetch commit queue did not drain")

    def __getattr__(self, name):
        # committed / lag / positions etc. delegate to the wrapped
        # consumer, and only exist if IT has them (callers feature-test
        # with hasattr). restore() adjusts .positions BEFORE the first
        # poll starts the thread; afterwards the thread owns them.
        return getattr(self.inner, name)

    # ---- lifecycle ---------------------------------------------------------

    def _start(self) -> None:
        self._thread = threading.Thread(
            target=self._run, name="feed-prefetch", daemon=True
        )
        self._thread.start()

    def stop(self, timeout: float = 5.0) -> None:
        """Drain queued commits and stop the thread (batches already
        prefetched but unread are dropped — uncommitted, so they replay)."""
        if self._thread is None:
            return
        self.flush_commits(timeout)
        self._stop.set()
        self._thread.join(timeout)
        if self._thread.is_alive():
            # The thread is stuck in a blocking inner call (broker stall).
            # Refuse to relinquish ownership: _stop stays set so it exits
            # when the call returns, and commit()/poll() keep routing
            # through the queue instead of touching the non-thread-safe
            # consumer concurrently.
            raise TimeoutError("prefetch thread did not stop in time")
        self._thread = None
        self._stop.clear()

    def _run(self) -> None:
        while not self._stop.is_set():
            self._drain_commits()
            if self._batches.full():
                # device side is behind; yield instead of spinning
                self._stop.wait(self.idle_sleep)
                continue
            self._started += 1
            round_no = self._started
            try:
                batch = self.inner.poll(self.poll_max)
            except Exception as e:  # noqa: BLE001 — hand to the caller:
                # retrying forever would turn a poison message or a dead
                # broker (which crashes the unwrapped worker for the
                # supervisor to restart) into a silent infinite loop
                log.exception("prefetch poll failed; surfacing to caller")
                with self._cv:
                    self._error = e
                    self._cv.notify_all()  # flush_commits waiters re-check
                break
            if batch is None or len(batch) == 0:
                self._idle.set()
                self._completed_start = round_no
                self._stop.wait(self.idle_sleep)
                continue
            self._idle.clear()
            self._completed_start = round_no
            self._batches.put(batch)
            self._track_bytes(batch.nbytes())
        self._drain_commits()

    def _drain_commits(self) -> None:
        while True:
            try:
                partition, next_offset = self._commits.get_nowait()
            except queue.Empty:
                return
            try:
                self.inner.commit(partition, next_offset)
            except Exception as e:  # noqa: BLE001 — flush_commits raises it:
                # reporting success for a commit that never reached the
                # broker would falsify "state durable -> offsets committed"
                log.exception("prefetch commit failed; surfacing to caller")
                with self._cv:
                    if self._error is None:
                        self._error = e
            finally:
                with self._cv:
                    self._pending -= 1
                    self._cv.notify_all()
