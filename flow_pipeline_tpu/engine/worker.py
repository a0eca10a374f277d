"""StreamWorker: the TPU processor service loop.

Wires consumer -> models -> sinks with at-least-once offset commits and
periodic snapshots. One worker owns one consumer (one partition subset) and
any number of aggregation models; scale-out is more workers on more
partitions — the sarama consumer-group model (ref: inserter/inserter.go:
238-256) — and/or a device mesh inside one worker (parallel/).
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass
from typing import Any, Optional, Sequence

import numpy as np

from ..families import registry
from ..guard import GuardConfig, GuardController
from ..models.ddos import DDoSDetector, DDoSState
from ..models.heavy_hitter import HHState
from ..models.window_agg import WindowAggregator, WindowStore
from ..obs import REGISTRY, get_logger
from ..obs.trace import TRACER
from ..obs.tracing import register_stage_histogram

# Buckets for the window-end -> sink-commit latency histogram: seconds,
# spanning "flushed within the batch" (~1s) to "stuck for an hour".
COMMIT_LATENCY_BUCKETS = (
    1.0, 5.0, 15.0, 30.0, 60.0, 120.0, 300.0, 600.0, 1_200.0, 1_800.0,
    3_600.0,
)
from .checkpoint import load_checkpoint, save_checkpoint
from .dataplane import choose
from .prefetch import PrefetchConsumer
from .windowed import WindowedHeavyHitter

log = get_logger("worker")


class _ShedPrep:
    """Stand-in prepared object when flowguard admission sheds an ENTIRE
    batch on the group thread: carries the (now empty) batch through the
    executor so its offset range still reaches the commit path — shed
    rows were consumed and accounted, never lost to replay."""

    __slots__ = ("batch",)

    def __init__(self, batch):
        self.batch = batch


@dataclass(frozen=True)
class WorkerConfig:
    poll_max: int = 8192
    snapshot_every: int = 50  # batches between snapshots (0 = never)
    checkpoint_path: Optional[str] = None
    idle_sleep: float = 0.05
    # Double-buffered feed (SURVEY §7): >0 wraps the consumer in a
    # PrefetchConsumer holding this many decoded batches ready, so host
    # fetch+decode for batch i+1 overlaps the device step for batch i.
    prefetch: int = 2
    # Fuse the per-model device updates into one jitted step per batch
    # with shared pre-aggregation (engine.fused) when the model set
    # supports it; falls back to the serial per-model path otherwise
    # (e.g. mesh-sharded models). Outputs are equivalence-tested.
    fused: bool = True
    # Host-grouped pre-aggregation (engine.hostfused): "auto" uses it
    # when the default backend is CPU (numpy's introsort beats XLA:CPU's
    # lax.sort ~20x on one core, so grouping host-side and shipping only
    # compact group tables to the XLA step is the idiomatic CPU layout);
    # "on"/"off" force/forbid. On TPU "auto" keeps the device-sorted
    # fused step.
    host_assist: str = "auto"
    # Sketch-step backend (flow_pipeline_tpu.hostsketch): "device" keeps
    # the jitted CMS/top-K apply (engine.hostfused/_cached_apply — the
    # TPU dataplane and the pre-r8 CPU path); "host" executes it in the
    # native threaded uint64 engine behind the same apply seam —
    # bit-exact on the integer envelope (tests/test_hostsketch.py): on a
    # CPU the jitted apply is the largest share of the wall time.
    # Requires the host-grouped pipeline (CPU backend or
    # host_assist="on"); falls back to device with a warning otherwise.
    sketch_backend: str = "device"
    # Ingest dataplane (flow_pipeline_tpu.ingest): "pipelined" runs the
    # host pre-aggregation on a group thread (overlapping the device
    # step), window extraction + sink writes on a background flusher, and
    # sharded grouping on a thread pool — engaged when the host-grouped
    # pipeline is active (it has the prepare/apply split); "serial" keeps
    # the single-threaded path (the pre-r6 behavior, the A/B baseline).
    ingest_mode: str = "pipelined"
    ingest_shards: int = 0       # grouping shards: 0 auto, 1 disables
    # Worker threads inside the native dataplane kernels (the fused
    # pass, the staged sketch engine, lane building, the wagg fold) —
    # every kernel is deterministic at ANY count, so this is purely a
    # throughput knob. 0 keeps the hostsketch engine's conservative
    # auto count (half the cores, capped at 4): the kernels are
    # memory-bound and extra threads thrash small hosts' shared cache.
    ingest_threads: int = 0
    ingest_native_group: bool = False  # C hash-group kernel (numpy fallback)
    # Single-pass fused native dataplane (native/flowfused.cc): "auto"
    # runs group->cascade->sketch in one C pass whenever the host sketch
    # backend is active and the library exports it (falling back to the
    # staged path LOUDLY — gauge + warning — when the .so is stale);
    # "on" demands it (raises when it cannot serve); "off" keeps the
    # staged prepare/apply split, the bit-exact parity reference.
    ingest_fused: str = "auto"
    # Full-fidelity raw archiving (the reference's flows_raw path,
    # ref: compose/clickhouse/create.sh:36-62): every consumed batch is
    # handed to sinks exposing archive_raw(batch). Off by default — the
    # pre-aggregated tables are the serving path; raw rows are for
    # drill-down/audit and cost one row per flow.
    archive_raw: bool = False
    # sketchwatch (-obs.audit, obs/audit.py): the sampled exact shadow
    # audit measuring how wrong the sketches are. "sample" keeps exact
    # uint64 counts for a deterministic ~1/256 key cohort and publishes
    # relative-error/recall/saturation metrics at every window close;
    # "full" audits every key (tests, the error-vs-fill sweep); "off"
    # disables. Needs a pipeline that feeds it (the host-grouped ones);
    # dataplane.choose reports the effective mode (Choice.audit).
    obs_audit: str = "sample"
    # flowguard (-guard.lag, guard/): watermark-lag budget in seconds
    # before the degradation ladder engages. 0 (the default) disarms
    # the controller entirely — every exact-parity path runs untouched.
    guard_lag: float = 0.0
    # Ladder ceiling: level 1 drops optional work, levels 2..max are
    # hash-sampled admission at keep rate 1/2^(level-1).
    guard_max_level: int = 6
    # The role this worker's flow_build_info identity gauge publishes
    # under. A mesh member's INNER worker must identify as "member" —
    # publishing a second role="worker" series next to the member's
    # would give one process two identities (MeshMember rewrites this).
    build_role: str = "worker"


class StreamWorker:
    """Drives models from a consumer; emits rows to sinks.

    models: {"name": model} — models expose update(batch) and one of
      flush(force)->rows-dict (WindowAggregator), flush(force)->list of
      row-dicts (WindowedHeavyHitter), or close_sub_window/alerts
      (DDoSDetector).
    sinks: objects with write(table: str, rows) -> None.
    """

    def __init__(self, consumer, models: dict[str, Any],
                 sinks: Sequence[Any] = (), config: WorkerConfig = WorkerConfig()):
        if config.prefetch and consumer is not None and not isinstance(
                consumer, PrefetchConsumer):
            consumer = PrefetchConsumer(consumer, depth=config.prefetch,
                                        poll_max=config.poll_max)
        self.consumer = consumer
        self.models = models
        self.sinks = list(sinks)
        self.config = config
        # what runs is chosen in one place (engine/dataplane.py): build
        # what it names, say what it says, apply what follows from it
        choice = self.choice = choose(
            models, config,
            None if consumer is None
            else isinstance(consumer, PrefetchConsumer))
        # flowguard: constructed unconditionally (its metric families
        # must exist — as zeros — on every worker for the honesty
        # tests), armed only when a lag budget is declared
        self.guard = GuardController(GuardConfig(
            lag_budget=config.guard_lag,
            max_level=config.guard_max_level))
        self.fused = (None if choice.pipeline is None
                      else choice.pipeline(models, **choice.kwargs))
        for level, words, args in choice.words:
            log.log(level, words, *args)
        if choice.error is not None:
            raise choice.error
        for name in choice.lateness_dropped:
            models[name].lateness = 0
        self.executor = None
        self.flusher = None
        if choice.pipelined:
            from ..ingest import AsyncFlusher, PipelinedExecutor

            # the guard admission runs INSIDE the prepare wrapper on
            # the group thread: shed rows never reach grouping, so
            # degradation sheds the pre-aggregation cost too
            self.executor = PipelinedExecutor(
                consumer, self._prepare_admitted, poll_max=config.poll_max)
            self.flusher = AsyncFlusher()
            for m in models.values():
                if isinstance(m, WindowedHeavyHitter) and \
                        hasattr(m.model, "top_lazy"):
                    m.lazy_extract = True
        self.batches_seen = 0
        self.flows_seen = 0
        # flowlint: unguarded -- worker thread only (set and read per _process step)
        self._newest_ts: dict = {}  # partition -> newest time_received
        # offsets covered by state (committable after next snapshot/flush)
        self._covered: dict[int, int] = {}
        self._emitted_since_snapshot = False
        # Guards model/window state against concurrent readers (the live
        # query API); the worker holds it across each run_once step.
        self.lock = threading.Lock()
        # flowserve hook (serve.WorkerServePublisher.attach): when set,
        # every _process step lets it publish an immutable snapshot for
        # the lock-free read path. Wired once before run() starts.
        # flowlint: unguarded -- bound once at wiring (before the loop), then read on the worker thread only
        self.serve = None
        self.m_flows = REGISTRY.counter("flows_processed_total",
                                        "flows decoded and aggregated")
        self.m_batches = REGISTRY.counter("batches_processed_total",
                                          "batches pulled off the bus")
        self.m_rows = REGISTRY.counter("insert_count",
                                       "rows flushed to sinks")
        self.m_lag = REGISTRY.gauge("consumer_lag", "bus messages behind")
        self.m_raw = REGISTRY.counter("raw_rows_archived",
                                      "rows archived to flows_raw")
        self.m_late = REGISTRY.gauge(
            "late_flows_dropped",
            "rows dropped because their sketch window had closed",
        )
        self.m_folded = REGISTRY.gauge(
            "late_flows_folded",
            "rows that came after their unit rolled and went into it, "
            "held open for -window.lateness",
        )
        self.m_proc = REGISTRY.summary("flow_processing_time_us",
                                       "per-batch processing time")
        # End-to-end watermark: the newest flow-export timestamp (window
        # end) whose rows are COMMITTED to the sinks, plus the
        # window-end -> sink-commit latency distribution. Registered
        # eagerly (not on first flush) so /metrics always carries the
        # families the dashboards chart.
        self.m_commit_wm = REGISTRY.gauge(
            "flow_commit_watermark_seconds",
            "newest flow-export timestamp (window end, epoch s) whose "
            "rows are committed to the sinks")
        self.m_commit_lat = REGISTRY.histogram(
            "flow_sink_commit_latency_seconds",
            "window end (flow export time) -> sink commit latency",
            buckets=COMMIT_LATENCY_BUCKETS)
        # host_fused phase counters (flowtrace): fed by the fused native
        # dataplane from the kernels' stats out-struct; the name/help
        # specs live in hostsketch.pipeline (the publisher) and are
        # registered here so the family exists — and scrapes as zeros —
        # on every worker, fused or not.
        from ..hostsketch.pipeline import (GROUPS_COUNTER, PHASE_COUNTERS,
                                           ROWS_COUNTER)

        REGISTRY.counter(*PHASE_COUNTERS["host_fused"])
        REGISTRY.counter(*ROWS_COUNTER)
        REGISTRY.counter(*GROUPS_COUNTER)
        # the degradation gauge likewise: the NativePathDegraded alert
        # must resolve against every worker's /metrics, not only those
        # whose pipeline selection happened to touch a native feature
        from .hostfused import _DEGRADED_GAUGE

        REGISTRY.gauge(*_DEGRADED_GAUGE)
        # sketchwatch families likewise registered eagerly (as zeros) on
        # every worker — the dashboard/alert honesty tests resolve the
        # sketch-health surface against this registration
        from ..obs.audit import register_audit_metrics

        register_audit_metrics()
        # runtime identity: what this worker ACTUALLY runs (native
        # capability set, trace mode, sketch backend) — dashboards and
        # bench artifacts join against it instead of trusting flags
        from ..obs.buildinfo import publish_build_info

        publish_build_info(config.build_role,
                           sketch_backend=config.sketch_backend,
                           hh_sketch=choice.hh_sketch)
        # flowlint: unguarded -- written by whichever single thread runs _write_rows (worker inline, or the one flusher thread)
        self._commit_watermark = 0.0
        # flowlint: unguarded -- worker thread only (set per _process step, read when queueing flush jobs)
        self._trace_chunk = -1
        # the bus's stamp on the newest batch applied (0.0: a transport
        # that does not stamp): a publish reckons its snapshot's age
        # from it
        # flowlint: unguarded -- worker thread only (set per _process step, read by the serve publisher on that thread)
        self.last_produced_at = 0.0
        # flow_stage_duration_us on every worker's /metrics, whichever
        # dataplane it picked (the dashboard's host_fused heatmap, the
        # honesty test); the worker itself times no stage: its spans do
        register_stage_histogram()
        if config.archive_raw:
            # fail fast on schema drift instead of crash-looping on 400s
            for sink in self.sinks:
                check = getattr(sink, "check_raw_schema", None)
                if check is not None:
                    check()

    # ---- main loop --------------------------------------------------------

    def run_once(self) -> bool:
        """Poll one batch through the pipeline. Returns False when idle."""
        if self.executor is not None:
            prep = self.executor.next()  # grouped off-thread (ingest)
            if prep is None:
                if self.guard.armed:
                    # idle = caught up: feed lag 0 so the ladder can
                    # step back up without needing fresh traffic
                    self.guard.observe(0.0)
                return False
            with self.lock:
                return self._process(prep.batch, prep)
        batch = self.consumer.poll(self.config.poll_max)
        if batch is None or len(batch) == 0:
            if self.guard.armed:
                self.guard.observe(0.0)
            return False
        with self.lock:
            return self._process(batch)

    def _prepare_admitted(self, batch):
        """Group-thread prepare with flowguard admission in FRONT of the
        grouping pass, so shed rows never pay pre-aggregation. A stale
        ``level`` read here sheds one batch at the previous level — the
        per-row scale factor keeps even that exact."""
        if self.guard.sample_shift > 0:
            batch, _ = self.guard.admit(batch)
            if len(batch) == 0:
                return _ShedPrep(batch)
        return self.fused.prepare(batch)

    def _process(self, batch, prep=None) -> bool:
        self._trace_chunk = getattr(batch, "chunk_id", -1)
        # one "apply" span per polled batch: everything the batch makes
        # the loop do (device steps, drain, flush, checkpoint, publish)
        # nests inside it
        with TRACER.span("apply", chunk=self._trace_chunk) as span:
            return self._process_batch(batch, prep, span)

    def _process_batch(self, batch, prep, span: dict) -> bool:
        t0 = time.perf_counter()
        # age of the batch's head (bus produce time -> this pickup);
        # unstamped transports (Kafka) report 0.0: the span then says
        # nothing and the guard's ladder simply never engages for them
        pa = self.last_produced_at = getattr(batch, "produced_at", 0.0)
        age = 0.0
        if pa > 0.0:
            age = time.time() - pa
            span["age_ms"] = age * 1e3
        guard = self.guard
        if guard.armed:
            # watermark lag, for the degradation ladder
            guard.observe(age)
            if prep is None and guard.sample_shift > 0:
                # serial path (no group thread): admit here instead
                batch, _ = guard.admit(batch)
            # level >= 1 drops optional work FIRST: every registered
            # family's audit cohort stops refreshing and the trace ring
            # stops recording before any data does
            for _kind, attr in registry.audit_attrs():
                shadow = getattr(self.fused, attr, None)
                if shadow is not None:
                    shadow.paused = guard.drop_optional
            TRACER.paused = guard.drop_optional
        if self.config.archive_raw:
            archived = False
            for sink in self.sinks:
                fn = getattr(sink, "archive_raw", None)
                if fn is not None:
                    self.m_raw.inc(fn(batch))
                    archived = True
            # Raw rows have no merge semantics to absorb replayed batches
            # (unlike the aggregate partials), so force the snapshot/commit
            # right after archiving: the duplicate exposure shrinks to a
            # crash inside the archive -> snapshot gap — the same
            # irreducible at-least-once window as sink flushes (_process
            # below), not snapshot_every batches' worth of raw rows.
            self._emitted_since_snapshot |= archived
        if len(batch) == 0:
            pass  # fully shed upstream; offsets still commit below
        elif prep is not None:
            self.fused.apply(prep)  # prepare ran on the group thread
        elif self.fused is not None:
            self.fused.update(batch)
        else:
            for model in self.models.values():
                model.update(batch)
        for name, model in self.models.items():
            dropped = getattr(model, "late_flows_dropped", None)
            if dropped:
                self.m_late.set(dropped, model=name)
            folded = getattr(model, "late_flows_folded", None)
            if folded:
                self.m_folded.set(folded, model=name)
        self.batches_seen += 1
        self.flows_seen += len(batch)
        self.m_flows.inc(len(batch))
        self.m_batches.inc()
        self.m_proc.observe((time.perf_counter() - t0) * 1e6)
        span["rows"] = len(batch)
        if len(batch):
            # read only (no close waits on a slower partition): the
            # watermark the units close by, and how far apart the
            # partitions' newest event times lie at this batch
            newest = self._newest_ts
            t = int(batch.columns["time_received"].max())
            if t > newest.get(batch.partition, -1):
                newest[batch.partition] = t
            span["watermark"] = max(newest.values())
            span["skew_s"] = span["watermark"] - min(newest.values())
        if batch.last_offset >= 0:
            prev = self._covered.get(batch.partition, 0)
            self._covered[batch.partition] = max(prev, batch.last_offset + 1)
        self.flush_closed()
        # Snapshot immediately after any flush that emitted rows: a replay
        # from an older snapshot would rebuild and re-emit those windows
        # (duplicate partials inflate merging sinks). With this coupling the
        # duplicate exposure shrinks to a crash inside the sink-write ->
        # snapshot gap — the irreducible at-least-once window without
        # transactional sinks.
        if self._emitted_since_snapshot or (
            self.config.snapshot_every
            and self.batches_seen % self.config.snapshot_every == 0
        ):
            self.snapshot_and_commit()
        if self.serve is not None:
            # flowserve publish decision (window close / refresh due):
            # runs HERE, under the lock the read path never takes —
            # extraction cost is paid per publish, never per query
            self.serve.on_batch(self)
        return True

    def run(self, max_batches: Optional[int] = None,
            stop_when_idle: bool = False) -> None:
        try:
            done = 0
            while max_batches is None or done < max_batches:
                if self.run_once():
                    done += 1
                elif stop_when_idle:
                    break
                else:
                    time.sleep(self.config.idle_sleep)
            self.finalize()
        except BaseException:
            # flight-recorder dump on the way down: the last ring's worth
            # of per-chunk spans is exactly the causality a post-mortem
            # needs, and it is gone once the supervisor restarts us
            path = TRACER.dump_on_error("worker")
            if path:
                log.error("worker error: flowtrace flight recorder "
                          "dumped to %s", path)
            raise
        finally:
            # A crash mid-loop (e.g. a sink raising in _emit) must not
            # leak the feed/group/flush threads: the group thread owns
            # the wrapped consumer, and with a real broker a zombie would
            # keep the partitions assigned while a supervisor-built
            # replacement starves. Best effort — never mask the original
            # exception.
            if self.executor is not None:
                try:
                    self.executor.stop()
                except Exception:  # noqa: BLE001
                    log.exception("ingest executor stop failed during unwind")
            if self.flusher is not None:
                try:
                    self.flusher.stop()
                except Exception:  # noqa: BLE001
                    log.exception("ingest flusher stop failed during unwind")
            if isinstance(self.consumer, PrefetchConsumer):
                try:
                    self.consumer.stop()
                except Exception:  # noqa: BLE001
                    log.exception("prefetch stop failed during unwind")

    # ---- flushing ---------------------------------------------------------

    def flush_closed(self, force: bool = False) -> None:
        """Emit rows for closed (or all, when force) windows to the sinks."""
        self._flush_closed(force)

    def sync_sketch_states(self) -> None:
        """Export host-backend sketch state into the models before a read
        (checkpoint, forced flush, live top-K query). No-op on the device
        backend, where model state is always current. Callers must hold
        self.lock (the worker loop does; query_api acquires it)."""
        sync = getattr(self.fused, "sync_states", None)
        if sync is not None:
            sync()

    def _flush_closed(self, force: bool) -> bool:
        if force:
            # force closes the OPEN window straight off model state;
            # mid-stream (force=False) closes go through the pipeline's
            # _advance_hh, which syncs itself
            self.sync_sketch_states()
        emitted = False
        for name, model in self.models.items():
            if isinstance(model, WindowAggregator):
                win = model.config.window_seconds
                if self.flusher is not None:
                    # detach the closed stores under the lock (cheap dict
                    # pops); row building + sink writes run on the flusher
                    stores = model.pop_closed(force)
                    if stores:
                        from ..models.window_agg import rows_from_stores

                        cfg = model.config
                        self._emit(name, lambda c=cfg, s=stores:
                                   rows_from_stores(c, s),
                                   export_ts=max(s for s, _ in stores)
                                   + win)
                        emitted = True
                else:
                    rows = model.flush(force)
                    if len(rows["timeslot"]):
                        self._emit(f"{name}", rows, len(rows["timeslot"]),
                                   export_ts=int(rows["timeslot"].max())
                                   + win)
                        emitted = True
            elif isinstance(model, WindowedHeavyHitter):
                for top in model.flush(force):
                    # dict, or an unresolved LazyWindowTop (lazy_extract):
                    # _emit materializes it wherever the write runs
                    self._emit(f"{name}", top,
                               export_ts=self._top_export_ts(model, top))
                    emitted = True
            elif isinstance(model, DDoSDetector):
                if force:
                    model.close_sub_window()
                if model.alerts:
                    alerts, model.alerts = model.alerts, []
                    self._emit(f"{name}", alerts, len(alerts))
                    emitted = True
        return emitted

    @staticmethod
    def _top_export_ts(model, top):
        """Window-end export timestamp for one flushed top-K window —
        dict rows carry a timeslot column, lazy handles the slot attr."""
        slot = getattr(top, "timeslot", None)
        if slot is None and isinstance(top, dict) and len(top["timeslot"]):
            slot = int(top["timeslot"][0])
        if slot is None:
            return None
        return int(slot) + model.window_seconds

    @staticmethod
    def _materialize(rows):
        """Rows as handed to _emit -> concrete columnar rows/list."""
        if callable(rows):
            return rows()
        if hasattr(rows, "resolve"):
            return rows.resolve()
        return rows

    @staticmethod
    def _row_count(rows) -> int:
        if isinstance(rows, dict):
            if "timeslot" in rows and "valid" not in rows:
                return len(rows["timeslot"])
            return int(rows["valid"].sum())
        return len(rows)

    def _emit(self, table: str, rows, n: Optional[int] = None,
              export_ts: Optional[float] = None) -> None:
        """Write rows (or a deferred producer of rows) to the sinks —
        inline, or via the background flusher when the ingest runtime is
        on. A flusher failure surfaces on the next submit/drain and fails
        that step BEFORE its offsets commit (at-least-once). export_ts
        (window end, epoch s) feeds the commit-latency watermark; the
        triggering chunk's id is captured here so flush spans stay tied
        to the chunk that closed the window, across the thread hop."""
        self._emitted_since_snapshot = True
        chunk = self._trace_chunk
        if self.flusher is not None:
            self.flusher.submit(
                lambda: self._write_rows(table, rows, n, export_ts, chunk))
            return
        self._write_rows(table, rows, n, export_ts, chunk)

    def _write_rows(self, table: str, rows, n: Optional[int],
                    export_ts: Optional[float] = None,
                    chunk: int = -1) -> None:
        # "flush_rows" and one "sink_put" a sink tile the "flush"
        with TRACER.span("flush", chunk=chunk, table=table) as span:
            with TRACER.span("flush_rows", chunk=chunk,
                             table=table) as made:
                rows = self._materialize(rows)
                n = self._row_count(rows) if n is None else n
                made["rows"] = n
            for sink in self.sinks:
                # a retry wrapper (ResilientSink) goes by the sink it
                # guards: its retries and backoff are that sink's time
                with TRACER.span("sink_put", chunk=chunk, table=table,
                                 sink=type(getattr(sink, "inner",
                                                   sink)).__name__,
                                 rows=n):
                    sink.write(table, rows)
            span["rows"] = n
        now = time.time()
        if export_ts is not None:
            # flow-export-timestamp -> sink-commit latency: how stale the
            # serving tables are relative to the traffic they describe.
            # A forced flush (shutdown) pops the still-OPEN window, whose
            # end lies in the future — clamp to now so the latency can't
            # go negative and the watermark never claims coverage beyond
            # wall clock (late rows for that window would be new partials)
            export_ts = min(export_ts, now)
            self.m_commit_lat.observe(now - export_ts, table=table)
            if export_ts > self._commit_watermark:
                self._commit_watermark = export_ts
                self.m_commit_wm.set(export_ts)
        self.m_rows.inc(n)
        log.info("flushed table=%s rows=%d", table, n)

    def finalize(self) -> None:
        """Drain everything (end of stream / shutdown)."""
        with self.lock:
            self.flush_closed(force=True)
            self.snapshot_and_commit()
            if self.serve is not None:
                # end-of-stream view: the final forced flush closed every
                # window; readers keep getting answers after the loop ends
                self.serve.publish(self)
        if hasattr(self.consumer, "lag"):
            self.m_lag.set(self.consumer.lag())
        if self.executor is not None:
            self.executor.stop()
        if self.flusher is not None:
            self.flusher.stop()
        if isinstance(self.consumer, PrefetchConsumer):
            self.consumer.stop()

    # ---- checkpoint / offsets --------------------------------------------

    def snapshot_and_commit(self) -> None:
        """Snapshot open state, then commit covered offsets. Order matters:
        state must be durable before the bus forgets the input."""
        state = None
        with TRACER.span("ckpt_state", chunk=self._trace_chunk) as span:
            if self.flusher is not None:
                # the snapshot no longer contains windows handed to the
                # flusher; their rows must be IN the sinks before the
                # state and offsets that forget them become durable — a
                # flush failure raises here and the step dies uncommitted
                # (replay)
                self.flusher.drain()
            if self.config.checkpoint_path:
                state = self._state()
                # the state's build has waited for the last step: its
                # live bound costs no wait of its own
                hh_live = getattr(self.fused, "hh_live", None)
                if hh_live is not None:
                    span.update(hh_live())
                # units held open for late rows, whose states this
                # checkpoint carries beside the open ones
                span["held_units"] = sum(
                    getattr(m, "held_unit", None) is not None
                    for m in self.models.values())
                # the spread detectors' register planes in it (a byte a
                # register whichever home they have), held ones too
                planes = [p["regs"].nbytes for ms in state["models"].values()
                          if ms.get("kind") == "windowed_spread"
                          for p in (ms["spread"]._asdict(),
                                    ms.get("held", {}).get("state"))
                          if p is not None]
                if planes:
                    span["spread_plane_bytes"] = sum(planes)
        if state is not None:
            # ckpt_d2h, ckpt_serialize, ckpt_write: inside save_checkpoint
            save_checkpoint(
                self.config.checkpoint_path, state,
                whole=getattr(self.fused, "checkpoint_whole", False))
        self._emitted_since_snapshot = False
        with TRACER.span("ckpt_commit", chunk=self._trace_chunk):
            for partition, next_off in sorted(self._covered.items()):
                self.consumer.commit(partition, next_off)
            if isinstance(self.consumer, PrefetchConsumer):
                # commits execute on the feed thread; wait so the
                # protocol's ordering (state durable -> offsets
                # committed) stays true
                self.consumer.flush_commits()
        if hasattr(self.consumer, "lag"):
            self.m_lag.set(self.consumer.lag())

    def _state(self) -> dict:
        # host-backend sketch state lives in the engine between syncs;
        # the snapshot must cover everything the committed offsets cover
        self.sync_sketch_states()
        models_state: dict[str, Any] = {}
        for name, model in self.models.items():
            fam = _model_family(model)
            if fam is not None:
                # backing models declare their checkpoint tag explicitly
                # (duck-typing on attribute names mis-dispatches the day
                # a model grows an attribute another kind uses); the
                # family registry owns the per-kind save hook
                models_state[name] = registry.hook(
                    fam, "checkpoint_save")(model)
            elif isinstance(model, DDoSDetector):
                # detector, not a mergeable family (NON_FAMILY_KINDS)
                models_state[name] = _with_held(model, {
                    "kind": "ddos",
                    "state": model.state,
                    "current_sub": model.current_sub,
                    "folds": model.folds,
                }, DDoSState._asdict)
        return {
            "covered": {str(k): v for k, v in self._covered.items()},
            "models": models_state,
            "batches_seen": self.batches_seen,
            "flows_seen": self.flows_seen,
        }

    def restore(self, path: Optional[str] = None) -> bool:
        """Rehydrate from the checkpoint; returns False if none exists.

        Per-kind state rehydration is the family registry's
        checkpoint_restore hook, dispatched on the checkpoint's own kind
        tag; unknown tags are skipped silently (exactly the pre-registry
        fall-through), kind/model mismatches skip loudly inside the
        hooks."""
        import jax.numpy as jnp

        from .checkpoint import checkpoint_exists

        path = path or self.config.checkpoint_path
        if not path or not checkpoint_exists(path):
            return False
        snap = load_checkpoint(path)
        self._covered = {int(k): v for k, v in snap["covered"].items()}
        self.batches_seen = snap["batches_seen"]
        self.flows_seen = snap["flows_seen"]
        for name, ms in snap["models"].items():
            model = self.models.get(name)
            if model is None:
                # e.g. a checkpoint written with -model.ports on, restarted
                # with it off: skip rather than crash-loop on a KeyError;
                # that model's state simply starts over if re-enabled later
                log.warning("checkpoint has state for unconfigured model "
                            "%r; skipping", name)
                continue
            fam = registry.family_for_checkpoint(ms["kind"])
            if fam is not None:
                registry.hook(fam, "checkpoint_restore")(model, ms, name)
            elif ms["kind"] == "ddos":
                def ddos_state(st: dict) -> DDoSState:
                    return DDoSState(
                        **{k: jnp.asarray(v) for k, v in st.items()})

                model.state = ddos_state(ms["state"])
                model.current_sub = ms["current_sub"]
                model.folds = ms["folds"]
                model.restore_held(ms.get("held"), ddos_state)
        # resume reading from the covered offsets, not the poll position
        for p, off in self._covered.items():
            if hasattr(self.consumer, "positions"):
                self.consumer.positions[p] = off
        return True


# ---- per-family checkpoint hooks (families/registry.py) -------------------
#
# save_*(model) -> the model's checkpoint state dict (including its
# "kind" tag); restore_*(model, ms, name) rehydrates one model from a
# decoded checkpoint entry, skipping LOUDLY on any shape/kind mismatch
# (that window's state starts over — never restore the wrong layout).


def _model_family(model):
    """Registered family owning one live model object, else None (DDoS
    detectors and unknown backings checkpoint outside the registry)."""
    if isinstance(model, WindowAggregator):
        return registry.family("wagg")
    if isinstance(model, WindowedHeavyHitter):
        return registry.family_for_snapshot(model.model.snapshot_kind)
    return None


def _kind_matches(model, ms: dict, name: str) -> bool:
    """The checkpoint's kind tag must match the live model's backing.
    e.g. a checkpoint from a build whose port models were sketch-backed
    restored into a dense-backed one: restoring the wrong state shape
    would silently lose the open window (and corrupt future snapshots);
    skip loudly instead — that window's sketch starts over."""
    want = getattr(getattr(model, "model", None), "snapshot_kind", None)
    if want != ms["kind"]:
        log.warning(
            "checkpoint kind %r does not match model %r backing (%r); "
            "skipping its state", ms["kind"], name, want)
        return False
    return True


def save_wagg_state(model) -> dict:
    """The window store's persistent form: for each open window its keys
    as one ``[G, lanes]`` uint32 array and its sums as one
    ``[G, nvals + 1]`` uint64 array: the store's own two arrays
    (WindowStore.snapshot), rows in key order. The checkpoint's count of
    npz members and its ``meta.json`` then do not grow with G, where an
    array a group and a JSON list a key made a window of 6x10^4 groups
    cost seconds to write and a minute to read."""
    model._drain()  # fold pending device partials first: the snapshot
    # must cover everything the committed offsets cover
    with TRACER.span("wagg_state", windows=len(model.windows)) as span:
        stores = []
        for slot, store in model.windows.items():
            keys, sums = store.snapshot()
            stores.append({"slot": slot, "keys": keys, "sums": sums})
        span["groups"] = sum(len(s["keys"]) for s in stores)
    return {
        "kind": "window_agg",
        "stores": stores,
        "watermark": model.watermark,
    }


def restore_wagg_state(model, ms: dict, name: str) -> None:
    """Reads both forms a checkpoint may hold: ``stores`` (above), adopted
    after one sort, and the ``windows`` dict of key tuples that builds
    before it wrote, which stays readable so that an operator upgrades
    across a restart and is turned into the two arrays here. The old
    form is slow by nature (an npz member and a JSON list a group to
    open and rebuild); nothing restores it faster than it was."""
    want = model.store_key_lanes
    if "stores" in ms:
        got = {int(s["keys"].shape[1]) for s in ms["stores"]}
    else:
        got = {len(k) for store in ms["windows"].values() for k in store}
    bad = next((n for n in got if n != want), None)
    if bad is not None:
        # a checkpoint from a different grouping layout (e.g.
        # pre-sampling builds without the rate lane): restoring
        # it would mis-split key tuples at flush and emit
        # garbage keys — skip loudly; open windows start over
        log.warning(
            "checkpoint window keys have %d lanes, model "
            "%r expects %d; skipping its window state",
            bad, name, want)
    elif "stores" in ms:
        model.windows = {
            int(s["slot"]): WindowStore.from_rows(s["keys"], s["sums"])
            for s in ms["stores"]}
    else:
        model.windows = {
            int(slot): WindowStore.from_rows(list(store),
                                             list(store.values()))
            for slot, store in ms["windows"].items() if store}
    model.watermark = ms["watermark"]


def _with_held(model, ms: dict, arrays=None) -> dict:
    """Under -window.lateness a unit that has rolled stays open for its
    late rows (models/held.py): a checkpoint taken meanwhile carries its
    state beside the open one, or the offsets committed after it would
    cover rows that are in no state and in no sink."""
    held = model.held_checkpoint(arrays or model.model.state_arrays)
    if held is not None:
        ms["held"] = held
    return ms


def _restore_held(model, ms: dict) -> None:
    model.restore_held(ms.get("held"), model.model.state_from_arrays)


def _with_ring(model, ms: dict) -> dict:
    """Under a slide the checkpoint also names the ring's closed
    sub-window states, each a member written once (SubWindowRing)."""
    if model.ring is not None:
        ms["ring"] = model.ring.checkpoint_state()
    return ms


def _restore_ring(model, ms: dict, name: str) -> None:
    if model.ring is None:
        if ms.get("ring"):
            log.warning("checkpoint holds a sliding window's ring for "
                        "model %r, which runs tumbling; dropping it", name)
    elif "ring" in ms:
        model.ring.restore(ms["ring"])


def save_hh_state(model) -> dict:
    return _with_held(model, _with_ring(model, {
        "kind": "windowed_hh",
        "hh": model.model.state,
        "current_slot": model.current_slot,
    }))


def restore_hh_state(model, ms: dict, name: str) -> None:
    if not _kind_matches(model, ms, name):
        return
    hh = ms["hh"]  # NamedTuple decoded as field dict
    inv_cfg = getattr(model.model.config, "hh_sketch",
                      "table") == "invertible"
    if ("keysum" in hh) != inv_cfg:
        # a table-family checkpoint restored into an
        # invertible-config model (or vice versa): the
        # state layouts do not convert — skip loudly,
        # that window's sketch starts over (the same
        # discipline as the kind-mismatch skip above)
        log.warning(
            "checkpoint hh state for model %r is %s "
            "but the model runs hh_sketch=%s; skipping "
            "its state", name,
            "invertible" if "keysum" in hh else "table",
            model.model.config.hh_sketch)
        return
    if inv_cfg:
        from ..models.heavy_hitter import InvState

        # numpy, NOT jnp: without x64 a jnp.asarray
        # would silently downcast the exact u64 planes
        model.model.state = InvState(
            cms=np.asarray(hh["cms"], dtype=np.uint64),
            keysum=np.asarray(hh["keysum"], dtype=np.uint64),
            keycheck=np.asarray(hh["keycheck"], dtype=np.uint64),
        )
    else:
        import jax.numpy as jnp

        model.model.state = HHState(
            cms=jnp.asarray(hh["cms"]),
            table_keys=jnp.asarray(hh["table_keys"]),
            table_vals=jnp.asarray(hh["table_vals"]),
        )
    model.current_slot = ms["current_slot"]
    _restore_ring(model, ms, name)
    _restore_held(model, ms)


def save_spread_state(model) -> dict:
    # the form that leaves the model: [depth, width, m] uint8 wherever
    # the state lives. A device state's leaves are device arrays still,
    # the registers narrowed there, so that save_checkpoint's ckpt_d2h
    # copies them once, a byte a register, as it copies every family's
    return _with_held(model, {
        "kind": "windowed_spread",
        "spread": model.model.leaving(),
        "current_slot": model.current_slot,
    })


def restore_spread_state(model, ms: dict, name: str) -> None:
    if not _kind_matches(model, ms, name):
        return
    # one form on disk whatever build wrote it (u8 registers + u32
    # table keys: the exact max monoid IS the canonical form); the
    # model places it where its dataplane keeps state
    model.model.state = model.model.state_from_arrays(ms["spread"])
    model.current_slot = ms["current_slot"]
    _restore_held(model, ms)


def save_dense_state(model) -> dict:
    return _with_held(model, _with_ring(model, {
        "kind": "windowed_dense",
        "totals": model.model.totals,
        "current_slot": model.current_slot,
    }))


def restore_dense_state(model, ms: dict, name: str) -> None:
    if not _kind_matches(model, ms, name):
        return
    import jax.numpy as jnp

    model.model.totals = jnp.asarray(ms["totals"])
    model.current_slot = ms["current_slot"]
    _restore_ring(model, ms, name)
    _restore_held(model, ms)
