"""flowserve publishers: the write side of the snapshot swap.

Two publishers share the store/ledger machinery:

- :class:`WorkerServePublisher` rides the StreamWorker's batch loop
  (``worker.serve`` hook, called under ``worker.lock`` on the worker
  thread): it publishes on the first batch, whenever a window closed
  since the last snapshot (a top-K slot advanced, or closed exact rows
  reached the range ledger), and at the ``-serve.refresh`` cadence for
  open-window freshness. Extraction cost (one device sync per top-K
  family) is paid HERE, once per publish — never per query.

- :class:`MeshServePublisher` runs its own thread next to the mesh
  coordinator: a window merge wakes it (``coordinator.serve`` hook) and
  the refresh cadence bounds open-window staleness between merges. It
  fans out to member state providers exactly like the pre-r14 per-query
  ``/topk mesh=`` path did — but per PUBLISH (one fan-out per top-K
  family, the provider protocol being per-model), so thousands of
  readers share one fan-out round instead of issuing one each.

Lock order, publish side: the worker publisher runs under worker.lock
and takes only the range ledger's lock inside it; the mesh publisher
takes coordinator._lock only through ``open_window_payloads`` (released
before any fan-out I/O). The READ side takes neither — that is the
whole point.
"""

from __future__ import annotations

# flowlint: lock-checked
# (worker publisher state mutates on the worker thread only, under
# worker.lock by construction of the `worker.serve` hook; the mesh
# publisher's state mutates on its own publisher thread only. The
# shared store/ledger carry their own contracts in serve/snapshot.py.)

import threading
import time
from typing import Optional

from ..engine.windowed import WindowedHeavyHitter
from ..families import registry
from ..models.heavy_hitter import key_width
from ..models.window_agg import WindowAggregator
from ..obs import get_logger
from ..obs.trace import TRACER
from .snapshot import FamilyView, RangeLedger, Snapshot, SnapshotStore

log = get_logger("serve")


# ---- per-family capture hooks (families/registry.py serve_capture) --------
#
# Worker side: (cms, key_lanes, regs) view parts for one live windowed
# model. Mesh side: (rows, cms, key_lanes, regs) for one merged spec, or
# None when no contribution exists yet. Registered by name in the
# SketchFamily descriptors so both publishers dispatch by iterating the
# registry instead of per-kind elif ladders.


def hh_view_parts(m: WindowedHeavyHitter):
    import numpy as np

    from ..hostsketch.state import frozen_cms
    from .snapshot import FrozenCms

    # under a slide the window a reader sees is the ring's fold
    planes = (m.model.state if m.ring is None else m.view_state()).cms
    if not isinstance(planes, np.ndarray):
        # device-backend jax array: hh_update DONATES its state arg,
        # so the next batch deletes these buffers on TPU/GPU — the
        # host copy must happen NOW, at publish. (Host-exported
        # states are already fresh numpy and safe to hold: they are
        # replaced, never mutated.) The expensive f32->u64 freeze
        # stays lazy either way — first estimate reader pays it.
        planes = np.asarray(planes)
    return FrozenCms(lambda a=planes: frozen_cms(a),
                     captured_bytes=planes.nbytes), key_width(m.config), \
        None


def spread_view_parts(m: WindowedHeavyHitter):
    from ..models.spread import spread_key_width

    regs = m.model.host_state().regs
    if not m.model.on_device:
        # the host update path mutates registers in place — the
        # snapshot must freeze its own copy (the immutability contract)
        regs = regs.copy()
    # a device state's host copy is the one this publish's top() made
    # (SpreadModel.host_state): the planes cross once, and nothing
    # mutates the copy
    return None, spread_key_width(m.config), regs


def dense_view_parts(m: WindowedHeavyHitter):
    return None, 1, None


def hh_merged_view(spec, slot, payloads):
    from ..mesh import merge as merge_ops
    from .snapshot import FrozenCms

    depth = spec.k or spec.config.capacity
    merged = merge_ops.merge_hh(payloads, spec.config)
    rows = merge_ops.hh_top_rows(merged, spec.config, depth, slot or 0)
    # the merge already materialized the u64 planes
    return rows, FrozenCms(value=merged["cms"]), key_width(spec.config), \
        None


def spread_merged_view(spec, slot, payloads):
    from ..mesh import merge as merge_ops
    from ..models.spread import spread_key_width

    if not payloads:
        return None
    depth = spec.k or spec.config.capacity
    merged = merge_ops.merge_spread(payloads, spec.config)
    rows = merge_ops.spread_top_rows(merged, spec.config, depth, slot or 0)
    return rows, None, spread_key_width(spec.config), merged["regs"]


def dense_merged_view(spec, slot, payloads):
    from ..mesh import merge as merge_ops

    if not payloads:
        return None
    depth = spec.k or spec.config.capacity
    totals = merge_ops.merge_dense(payloads)
    rows = merge_ops.dense_top_rows(totals, spec.config, depth, slot or 0)
    return rows, None, 1, None


def _family_from_model(name: str, m: WindowedHeavyHitter) -> FamilyView:
    """Freeze one windowed top-K model into a read view. Caller holds
    worker.lock and has synced sketch states, so ``m.model.state`` /
    ``.totals`` are current; ``top(depth)`` is the SAME extraction the
    locked query path runs, so a snapshot-served k-row answer is the
    locked answer's exact prefix. Under ``-window.slide`` both read the
    window that ends with the open sub-window: the ring's kept fold of
    its closed states merged with the open one (``SubWindowRing.view``:
    two states a publish, not K). The per-kind view parts come from the
    family registry's serve_capture hook (unknown snapshot kinds fall
    back to the dense shape, as before)."""
    depth = m.k
    # one "publish_view" a family: the first of a publish blocks on the
    # step in flight, and the capture is the planes' device->host copy
    with TRACER.span("publish_view", model=name) as span:
        rows = m.top(depth)
        fam = registry.family_for_snapshot(m.model.snapshot_kind) \
            or registry.family("dense")
        cms, lanes, regs = registry.hook(fam, "serve_capture")(m)
        span["rows"] = int(rows["valid"].sum())
        span["bytes"] = (sum(v.nbytes for v in rows.values())
                         + (cms.captured_bytes if cms is not None else 0)
                         + (regs.nbytes if regs is not None else 0))
    return FamilyView(
        name=name, kind=fam.kind,
        window_start=m.window_start,
        depth=int(len(rows["valid"])), rows=rows, key_lanes=lanes,
        cms=cms, value_cols=tuple(getattr(m.config, "value_cols", ())),
        regs=regs)


class WorkerServePublisher:
    """Publishes a single worker's snapshots from inside its batch loop."""

    def __init__(self, store: Optional[SnapshotStore] = None,
                 refresh: float = 2.0, range_slots: int = 0):
        self.store = store or SnapshotStore()
        self.refresh = refresh
        self.ledger = RangeLedger(
            (), **({"max_slots": range_slots} if range_slots else {}))
        # flowlint: unguarded -- worker thread only (on_batch/publish run under worker.lock on that thread)
        self._last_slots: dict[str, Optional[int]] = {}
        # flowlint: unguarded -- worker thread only
        self._last_gen = -1
        # flowlint: unguarded -- worker thread only
        self._last_publish = 0.0

    def attach(self, worker) -> "WorkerServePublisher":
        """Wire into a StreamWorker BEFORE it runs: the range ledger
        becomes one of its sinks (closed exact-window rows flow through
        the normal flush path) and the worker's per-batch hook points
        here."""
        self.ledger.tables |= {
            name for name, m in worker.models.items()
            if isinstance(m, WindowAggregator)}
        worker.sinks.append(self.ledger)
        worker.serve = self
        return self

    # ---- worker hooks (worker.lock held) -----------------------------------

    def on_batch(self, worker) -> None:
        """Per-batch publish decision: first snapshot, any window close
        since the last one, or the refresh cadence coming due."""
        gen = self.ledger.generation
        closed = gen != self._last_gen or any(
            m.current_slot != self._last_slots.get(name)
            for name, m in worker.models.items()
            if isinstance(m, WindowedHeavyHitter))
        # how long ago the cadence came due: the loop asks once a batch,
        # after its flush and checkpoint
        late = time.monotonic() - (self._last_publish + self.refresh)
        if self.store.current is None:
            self.publish(worker, reason="first")
        elif closed:
            self.publish(worker, reason="close")
        elif self.refresh > 0 and late >= 0:
            self.publish(worker, reason="refresh", late_s=late)

    def publish(self, worker, reason: str = "forced",
                late_s: float = 0.0) -> Snapshot:
        """Build + swap one snapshot. Caller holds worker.lock (the
        worker calls this from its own loop, which says why: ``first``,
        a window ``close``, the ``refresh`` cadence and how late it is;
        finalize and tests on a quiesced worker call it unasked)."""
        # "publish_view" a family + "publish_swap" tile it
        with TRACER.span("snapshot_publish",
                         chunk=getattr(worker, "_trace_chunk", None),
                         reason=reason, late_ms=late_s * 1e3) as span:
            return self._publish(worker, span)

    def _publish(self, worker, span: dict) -> Snapshot:
        t0 = time.monotonic()
        worker.sync_sketch_states()
        families = {}
        watermark = 0.0
        for name, m in worker.models.items():
            if isinstance(m, WindowedHeavyHitter):
                fam = _family_from_model(name, m)
                families[name] = fam
                self._last_slots[name] = m.current_slot
                if m.current_slot is not None:
                    watermark = max(watermark, float(m.current_slot))
            elif isinstance(m, WindowAggregator):
                watermark = max(watermark, float(m.watermark))
        self._last_gen = self.ledger.generation
        audit = None
        for _kind, attr in registry.audit_attrs():
            shadow = getattr(worker.fused, attr, None)
            if shadow is not None:
                # per-family shadow reports share the /query/audit
                # namespace — family names are distinct model names, so
                # a plain merge
                audit = {**(audit or {}), **shadow.last_reports}
        guard = getattr(worker, "guard", None)
        if guard is not None and guard.armed:
            # flowguard is never silent: snapshot metadata records the
            # sampling level the answers were built under, riding the
            # audit dict (which the gateway delta codec already diffs)
            # as a reserved pseudo-model key
            audit = dict(audit or {})
            audit["flowguard"] = guard.meta()
        with TRACER.span("publish_swap") as swap:
            ranges = self.ledger.freeze()
            snap = self.store.publish(
                watermark=watermark, flows_seen=worker.flows_seen,
                source="worker", families=families, ranges=ranges,
                # sketchwatch: the newest per-family close reports ride
                # the snapshot (read under worker.lock here; served
                # lock-free)
                audit=audit)
            swap["ranges"] = sum(len(slots) for slots in ranges.values())
        self._last_publish = time.monotonic()
        span.update(version=snap.version, flows_seen=snap.flows_seen,
                    families=len(families))
        # the snapshot's age at the swap: the bus's stamp on the newest
        # batch applied -> now (left out for a transport that does not
        # stamp)
        produced_at = getattr(worker, "last_produced_at", 0.0)
        if produced_at > 0.0:
            span["age_ms"] = (snap.created - produced_at) * 1e3
        log.debug("flowserve published v%d (%.1f ms, %d families)",
                  snap.version, (self._last_publish - t0) * 1e3,
                  len(families))
        return snap


class MeshServePublisher:
    """Publishes the mesh coordinator's MERGED view on its own thread."""

    def __init__(self, coordinator, store: Optional[SnapshotStore] = None,
                 refresh: float = 2.0, range_slots: int = 0,
                 err_backoff_base: float = 0.5,
                 err_backoff_max: float = 30.0,
                 err_log_interval: float = 30.0):
        self.coordinator = coordinator
        self.store = store or SnapshotStore()
        self.refresh = refresh
        # flowchaos failure-path discipline: exponential backoff between
        # failed publishes (a flapping member previously drove a retry —
        # and a full log.exception — every wake) and a rate limit on the
        # traceback logging; serve_publish_failures_total carries the
        # signal the suppressed log lines used to
        self.err_backoff_base = err_backoff_base
        self.err_backoff_max = err_backoff_max
        self.err_log_interval = err_log_interval
        # flowlint: unguarded -- publisher thread only
        self._fail_streak = 0
        # flowlint: unguarded -- publisher thread only
        self._last_err_log = 0.0
        self.ledger = RangeLedger(
            (), **({"max_slots": range_slots} if range_slots else {}))
        # flowlint: unguarded -- the events themselves; bound once
        self._wake = threading.Event()
        self._stop = threading.Event()  # flowlint: unguarded -- bound once
        # flowlint: unguarded -- publisher thread only after start(); attach() runs before it
        self._thread: Optional[threading.Thread] = None

    def attach(self) -> "MeshServePublisher":
        """Wire into the coordinator BEFORE members join: merged exact
        rows reach the range ledger through the coordinator's sink list;
        a completed merge wakes the publisher thread."""
        self.ledger.tables |= {s.name for s in self.coordinator.specs
                               if not registry.family(s.kind).ranked}
        self.coordinator.sinks.append(self.ledger)
        self.coordinator.serve = self
        return self

    def on_merge(self) -> None:
        """Coordinator hook (runs on the submitting member's thread, no
        coordinator lock held): schedule a publish, don't do the fan-out
        here — a member's submit path must not pay it."""
        self._wake.set()

    # ---- publisher thread --------------------------------------------------

    def start(self) -> "MeshServePublisher":
        self._thread = threading.Thread(
            target=self._run, name="serve-publish", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                self.publish_now()
                self._fail_streak = 0
            except Exception as e:  # noqa: BLE001 -- serving must outlive a flaky member fetch
                self._on_publish_error(e)
                # backoff honors the failure streak and IGNORES merge
                # wakes: a flapping member must not convert every merge
                # into an immediate doomed retry (+ a logged traceback)
                self._stop.wait(self._error_backoff())
                continue
            self._wake.wait(self.refresh if self.refresh > 0 else None)
            self._wake.clear()

    def _on_publish_error(self, exc: BaseException) -> None:
        """Count + rate-limit one failed publish. Readers keep the
        previous snapshot — the counter (and the backoff) are the
        operator signal, not a log flood."""
        self._fail_streak += 1
        self.store.m_publish_failures.inc()
        now = time.monotonic()
        if now - self._last_err_log >= self.err_log_interval:
            self._last_err_log = now
            log.exception("flowserve mesh publish failed (streak %d); "
                          "backing off %.1fs between retries "
                          "(serve_publish_failures_total counts the "
                          "suppressed repeats)",
                          self._fail_streak, self._error_backoff())
        else:
            log.debug("flowserve mesh publish failed (streak %d): %s",
                      self._fail_streak, exc)

    def _error_backoff(self) -> float:
        """Exponential in the failure streak, floored at the refresh
        cadence, capped at err_backoff_max."""
        base = max(self.err_backoff_base,
                   self.refresh if self.refresh > 0 else 0.0)
        return min(self.err_backoff_max,
                   base * (2 ** max(0, self._fail_streak - 1)))

    def publish_now(self) -> Snapshot:
        """One fan-out PER TOP-K FAMILY (the provider protocol is
        per-model) + merge + extract + swap — amortized over every
        reader until the next publish, where the pre-r14 path paid a
        fan-out per QUERY."""
        from ..utils.faults import FAULTS

        if FAULTS.active:  # flowchaos seam: a failed fan-out/publish —
            # readers keep the previous snapshot, the error path above
            # counts + backs off
            FAULTS.check("serve.publish")

        coord = self.coordinator
        families = {}
        for spec in coord.specs:
            fam = registry.family(spec.kind)
            capture = registry.hook(fam, "serve_capture_merged")
            if capture is None:
                continue  # wagg: exact rows ride the range ledger
            slot, payloads = coord.open_window_payloads(spec.name)
            parts = capture(spec, slot, payloads)
            if parts is None:
                continue
            rows, cms, lanes, regs = parts
            families[spec.name] = FamilyView(
                name=spec.name, kind=spec.kind, window_start=slot,
                depth=int(len(rows["valid"])), rows=rows,
                key_lanes=lanes, cms=cms,
                value_cols=tuple(getattr(spec.config, "value_cols", ())),
                regs=regs)
        return self.store.publish(
            watermark=float(coord.commit_watermark()), flows_seen=None,
            source="mesh", families=families, ranges=self.ledger.freeze(),
            # sketchwatch: the coordinator's NETWORK-WIDE audit reports
            # (merged cohort vs merged sketch, refreshed at merge time)
            audit=coord.audit_reports()
            if hasattr(coord, "audit_reports") else None)


def attach_worker(worker, refresh: float = 2.0,
                  store: Optional[SnapshotStore] = None,
                  ) -> WorkerServePublisher:
    """One-call wiring for a standalone worker (the cli path)."""
    return WorkerServePublisher(store, refresh=refresh).attach(worker)


def attach_mesh(coordinator, refresh: float = 2.0,
                store: Optional[SnapshotStore] = None,
                start: bool = True) -> MeshServePublisher:
    """One-call wiring for a mesh coordinator (the cli path). ``start``
    launches the publisher thread; tests pass False and drive
    ``publish_now`` deterministically."""
    pub = MeshServePublisher(coordinator, store, refresh=refresh).attach()
    if start:
        pub.start()
    return pub
