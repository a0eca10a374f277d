"""HostSketchPipeline: the `-sketch.backend=host` dataplane.

A HostGroupPipeline whose heavy-hitter apply half runs on the HOST
sketch engine instead of the jitted step — the prepare half (sharded
grouping, family cascade, padding) is inherited untouched, so the two
backends consume byte-identical group tables and bit-exact parity
reduces to the engine reproducing ``_apply_grouped``
(tests/test_hostsketch.py). Dense port scatters and the DDoS
accumulate keep the jitted path (they are cheap next to the CMS
scatter and have no host engine yet); flows_5m already bypasses the
device on the host-grouped pipeline.

State ownership: while streaming, sketch state lives in the engine's
uint64 buffers and the wrapped models' ``.state`` goes stale; every
read point syncs first — ``_advance_hh`` before a window close,
``StreamWorker.sync_sketch_states()`` before snapshots, forced
flushes, and live top-K queries. Staleness is tracked by object
identity: ``model.reset()`` and ``worker.restore()`` REPLACE the state
object, which the next apply detects and re-imports, so backend
switches at restore need no extra plumbing.
"""

from __future__ import annotations

# flowlint: lock-checked
# (every mutation below runs on the worker thread under worker.lock —
# apply() via _process, sync_states() via the worker's read hooks; the
# engine buffers are only ever touched from that context)

from typing import Optional

import numpy as np

from ..engine.hostfused import (
    HostGroupPipeline,
    PreparedChunk,
    _cached_apply,
    _degradation_reason,
    mark_native_serving,
    report_native_degradation,
)
from .state import HostInvState
from ..families import registry
from ..ingest.shard import ShardPool
from ..obs import REGISTRY, get_logger
from .engine import HostSketchEngine, sketch_backend_available

log = get_logger("hostsketch")

# flowtrace phase counters: the in-kernel attribution (radix/refine/
# regroup/fold/cms/prefilter/topk wall ns + row/group counts) the fused
# pass accumulates into its stats out-struct, re-published as Prometheus
# counters so the `host_fused` stage share can be broken down without
# attaching a profiler. Labels are the FF_STAT phase names. This table
# is the ONE definition of these families' names/help — StreamWorker
# imports it to pre-register them so /metrics carries the family (as
# zeros) on every worker, fused or not.
PHASE_COUNTERS = {
    "host_fused": (
        "host_fused_phase_ns_total",
        "host_fused stage wall ns by in-kernel phase "
        "(radix|refine|regroup|fold|cms|prefilter|topk)"),
    "host_sketch": (
        "host_sketch_phase_ns_total",
        "host_sketch (staged engine) wall ns by in-kernel phase"),
    "host_group": (
        "host_group_phase_ns_total",
        "host_group ff_group_sum wall ns by in-kernel phase"),
}
ROWS_COUNTER = ("host_fused_rows_total",
                "rows through the fused native dataplane")
GROUPS_COUNTER = ("host_fused_groups_total",
                  "groups produced by the fused native dataplane")

# registry native-probe feature -> flow_pipeline_tpu.native gate; the
# families own the (feature, symbol, revision) facts, this module owns
# how a probe is answered on this box
_PROBE_AVAIL = {
    "fused": "fused_available",
    "invsketch": "inv_available",
    "spread": "spread_available",
}


def _probe_reason(kind: str, feature: str) -> str:
    """Degradation reason for a registered family's native probe: the
    family descriptor owns the (symbol, revision) pair, so a new probe
    never hand-copies them into this module again."""
    for feat, symbol, rev in registry.family(kind).native_probes:
        if feat == feature:
            return _degradation_reason(symbol, rev)
    raise KeyError(f"family {kind!r} has no native probe {feature!r}")


def _publish_stats(stage: str, stats) -> None:
    """Fold one zeroed-then-accumulated stats buffer into the stage's
    phase counters (cheap: a handful of locked adds per chunk)."""
    from .. import native

    ctr = REGISTRY.counter(*PHASE_COUNTERS[stage])
    for phase, slot in native.FF_STAT_SLOTS.items():
        v = int(stats[slot])
        if v:
            ctr.inc(v, phase=phase)
    if stage == "host_fused":
        rows = int(stats[native.FF_STAT_ROWS])
        groups = int(stats[native.FF_STAT_GROUPS])
        if rows:
            REGISTRY.counter(*ROWS_COUNTER).inc(rows)
        if groups:
            REGISTRY.counter(*GROUPS_COUNTER).inc(groups)


class HostSketchPipeline(HostGroupPipeline):
    """Host-grouped pipeline with the native host sketch apply half.

    ``fused`` selects the single-pass native dataplane (-ingest.fused):
    "on"/"auto" route every hh family tree through ``ff_fused_update`` —
    radix groupby, cascade regroup AND CMS/prefilter/top-K updates in
    one C pass at apply time, no intermediate group rows surfacing to
    Python — while "off" (and any box whose library predates the fused
    exports) keeps the staged prepare/apply split, which doubles as the
    bit-exact parity reference (tests/test_fusedplane.py)."""

    # the engine's u64 planes are where an invertible family's state
    # lives; the jitted table step cannot fold it
    serves_invertible = True

    def __init__(self, models: dict, shards: int = 0,
                 native_group: bool = False,
                 pool: Optional[ShardPool] = None,
                 sketch_native: str = "auto",
                 fused: str = "auto",
                 audit: str = "off",
                 threads: int = 0):
        super().__init__(models, shards=shards, native_group=native_group,
                         pool=pool, audit=audit)
        # -ingest.threads: one thread source for the whole fused/staged
        # dataplane (engine kernels, the fused pass, lane building, the
        # wagg fold) — 0 keeps the engine's conservative auto count
        self._engine = HostSketchEngine(
            [w.config for _, w in self._hh], use_native=sketch_native,
            threads=threads)
        self._native_ladder("sketch", self._engine.native,
                            _degradation_reason("hs_cms_update", "r8"),
                            sketch_native)
        # The jitted rest-step covers what the engine does not: dense
        # port scatters + the DDoS accumulate. Same module-level cache
        # as the full apply, keyed with no hh families.
        self._apply_rest = _cached_apply(
            (), tuple(w.config for _, w in self._dense),
            tuple(d.config for _, d in self._ddos),
        ) if (self._dense or self._ddos) else None
        # Identity tokens of the HHState objects the engine's buffers
        # mirror: `model.state is not token` means reset()/restore()
        # swapped the state under us -> re-import before the next fold.
        # flowlint: unguarded -- worker thread only (apply/sync under worker.lock)
        self._shadow: list = [None] * len(self._hh)
        # flowlint: unguarded -- worker thread only (apply/sync under worker.lock)
        self._sketch_dirty: list = [False] * len(self._hh)
        # flowlint: unguarded -- resolved once at construction (_init_fused), read-only after
        self._fused: bool = False
        # flowlint: unguarded -- built once at construction (_init_fused), read-only after
        self._fused_trees: list = []
        # flowtrace stats buffers, one per thread context: the apply
        # half (fused pass / staged engine) runs on the worker thread,
        # the prepare half (ff_group_sum) on the ingest group thread —
        # sharing one buffer would race the accumulation.
        # flowlint: unguarded -- worker thread only (audited chunk counter for the throttled churn probe)
        self._audit_chunks = 0
        # flowlint: unguarded -- worker thread only (apply half)
        self._apply_stats = None
        # flowlint: unguarded -- group thread only (prepare half)
        self._group_stats = None
        # flowspread fold knobs, resolved by _init_family_folds below
        # flowlint: unguarded -- set during construction, read on the worker thread only (fold half)
        self._spread_threads = 1
        # flowlint: unguarded -- built during construction; zeroed/accumulated on the worker thread only
        self._spread_stats = None
        # r19 flowspeed: lanes built in C off the decoded columns when
        # the library exports the builders; the numpy twins
        # (_key_lanes_into / _value_planes_np / the wagg fill) remain
        # the bit-exact fallback. Degradation reporting rides
        # _init_fused (the engine must be native for it to matter).
        # flowlint: unguarded -- resolved once at construction, read-only after
        self._native_lanes = False
        from .. import native as _native

        if _native.available():
            self._apply_stats = _native.new_stats()
            self._group_stats = _native.new_stats()
        if self._engine.native:
            self._native_lanes = self._native_ladder(
                "lanes", _native.lanes_available(),
                _degradation_reason("ff_build_lanes", "r19"),
                sketch_native)
        self._init_fused(fused, sketch_native)
        self._init_family_folds(sketch_native)

    # ---- per-family fold knobs ---------------------------------------------

    # families whose fold runs standalone on the host (outside the
    # fused/staged hh plan) and therefore owns a threads + stats pair
    _FOLD_FAMILIES = ("spread",)

    def _native_ladder(self, feature: str, available: bool,
                       reason: str, sketch_native: str) -> bool:
        """One rung of the loud-degradation ladder every native feature
        shares: serving marks the gauge, a stale .so under a native
        flag reports the degradation (the explicit numpy opt-out stays
        silent). Returns whether the feature serves natively."""
        if available:
            mark_native_serving(feature)
            return True
        if sketch_native != "numpy":
            report_native_degradation(feature, reason)
        return False

    def _init_family_folds(self, sketch_native: str) -> None:
        """Resolve every standalone family fold's backend knobs from
        the registry's native probes. Each fold (today: spread, whose
        inherited _fold_spread prefers the native hs_spread_update
        kernel) gets the same triple _init_fused hand-rolls for the
        fused pass — a thread count, a dedicated flowtrace stats
        buffer, and the ladder discipline: a stale .so quietly serving
        the numpy twin under a native flag must be LOUD, like every
        other feature."""
        from .. import native

        for kind in self._FOLD_FAMILIES:
            setattr(self, f"_{kind}_threads", self._engine.threads)
            if not getattr(self, f"_{kind}"):
                continue
            for feature, symbol, rev in registry.family(kind).native_probes:
                avail = getattr(native, _PROBE_AVAIL[feature])()
                if self._native_ladder(
                        feature, avail, _degradation_reason(symbol, rev),
                        sketch_native):
                    # flowtrace buffer for the kernel's stats slot — its
                    # own buffer (worker thread), not _apply_stats: the
                    # staged engine zeroes that one per hh chunk
                    setattr(self, f"_{kind}_stats", native.new_stats())

    def _fold_spread(self, ch: PreparedChunk) -> None:
        stats = self._spread_stats
        if stats is not None:
            stats[:] = 0
        super()._fold_spread(ch)
        if stats is not None:
            _publish_stats("host_sketch", stats)

    # ---- native lane building (r19 flowspeed) ------------------------------

    def _native_build(self, fn, *args, **kw):
        """Run one lane-builder kernel on the prepare half's stats
        buffer and publish its `lanes` phase wall under host_group (the
        stage that wraps the prepare half)."""
        stats = self._group_stats
        if stats is not None:
            stats[:] = 0
        out = fn(*args, threads=self._engine.threads, stats=stats, **kw)
        if stats is not None:
            _publish_stats("host_group", stats)
        return out

    def _build_key_lanes(self, cols, key_cols):
        if not self._native_lanes:
            return super()._build_key_lanes(cols, key_cols)
        from .. import native

        return self._native_build(
            native.build_lanes, [cols[name] for name in key_cols])

    def _build_value_planes(self, cols, value_cols, scale_col):
        if not self._native_lanes:
            return super()._build_value_planes(cols, value_cols,
                                               scale_col)
        from .. import native

        return self._native_build(
            native.build_planes_f32,
            [cols[name] for name in value_cols],
            scale=cols[scale_col] if scale_col else None)

    def _build_wagg_inputs(self, cfg, cols, n):
        if not self._native_lanes:
            return super()._build_wagg_inputs(cfg, cols, n)
        from .. import native

        columns = [cols["time_received"]]
        mods = [cfg.window_seconds]
        for name in cfg.key_cols:
            columns.append(cols[name])
            mods.append(0)
        if cfg.scale_col:
            columns.append(cols[cfg.scale_col])
            mods.append(0)
        lanes = self._native_build(native.build_lanes, columns,
                                   mods=mods)
        planes = self._native_build(
            native.build_planes_u64,
            [cols[name] for name in cfg.value_cols])
        return lanes, planes

    # ---- fused dataplane plan ---------------------------------------------

    def _init_fused(self, fused: str, sketch_native: str) -> None:
        """Resolve the -ingest.fused mode and precompute the per-tree
        FusedPlan parameter blocks (static per pipeline; only lanes,
        value planes and state pointers vary per chunk)."""
        from .. import native

        if fused not in ("auto", "on", "off"):
            raise ValueError(f"fused must be auto|on|off, got {fused!r}")
        any_inv = any(
            getattr(w.config, "hh_sketch", "table") == "invertible"
            for _, w in self._hh)
        can = native.fused_available() and self._engine.native
        if any_inv and can and not native.inv_available():
            # an .so with the fused plane but no hs_inv_update predates
            # the invertible trailer on ff_fused_update — routing an
            # invertible tree through it would run the table path on
            # the wrong state layout (the degradation is reported once,
            # below, with the staged engine's)
            can = False
        if fused == "on" and not can:
            raise RuntimeError(
                "ingest.fused=on but the fused native dataplane cannot "
                "serve: " + ("the sketch engine is not native"
                             if not self._engine.native else
                             _probe_reason("hh", "fused")
                             if not native.fused_available() else
                             _probe_reason("hh", "invsketch")))
        self._fused = fused != "off" and can
        if any_inv and self._engine.native:
            # the staged engine ALSO routes invertible families through
            # hs_inv_update: a stale .so quietly serving the numpy twin
            # under a native flag must be loud (gauge + warning), and
            # the healthy 0 published explicitly like every feature
            self._native_ladder("invsketch", native.inv_available(),
                                _probe_reason("hh", "invsketch"),
                                sketch_native)
        if fused == "auto" and not can and sketch_native != "numpy":
            # production default wanted the fused plane: degrading to the
            # staged path must be loud (same contract as native_group)
            report_native_degradation(
                "fused", _probe_reason("hh", "fused")
                if not native.fused_available()
                else _probe_reason("hh", "invsketch")
                if any_inv and not native.inv_available()
                else "sketch engine is not native")
        elif self._fused:
            mark_native_serving("fused")
        if not self._fused:
            return  # staged mode never reads the tree plans
        # Family trees from _fam_plan: each "own" family roots a tree;
        # every cascade family joins its (possibly chained) parent's
        # tree, parents placed before children — the order ff_fused_
        # update requires.
        members: dict[int, list[int]] = {}
        root_of: dict[int, int] = {}
        for i, plan in enumerate(self._fam_plan):
            if plan[0] == "own":
                members[i] = [i]
                root_of[i] = i
        pending = [i for i, pl in enumerate(self._fam_plan)
                   if pl[0] == "cascade"]
        while pending:
            rest = []
            for i in pending:
                parent = self._fam_plan[i][1]
                if parent in root_of:
                    r = root_of[parent]
                    members[r].append(i)
                    root_of[i] = r
                else:
                    rest.append(i)
            assert len(rest) < len(pending), "cascade chain has no root"
            pending = rest
        cfgs = [w.config for _, w in self._hh]
        self._fused_trees = []
        for root in sorted(members):
            ms = members[root]
            pos = {fam: k for k, fam in enumerate(ms)}
            parent = [-1]
            sel: list[int] = []
            sel_off = [0, 0]  # root consumes no selection
            for fam in ms[1:]:
                _, par, fsel = self._fam_plan[fam]
                parent.append(pos[par])
                sel.extend(fsel)
                sel_off.append(len(sel))
            ddos_parent, ddos_sel, ddos_plane = -1, None, -1
            if (self._ddos_plan is not None
                    and self._ddos_plan[0] == "cascade"
                    and self._ddos_plan[1] in pos):
                _, dpar, dsel, dplane = self._ddos_plan
                ddos_parent = pos[dpar]
                ddos_sel = np.asarray(dsel, np.int64)
                ddos_plane = dplane
            self._fused_trees.append((ms, native.FusedPlan(
                parent=np.asarray(parent, np.int64),
                sel=np.asarray(sel, np.int64),
                sel_off=np.asarray(sel_off, np.int64),
                depth=np.asarray([cfgs[f].depth for f in ms], np.int64),
                width=np.asarray([cfgs[f].width for f in ms], np.int64),
                cap=np.asarray([cfgs[f].capacity for f in ms], np.int64),
                conservative=np.asarray(
                    [cfgs[f].conservative for f in ms], np.uint8),
                prefilter=np.asarray(
                    [cfgs[f].table_prefilter for f in ms], np.uint8),
                admission_plain=np.asarray(
                    [cfgs[f].table_admission == "plain" for f in ms],
                    np.uint8),
                ddos_parent=ddos_parent, ddos_sel=ddos_sel,
                ddos_plane=ddos_plane,
                invertible=np.asarray(
                    [getattr(cfgs[f], "hh_sketch", "table")
                     == "invertible" for f in ms], np.uint8))))

    # ---- prepare half (fused: lane extraction only) ------------------------

    def _prepare_chunk(self, cols: dict, n: int) -> PreparedChunk:
        if not self._fused:
            return super()._prepare_chunk(cols, n)
        # Fused dataplane: NO hh group tables here — grouping + cascade +
        # sketch all happen in one native pass at apply time. The
        # prepare half only extracts lanes/planes (vectorized numpy) and
        # keeps the inputs the jitted rest-step still needs.
        wagg = [self._wagg_rows(m, cols, n) for _, m in self._waggs]
        ddos_in = None
        if self._ddos_plan is not None and self._ddos_plan[0] == "own":
            # no hh family carries dst_addr: group raw rows exactly like
            # the staged path — this table never rides the fused pass
            dcfg = self._ddos[0][1].config
            lanes = self._build_key_lanes(cols, ("dst_addr",))
            vals = self._build_value_planes(
                cols, (dcfg.value_col,), dcfg.scale_col)[:, 0]
            uniq, sums, _ = self._group(lanes, [vals], exact=False)
            ddos_in = self._pad_ddos(uniq, sums[0].astype(np.float32))
        fused_in = []
        for ms, _plan in self._fused_trees:
            cfg = self._hh[ms[0]][1].config
            # lanes built in ONE pass — natively off the decoded
            # columns when the library exports the builders (r19), else
            # straight into one preallocated numpy buffer (r16): the
            # extraction IS this path's prepare cost (ROADMAP 4a)
            lanes = self._build_key_lanes(cols, cfg.key_cols)
            vals = self._build_value_planes(cols, cfg.value_cols,
                                            cfg.scale_col)
            fused_in.append((lanes, vals))
        audit_in = None
        if self.audit is not None:
            # audit pre-extraction on the prepare half (group thread):
            # the per-family hash+mask over raw lanes is the audit's
            # whole hot-path cost, and it overlaps the worker here
            audit_in = [(name, self.audit.prepare_rows(name, fl, vals))
                        for tree, (lanes, vals) in zip(self._fused_trees,
                                                       fused_in)
                        for name, fl in self._audit_family_lanes(tree,
                                                                 lanes)]
        # spread families keep the staged pair grouping even in fused
        # mode: their (key + counted element) grouping key cannot ride
        # the hh family trees, and the pair tables are the fold's input
        return PreparedChunk(wagg, None, self._prep_dense(cols, n),
                             ddos_in, fused_in, audit_in,
                             spread_in=(self._prep_spread(cols)
                                        if self._spread else None))

    def _audit_family_lanes(self, tree, lanes: np.ndarray):
        """Yield (family name, key-lane view) for every member of one
        fused tree: the root consumes the raw lanes, each cascade
        member its (possibly chained) parent's lane projection. Strided
        VIEWS only — the audit copies just the sampled subset. The ONE
        definition of the projection rule, shared by the prepare-half
        pre-extraction and the unsplit _audit_chunk fallback."""
        ms, plan = tree
        proj = [lanes]
        for k, fam in enumerate(ms):
            if k > 0:
                sel = [int(x) for x in plan.sel[
                    int(plan.sel_off[k]):int(plan.sel_off[k + 1])]]
                proj.append(proj[int(plan.parent[k])][:, sel])
            yield self._hh[fam][0], proj[k]

    def _group_exact_planes(self, lanes: np.ndarray, planes: np.ndarray):
        if self._fused:
            from .. import native

            stats = self._group_stats
            if stats is not None:
                stats[:] = 0
            # the wagg fold rides the threaded r19 kernel (grouping +
            # per-group-range u64 fold — exact, bit-identical at any
            # thread count); a pre-r19 .so serves the serial path
            res = native.group_sum(lanes, planes, stats=stats,
                                   threads=self._engine.threads)
            if stats is not None:
                _publish_stats("host_group", stats)
            if res is not None:
                return res
            # 64-bit hash collision between distinct keys (~n^2/2^65):
            # the staged path takes its exact lexicographic fallback
        return super()._group_exact_planes(lanes, planes)

    # ---- apply half --------------------------------------------------------

    def _timed_apply_chunk(self, ch: PreparedChunk, do_hh: bool,
                           do_dd: bool) -> None:
        # split attribution: host_fused is the single-pass native
        # dataplane, host_sketch the staged engine, device_apply what
        # remains jitted — so the A/B's per-stage budget compares the
        # same seam under every backend/mode combination
        self._apply_chunk(ch, do_hh, do_dd)

    def _run_fused(self, ch: PreparedChunk, do_hh: bool, do_dd: bool):
        """The single native pass per family tree: group + cascade +
        sketch-update in ff_fused_update. Returns the padded ddos table
        when one tree carries the per-dst cascade (else ch.ddos_in,
        which holds the "own"-grouped table or None)."""
        from .. import native

        ddos_in = ch.ddos_in
        need_ddos = do_dd and any(
            plan.ddos_parent >= 0 for _, plan in self._fused_trees)
        if not (do_hh or need_ddos):
            return ddos_in
        stats = self._apply_stats
        if stats is not None:
            stats[:] = 0
        with self.stages.stage("host_fused"):
            for (ms, plan), (lanes, vals) in zip(self._fused_trees,
                                                 ch.fused_in):
                tree_ddos = plan.ddos_parent >= 0
                if not (do_hh or (need_ddos and tree_ddos)):
                    continue
                states = None
                if do_hh:
                    for i in ms:
                        self._ensure_imported(i)
                    states = [self._engine.states[i] for i in ms]
                # do_dd False: _apply_chunk would discard the table —
                # skip the native per-dst regroup and its output buffers
                res = native.fused_update(lanes, vals, plan, states,
                                          do_sketch=do_hh,
                                          do_ddos=need_ddos and tree_ddos,
                                          threads=self._engine.threads,
                                          stats=stats)
                if do_hh:
                    for i in ms:
                        self._sketch_dirty[i] = True
                if res is not None:
                    ddos_in = self._pad_ddos(res[0], res[1])
        if stats is not None:
            _publish_stats("host_fused", stats)
        return ddos_in

    def _apply_chunk(self, ch: PreparedChunk, do_hh: bool,
                     do_dd: bool) -> None:
        raw_ddos = ch.ddos_in
        if ch.fused_in is not None:
            raw_ddos = self._run_fused(ch, do_hh, do_dd)
        elif do_hh and ch.hh_in is not None:
            stats = self._apply_stats if self._engine.native else None
            if stats is not None:
                stats[:] = 0
            with self.stages.stage("host_sketch"):
                for i, (u, s, g) in enumerate(ch.hh_in):
                    self._ensure_imported(i)
                    self._engine.update(i, u, s, g, stats=stats)
                    self._sketch_dirty[i] = True
            if stats is not None:
                _publish_stats("host_sketch", stats)
        # do_hh False is a late part: the jitted path would run the merge
        # with all-invalid candidates, a proven no-op — skipping is exact.
        if self._apply_rest is None:
            return
        dense_in = ch.dense_in if (self._dense and do_hh) else None
        ddos_in = None
        if raw_ddos is not None and do_dd:
            u, s, g = raw_ddos
            v = np.zeros(u.shape[0], bool)
            v[:g] = True
            ddos_in = (u, s, v)
        if dense_in is None and ddos_in is None:
            return
        with self.stages.stage("device_apply"):
            states = (
                (),
                tuple(w.model.totals for _, w in self._dense),
                tuple(d.state for _, d in self._ddos),
            )
            _, new_dense, new_ddos = self._apply_rest(
                states, (), dense_in, ddos_in)
            if dense_in is not None:
                for (_, w), tot in zip(self._dense, new_dense):
                    w.model.totals = tot
            for (_, d), st in zip(self._ddos, new_ddos):
                d.state = st

    # ---- sketchwatch hooks -------------------------------------------------

    def _audit_chunk(self, ch: PreparedChunk) -> None:
        """Fused chunks carry RAW rows (no group tables surface to
        Python): the root family audits the lanes directly, cascade
        members audit their parent's lane projection — each raw row
        contributes its per-row uint64 addend plus count 1, which on
        the exact envelope telescopes to the same totals the staged
        group tables fold (obs/audit.py states the argument). The
        prepare half normally pre-extracts (ch.audit_in, group
        thread); the raw-rows path below covers unsplit callers."""
        if ch.audit_in is not None or ch.fused_in is None:
            super()._audit_chunk(ch)
        else:
            for tree, (lanes, vals) in zip(self._fused_trees,
                                           ch.fused_in):
                for name, fl in self._audit_family_lanes(tree, lanes):
                    self.audit.observe_rows(name, fl, vals)
        # admission-churn probe off the host-resident tables (the
        # engine's buffers — current after the fold above, no sync).
        # Every 8th chunk: churn is a rate signal, not part of the
        # exactness envelope, and hashing capacity rows per family per
        # chunk is pure audit overhead otherwise
        self._audit_chunks += 1
        if self._audit_chunks % 8 == 1:
            for i, (name, _) in enumerate(self._hh):
                st = self._engine.states[i]
                if st is not None and not isinstance(st, HostInvState):
                    # invertible families have no candidate table — the
                    # admission churn this probe measures does not exist
                    self.audit.note_table(name, st.table_keys)

    # ---- state synchronization --------------------------------------------

    def _ensure_imported(self, i: int) -> None:
        model = self._hh[i][1].model
        if model.state is not self._shadow[i]:
            # reset()/restore() replaced the state object: adopt it
            self._engine.import_state(i, model.state)
            self._shadow[i] = model.state
            self._sketch_dirty[i] = False

    def sync_states(self) -> None:
        """Export engine state back into the wrapped models so reads
        (window close, checkpoint, live queries) see current sketches.
        Cheap when nothing folded since the last sync."""
        for i, (_, w) in enumerate(self._hh):
            if not self._sketch_dirty[i]:
                continue
            state = self._engine.export_state(i)
            w.model.state = state
            self._shadow[i] = state
            self._sketch_dirty[i] = False

    def _advance_hh(self, slot: int, n_rows: int) -> bool:
        cur = self._whh[0].current_slot if self._whh else None
        if cur is not None and slot > cur:
            # the close extracts top-K from model state: sync first
            self.sync_states()
        return super()._advance_hh(slot, n_rows)
