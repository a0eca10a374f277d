"""Host-grouped fused step: the CPU-backend twin of engine.fused.

Same model surface, same window lifecycle (it IS a FusedPipeline
subclass — the cut at slot/sub boundaries and the lifecycle advancement
are WindowLifecycle's; each (slot, sub) group is compacted into a part
of its own here, where FusedPipeline runs masks over rows in place),
different pre-aggregation substrate: batches are
grouped on the HOST with numpy (ops.hostgroup — ~20x cheaper than
XLA:CPU's single-threaded lax.sort on one core) and only the compact
group tables cross into the XLA step, which keeps what XLA is still
best at even on CPU: the CMS scatter updates, top-K table merges and
dense port scatters, in ONE dispatch per chunk.

Additional wins over the device-sorted path on CPU:

- flows_5m bypasses the device entirely: the host groupby is already
  exact in uint64, so rows fold straight into the window store
  (WindowAggregator.add_host_rows) — no 16-bit planes, no partial
  queue, no collision fallback machinery.
- Sketch families cascade: the finest key family (the 5-tuple top
  talkers) is grouped once from raw rows, and every family whose key
  set is a subset (src-IP, dst-IP) regroups the ~8-12k GROUP rows
  instead of 32k raw rows. The DDoS per-dst accumulate reads the dst
  family's table for free.
- Group tables are padded to a shared power-of-two bucket, so the XLA
  step sees a handful of static shapes and its CMS/top-K cost scales
  with actual batch cardinality, not the raw batch size.

Model selection lives in StreamWorker: host_assist="auto" picks this
pipeline iff the default backend is CPU ("on"/"off" force/forbid).
The TPU path is engine.fused, unchanged — this module is why the same
framework is honest on both: each backend gets the pre-aggregation its
memory hierarchy wants.

Equivalence vs the device-sorted pipeline (and transitively the
unfused per-model path) is proven in tests/test_hostfused.py, late
rows and window boundaries included.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import numpy as np

from ..ingest.shard import ShardPool, group_by_key_sharded, shared_pool
from ..models import heavy_hitter as hh
from ..models.ddos import _accumulate_grouped
from ..models.dense_top import dense_update
from ..models.spread import SpreadState, spread_key_width
from ..obs import REGISTRY, get_logger
from ..obs.trace import TRACER
from ..obs.tracing import StageTimer
from ..ops.hostgroup import native_group_available, select_lanes
from ..schema.batch import FlowBatch, lane_width
from .fused import FusedPipeline

log = get_logger("hostfused")

_DEGRADED_GAUGE = (
    "native_path_degraded",
    "1 when a requested native dataplane feature fell back to the slow "
    "path (label: feature) — benchmarks must check this is 0",
)


def report_native_degradation(feature: str, reason: str) -> None:
    """A requested native-dataplane feature falling back to numpy must be
    LOUD: a startup warning AND a scrapeable gauge. A log line alone let
    pre-r6 .so builds quietly serve numpy grouping under benchmarks that
    believed they measured the C kernel."""
    REGISTRY.gauge(*_DEGRADED_GAUGE).set(1, feature=feature)
    log.warning(
        "NATIVE PATH DEGRADED [%s]: %s — throughput from this process "
        "measures the fallback path; run `make native` (or rebuild the "
        "stale .so) for the fast path", feature, reason)


def mark_native_serving(feature: str) -> None:
    """Publish the healthy 0 explicitly so dashboards and bench capture
    can assert on the series instead of inferring from its absence."""
    REGISTRY.gauge(*_DEGRADED_GAUGE).set(0, feature=feature)


def _degradation_reason(symbol: str, since: str) -> str:
    from .. import native

    if not native.available():
        return "libflowdecode.so is not built or failed to load"
    return (f"loaded libflowdecode.so is stale (pre-{since}: "
            f"no {symbol} export)")


class PreparedChunk(NamedTuple):
    """Host pre-aggregation of one device-sized chunk — everything the
    apply half needs, with no model state touched yet. Group tables are
    computed UNCONDITIONALLY (the prepare stage cannot know whether the
    chunk's window is late until apply-time lifecycle advances); apply
    gates them with the do_hh/do_dd valid planes exactly like the serial
    path gates its device call."""
    wagg: list            # per wagg model: (keys, sums, counts) host rows
    hh_in: Optional[list]     # per hh family: (u [B,W], s [B,P+1], g)
    dense_in: Optional[tuple]  # (dcols padded, dvalid) or None
    ddos_in: Optional[tuple]   # (u [B,4], s [B], g) or None
    # fused dataplane (hostsketch/pipeline.py, -ingest.fused): per tree
    # (root lanes [N,W] u32, value planes [N,P] f32) — grouping, cascade
    # AND sketch updates all happen in ONE native pass at apply time, so
    # no hh group tables are materialized here. None = staged path.
    fused_in: Optional[list] = None
    # sketchwatch pre-extraction (obs/audit.py): per hh family
    # (name, (sampled rows, u64 addends) | None), computed on the
    # GROUP thread (pure hash+mask work) so the worker thread only pays
    # the uint64 fold. None = audit off, or an unsplit caller.
    audit_in: Optional[list] = None
    # flowspread (models/spread.py): per spread family
    # (pairs [G, kw+ew] u32 unique (key, element) rows,
    #  cand_keys [Gk, kw] u32, cand_counts [Gk] f32 per-key distinct-
    # pair counts — the table admission metric). Grouping to unique
    # pairs happens here on the group thread; the apply half only pays
    # the register scatter-max + table merge. None = no spread models.
    spread_in: Optional[list] = None


class PreparedBatch(NamedTuple):
    batch: FlowBatch      # original batch (offsets / archive_raw / metrics)
    parts: list           # [(slot, sub, n_rows, [PreparedChunk])]
    watermark: int

_U32_MAX = np.uint64(0xFFFFFFFF)


def _u32_lane(col: np.ndarray) -> np.ndarray:
    """One raw host column -> uint32 lane(s), saturating uint64 columns
    exactly like FlowBatch.device_columns (so host and device grouping
    see identical key/value words)."""
    if col.dtype == np.uint64:
        return np.minimum(col, _U32_MAX).astype(np.uint32)
    return col.astype(np.uint32, copy=False)


def _key_lanes_np(cols: dict, key_cols) -> np.ndarray:
    parts = []
    for name in key_cols:
        a = _u32_lane(cols[name])
        parts.append(a if a.ndim == 2 else a[:, None])
    return parts[0] if len(parts) == 1 else np.concatenate(parts, axis=1)


def _fill_lanes(out: np.ndarray, off: int, lanes) -> int:
    """Write 1-D/2-D uint32 lane arrays into ``out`` columns starting at
    ``off``; returns the next free column. The ONE lane-layout fill loop
    (_key_lanes_into + _wagg_rows share it)."""
    for a in lanes:
        if a.ndim == 1:
            out[:, off] = a
            off += 1
        else:
            w = a.shape[1]
            out[:, off:off + w] = a
            off += w
    return off


def _key_lanes_into(cols: dict, key_cols) -> np.ndarray:
    """[N, W] uint32 key lanes written straight into ONE preallocated
    C-contiguous buffer — no per-lane ``[:, None]`` reshapes and no
    ``np.concatenate`` pass (ROADMAP 4a: the concat's temporaries were
    most of the residual host_group share on the fused leg, where lane
    extraction IS the prepare half). Same words as _key_lanes_np by
    construction."""
    lanes = [_u32_lane(cols[name]) for name in key_cols]
    n = lanes[0].shape[0]
    total = sum(1 if a.ndim == 1 else a.shape[1] for a in lanes)
    out = np.empty((n, total), np.uint32)
    _fill_lanes(out, 0, lanes)
    return out


def _value_planes_np(cols: dict, value_cols,
                     scale_col: str | None = None) -> np.ndarray:
    """[N, P] float32 value planes with the device path's u32 saturation,
    multiplied by max(<scale_col>, 1) when sampling scaling is on (same
    f32 factor the device step applies)."""
    planes = np.stack([_u32_lane(cols[name]).astype(np.float32)
                       for name in value_cols], axis=1)
    if scale_col:
        r = np.maximum(_u32_lane(cols[scale_col]).astype(np.float32), 1.0)
        planes = planes * r[:, None]
    return planes


def _pow2_bucket(n: int, hi: int, lo: int = 1024) -> int:
    """Smallest power-of-two >= n in [lo, hi]; hi must be >= any possible
    n (callers pass the chunk size — a chunk of N rows cannot group into
    more than N rows)."""
    b = lo
    while b < n and b < hi:
        b <<= 1
    return b


@functools.lru_cache(maxsize=None)
def _cached_apply(hh_cfgs: tuple, dense_cfgs: tuple, ddos_cfgs: tuple):
    """One jitted state-update step over pre-grouped inputs.

    hh_in:   tuple of (uniq [B, W] u32, sums3 [B, P+1] f32, valid [B])
    dense_in: (cols dict of [Nd] int32, valid [Nd]) or None
    ddos_in: (uniq [B, 4] u32, sums [B] f32, valid [B]) or None

    Module-cached on the static config spec exactly like
    engine.fused._cached_step — rebuilt pipelines must share the
    compiled program.
    """

    def apply(states, hh_in, dense_in, ddos_in):
        hh_states, dense_tots, ddos_states = states
        new_hh = tuple(
            hh._apply_grouped(st, u, s, v, cfg)
            for st, (u, s, v), cfg in zip(hh_states, hh_in, hh_cfgs)
        )
        new_dense = dense_tots
        if dense_in is not None:
            dcols, dvalid = dense_in
            new_dense = tuple(
                dense_update(t, dcols, dvalid, config=c)
                for t, c in zip(dense_tots, dense_cfgs)
            )
        new_ddos = tuple(
            _accumulate_grouped(st, ddos_in[0], ddos_in[1], ddos_in[2], cfg)
            for st, cfg in zip(ddos_states, ddos_cfgs)
        ) if ddos_in is not None else ddos_states
        return new_hh, new_dense, new_ddos

    return jax.jit(apply, donate_argnums=(0,))


class HostGroupPipeline(FusedPipeline):
    """FusedPipeline with host (numpy) pre-aggregation — CPU backend."""

    # the prepared tables of a late part go nowhere here (apply()); the
    # worker says so at start-up and runs the families at lateness 0
    honours_lateness = False
    has_prepare_split = True
    feeds_audit = True
    # the register planes stay host numpy, the native kernel's (or its
    # numpy twin's) to mutate in place: _fold_spread
    spread_in_step = False

    @staticmethod
    def eligible(mode: str = "auto") -> bool:
        """Whether this pipeline should be picked over engine.fused.
        "auto" -> only when the default backend is CPU (the whole premise
        is that host memory IS device memory there)."""
        if mode == "on":
            return True
        if mode == "off":
            return False
        if mode != "auto":
            raise ValueError(
                f"host_assist must be auto|on|off, got {mode!r}")
        return jax.default_backend() == "cpu"

    def __init__(self, models: dict, shards: int = 0,
                 native_group: bool = False,
                 pool: Optional[ShardPool] = None,
                 audit: str = "off"):
        super().__init__(models)
        self.stages = StageTimer()
        # sketchwatch (-obs.audit, obs/audit.py): the sampled exact
        # shadow audit rides the host-grouped pipelines — observation
        # consumes the group tables (staged) or raw lanes (fused) this
        # pipeline already materializes, and window closes seal the
        # cohort via the wrapped models' audit_hook. Purely
        # observational: `make audit-parity` pins audit-on/off sink
        # rows bit-exact.
        if audit not in ("off", "sample", "full"):
            raise ValueError(
                f"audit must be off|sample|full, got {audit!r}")
        self.audit = None
        if audit != "off" and self._hh:
            from ..obs.audit import SketchAudit

            self.audit = SketchAudit(
                {name: (w.config, w.k) for name, w in self._hh},
                mode=audit)
            for name, w in self._hh:
                w.audit_hook = self._audit_close_hook(name)
        # flowspread shadow: exact distinct SETS per sampled key (the
        # set insert is idempotent, so the shadow shares the registers'
        # order-freedom). Same mode knob, same ~1/256 protocol sampler.
        self.spread_audit = None
        if audit != "off" and self._spread:
            from ..obs.audit import SpreadAudit

            self.spread_audit = SpreadAudit(
                {name: w.config for name, w in self._spread}, mode=audit)
            for name, w in self._spread:
                w.audit_hook = self._spread_close_hook(name)
        # Grouping backends (ingest runtime knobs): shards=1 disables the
        # sharded path entirely; 0 sizes it to the pool. native_group
        # requests the C hash-group kernel and quietly degrades to numpy
        # when the library is unbuilt — record which backend actually
        # serves so operators can tell from the log.
        self._native = native_group and native_group_available()
        if native_group and not self._native:
            report_native_degradation(
                "group", _degradation_reason("flow_hash_group", "r6"))
        elif native_group:
            mark_native_serving("group")
        self._shards = shards
        self._pool = None if shards == 1 else (pool or shared_pool())
        # flowspread fold knobs: the staged pipeline folds the register
        # scatter single-threaded on the worker thread with no stats
        # buffer; HostSketchPipeline._init_family_folds raises the thread
        # count to its engine's and attaches a flowtrace buffer (the
        # native kernel's per-depth ownership keeps ANY count bit-exact).
        self._spread_threads = 1
        self._spread_stats = None
        self._widths = {}
        # Sketch-family plan: group the maximal key families from raw
        # rows; regroup every strict-subset family (equal value planes)
        # from its parent's ~10x smaller group table.
        cfgs = [w.config for _, w in self._hh]
        for c in cfgs:
            for name in c.key_cols:
                self._widths[name] = lane_width(name)
        order = sorted(range(len(cfgs)),
                       key=lambda i: -len(cfgs[i].key_cols))
        self._fam_plan: list[tuple] = [()] * len(cfgs)
        planned: list[int] = []
        for i in order:
            parent = None
            for j in planned:
                if (set(cfgs[i].key_cols) < set(cfgs[j].key_cols)
                        and tuple(cfgs[i].value_cols)
                        == tuple(cfgs[j].value_cols)
                        and cfgs[i].scale_col == cfgs[j].scale_col):
                    if parent is None or len(cfgs[j].key_cols) < len(
                            cfgs[parent].key_cols):
                        parent = j
            if parent is None:
                self._fam_plan[i] = ("own",)
            else:
                sel = select_lanes(cfgs[parent].key_cols, self._widths,
                                   cfgs[i].key_cols)
                self._fam_plan[i] = ("cascade", parent, tuple(sel))
            planned.append(i)
        # DDoS per-dst sums: ride a family whose keys include dst_addr
        # and whose value planes carry the detector's value column.
        self._ddos_plan = None
        if self._ddos:
            dcfg = self._ddos[0][1].config
            for j, c in enumerate(cfgs):
                if ("dst_addr" in c.key_cols
                        and dcfg.value_col in c.value_cols
                        and c.scale_col == dcfg.scale_col):
                    self._ddos_plan = (
                        "cascade", j,
                        tuple(select_lanes(c.key_cols, {
                            **self._widths, "dst_addr": 4}, ("dst_addr",))),
                        c.value_cols.index(dcfg.value_col),
                    )
                    break
            if self._ddos_plan is None:
                self._ddos_plan = ("own",)
        self._apply = _cached_apply(
            tuple(w.config for _, w in self._hh),
            tuple(w.config for _, w in self._dense),
            tuple(d.config for _, d in self._ddos),
        )

    # ---- prepare half: pure host pre-aggregation ---------------------------
    #
    # prepare() touches NO model state, so the ingest executor runs it on
    # its group thread while the worker thread applies the previous
    # batch. update() = apply(prepare()) keeps the serial path the same
    # code — pipelined and serial modes cannot drift apart.

    def _split_parts(self, batch: FlowBatch):
        """Split a batch at (window slot, DDoS sub-window) boundaries into
        homogeneous parts, in (slot, sub) order. Returns (parts, wm) with
        parts = [(slot, sub, FlowBatch)] and wm the batch watermark. The
        cut is WindowLifecycle._split_groups'; a part that is not the
        whole batch is compacted into a copy (the host groupby wants
        contiguous rows; FusedPipeline masks rows where they are)."""
        groups, wm = self._split_groups(batch)
        parts = [
            (slot, sub, batch if rows is None else FlowBatch(
                {k: v[rows] for k, v in batch.columns.items()},
                batch.partition))
            for slot, sub, rows in groups
        ]
        return parts, wm

    def prepare(self, batch: FlowBatch) -> Optional[PreparedBatch]:
        if len(batch) == 0:
            return None
        parts, wm = self._split_parts(batch)
        out_parts = []
        with self.stages.stage("host_group"):
            for slot, sub, part in parts:
                chunks = []
                bs = self._bs
                for start in range(0, len(part), bs):
                    chunk = part.slice(start, start + bs)
                    chunks.append(self._prepare_chunk(
                        chunk.columns, len(chunk)))
                out_parts.append((slot, sub, len(part), chunks))
        return PreparedBatch(batch, out_parts, wm)

    def _prepare_chunk(self, cols: dict, n: int) -> PreparedChunk:
        # flows_5m: exact uint64 groupby straight into the window store —
        # no device partials on this path
        wagg = [self._wagg_rows(m, cols, n) for _, m in self._waggs]
        spread_in = self._prep_spread(cols) if self._spread else None
        if not (self._hh or self._dense or self._ddos):
            return PreparedChunk(wagg, None, None, None,
                                 spread_in=spread_in)
        fams = (self._group_families(cols)
                if (self._hh or self._ddos) else None)
        prep = PreparedChunk(wagg, *self._prep_device(cols, fams, n),
                             spread_in=spread_in)
        if self.audit is not None and prep.hh_in is not None:
            # audit pre-extraction rides the prepare half (group
            # thread) exactly like the tables it samples from
            prep = prep._replace(audit_in=[
                (name, self.audit.prepare_grouped(name, u, s, g))
                for (name, _), (u, s, g) in zip(self._hh, prep.hh_in)])
        return prep

    def _group(self, lanes, planes, exact):
        return group_by_key_sharded(lanes, planes, self._pool,
                                    self._shards, exact=exact,
                                    native=self._native)

    def _wagg_rows(self, m, cols: dict, n: int):
        lanes, planes = self._build_wagg_inputs(m.config, cols, n)
        return self._group_exact_planes(lanes, planes)

    def _prep_spread(self, cols: dict) -> list:
        """Per spread family: group the chunk to unique (key, element)
        pair rows — the registers' input; the max monoid makes the
        pre-grouping bit-identical to raw-row updates — then regroup
        the keys for the per-chunk distinct-pair admission metric.
        Backend-dependent group ORDER is irrelevant: the register fold
        is an order-free max and the table merge lex-groups its
        candidates, so sharded/native/numpy grouping all land the same
        state (the argument tests/test_spread.py pins down)."""
        out = []
        for name, w in self._spread:
            cfg = w.config
            kw = spread_key_width(cfg)
            pair_lanes = self._build_key_lanes(
                cols, (*cfg.key_cols, cfg.elem_col))
            pairs, _, _ = self._group(pair_lanes, [], exact=False)
            pairs = np.ascontiguousarray(pairs, dtype=np.uint32)
            cand_keys, _, pair_counts = self._group(
                np.ascontiguousarray(pairs[:, :kw]), [], exact=False)
            aud = (self.spread_audit.prepare_pairs(name, pairs)
                   if self.spread_audit is not None
                   and not self.spread_audit.paused else None)
            out.append((pairs,
                        np.ascontiguousarray(cand_keys, np.uint32),
                        pair_counts.astype(np.float32),
                        aud))
        return out

    # ---- lane building seams (r19 flowspeed) -------------------------------
    #
    # The three lane layouts the prepare half extracts from decoded
    # columns, behind override points so the hostsketch pipeline can
    # route them through the native ff_build_lanes / ff_build_planes
    # kernels. These numpy bodies are the bit-exact twins AND the
    # fallback when the library predates the lane builders — parity is
    # pinned by tests/test_hostfused.py TestLaneBuilders.

    def _build_key_lanes(self, cols: dict, key_cols) -> np.ndarray:
        return _key_lanes_into(cols, key_cols)

    def _build_value_planes(self, cols: dict, value_cols,
                            scale_col) -> np.ndarray:
        return np.ascontiguousarray(
            _value_planes_np(cols, value_cols, scale_col),
            dtype=np.float32)

    def _build_wagg_inputs(self, cfg, cols: dict, n: int):
        """(lanes [N, 1+W(+1)] u32, planes [N, P] u64-saturated) for one
        wagg model: slot first, key lanes, rate lane LAST, matching
        group_cols(cfg) — lanes filled straight into one preallocated
        buffer (the no-concat discipline of _key_lanes_into)."""
        t = np.minimum(cols["time_received"], _U32_MAX).astype(np.uint32)
        slot = t - t % np.uint32(cfg.window_seconds)
        key_lanes = [_u32_lane(cols[name]) for name in cfg.key_cols]
        total = 1 + sum(1 if a.ndim == 1 else a.shape[1]
                        for a in key_lanes) + (1 if cfg.scale_col else 0)
        lanes = np.empty((n, total), np.uint32)
        lanes[:, 0] = slot
        off = _fill_lanes(lanes, 1, key_lanes)
        if cfg.scale_col:
            lanes[:, off] = _u32_lane(cols[cfg.scale_col])
        planes = [np.minimum(cols[name], _U32_MAX) for name in cfg.value_cols]
        return lanes, np.stack(planes, axis=1)

    def _group_exact_planes(self, lanes: np.ndarray, planes: np.ndarray):
        """Exact groupby-sum of stacked [N, P] uint64 planes — the
        flows_5m substrate. Seam: the fused pipeline overrides this with
        the single-pass ff_group_sum kernel."""
        uniq, sums, counts = self._group(lanes, [planes], exact=True)
        return uniq, sums[0], counts

    def _group_families(self, cols: dict) -> list[tuple]:
        """Per-hh-family (uniq [G,W] u32, vsum [G,P] f64, cnt [G]) plus the
        DDoS per-dst tuple appended last when planned."""
        out: list = [None] * len(self._hh)
        for i, (plan, (_, w)) in enumerate(
                zip(self._fam_plan, self._hh)):
            if plan[0] != "own":
                continue
            cfg = w.config
            lanes = self._build_key_lanes(cols, cfg.key_cols)
            vals = self._build_value_planes(cols, cfg.value_cols,
                                            cfg.scale_col)
            uniq, sums, counts = self._group(lanes, [vals], exact=False)
            out[i] = (uniq, sums[0], counts)
        for i, plan in enumerate(self._fam_plan):
            if plan[0] != "cascade":
                continue
            _, parent, sel = plan
            p_uniq, p_vsum, p_cnt = out[parent]
            uniq, sums, _ = self._group(
                p_uniq[:, list(sel)], [p_vsum, p_cnt], exact=False)
            out[i] = (uniq, sums[0], sums[1].astype(np.int64))
        if self._ddos_plan is not None:
            dcfg = self._ddos[0][1].config
            if self._ddos_plan[0] == "cascade":
                _, parent, sel, plane = self._ddos_plan
                p_uniq, p_vsum, p_cnt = out[parent]
                uniq, sums, _ = self._group(
                    p_uniq[:, list(sel)], [p_vsum[:, plane]], exact=False)
                out.append((uniq, sums[0].astype(np.float32)))
            else:
                lanes = self._build_key_lanes(cols, ("dst_addr",))
                vals = self._build_value_planes(
                    cols, (dcfg.value_col,), dcfg.scale_col)[:, 0]
                uniq, sums, _ = self._group(lanes, [vals], exact=False)
                out.append((uniq, sums[0].astype(np.float32)))
        return out

    def _prep_device(self, cols: dict, fams, n: int):
        """Pad group tables / dense columns to their static shapes —
        the host half of the device step. Valid planes are NOT built
        here: they depend on apply-time lifecycle (do_hh / do_dd).

        Buckets are PER FAMILY (not the old shared max): a cascade family
        (src/dst IPs) typically groups 3-4x smaller than the 5-tuple
        talkers, and the CMS scatter + merge cost scales with padded
        rows — sharing the talkers' bucket made every family pay the
        largest family's price. Each family still draws from the same
        handful of power-of-two shapes, so the jit cache stays small."""
        hi = max(self._bs, 1024)
        hh_in = []
        for i, (_, w) in enumerate(self._hh):
            uniq, vsum, cnt = fams[i]
            g = uniq.shape[0]
            B = _pow2_bucket(g, hi=hi)
            W = uniq.shape[1]
            P = vsum.shape[1]
            u = np.zeros((B, W), np.uint32)
            s = np.zeros((B, P + 1), np.float32)
            u[:g] = uniq
            s[:g, :P] = vsum
            s[:g, P] = cnt
            hh_in.append((u, s, g))
        ddos_in = None
        if self._ddos_plan is not None:
            uniq, dsum = fams[-1]
            ddos_in = self._pad_ddos(uniq, dsum)
        return hh_in, self._prep_dense(cols, n), ddos_in

    def _prep_dense(self, cols: dict, n: int):
        """Dense-model columns padded to the static batch shape (shared
        by the staged and fused prepare halves)."""
        if not self._dense:
            return None
        need = set()
        for _, w in self._dense:
            need.add(w.config.key_col)
            need.update(w.config.value_cols)
            if w.config.scale_col:
                need.add(w.config.scale_col)
        bs = self._bs
        dcols = {}
        for name in need:
            src = _u32_lane(cols[name])
            a = np.zeros(bs, np.uint32)
            a[:n] = src
            dcols[name] = a.view(np.int32)
        dvalid = np.zeros(bs, bool)
        dvalid[:n] = True
        return (dcols, dvalid)

    def _pad_ddos(self, uniq: np.ndarray, dsum: np.ndarray):
        """Pad a per-dst group table to its power-of-two bucket for the
        jitted accumulate (shared by the staged prepare and the fused
        apply, which receives the table from the native pass)."""
        g = uniq.shape[0]
        B = _pow2_bucket(g, hi=max(self._bs, 1024))
        u = np.zeros((B, 4), np.uint32)
        s = np.zeros(B, np.float32)
        u[:g] = uniq
        s[:g] = dsum
        return (u, s, g)

    # ---- apply half: lifecycle + model state -------------------------------

    def apply(self, prep: Optional[PreparedBatch]) -> None:
        """Advance window lifecycles and fold one prepared batch into the
        models. Must run on the thread that owns model state (the worker
        thread, under its lock), in batch order."""
        if prep is None:
            return
        for slot, sub, n_rows, chunks in prep.parts:
            do_hh = self._advance_hh(slot, n_rows)
            do_dd = self._advance_ddos(sub, n_rows)
            for ch in chunks:
                for (_, m), rows in zip(self._waggs, ch.wagg):
                    m.add_host_rows(*rows)
                if not (do_hh or do_dd):
                    continue  # late part: device models take nothing
                if do_hh and ch.spread_in is not None:
                    self._fold_spread(ch)
                if ch.hh_in is None and ch.dense_in is None \
                        and ch.ddos_in is None and ch.fused_in is None:
                    continue
                self._timed_apply_chunk(ch, do_hh, do_dd)
                if do_hh and self.audit is not None:
                    # after the fold, mirroring the sketch's own gating:
                    # the shadow cohort covers exactly the rows the
                    # sketches took (late parts fold nowhere). Timed as
                    # its own stage: the audit's budget is measured
                    # in-run (share of wall), not inferred from paired
                    # A/B legs a 2-core box's frequency drift swamps
                    self._audit_chunk_timed(ch)
        for _, m in self._waggs:
            if prep.watermark > m.watermark:
                m.watermark = prep.watermark

    def update(self, batch: FlowBatch) -> None:
        self.apply(self.prepare(batch))

    def _fold_spread(self, ch: PreparedChunk) -> None:
        """Fold one chunk's prepared pair tables into the spread models
        (worker thread — mutates model state, like every apply).
        spread_apply_update routes the register scatter through the
        native hs_spread_update kernel when the library exports it, the
        numpy twin otherwise — either way bit-identical to
        SpreadModel.update over the same chunk, which is the parity
        anchor tests/test_spread.py pins. One ``spread_fold`` span a
        chunk: the host fold between two dispatches that FusedPipeline
        no longer has (its step updates the planes on the device)."""
        from ..hostsketch.engine import (
            np_spread_table_merge,
            spread_apply_update,
        )

        with self.stages.stage("host_spread"), TRACER.span("spread_fold"):
            for (name, w), (pairs, cand_keys, cand_counts, aud) in zip(
                    self._spread, ch.spread_in):
                m = w.model
                kw = spread_key_width(w.config)
                spread_apply_update(m.state.regs, pairs[:, :kw],
                                    pairs[:, kw:],
                                    threads=self._spread_threads,
                                    stats=self._spread_stats)
                tk, tm = np_spread_table_merge(
                    m.state.table_keys, m.state.table_metric,
                    cand_keys, cand_counts)
                m.state = SpreadState(m.state.regs, tk, tm)
                if aud is not None:
                    self.spread_audit.fold_prepared(name, aud)

    # ---- sketchwatch hooks -------------------------------------------------

    def _audit_close_hook(self, name: str):
        """Per-family window-close hook handed to the wrapped model:
        seals the sampled cohort against the closing state (or ships it
        to the mesh member's capture)."""
        def hook(slot, model):
            # its own stage, separate from the per-chunk observation:
            # the close evaluation (CMS freeze + fill scan + report) is
            # a once-per-WINDOW lump, not a continuous hot-path tax —
            # budgeting them together would charge a 300s window's
            # close against whatever wall the bench stream compressed
            # that window into
            with self.stages.stage("sketch_audit_close"):
                self.audit.on_close(name, slot, model)
        return hook

    def _spread_close_hook(self, name: str):
        """Window-close seal for a spread family: decode the closing
        registers against the exact distinct sets accumulated for the
        sampled cohort and publish the error histogram."""
        def hook(slot, model):
            with self.stages.stage("sketch_audit_close"):
                self.spread_audit.on_close(name, slot, model)
        return hook

    def _audit_chunk_timed(self, ch: PreparedChunk) -> None:
        with self.stages.stage("sketch_audit"):
            self._audit_chunk(ch)

    def _audit_chunk(self, ch: PreparedChunk) -> None:
        """Feed one applied chunk to the shadow audit: fold the
        pre-extracted cohort rows when the prepare half supplied them,
        else extract here (serial/unsplit callers). The staged tables
        carry group-summed planes; the audit's uint64 fold makes the
        granularity irrelevant on the exact envelope."""
        if ch.audit_in is not None:
            for name, prepared in ch.audit_in:
                self.audit.fold_prepared(name, prepared)
            return
        if ch.hh_in is None:
            return
        for (name, _), (u, s, g) in zip(self._hh, ch.hh_in):
            self.audit.observe_grouped(name, u, s, g)

    def _timed_apply_chunk(self, ch: PreparedChunk, do_hh: bool,
                           do_dd: bool) -> None:
        """Stage attribution seam: here the whole chunk apply IS the
        jitted device step. The hostsketch pipeline overrides this to
        split its chunk between host_sketch (the native engine) and
        device_apply (what remains jitted), so the two backends' stage
        budgets stay comparable per stage."""
        with self.stages.stage("device_apply"):
            self._apply_chunk(ch, do_hh, do_dd)

    def _apply_chunk(self, ch: PreparedChunk, do_hh: bool,
                     do_dd: bool) -> None:
        hh_in = []
        for u, s, g in ch.hh_in:
            v = np.zeros(u.shape[0], bool)
            v[:g] = do_hh
            hh_in.append((u, s, v))
        dense_in = ch.dense_in if (self._dense and do_hh) else None
        ddos_in = None
        if ch.ddos_in is not None:
            u, s, g = ch.ddos_in
            v = np.zeros(u.shape[0], bool)
            v[:g] = do_dd
            ddos_in = (u, s, v)
        states = (
            tuple(w.model.state for _, w in self._hh),
            tuple(w.model.totals for _, w in self._dense),
            tuple(d.state for _, d in self._ddos),
        )
        new_hh, new_dense, new_ddos = self._apply(
            states, tuple(hh_in), dense_in, ddos_in)
        for (_, w), st in zip(self._hh, new_hh):
            w.model.state = st
        if dense_in is not None:
            for (_, w), tot in zip(self._dense, new_dense):
                w.model.totals = tot
        for (_, d), st in zip(self._ddos, new_ddos):
            d.state = st
