"""Fused per-batch device step: one dispatch, shared pre-aggregation.

The unfused worker updates every model serially and each sketch/exact
model independently re-sorts the same batch — five multi-key sorts and
~eight dispatches per batch at the default model set. The reference's
ClickHouse rollup chain makes ONE pass over the raw rows per ingest and
fans the materialized views out from it (ref: compose/clickhouse/
create.sh:92-110). This module is the TPU-first equivalent:

- Each heavy-hitter key family gets a HASH-grouped pre-agg
  (ops.segment.hash_groupby_float): the sort runs over the 64-bit key
  hash (2 lanes) instead of the raw 4-11 key lanes, which beats the
  previous shared 10-lane master sort even though families no longer
  share a sort — lax.sort cost scales with operand count, and three
  2-lane sorts are cheaper than one 10-lane sort plus a 4-lane dst
  sort.
- The dst-keyed hash sort is still shared between the top-dst-IP
  sketch and the DDoS per-dst accumulate (they want the same per-dst
  groups under different row masks).
- The flows_5m exact groupby, the dense port scatters, and all sketch
  table merges run in the SAME jitted step, so the worker makes one
  device dispatch per chunk and every column crosses the host boundary
  once.

Window lifecycle (closing sketches at slot roll, DDoS sub-windows, late
-row drops) stays host-side and byte-identical to the unfused models':
the batch is cut at (slot, sub-window) boundaries and the wrapped
models' own lifecycle hooks advance before the device call for their
rows.

What a cut poll runs (engine/lifecycle.py: runs). A family is cut at
its own unit only, as on the per-model path and under the mesh: the
groups of one window slot are a *slot run* and take ONE fused step, in
which flows_5m (which keys by timeslot itself), the tables and the ports
take the run's rows and the detector the rows of the run's newest
sub-window (the step has a mask for each). The run's older sub-windows
run the detector's own small program alone (models.ddos.ddos_accumulate,
the per-model path's), first and in order, over the columns already on
the device. So a poll that crosses only a sub-window (one in twenty in
order, two in five on two partitions with a few seconds of disorder)
costs one step and a few ms, not two padded steps; only a slot roll runs
the step twice. Rows are never compacted: lanes are built and placed
once a poll and a run is a boolean mask. tests/test_fused.py proves
output equivalence against the unfused path, late rows and count-min
estimates included.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..models import heavy_hitter as hh
from ..models.ddos import (
    DDoSDetector,
    _accumulate_grouped,
    ddos_accumulate,
    ddos_input_cols,
)
from ..models.dense_top import DenseTopKModel, dense_update
from ..models.heavy_hitter import HeavyHitterModel
from ..models.spread import SpreadModel, SpreadState
from ..models.window_agg import WindowAggregator
from ..models.window_agg import _cached_update as _cached_wagg_update
from ..obs import get_logger
from ..obs.trace import TRACER
from ..schema.batch import FlowBatch, lane_width
from ..ops import spread as spread_ops
from ..ops.segment import (
    _hash_grouped,
    hash_groupby_float,
    hash_lanes,
    hash_sort,
    presorted_segments,
)
from .lifecycle import WindowLifecycle, _count, _runs
from .windowed import WindowedHeavyHitter

log = get_logger("fused")

# numpy (not jnp): a module-level jnp constant would initialize the JAX
# backend at import time — importing the engine must never claim a chip
_SENTINEL = np.uint32(0xFFFFFFFF)
ETYPE_IPV4 = 0x0800


def _hh_plan(cfg) -> tuple:
    """How a heavy-hitter config's pre-agg is computed inside the fused
    step: ("B",) = the shared dst-keyed hash sort (dual-masked with the
    DDoS accumulate); ("own",) = its own hash_groupby_float (still inside
    the fused dispatch, just not shared)."""
    if tuple(cfg.value_cols) == ("bytes", "packets") and \
            cfg.key_cols == ("dst_addr",):
        return ("B",)
    return ("own",)


@functools.lru_cache(maxsize=None)
def _cached_step(hh_specs, dense_cfgs, ddos_cfgs, wagg_cfgs,
                 spread_specs=()):
    """Build + jit the fused device step for one static model spec.

    Module-level cache: pipelines are rebuilt freely (a benchmark run's
    worker, supervisor restarts), and the fused graph is the most expensive
    compile in the framework — it must be shared the same way the
    unfused models' module-level jits are. All spec elements are frozen
    config dataclasses / string tuples, so the key is hashable.
    """
    from ..models.window_agg import group_cols as _wagg_group_cols

    wagg_fns = tuple(_cached_wagg_update(c.window_seconds,
                                         _wagg_group_cols(c),
                                         c.value_cols) for c in wagg_cfgs)
    # The shared B path scales its payload planes by the FIRST B config's
    # rate; a second dst-keyed family with a different scale_col would
    # silently get the wrong sampling correction — demote it to its own
    # groupby (mirrors the chain-absorb scale_col equality check below).
    b_scale = next((cfg.scale_col for plan, cfg in hh_specs
                    if plan[0] == "B"), None)
    hh_specs = tuple(
        (("own",) if plan[0] == "B" and cfg.scale_col != b_scale else plan,
         cfg)
        for plan, cfg in hh_specs)
    hh_b = any(plan[0] == "B" for plan, _ in hh_specs)
    need_b = hh_b or bool(ddos_cfgs)
    hh_vals = ("bytes", "packets")  # the dst-shared payload planes

    # Nested-family chains: an "own" family whose key tuple is a PREFIX
    # of another's (src-address under the 5-tuple top-talkers) rides the
    # same sort — lanes are [h64(prefix), h64(full)], so rows group by
    # the prefix at sort-lane width 2 and by the full key at width 4.
    # Two extra lanes on one sort beat a whole second 2-lane sort.
    own_ix = [i for i, (plan, cfg) in enumerate(hh_specs)
              if plan[0] == "own" and tuple(cfg.value_cols) == hh_vals]
    own_ix.sort(key=lambda i: -len(hh_specs[i][1].key_cols))
    chains, absorbed = [], set()
    for i in own_ix:
        if i in absorbed:
            continue
        members = [i]
        pk = hh_specs[i][1].key_cols
        for j in own_ix:
            if j in absorbed or j == i:
                continue
            ck = hh_specs[j][1].key_cols
            if (len(ck) < len(pk) and pk[:len(ck)] == ck
                    and hh_specs[j][1].scale_col
                    == hh_specs[i][1].scale_col):
                members.append(j)
                absorbed.add(j)
        if len(members) > 1:
            members.sort(key=lambda m: len(hh_specs[m][1].key_cols))
            chains.append(tuple(members))
            absorbed.add(i)

    def to_f32(col):
        # int32 bit-patterns of uint32 counters: reinterpret unsigned
        # before the float cast so saturated values stay positive
        return col.astype(jnp.uint32).astype(jnp.float32)

    def rate_of(cols, cfg):
        # serving-side sampling factor (see HeavyHitterConfig.scale_col);
        # rate 0 ("unknown") scales by 1
        if not getattr(cfg, "scale_col", None):
            return None
        return jnp.maximum(to_f32(cols[cfg.scale_col]), 1.0)

    # jax.named_scope names below are a contract with the trace readers
    # (benchmark/kernel_scopes.py, docs/OBSERVABILITY.md): device time is
    # followed per scope from one compilation to the next, where XLA's own
    # instruction numbering is not stable. They change HLO metadata only.
    def step(states, cols, valid, valid_hh, valid_dd):
        if spread_specs:
            hh_states, dense_tots, ddos_states, spread_states = states
        else:
            hh_states, dense_tots, ddos_states = states

        chain_results: dict[int, tuple] = {}
        for members in chains:
            with jax.named_scope("hh_chain_sort"):
                parent_cfg = hh_specs[members[-1]][1]
                full_lanes = hh._key_lanes(cols, parent_cfg.key_cols)
                n = full_lanes.shape[0]
                sort_lanes = []
                for m in members:
                    h1, h2 = hash_lanes(hh._key_lanes(
                        cols, hh_specs[m][1].key_cols))
                    sort_lanes.append(jnp.where(valid_hh, h1, _SENTINEL))
                    sort_lanes.append(jnp.where(valid_hh, h2, _SENTINEL))
                out = lax.sort(sort_lanes + [lax.iota(jnp.int32, n)],
                               num_keys=2 * len(members))
                perm = out[-1]
                sh = jnp.stack(out[:-1], axis=1)
                sk = jnp.where(valid_hh[:, None],
                               full_lanes.astype(jnp.uint32),
                               _SENTINEL)[perm]
                sv = jnp.stack([to_f32(cols[c]) for c in hh_vals], axis=1)
                r = rate_of(cols, parent_cfg)  # members share scale_col
                if r is not None:
                    sv = sv * r[:, None]
                sv = jnp.where(valid_hh[:, None], sv, 0.0)[perm]
                sc = valid_hh[perm].astype(jnp.int32)
                for level, m in enumerate(members):
                    width = sum(
                        lane_width(c) for c in hh_specs[m][1].key_cols)
                    uniq, sums, counts, _ = _hash_grouped(
                        sh[:, :2 * (level + 1)], sk[:, :width], sv, sc,
                        False)
                    chain_results[m] = (uniq, sums, counts)

        if need_b:
            with jax.named_scope("dst_sort"):
                # One dst-keyed hash sort serves the top-dst-IP sketch AND
                # the DDoS per-dst accumulate under their own row masks:
                # masks apply to the GATHERED rows, so the dual-mask planes
                # cost gathers, not extra sort lanes
                # (ops.segment.hash_sort).
                dst = cols["dst_addr"].astype(jnp.uint32)
                vb = valid_hh if hh_b else jnp.zeros_like(valid_hh)
                vd = (valid_dd if ddos_cfgs
                      else jnp.zeros_like(valid_hh))
                va = vb | vd
                n = dst.shape[0]
                sh_b, perm = hash_sort(dst, va)
                sk_b = jnp.where(va[:, None], dst, _SENTINEL)[perm]
                vbp, vdp = vb[perm], vd[perm]
                planes, cnts = [], []
                if hh_b:
                    b_cfg = next(cfg for plan, cfg in hh_specs
                                 if plan[0] == "B")
                    rb = rate_of(cols, b_cfg)
                    for c in hh_vals:
                        p = to_f32(cols[c])
                        if rb is not None:
                            p = p * rb
                        planes.append(jnp.where(vbp, p[perm], 0.0))
                    cnts.append(vbp.astype(jnp.int32))
                for dcfg in ddos_cfgs[:1]:  # detectors share cadence+col set
                    p = to_f32(cols[dcfg.value_col])
                    rd = rate_of(cols, dcfg)
                    if rd is not None:
                        p = p * rd
                    planes.append(jnp.where(vdp, p[perm], 0.0))
                    cnts.append(vdp.astype(jnp.int32))
                sv_b = jnp.stack(planes, axis=1)
                sc_b = jnp.stack(cnts, axis=1)  # [N, nc]
                seg = presorted_segments(sh_b)
                sums_b = jax.ops.segment_sum(sv_b, seg, num_segments=n)
                cnt_b = jax.ops.segment_sum(sc_b, seg, num_segments=n)
                # min, not max: rows masked for NEITHER consumer keep their
                # sentinel keys and may share a hash segment with real rows
                # only on a ~2^-64 hash collision — min lets the real key
                # win
                uniq_b = jax.ops.segment_min(sk_b, seg, num_segments=n)

            def consume_b(plane_ix, cnt_ix, nplanes):
                with jax.named_scope("dst_sort"):
                    counts = cnt_b[:, cnt_ix]
                    real = counts > 0
                    s = jnp.where(
                        real[:, None],
                        sums_b[:, plane_ix:plane_ix + nplanes], 0.0)
                    u = jnp.where(real[:, None], uniq_b, _SENTINEL)
                    return u, s, counts

        new_hh, live = [], []
        key_groups: dict[tuple, tuple] = {}  # key_cols -> (uniq, counts)
        for i, ((plan, cfg), st) in enumerate(zip(hh_specs, hh_states)):
            if plan[0] == "B":
                uniq, sums, counts = consume_b(0, 0, 2)
            elif i in chain_results:
                uniq, sums, counts = chain_results[i]
            else:
                with jax.named_scope("hh_group_own"):
                    lanes = hh._key_lanes(cols, cfg.key_cols)
                    vals = jnp.stack(
                        [to_f32(cols[c]) for c in cfg.value_cols], axis=1)
                    r = rate_of(cols, cfg)
                    if r is not None:
                        vals = vals * r[:, None]
                    uniq, sums, counts = hash_groupby_float(
                        lanes, vals, valid_hh)
            key_groups.setdefault(tuple(cfg.key_cols), (uniq, counts))
            # one scope per family, by its index among the hh families
            with jax.named_scope(f"hh_table_merge_{i}"):
                sums3 = jnp.concatenate(
                    [sums, counts.astype(jnp.float32)[:, None]], axis=1)
                new_hh.append(
                    hh._apply_grouped(st, uniq, sums3, counts > 0, cfg))
                # the bound _apply_grouped gathers under, a family
                # (FusedPipeline.hh_live)
                live.append(hh.live_rows(counts > 0))

        with jax.named_scope("dense_scatter"):
            new_dense = tuple(
                dense_update(t, cols, valid_hh, config=c)
                for t, c in zip(dense_tots, dense_cfgs)
            )

        new_ddos = []
        for dcfg, dst_state in zip(ddos_cfgs, ddos_states):
            plane_ix = 2 if hh_b else 0
            cnt_ix = 1 if hh_b else 0
            u, s, counts = consume_b(plane_ix, cnt_ix, 1)
            with jax.named_scope("ddos_accumulate"):
                new_ddos.append(_accumulate_grouped(
                    dst_state, u, s[:, 0], counts > 0, dcfg))

        # one pair of scopes a detector, by its name (a trace reader's
        # contract like the others': benchmark/spread_scopes.py)
        new_spread = []
        for (name, cfg), st in zip(spread_specs,
                                   spread_states if spread_specs else ()):
            shape = (cfg.depth, cfg.width, cfg.registers)
            lanes = hh._key_lanes(cols, cfg.key_cols)
            with jax.named_scope(f"spread_regs_{name}"):
                regs = spread_ops.spread_scatter(
                    st.regs, shape, lanes,
                    hh._key_lanes(cols, (cfg.elem_col,)), valid_hh)
            with jax.named_scope(f"spread_table_{name}"):
                # the batch's sources, once each: an hh family keyed as
                # the detector has grouped them already (top_src_ips in
                # the default estate), else a sort of the detector's own
                if tuple(cfg.key_cols) in key_groups:
                    uniq, counts = key_groups[tuple(cfg.key_cols)]
                else:
                    uniq, _, counts = hash_groupby_float(
                        lanes, jnp.zeros((lanes.shape[0], 0), jnp.float32),
                        valid_hh)
                tk, tm = spread_ops.spread_table_admit(
                    st.table_keys, st.table_metric, uniq,
                    spread_ops.spread_decode_device(regs, shape, uniq),
                    counts > 0)
            new_spread.append(SpreadState(regs, tk, tm))

        with jax.named_scope("wagg_groupby"):
            wagg_parts = tuple(fn(cols, valid) for fn in wagg_fns)
        new_states = (tuple(new_hh), new_dense, tuple(new_ddos))
        if spread_specs:
            new_states += (tuple(new_spread),)
        return (new_states, wagg_parts,
                jnp.stack(live) if live else jnp.zeros(0, jnp.int32))

    return jax.jit(step, donate_argnums=(0,))


class _Lanes:
    """One polled batch on the device: each device step's padded
    columns, built and placed at their first use and kept until the
    batch is done (every run of a cut poll reads the same placement
    under a mask of its own)."""

    def __init__(self, batch: FlowBatch, bs: int, names: tuple):
        self._batch, self._bs, self._names = batch, bs, names
        self._placed: dict = {}

    def starts(self) -> range:
        return range(0, len(self._batch), self._bs)

    def place(self, start: int) -> tuple:
        """(chunk, host columns, device columns, device pad mask) of
        the device step whose first row is ``start``."""
        if start not in self._placed:
            bs = self._bs
            chunk = self._batch.slice(start, start + bs)
            # rows whose address lanes hold a v4 address, left-padded:
            # counted before the span that times the build, and only for
            # a recorder that keeps it
            v4 = ({"v4_rows": int(np.count_nonzero(
                chunk.columns["etype"] == ETYPE_IPV4))}
                if TRACER.recording else {})
            with TRACER.span("lane_build", rows=len(chunk), padded=bs,
                             **v4):
                padded, mask = chunk.pad_to(bs)
                host_cols = padded.device_columns(self._names)
            with TRACER.span("h2d", cols=len(host_cols) + 1) as span:
                cols = {k: jnp.asarray(v) for k, v in host_cols.items()}
                valid = jnp.asarray(mask)
                span["bytes"] = mask.nbytes + sum(
                    v.nbytes for v in host_cols.values())
            self._placed[start] = (chunk, host_cols, cols, valid)
        return self._placed[start]

    def mask(self, start: int, rows) -> tuple:
        """(host mask, its count) of the run ``rows`` (a mask over the
        whole batch; None: every row) in the device step at ``start``."""
        bs = self._bs
        mask = np.zeros(bs, dtype=bool)
        n = min(bs, len(self._batch) - start)
        if rows is None:
            mask[:n] = True
        else:
            mask[:n] = rows[start:start + n]
        return mask, int(mask.sum())


class FusedPipeline(WindowLifecycle):
    """Drives a worker's whole model dict through one jitted step/batch."""

    # -window.lateness: a late run is a dispatch (of the step, or of the
    # detector's program) with the family's held state in the open
    # one's place (WindowLifecycle._units)
    honours_lateness = True
    # the spread detectors' state lives on the device and the jitted
    # step updates it; the host-grouped subclasses keep it in host numpy
    # and fold it themselves (engine/hostfused.py: _fold_spread)
    spread_in_step = True
    _compiled_step_text = None  # compiled_step_text()'s, once had

    @staticmethod
    def supported(models: dict[str, Any]) -> bool:
        """True iff every model is a plain single-chip kind this pipeline
        knows how to fuse (sharded/mesh variants keep the per-model path:
        their states live as mesh-sharded arrays with their own update
        programs) and the windowed models agree on cadence/chunking."""
        whh_windows, subs, batch_sizes = set(), set(), set()
        for m in models.values():
            if type(m) is WindowAggregator:
                batch_sizes.add(m.config.batch_size)
            elif type(m) is WindowedHeavyHitter and type(m.model) in (
                    HeavyHitterModel, DenseTopKModel, SpreadModel):
                whh_windows.add((m.window_seconds, m.slot_seconds,
                                 m.lateness))
                batch_sizes.add(m.config.batch_size)
            elif type(m) is DDoSDetector:
                subs.add(m.config.sub_window_seconds)
                batch_sizes.add(m.config.batch_size)
            else:
                return False
        n_ddos = sum(type(m) is DDoSDetector for m in models.values())
        return (len(whh_windows) <= 1 and len(subs) <= 1 and n_ddos <= 1
                and len(batch_sizes) == 1)

    def __init__(self, models: dict[str, Any]):
        if not self.supported(models):
            raise ValueError("model set not fusable (see supported())")
        self._waggs: list[tuple[str, WindowAggregator]] = []
        self._hh: list[tuple[str, WindowedHeavyHitter]] = []
        self._dense: list[tuple[str, WindowedHeavyHitter]] = []
        self._ddos: list[tuple[str, DDoSDetector]] = []
        # spread wrappers ride the SAME window lifecycle (_advance_hh
        # closes every _whh member in lockstep) and, where
        # spread_in_step, the jitted step: the registers take a
        # scatter-max of the run's rows (the max monoid makes that
        # bit-identical to any other chunking/ordering), the candidate
        # table the batch's sources at what the registers then decode
        # to (ops.spread.spread_table_admit).
        self._spread: list[tuple[str, WindowedHeavyHitter]] = []
        self._whh: list[WindowedHeavyHitter] = []  # hh/dense/spread wrappers
        for name, m in models.items():
            if type(m) is WindowAggregator:
                self._waggs.append((name, m))
            elif type(m) is DDoSDetector:
                self._ddos.append((name, m))
            elif type(m.model) is HeavyHitterModel:
                self._hh.append((name, m))
                self._whh.append(m)
            elif type(m.model) is SpreadModel:
                self._spread.append((name, m))
                self._whh.append(m)
            else:
                self._dense.append((name, m))
                self._whh.append(m)
        first = next(iter(models.values()))
        self._bs = first.config.batch_size
        # the grain the wrappers' slot rolls at: their window, or under
        # -window.slide their slide (a multiple of the detector's
        # sub-window cuts no batch the detector does not cut)
        self._window_seconds = (self._whh[0].slot_seconds
                                if self._whh else None)
        self._sub_seconds = (self._ddos[0][1].config.sub_window_seconds
                             if self._ddos else None)
        self._hh_specs = tuple(
            (_hh_plan(w.config), w.config) for _, w in self._hh)
        # the detectors this pipeline's step updates: their state moves
        # to the device (none where a subclass folds them on the host)
        self._stepped_spread = self._spread if self.spread_in_step else []
        for _, w in self._stepped_spread:
            w.model.to_device()
        self._cols = self._column_union()
        self._behind = False  # the batch in hand fills a device step
        self._detector_warm = not self._ddos  # see _warm_detector
        # [families] int32 on the device: live_rows of the last step that
        # fed the tables; read by hh_live alone
        self._live_rows = None
        # {family: its table's keys as byte strings} at hh_live's last
        # read under a recorder, and the steps that fed the tables since
        self._table_rows: dict = {}
        self._hh_steps = 0
        # The compiled step is cached on the static spec, NOT per instance:
        # every benchmark run / supervisor restart builds a fresh pipeline,
        # and a per-instance jit would recompile the whole fused graph
        # each time (the unfused models' jits are module-cached too).
        self._step = _cached_step(
            self._hh_specs,
            tuple(w.config for _, w in self._dense),
            tuple(d.config for _, d in self._ddos),
            tuple(m.config for _, m in self._waggs),
            tuple((name, w.config) for name, w in self._stepped_spread),
        )

    # ---- device step ------------------------------------------------------

    def _column_union(self) -> tuple[str, ...]:
        cols: list[str] = []

        def add(*names):
            for n in names:
                if n not in cols:
                    cols.append(n)

        def scale_of(cfg):
            return (cfg.scale_col,) if getattr(cfg, "scale_col", None) \
                else ()

        for _, m in self._waggs:
            add("time_received", *m.config.key_cols, *m.config.value_cols,
                *scale_of(m.config))
        for _, w in self._hh:
            add(*w.config.key_cols, *w.config.value_cols,
                *scale_of(w.config))
        for _, w in self._dense:
            add(w.config.key_col, *w.config.value_cols,
                *scale_of(w.config))
        for _, w in self._spread:
            add(*w.config.key_cols, w.config.elem_col)
        for _, d in self._ddos:
            add("dst_addr", d.config.value_col, *scale_of(d.config))
        return tuple(cols)

    def compiled_step_text(self) -> str:
        """Optimized HLO text of this pipeline's step on the default
        backend. A profiler trace names device ops by XLA's instruction
        numbering (``%fusion.535``); each instruction's ``op_name`` here
        holds the kernel scope it belongs to (``hh_chain_sort``, ...).

        The executable that runs may have come from the persistent cache,
        which is keyed without metadata: a hit may have been compiled
        from a source with other scope names (same program, same
        instruction numbering). So this compiles the step again, as a jit
        of its own, under a cache key that includes the metadata: a full
        compile the first time for a source, a cache hit after. Not for
        the dispatch loop. Kept once had: the step, its batch size, its
        columns and its states' shapes are fixed when the pipeline is
        built, and a traced run's readers ask several times."""
        if self._compiled_step_text is not None:
            return self._compiled_step_text

        def shape(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        padded, mask = FlowBatch.empty(0).pad_to(self._bs)
        cols = {k: shape(v)
                for k, v in padded.device_columns(self._cols).items()}
        valid = shape(mask)
        states = jax.tree_util.tree_map(shape, self._states())
        inner = self._step.__wrapped__

        def step(*args):  # a new function: JAX memoizes lowerings by it
            return inner(*args)

        lowered = jax.jit(step, donate_argnums=(0,)).lower(
            states, cols, valid, valid, valid)
        keyed = "jax_compilation_cache_include_metadata_in_key"
        before = getattr(jax.config, keyed)
        jax.config.update(keyed, True)
        try:
            self._compiled_step_text = lowered.compile().as_text()
        finally:
            jax.config.update(keyed, before)
        return self._compiled_step_text

    def hh_live(self) -> dict:
        """Args for the span that reads them (``ckpt_state``): the
        largest family's live bound in the last step that fed the
        tables, and the slots a family has there. A device->host read:
        for a caller that has already waited for that step, as a
        checkpoint has once its state is built, and for no one else in
        the dispatch loop."""
        if self._live_rows is None or not self._hh:
            return {}
        out = {"hh_live_rows": int(np.max(self._live_rows)),
               "hh_slots": self._bs}
        if TRACER.recording:
            out["hh_admitted"] = self._admitted()
            out["hh_steps"], self._hh_steps = self._hh_steps, 0
        return out

    def _admitted(self) -> dict:
        """{family: keys its table holds now and did not hold at the
        last call}, counted on the host (a key that came and went in
        between is not seen; a table a close emptied reads as all new).
        The tables' keys cross to the host in one read, ~40 KB a
        family: for hh_live's caller alone."""
        out = {}
        tables = jax.device_get([w.model.state.table_keys
                                 for _, w in self._hh])
        for (name, _), keys in zip(self._hh, tables):
            rows = keys[(keys != _SENTINEL).any(axis=1)]
            # a key row as one byte string
            held = set(rows.view(f"V{rows.strides[0]}").ravel().tolist())
            out[name] = len(held - self._table_rows.get(name, set()))
            self._table_rows[name] = held
        return out

    def _states(self) -> tuple:
        """The step's donated argument: every family's open state (or
        the held one a late run has put in its place)."""
        states = (tuple(w.model.state for _, w in self._hh),
                  tuple(w.model.totals for _, w in self._dense),
                  tuple(d.state for _, d in self._ddos))
        if self._stepped_spread:
            states += (tuple(w.model.state
                             for _, w in self._stepped_spread),)
        return states

    @property
    def spread_families(self) -> tuple:
        """The spread detectors the step updates, by the names in its
        ``spread_regs_<name>`` / ``spread_table_<name>`` scopes."""
        return tuple(name for name, _ in self._stepped_spread)

    @property
    def hh_families(self) -> tuple:
        """The sketch families' names in the order of the step's
        ``hh_table_merge_<i>`` scopes: the index a trace reader needs to
        tell one family's merge from another's."""
        return tuple(name for name, _ in self._hh)

    # ---- host lifecycle ---------------------------------------------------

    def update(self, batch: FlowBatch) -> None:
        if len(batch) == 0:
            return
        with TRACER.span("split_parts") as span:
            groups, wm = self._split_groups(batch)
            slot_runs = _runs(groups, 0)
            span["parts"] = len(groups)
        # a batch that fills a device step: the source holds a backlog
        # (WindowAggregator._min_slot)
        self._behind = len(batch) >= self._bs
        self._run_chunks(batch, groups, slot_runs)
        for _, m in self._waggs:
            if wm > m.watermark:
                m.watermark = wm
        self._advance_watermark(wm)

    def _run_chunks(self, batch: FlowBatch, groups: list,
                    slot_runs: dict) -> None:
        """What each run of a cut poll runs. A slot run (the groups of
        one window slot: the whole poll, but at a slot roll) is ONE
        fused step a chunk: flows_5m, the tables and the ports take the
        run's rows, the detector those of the run's newest sub-window.
        The run's older sub-windows take the detector's own program
        alone, first and in order, each against the state its unit has
        then (sub-window n is scored before n + 1). Rows stay where
        they are: a run is a mask over lanes placed once a poll."""
        lanes = _Lanes(batch, self._bs, self._cols)
        if not self._detector_warm:
            self._warm_detector(lanes)
        firsts = [*slot_runs, len(groups)]
        for first, end in zip(firsts, firsts[1:]):
            slot, rows = slot_runs[first]
            hh_unit = self._advance_hh(slot, _count(rows, batch))
            *older, (_, sub, newest_rows) = groups[first:end]
            for _, old_sub, old_rows in older:
                dd_unit = self._advance_ddos(old_sub,
                                             _count(old_rows, batch))
                if dd_unit is not None:
                    with self._units(None, dd_unit):
                        self._run_detector(lanes, old_rows, dd_unit)
            # a run of one sub-window: the detector takes the run's rows
            dd_rows = newest_rows if older else rows
            dd_unit = self._advance_ddos(sub, _count(dd_rows, batch))
            # the states of this run's steps: a family's open unit, or
            # the held one its rows belong to
            with self._units(hh_unit, dd_unit):
                self._run_step(lanes, rows, dd_rows, hh_unit, dd_unit)

    def _run_detector(self, lanes: _Lanes, rows: np.ndarray,
                      dd_unit: str) -> None:
        """The detector's accumulate alone (the per-model path's
        program, models.ddos.ddos_accumulate) for the rows of one
        sub-window, over the columns the poll already has on the
        device."""
        for start in lanes.starts():
            mask, n = lanes.mask(start, rows)
            if not n:
                continue
            cols = lanes.place(start)[2]
            for _, d in self._ddos:
                # one span per dispatch: their count inside one "apply"
                # is how often a poll crossed only a sub-window
                with TRACER.span("detector_dispatch", rows=n,
                                 padded=self._bs, dd_unit=dd_unit):
                    d.state = ddos_accumulate(
                        d.state,
                        {k: cols[k] for k in ddos_input_cols(d.config)},
                        jnp.asarray(mask), config=d.config)

    def _warm_detector(self, lanes: _Lanes) -> None:
        """Compile the detector's own program in the pipeline's first
        batch, on a scratch state under an all-false mask: the first
        poll that crosses only a sub-window may come much later, and
        nothing may compile then (a measured window counts compiles)."""
        _, _, cols, valid = lanes.place(0)
        for _, d in self._ddos:
            ddos_accumulate(
                d._fresh_state(),
                {k: cols[k] for k in ddos_input_cols(d.config)},
                jnp.zeros_like(valid), config=d.config)
        self._detector_warm = True

    def _run_step(self, lanes: _Lanes, rows, dd_rows,
                  hh_unit: str | None, dd_unit: str | None) -> None:
        bs = self._bs
        do_hh, do_dd = hh_unit is not None, dd_unit is not None
        for start in lanes.starts():
            mask, n = lanes.mask(start, rows)
            if not n:
                continue
            _chunk, host_cols, cols, pad_valid = lanes.place(start)
            dd_mask, dd_n = ((mask, n) if dd_rows is rows
                             else lanes.mask(start, dd_rows))
            states = self._states()
            # one span per device step: their count inside one "apply" is
            # device steps per batch, rows/padded the step's fill. The
            # masks of a run that is not the whole chunk cross here
            with TRACER.span("step_dispatch", rows=n, padded=bs,
                             do_hh=do_hh, do_dd=do_dd,
                             hh_unit=hh_unit or "dropped",
                             dd_unit=dd_unit or "dropped",
                             dd_rows=dd_n if do_dd else 0):
                valid = pad_valid if rows is None else jnp.asarray(mask)
                valid_dd = (valid if dd_mask is mask
                            else jnp.asarray(dd_mask))
                zeros = (jnp.zeros_like(valid)
                         if not (do_hh and do_dd) else None)
                new_states, wagg_parts, live_rows = self._step(
                    states, cols, valid,
                    valid if do_hh else zeros,
                    valid_dd if do_dd else zeros,
                )
            if do_hh:
                self._live_rows = live_rows
                self._hh_steps += 1
            new_hh, new_dense, new_ddos = new_states[:3]
            if self._stepped_spread:
                for (_, w), st in zip(self._stepped_spread, new_states[3]):
                    w.model.state = st
            for (_, w), st in zip(self._hh, new_hh):
                w.model.state = st
            for (_, w), tot in zip(self._dense, new_dense):
                w.model.totals = tot
            for (_, d), st in zip(self._ddos, new_ddos):
                d.state = st
            for (_, m), out in zip(self._waggs, wagg_parts):
                # exact fallback for the ~2^-64 hash-collision case: the
                # chunk re-runs its own lexicographic groupby at drain
                # time (flows_5m stays bit-exact). Closes over the HOST
                # columns so pending fallbacks don't pin device buffers
                # (see WindowAggregator._exact_fallback). With a slot
                # bound the flush probe leaves this partial on the
                # device until the next step is queued behind it.
                m.add_partial(
                    out, fallback=m._exact_fallback(host_cols, mask),
                    min_slot=m._min_slot(host_cols, mask, self._behind))
