"""Heavy-hitter model: count-min sketch + top-K candidate table.

The flagship sketch pipeline (BASELINE configs #2 and #3):

    batch columns
      -> sort_groupby on the key tuple        (exact per-batch pre-agg)
      -> conservative count-min update        (bounded-error totals)
      -> top-K table merge                    (identity tracking)

State lives on device for the whole window; the host only sees the final
top-K rows at window close. The key tuple is configurable — (SrcAddr,
DstAddr) for config #2, the 5-tuple (SrcAddr, DstAddr, SrcPort, DstPort,
Proto) "top talkers" for config #3. Estimates come from the CMS query
(min over depth), which upper-bounds true totals by <= e/width * stream
mass; ranking uses the table's accumulated sums.

Window semantics mirror the exact aggregator: the model is windowed by the
driver (engine/) which calls ``flush`` at watermark close — same tumbling
5-minute windows as the reference's flows_5m rollup
(ref: compose/clickhouse/create.sh:96), or under ``-window.slide`` one
model per sub-window, their states folded at every slide
(engine/windowed.py; ``hh_fold_program``).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import cms as cms_ops
from ..ops import topk as topk_ops
from ..ops.fold import fold_planes, fold_tables, named_program
from ..ops.segment import hash_groupby_float, hash_lanes
from ..schema.batch import FlowBatch, lane_width


@dataclass(frozen=True)
class HeavyHitterConfig:
    key_cols: tuple[str, ...] = ("src_addr", "dst_addr")
    value_cols: tuple[str, ...] = ("bytes", "packets")  # plane 0 ranks
    depth: int = 4
    width: int = 1 << 16  # 65536, multiple of 128
    capacity: int = 1024  # candidate table rows
    batch_size: int = 8192
    conservative: bool = True
    # Feed the table merge only 2*capacity candidates — the batch's top
    # groups by plane-0 sum PLUS every group whose key is already
    # RESIDENT in the table — shrinking its sort from (capacity + batch)
    # rows to 3*capacity. Residency is hash membership against the
    # current table keys: on the device one dense [capacity, groups]
    # compare of 32-bit hashes (_resident: no dependent gathers); the
    # host twins (hostsketch/engine.py, native hs_hh_prefilter) keep a
    # binary search in the sorted hashes, the right algorithm on a host,
    # and give the same mask. The CMS still counts EVERY row (estimates
    # unaffected). Resident keys therefore accumulate their increments
    # every round, exactly like the unfiltered merge — the r4 prefilter
    # starved residents that didn't rank per batch, silently
    # under-counting them ~25x on near-uniform streams (VERDICT r4 #4).
    # Only ADMISSION loosens: a NEW key must rank in some batch's top
    # 2*capacity to enter, adding at most one batch's rank-2C value per
    # round to the Misra-Gries dropped-mass bound. Default ON: the table
    # merges are the largest part of the fused step (PERF.md §5), and
    # the top-K gate of tests/test_models.py holds with it on a Zipf
    # stream flatter than real flow traffic.
    table_prefilter: bool = True
    # Top-K table admission rule: "est" (default) is space-saving
    # admission via ops.topk.topk_merge_est — a NEW key enters with its
    # CMS estimate so table values upper-bound true totals; "plain" is
    # the pre-r4 batch-sum merge (ops.topk.topk_merge), which silently
    # under-counts keys admitted mid-window. "plain" exists for the A/B
    # of what the est admission's extra planes cost on the hot path
    # (VERDICT #2); no cell of the benchmark runs it.
    table_admission: str = "est"
    # Serving-side sampling correction: multiply every value plane by
    # max(<scale_col>, 1) per row, so ranked bytes/packets estimate the
    # TRUE traffic the samples represent — the reference's dashboards
    # apply the same factor at query time (ref: compose/grafana/
    # dashboards/viz-ch.json sum(Bytes*SamplingRate)). float32 multiply:
    # sketches are approximate by contract. None disables. With the
    # mocker (rate 1) outputs are unchanged.
    scale_col: str | None = "sampling_rate"
    # Sketch family (-hh.sketch): "table" keeps the CMS + top-K
    # admission table (prefilter -> admission CMS query -> table merge,
    # the larger part of an update); "invertible" replaces
    # the whole admission path with key-recovery planes folded next to
    # the CMS buckets (keysum/keycheck u64 wrap sums — ops/invsketch,
    # hostsketch/engine np_inv_*, native hs_inv_*): update is one pure
    # per-bucket fold, heavy keys are DECODED from the sketch at window
    # close, and the mesh merge degenerates to a plain element-wise u64
    # sum. Invertible forces the PLAIN count-plane update (decode
    # divides by the count cell, which must be the bucket's exact sum),
    # so `conservative`, `table_prefilter` and `table_admission` are
    # ignored for this family. Production home: the host dataplane
    # (-sketch.backend=host, fused or staged); other pipelines fall
    # back to the per-model numpy path with a warning.
    hh_sketch: str = "table"


class HHState(NamedTuple):
    """Device-resident sketch state (a pytree — psum/donate friendly)."""

    cms: jnp.ndarray  # [P+1, depth, width] (value planes + count plane)
    table_keys: jnp.ndarray  # [C, W]
    table_vals: jnp.ndarray  # [C, P+1]


class InvState(NamedTuple):
    """Invertible-family sketch state (hh_sketch="invertible"): exact
    uint64 planes, HOST-resident numpy by design — the key-recovery
    planes have no f32 device layout (a lane times a count does not fit
    the float-exact envelope), so the u64 monoid IS the canonical form.
    The jnp twin (ops/invsketch) serves x64-enabled devices; the
    production home is the native host dataplane."""

    cms: np.ndarray       # [P+1, depth, width] uint64
    keysum: np.ndarray    # [depth, width, key_width] uint64
    keycheck: np.ndarray  # [depth, width] uint64


def key_width(config: HeavyHitterConfig) -> int:
    return sum(lane_width(name) for name in config.key_cols)


def input_cols(config: HeavyHitterConfig) -> list[str]:
    """Columns the update step reads: keys + values + the scale column."""
    out = [*config.key_cols, *config.value_cols]
    if config.scale_col:
        out.append(config.scale_col)
    return out


def inv_init(config: HeavyHitterConfig) -> InvState:
    planes = len(config.value_cols) + 1  # + count
    w = key_width(config)
    return InvState(
        cms=np.zeros((planes, config.depth, config.width), np.uint64),
        keysum=np.zeros((config.depth, config.width, w), np.uint64),
        keycheck=np.zeros((config.depth, config.width), np.uint64),
    )


def hh_init(config: HeavyHitterConfig):
    if config.hh_sketch not in ("table", "invertible"):
        raise ValueError(
            f"hh_sketch must be table|invertible, got "
            f"{config.hh_sketch!r}")
    if config.hh_sketch == "invertible":
        return inv_init(config)
    planes = len(config.value_cols) + 1  # + count
    tk, tv = topk_ops.topk_init(config.capacity, key_width(config), planes)
    return HHState(
        cms=cms_ops.cms_init(planes, config.depth, config.width),
        table_keys=tk,
        table_vals=tv,
    )


def _key_lanes(cols: dict, key_cols) -> jnp.ndarray:
    lanes = []
    for name in key_cols:
        arr = cols[name].astype(jnp.uint32)
        if arr.ndim == 1:
            lanes.append(arr[:, None])
        else:
            lanes.append(arr)
    return jnp.concatenate(lanes, axis=1)


def _cms_add(config: HeavyHitterConfig, n_live):
    """The CMS update op for ``conservative``. Both share ops.cms's
    bucket scheme and state layout, so the selection can change between
    runs (even mid-stream) without invalidating a sketch. ``n_live``
    (live_rows of the groups to come) goes to the one op whose cost it
    bounds: the conservative update, whose estimate gathers the rows
    below it and whose scatters drop the rows that are not valid (all
    at or beyond it, and the holes below)."""
    if config.conservative:
        return partial(cms_ops.cms_add_conservative, n_live=n_live)
    return cms_ops.cms_add


def live_rows(row_valid):
    """[] int32: 1 + the index of the last True of ``row_valid`` [N], 0
    for none: every real group lies below it. Not the count of real
    groups: a device group-by puts them in a prefix (the sentinel hash
    sorts last), but under the fused step's shared dst sort
    (engine.fused consume_b) a family's real groups may have another
    consumer's between them, and the bound has to hold them all."""
    n = row_valid.shape[0]
    return jnp.max(jnp.where(row_valid, jax.lax.iota(jnp.int32, n) + 1, 0))


def _resident(th, gh, row_valid):
    """[N] bool: the valid groups whose hash ``gh`` [N] uint32 is one of
    the table's hashes ``th`` [C] uint32 (empty slots hash like any row).

    One dense compare, OR-reduced over the table: C x N independent
    uint32 compares, which XLA fuses into one pass that writes no [C, N]
    predicate. The table is the major axis, so the reduction combines
    whole vectors of groups element-wise and crosses no lanes (on a v5e
    the other orientation and a form blocked by 128 table rows read the
    same). sort(th) + searchsorted + ts[pos] == gh gives the same mask,
    but searchsorted is a loop of ceil(log2(C + 1)) gathers, each waiting
    for the one before: 2.1 ms a family on a v5e at C = 1,024, N = 32,768
    where this takes 0.04 (PERF.md §6, PR 32). The prefilter's static
    guard keeps C < N / 2 wherever this runs."""
    return (th[:, None] == gh[None, :]).any(axis=0) & row_valid


def _apply_grouped(state: HHState, uniq, sums, row_valid,
                   config: HeavyHitterConfig) -> HHState:
    """CMS + table merge over pre-aggregated groups (the post-sort half of
    the step). ``uniq`` [N, key_width] uint32 unique key rows, ``sums``
    [N, P+1] float32 per-group value sums with the count plane LAST,
    ``row_valid`` [N] bool. Shared by hh_update and the fused pipeline
    (engine.fused), which computes the groupby once per key family."""
    new_cms = _cms_add(config, live_rows(row_valid))(
        state.cms, uniq, sums, row_valid)
    if config.table_prefilter and uniq.shape[0] > 2 * config.capacity:
        # Table-aware prefilter: boost groups whose key is already in the
        # table so residents are NEVER starved of their increments (see
        # the config docstring). Membership rides one 32-bit hash lane:
        # a resident's hash is in the table's hash set by construction
        # (no false negatives); a false positive (~C/2^32 per group)
        # merely spends one of the 2C candidate slots on a loser. The
        # test is dense (_resident): a binary search in the sorted hashes
        # is a chain of dependent gathers on the device; the host twins
        # keep theirs.
        c = config.capacity
        th, _ = hash_lanes(state.table_keys)
        gh, _ = hash_lanes(uniq)
        resident = _resident(th, gh, row_valid)
        metric = jnp.where(row_valid, sums[:, 0], -jnp.inf)
        metric = jnp.where(resident, jnp.inf, metric)
        _, sel = jax.lax.top_k(metric, 2 * c)
        uniq, sums, row_valid = uniq[sel], sums[sel], row_valid[sel]
    if config.table_admission == "plain":
        # A/B leg: batch-sum merge without the CMS-seeded admission (see
        # HeavyHitterConfig.table_admission — benchmarking only)
        tk, tv = topk_ops.topk_merge(
            state.table_keys, state.table_vals, uniq, sums, row_valid
        )
        return HHState(cms=new_cms, table_keys=tk, table_vals=tv)
    if config.table_admission != "est":
        raise ValueError(
            f"table_admission must be est|plain, got "
            f"{config.table_admission!r}")
    # Space-saving admission: new keys enter with their CMS estimate (the
    # CMS above counted the FULL batch, so the estimate covers pre-entry
    # mass); resident keys take exact increments (topk_merge_est).
    est = cms_ops.cms_query(new_cms, uniq)
    tk, tv = topk_ops.topk_merge_est(
        state.table_keys, state.table_vals, uniq, sums, est, row_valid
    )
    return HHState(cms=new_cms, table_keys=tk, table_vals=tv)


@partial(jax.jit, static_argnames=("config",), donate_argnames=("state",))
def hh_update(state: HHState, cols: dict, valid, *, config: HeavyHitterConfig) -> HHState:
    """One batch step, fully on device."""
    keys = _key_lanes(cols, config.key_cols)
    # Columns arrive as int32 bit-patterns of uint32 counters; reinterpret as
    # unsigned before the float cast so saturated values (>2^31) stay
    # positive — a negative addend would break the CMS upper-bound invariant.
    planes = [
        cols[name].astype(jnp.uint32).astype(jnp.float32)
        for name in config.value_cols
    ]
    if config.scale_col:
        rate = jnp.maximum(
            cols[config.scale_col].astype(jnp.uint32).astype(jnp.float32),
            1.0)
        planes = [p * rate for p in planes]
    values = jnp.stack(
        planes + [jnp.ones(keys.shape[0], jnp.float32)],
        axis=1,
    )
    # Hash-grouped pre-agg: sorting the 64-bit key hash (2 lanes) instead
    # of the raw 4-11 key lanes cuts the dominant sort cost 2-4x; two
    # distinct tuples colliding in the full hash (~n^2/2^65 per batch)
    # merge into one candidate — the same bounded failure mode the CMS
    # planes already have by design (ops.segment.hash_groupby_float).
    uniq, sums, counts = hash_groupby_float(keys, values, valid)
    return _apply_grouped(state, uniq, sums, counts > 0, config)


@partial(jax.jit, static_argnames=("config",))
def hh_estimates(state: HHState, *, config: HeavyHitterConfig):
    """CMS point estimates for every table key. [C, P+1] float32."""
    return cms_ops.cms_query(state.cms, state.table_keys)


def _top_from_state(state: HHState, config: HeavyHitterConfig,
                    k: int) -> dict[str, np.ndarray]:
    """Materialize top-k rows from one captured state — pure function so
    lazy extraction (top_lazy) stays valid after the model moves on."""
    keys, vals, valid = topk_ops.topk_extract(
        state.table_keys, state.table_vals, k
    )
    ests = hh_estimates(state, config=config)[:k]
    keys = np.asarray(keys)
    vals = np.asarray(vals)
    ests = np.asarray(ests)
    valid = np.asarray(valid)
    out: dict[str, np.ndarray] = {}
    col = 0
    for name in config.key_cols:
        w = lane_width(name)
        out[name] = keys[:, col : col + w] if w == 4 else keys[:, col]
        col += w
    for j, name in enumerate(config.value_cols):
        out[name] = vals[:, j]
        out[f"{name}_est"] = ests[:, j]
    out["count"] = vals[:, -1]
    out["count_est"] = ests[:, -1]
    out["valid"] = valid
    return out


def _inv_top_from_state(state: InvState, config: HeavyHitterConfig,
                        k: int) -> dict[str, np.ndarray]:
    """Top-k rows from one invertible state — the decode-at-close twin
    of _top_from_state: heavy keys recovered from the sketch itself
    (hostsketch.engine.inv_extract), ranked exactly like the table
    family ((primary desc, lex asc)); est columns stay the CMS
    min-over-depth point estimates off the same count/value planes.
    Output columns are shape- and dtype-identical to the table path's."""
    from ..hostsketch.engine import inv_extract, np_cms_query

    keys, vals = inv_extract(state, config.capacity)
    keys, vals = keys[:k], vals[:k]
    valid = (keys != np.uint32(0xFFFFFFFF)).any(axis=1)
    ests = np_cms_query(np.asarray(state.cms), keys)
    out: dict[str, np.ndarray] = {}
    col = 0
    for name in config.key_cols:
        w = lane_width(name)
        out[name] = keys[:, col:col + w] if w == 4 else keys[:, col]
        col += w
    for j, name in enumerate(config.value_cols):
        out[name] = vals[:, j]
        out[f"{name}_est"] = ests[:, j]
    out["count"] = vals[:, -1]
    out["count_est"] = ests[:, -1]
    out["valid"] = valid
    return out


@functools.lru_cache(maxsize=None)
def hh_fold_program(name: str, n: int):
    """The jitted fold of ``n`` states into one (a tuple of ``n``
    ``HHState`` in, oldest first): the monoid the four-chip close runs
    over its replicas (``parallel.sharded.sharded_hh_merge``; bodies in
    ``ops/fold.py``), over the sub-windows of a sliding window's ring
    (``engine.windowed.SubWindowRing``). The compiled module is
    ``jit_<name>``; the two scopes are a contract with the trace readers
    (docs/OBSERVABILITY.md). Cached on (name, n) as the step is cached
    on its spec: pipelines are rebuilt freely."""

    @named_program(name)
    def fold(states):
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
        with jax.named_scope("slide_fold_planes"):
            cms = fold_planes(stacked.cms)
        with jax.named_scope("slide_fold_tables"):
            tk, tv = fold_tables(stacked.table_keys, stacked.table_vals)
        return HHState(cms=cms, table_keys=tk, table_vals=tv)

    return jax.jit(fold)


class HeavyHitterModel:
    """Host wrapper: feed batches, extract top-K at window close."""

    snapshot_kind = "windowed_hh"  # worker checkpoint dispatch tag

    def __init__(self, config: HeavyHitterConfig = HeavyHitterConfig()):
        self.config = config
        self.state = hh_init(config)

    def update(self, batch: FlowBatch) -> None:
        if self.config.hh_sketch == "invertible":
            self._inv_update(batch)
            return
        bs = self.config.batch_size
        for start in range(0, len(batch), bs):  # chunk arbitrary batch sizes
            padded, mask = batch.slice(start, start + bs).pad_to(bs)
            cols = padded.device_columns(input_cols(self.config))
            cols = {k: jnp.asarray(v) for k, v in cols.items()}
            self.state = hh_update(
                self.state, cols, jnp.asarray(mask), config=self.config
            )

    def _inv_update(self, batch: FlowBatch) -> None:
        """Per-model fallback for the invertible family (the production
        home is the host pipeline, whose engine folds the prepared
        group tables instead): group each chunk exactly like the staged
        prepare half, then run the numpy twin in place. Mutates the
        state arrays (callers that capture state — top_lazy — copy)."""
        from ..engine.hostfused import _key_lanes_np, _value_planes_np
        from ..hostsketch.engine import np_inv_update
        from ..ops.hostgroup import group_by_key

        cfg = self.config
        bs = cfg.batch_size
        for start in range(0, len(batch), bs):
            chunk = batch.slice(start, start + bs)
            if len(chunk) == 0:
                continue
            cols = chunk.columns
            lanes = _key_lanes_np(cols, cfg.key_cols)
            vals = _value_planes_np(cols, cfg.value_cols, cfg.scale_col)
            uniq, sums, counts = group_by_key(lanes, [vals], exact=False)
            addends = np.concatenate(
                [sums[0].astype(np.float32),
                 counts.astype(np.float32)[:, None]], axis=1)
            np_inv_update(self.state, np.ascontiguousarray(
                uniq, dtype=np.uint32), addends)

    def top(self, k: int | None = None) -> dict[str, np.ndarray]:
        """Top-k rows: keys split back into columns + estimated sums.

        Table values rank the rows and UPPER-BOUND true totals: a key
        admitted mid-window is seeded with its CMS estimate at admission
        (space-saving admission, ops.topk.topk_merge_est — the estimate
        covers the key's pre-entry mass) and then takes exact increments
        while resident. ``est`` columns are the CMS point estimates at
        extraction time — an independent upper bound (tighter under
        conservative update); for a key resident since window start the
        table value is the exact observed sum and ``est`` bounds it.

        The invertible family has no table: the ranking is DECODED from
        the sketch here (hostsketch.engine.inv_extract — once per read,
        which window-close extraction and snapshot publishes amortize),
        and decoded values are the keys' exact sums, not upper bounds."""
        k = k or self.config.capacity
        if self.config.hh_sketch == "invertible":
            return _inv_top_from_state(self.state, self.config, k)
        return _top_from_state(self.state, self.config, k)

    def top_lazy(self, k: int | None = None):
        """Zero-arg closure producing top(k) from the state captured NOW.

        For the ingest runtime's background flusher: state arrays are
        immutable and reset()/update() replace rather than mutate them,
        so the extraction (a device sync) can run off-thread after the
        window rolls. The invertible fallback path (_inv_update) mutates
        in place, so that family captures fresh copies — once per
        window close, the same cost class as the decode itself."""
        state, config = self.state, self.config
        k = k or config.capacity
        if config.hh_sketch == "invertible":
            state = InvState(state.cms.copy(), state.keysum.copy(),
                             state.keycheck.copy())
            return lambda: _inv_top_from_state(state, config, k)
        return lambda: _top_from_state(state, config, k)

    def reset(self) -> None:
        self.state = hh_init(self.config)

    # ---- a sliding window's ring (engine.windowed.SubWindowRing) ------

    def window_state(self) -> HHState:
        return self.state

    def load_window_state(self, state) -> None:
        """The state to go on with: a held window's, or the open one's
        back in its place (``models/held.py``)."""
        self.state = state

    def empty_state(self) -> HHState:
        return hh_init(self.config)

    def fold_program(self, name: str, n: int):
        if self.config.hh_sketch != "table":
            raise ValueError(
                "a sliding window folds device states; hh_sketch="
                f"{self.config.hh_sketch!r} keeps its planes on the host")
        return hh_fold_program(name, n)

    def top_from(self, state: HHState, k: int | None = None):
        return _top_from_state(state, self.config,
                               k or self.config.capacity)

    @staticmethod
    def state_arrays(state: HHState) -> dict:
        return state._asdict()

    @staticmethod
    def state_from_arrays(arrays: dict) -> HHState:
        return HHState(**{f: jnp.asarray(arrays[f])
                          for f in HHState._fields})
