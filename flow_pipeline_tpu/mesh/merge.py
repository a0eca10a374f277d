"""flowmesh window-close merges: the monoid algebra, host-side.

These are the `parallel/sharded.py` collective merges lifted off the
device mesh onto serialized payloads (PAPERS.md's data-plane HH model —
HashPipe 1611.04825, 1902.06993: per-shard detection, network-wide
exact merge):

- exact window aggregates : per-key uint64 SUM (associative, exact)
- CMS planes              : element-wise uint64 SUM — the count-min
                            sketch is linear in the stream, so the sum
                            of per-shard sketches IS the sketch of the
                            union stream (bit-identical for the plain
                            update; a valid, slightly looser upper
                            bound under conservative update)
- top-K candidate tables  : concat -> group-by-key sum -> rank by
                            primary desc with the stable lexicographic
                            tie-break (`jnp.argsort(-primary)`'s exact
                            behavior — the same table-table fold
                            ops.topk.topk_merge runs on device). With
                            key-hash sharding the key sets are
                            disjoint, so the per-key sum degenerates to
                            a copy and the merged values are exact.
- dense accumulators      : element-wise integer sum (the (lo, hi)
                            planes recombine exactly at extraction)

Pure numpy — the coordinator merges without touching a device.
"""

from __future__ import annotations

import numpy as np

from ..hostsketch.engine import np_cms_query
from ..models.heavy_hitter import HeavyHitterConfig, key_width
from ..models.window_agg import WindowStore
from ..ops.hostgroup import _lex_regroup
from ..schema.batch import lane_width

_SENTINEL = np.uint32(0xFFFFFFFF)


# ---- exact window aggregates ----------------------------------------------


def merge_wagg(payloads: list[dict], config=None) -> WindowStore:
    """Fold wagg payloads (keys [G, L] u32, vals [G, V] u64) into one
    window store — per-key sums, exact, by the sort the worker's own
    fold starts with.

    ``config`` is unused (the fold is shape-generic) but accepted so
    every registered family's merge hook shares one signature
    (families/registry.py)."""
    real = [p for p in payloads if len(p["keys"])]
    if not real:
        return WindowStore(np.zeros((0, 0), np.uint32),
                           np.zeros((0, 0), np.uint64))
    return WindowStore.from_rows(
        np.concatenate([p["keys"] for p in real]),
        np.concatenate([p["vals"] for p in real]))


# ---- heavy-hitter sketch state --------------------------------------------


def merge_hh(payloads: list[dict], config: HeavyHitterConfig) -> dict:
    """Fold hh payloads into one merged {cms, table_keys, table_vals}.

    CMS: uint64 element sum. Table: the table-table fold — every real
    row from every table, grouped by key (lexicographic), per-key plane
    sums, ranked by plane-0 descending with the stable lex tie-break,
    truncated to capacity.

    Invertible payloads (kind="hh_inv", the -hh.sketch=invertible
    family) dispatch to :func:`merge_hh_inv`: every plane merges by a
    plain element-wise u64 sum — no table folds, no device-rank
    semantics — and the merged table view is DECODED from the merged
    sketch. Either way the merged dict carries {cms, table_keys,
    table_vals}, so extraction, serving and the audit consume one
    shape.
    """
    if any(p.get("kind") == "hh_inv" for p in payloads):
        if not all(p.get("kind") == "hh_inv" for p in payloads):
            # one family must run ONE sketch flavor mesh-wide: a mixed
            # fold has no exactness story (u64 planes vs f32 tables)
            raise ValueError(
                "cannot merge mixed hh/hh_inv payloads for one family "
                "— every member must run the same -hh.sketch")
        return merge_hh_inv(payloads, config)
    planes = len(config.value_cols) + 1
    kw = key_width(config)
    cms = np.zeros((planes, config.depth, config.width), np.uint64)
    rows_k, rows_v = [], []
    for p in payloads:
        cms += p["cms"].astype(np.uint64)
        tk = p["table_keys"].astype(np.uint32)
        tv = p["table_vals"].astype(np.float32)
        real = (tk != _SENTINEL).any(axis=1)
        rows_k.append(tk[real])
        rows_v.append(tv[real])
    new_keys = np.full((config.capacity, kw), _SENTINEL, np.uint32)
    new_vals = np.zeros((config.capacity, planes), np.float32)
    keys = np.concatenate(rows_k) if rows_k else new_keys[:0]
    vals = np.concatenate(rows_v) if rows_v else new_vals[:0]
    if len(keys):
        order, starts = _lex_regroup(keys)
        uniq = keys[order][starts]
        sums = np.add.reduceat(vals[order], starts,
                               axis=0).astype(np.float32)
        top = np.argsort(-sums[:, 0], kind="stable")[:config.capacity]
        new_keys[:len(top)] = uniq[top]
        new_vals[:len(top)] = sums[top]
    out = {"kind": "hh", "cms": cms, "table_keys": new_keys,
           "table_vals": new_vals}
    # sketchwatch: per-member sampled exact cohorts ride inside the hh
    # payloads; their fold is the same uint64 per-key sum the CMS
    # linearity argument rests on — the merged cohort IS the cohort a
    # single worker seeing the whole stream would have built
    audits = [p["audit"] for p in payloads if p.get("audit") is not None]
    if audits:
        out["audit"] = merge_audit(audits)
    return out


def merge_hh_inv(payloads: list[dict], config: HeavyHitterConfig) -> dict:
    """Fold invertible-family payloads: element-wise u64 wrap sum of
    the count/value planes AND the key-recovery planes — the whole
    merge (the sketch is linear in the stream, so the sum of per-shard
    states IS the state of the union stream, bit-exactly). The merged
    table view is then decoded ONCE from the merged sketch
    (hostsketch.engine.inv_extract), so `hh_top_rows`, the serve
    publisher and the merged-cohort audit consume the same
    {cms, table_keys, table_vals} shape table merges produce."""
    from ..hostsketch.engine import inv_extract

    planes = len(config.value_cols) + 1
    kw = key_width(config)
    cms = np.zeros((planes, config.depth, config.width), np.uint64)
    keysum = np.zeros((config.depth, config.width, kw), np.uint64)
    keycheck = np.zeros((config.depth, config.width), np.uint64)
    with np.errstate(over="ignore"):
        for p in payloads:
            # asarray, not astype: hh_inv payloads are u64 by
            # construction (codec._u64_plane) — astype would allocate a
            # throwaway copy of every plane set per member per merge
            cms += np.asarray(p["cms"], dtype=np.uint64)
            keysum += np.asarray(p["keysum"], dtype=np.uint64)
            keycheck += np.asarray(p["keycheck"], dtype=np.uint64)
    table_keys, table_vals = inv_extract(
        {"cms": cms, "keysum": keysum, "keycheck": keycheck},
        config.capacity)
    out = {"kind": "hh", "cms": cms, "table_keys": table_keys,
           "table_vals": table_vals, "keysum": keysum,
           "keycheck": keycheck}
    audits = [p["audit"] for p in payloads
              if p.get("audit") is not None]
    if audits:
        out["audit"] = merge_audit(audits)
    return out


def merge_audit(parts: list[dict]) -> dict:
    """Fold audit partials ({keys [K, W] u32, vals [K, P+1] u64}) into
    one: per-key uint64 sums, keys in lexicographic order (the same
    canonical order members serialize, so merge(one part) == the part
    bit-for-bit and the mesh-vs-oracle equality is array equality)."""
    real = [p for p in parts if len(p["keys"])]
    evictions = int(sum(int(p.get("evictions", 0)) for p in parts))
    scale = int(max(int(p.get("scale", 1)) for p in parts))
    if not real:
        first = parts[0]
        return {"keys": first["keys"][:0].astype(np.uint32),
                "vals": first["vals"][:0].astype(np.uint64),
                "evictions": evictions, "scale": scale}
    keys = np.concatenate([p["keys"].astype(np.uint32) for p in real])
    vals = np.concatenate([p["vals"].astype(np.uint64) for p in real])
    order, starts = _lex_regroup(keys)
    return {"keys": np.ascontiguousarray(keys[order][starts]),
            "vals": np.add.reduceat(vals[order], starts, axis=0),
            "evictions": evictions, "scale": scale}


def hh_top_rows(merged: dict, config: HeavyHitterConfig, k: int,
                slot: int) -> dict[str, np.ndarray]:
    """Columnar top-k rows from one merged hh payload — the numpy twin of
    models.heavy_hitter._top_from_state plus the timeslot column
    WindowedHeavyHitter stamps at window close, so merged output rows are
    shape- and dtype-identical to a single worker's."""
    k = min(k, config.capacity)
    keys = merged["table_keys"][:k]
    vals = merged["table_vals"][:k]
    valid = (keys != _SENTINEL).any(axis=1)
    ests = np_cms_query(merged["cms"], keys)[:k]
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
    out["timeslot"] = np.full(len(valid), slot, dtype=np.uint64)
    return out


# ---- spread (distinct-count) sketch state ---------------------------------


def merge_spread(payloads: list[dict], config) -> dict:
    """Fold spread payloads into one merged {regs, table_keys,
    table_metric}.

    Registers: element-wise u8 MAX — the HLL register plane is an exact
    max monoid over the element stream (ops/spread.py), so the max of
    per-shard planes IS the plane of the union stream, bit-exactly,
    for any member count and any stream split. Candidate tables:
    concat -> group-by-key SUM of the admission metric (each member's
    metric is its accumulated per-chunk distinct-pair count — a valid
    union-bound upper bound on the key's true distinct count; the sum
    preserves that bound but is NOT chunking-invariant, since members
    chunk their own sub-streams), ranked metric-descending with the
    stable lex tie-break, truncated to capacity. The metric only
    decides which keys stay tracked — reported spread values are
    decoded from the merged registers at extraction (spread_top_rows),
    never from the metric, so merged answers are exact wherever the
    register planes are."""
    from ..models.spread import spread_key_width

    if any(p.get("kind") != "spread" for p in payloads):
        # one family must fold ONE payload shape mesh-wide: a spread
        # max fold has no meaning over hh/dense sum payloads
        raise ValueError(
            "cannot merge mixed spread/non-spread payloads for one "
            "family — every member must run the same model kind")
    regs = np.zeros((config.depth, config.width, config.registers),
                    np.uint8)
    rows_k, rows_m = [], []
    for p in payloads:
        np.maximum(regs, np.asarray(p["regs"], dtype=np.uint8), out=regs)
        tk = p["table_keys"].astype(np.uint32)
        tm = p["table_metric"].astype(np.float32)
        real = (tk != _SENTINEL).any(axis=1)
        rows_k.append(tk[real])
        rows_m.append(tm[real])
    kw = spread_key_width(config)
    new_keys = np.full((config.capacity, kw), _SENTINEL, np.uint32)
    new_metric = np.zeros(config.capacity, np.float32)
    keys = np.concatenate(rows_k) if rows_k else new_keys[:0]
    metric = np.concatenate(rows_m) if rows_m else new_metric[:0]
    if len(keys):
        order, starts = _lex_regroup(keys)
        uniq = keys[order][starts]
        sums = np.add.reduceat(metric[order], starts).astype(np.float32)
        top = np.argsort(-sums, kind="stable")[:config.capacity]
        new_keys[:len(top)] = uniq[top]
        new_metric[:len(top)] = sums[top]
    return {"kind": "spread", "regs": regs, "table_keys": new_keys,
            "table_metric": new_metric}


def spread_top_rows(merged: dict, config, k: int,
                    slot: int) -> dict[str, np.ndarray]:
    """Columnar top-k rows from one merged spread payload — the shared
    decode-at-read extraction (models.spread.spread_top_from: rank by
    register-decoded spread, stable lex tie-break) plus the timeslot
    column WindowedHeavyHitter stamps at window close, so merged output
    rows are shape- and dtype-identical to a single worker's."""
    from ..models.spread import spread_top_from

    top = spread_top_from(merged, config, k)
    top["timeslot"] = np.full(len(top["valid"]), slot, dtype=np.uint64)
    return top


# ---- dense accumulators ---------------------------------------------------


def merge_dense(payloads: list[dict], config=None) -> np.ndarray:
    """Element-wise int64 sum of dense (lo, hi) planes. ``config`` is
    unused (the sum is shape-generic) but accepted so every registered
    family's merge hook shares one signature (families/registry.py)."""
    out = payloads[0]["totals"].astype(np.int64).copy()
    for p in payloads[1:]:
        out += p["totals"].astype(np.int64)
    return out


def dense_top_rows(totals: np.ndarray, config, k: int,
                   slot: int) -> dict[str, np.ndarray]:
    """Top-k rows from merged dense totals, via the model's own exact
    extraction (summed lo planes stay far below int32 before the exact
    lo + (hi << 16) recombination)."""
    from ..models.dense_top import DenseTopKModel

    model = DenseTopKModel.__new__(DenseTopKModel)
    model.config = config
    model.totals = np.asarray(totals, dtype=np.int64).astype(np.int32)
    top = model.top(k)
    top["timeslot"] = np.full(len(top["valid"]), slot, dtype=np.uint64)
    return top
