"""Exact aggregation oracle (numpy, host-side).

Reproduces the reference's ClickHouse ``flows_5m`` materialized-view
semantics exactly (ref: compose/clickhouse/create.sh:92-110):

    SELECT Date, toStartOfFiveMinute(TimeReceived) AS Timeslot,
           SrcAS, DstAS, EType, sum(Bytes), sum(Packets), count()
    GROUP BY Date, Timeslot, SrcAS, DstAS, EType

This is the ground truth every sketch/device path is gated against
(BASELINE: <=1% top-K Bytes error vs exact flows_5m). Pure numpy with
uint64 accumulators — slow is fine, wrong is not.

``late_unit_sums`` is the plain reference of ``-window.lateness`` for the
families whose closed state cannot reopen (the ranked tables' windows,
the detector's sub-windows): which rows a unit admits when batches come
out of event-time order, as from two partitions. It imports nothing of
the engine; tests hold the per-model path, ``FusedPipeline`` and
``ShardedPipeline`` to it.

``distinct_exact`` is the plain reference of the distinct-count (spread)
family: per window and key, how many distinct elements the key touched.
A sort and a unique; no hash of ``ops/spread.py``'s.
"""

from __future__ import annotations

import numpy as np

from ..schema.batch import FlowBatch

SECONDS_PER_SLOT = 300  # toStartOfFiveMinute
SECONDS_PER_DAY = 86_400  # toDate


def _key_matrix(batch: FlowBatch, key_cols: list[str], timeslot: bool) -> np.ndarray:
    """Stack key columns into an [N, W] uint64 matrix (addresses expand to
    4 words each) for lexicographic row grouping."""
    lanes = []
    if timeslot:
        ts = batch.columns["time_received"].astype(np.uint64)
        lanes.append((ts // SECONDS_PER_SLOT * SECONDS_PER_SLOT)[:, None])
    for name in key_cols:
        arr = batch.columns[name]
        if arr.ndim == 2:
            lanes.append(arr.astype(np.uint64))
        else:
            lanes.append(arr.astype(np.uint64)[:, None])
    return np.concatenate(lanes, axis=1)


def exact_groupby(
    batch: FlowBatch,
    key_cols: list[str],
    value_cols: list[str] = ("bytes", "packets"),
    timeslot: bool = True,
    scale_col: str | None = None,
) -> dict[str, np.ndarray]:
    """Exact groupby-sum over arbitrary key tuples.

    Returns a dict with one array per key column (addresses as [G,4]),
    optionally a leading ``timeslot`` key, summed ``value_cols`` (uint64),
    and ``count``. Rows are in lexicographic key order.

    With ``scale_col`` the dict additionally carries exact uint64
    ``<value>_scaled`` sums of value * max(rate, 1) — the reference's
    query-time ``sum(Bytes*SamplingRate)`` semantics
    (ref: compose/grafana/dashboards/viz-ch.json), ground truth for the
    sampling-corrected serving path.
    """
    keys = _key_matrix(batch, key_cols, timeslot)
    # Row-wise unique via void view (contiguous rows as opaque keys)
    kc = np.ascontiguousarray(keys)
    voided = kc.view([("", kc.dtype)] * kc.shape[1]).reshape(-1)
    uniq, inverse = np.unique(voided, return_inverse=True)
    g = len(uniq)
    uniq_rows = uniq.view(kc.dtype).reshape(g, kc.shape[1])

    out: dict[str, np.ndarray] = {}
    col_idx = 0
    if timeslot:
        out["timeslot"] = uniq_rows[:, 0]
        col_idx = 1
    for name in key_cols:
        arr = batch.columns[name]
        w = 4 if arr.ndim == 2 else 1
        cols = uniq_rows[:, col_idx : col_idx + w]
        out[name] = cols if w == 4 else cols[:, 0]
        col_idx += w
    rate = None
    if scale_col is not None:
        rate = np.maximum(batch.columns[scale_col].astype(np.uint64), 1)
    for name in value_cols:
        # np.add.at, not float bincount: uint64-exact accumulation
        vals = batch.columns[name].astype(np.uint64)
        acc = np.zeros(g, dtype=np.uint64)
        np.add.at(acc, inverse, vals)
        out[name] = acc
        if rate is not None:
            sacc = np.zeros(g, dtype=np.uint64)
            np.add.at(sacc, inverse, vals * rate)
            out[f"{name}_scaled"] = sacc
    out["count"] = np.bincount(inverse, minlength=g).astype(np.uint64)
    return out


def flows_5m(batch: FlowBatch) -> dict[str, np.ndarray]:
    """The reference rollup: (Date, Timeslot, SrcAS, DstAS, EType) ->
    sum Bytes, sum Packets, count. Date is derived from the timeslot
    (ref: create.sh:65 toDate(TimeReceived)), so grouping by timeslot alone
    is equivalent; we emit the Date column for row-shape parity."""
    out = exact_groupby(batch, ["src_as", "dst_as", "etype"], timeslot=True)
    out["date"] = (out["timeslot"] // SECONDS_PER_DAY).astype(np.uint64)
    return out


def topk_exact(
    batch: FlowBatch,
    key_cols: list[str],
    k: int,
    value_col: str = "bytes",
    timeslot: bool = False,
    scale_col: str | None = None,
) -> dict[str, np.ndarray]:
    """Exact top-K keys by summed value — heavy-hitter ground truth.
    Ties broken by key order (stable) so results are deterministic.

    With ``scale_col`` the ranking is by the exact ``<value>_scaled`` sum
    (value * max(rate, 1), uint64): what a ranked family with
    ``HeavyHitterConfig.scale_col`` estimates, and the dashboards'
    ``sum(bytes*sampling_rate)``. The raw sums ride along."""
    g = exact_groupby(batch, key_cols, [value_col], timeslot=timeslot,
                      scale_col=scale_col)
    ranked = g[f"{value_col}_scaled" if scale_col else value_col]
    # descending on uint64 without a signed cast: sums may pass 2^63
    order = np.argsort(ranked.max(initial=0) - ranked, kind="stable")[:k]
    return {name: arr[order] for name, arr in g.items()}


def distinct_exact(
    batch: FlowBatch,
    key_cols: list[str],
    elem_col: str,
    timeslot: bool = True,
) -> dict[str, np.ndarray]:
    """Exact distinct count of ``elem_col`` per key (and per 5-minute
    timeslot of ``time_received``) over the rows of ``batch``: the
    ground truth of ``models/spread.py``'s register-decoded estimates
    (superspreaders: src_addr -> dst_addr; portscan: src_addr ->
    dst_port).

    Returns one array per key column (addresses as [G, 4]), a leading
    ``timeslot`` when asked, ``distinct`` (uint64: the distinct elements)
    and ``count`` (uint64: the rows, what a sum in the place of the max
    would report). Rows are in lexicographic key order."""
    keys = _key_matrix(batch, list(key_cols), timeslot)
    rows = np.concatenate(
        [keys, _key_matrix(batch, [elem_col], False)], axis=1)
    kw = keys.shape[1]
    pairs, per_pair = np.unique(rows, axis=0, return_counts=True)
    uniq, start, distinct = np.unique(
        pairs[:, :kw], axis=0, return_index=True, return_counts=True)
    out: dict[str, np.ndarray] = {}
    col = 0
    if timeslot:
        out["timeslot"], col = uniq[:, 0], 1
    for name in key_cols:
        w = 4 if batch.columns[name].ndim == 2 else 1
        out[name] = uniq[:, col:col + w] if w == 4 else uniq[:, col]
        col += w
    out["distinct"] = distinct.astype(np.uint64)
    out["count"] = np.add.reduceat(per_pair, start).astype(np.uint64) \
        if len(start) else np.zeros(0, np.uint64)
    return out


def late_unit_sums(
    batches: list[FlowBatch],
    unit_seconds: int,
    lateness: int,
    key_cols: list[str],
    value_cols: list[str] = ("bytes", "packets"),
) -> dict:
    """Which rows each unit of event time admits, exactly.

    ``batches`` come in arrival order, each one partition's poll (its
    ``partition`` is carried, the semantics do not read it: the
    watermark is the newest ``time_received`` over all of them). A unit
    is ``time_received // unit_seconds * unit_seconds``. A batch's rows
    are taken unit by unit, oldest first:

    - the first unit seen is the open one; a newer unit rolls: with
      ``lateness`` 0 the open unit closes, else it is *held* (after the
      unit held until then, if any, has closed) and the newer one opens;
    - rows of the open unit and of the held one are admitted; any other
      row is older than both and is dropped;
    - once a batch is folded the watermark takes its newest row, and the
      held unit closes if the watermark has reached its end + lateness;
    - when the stream ends the held unit closes, then the open one.

    Returns ``{"units": {unit: exact_groupby of the rows it admitted},
    "dropped": rows dropped, "folded": rows admitted into a held unit,
    "closed_at": {unit: index of the batch that closed it, len(batches)
    for the end of the stream}, "order": units in closing order}``."""
    admitted: dict[int, list] = {}
    closed_at: dict[int, int] = {}
    order: list[int] = []
    open_unit = held = watermark = None
    dropped = folded = 0

    def close(unit, at):
        closed_at[unit] = at
        order.append(unit)

    for i, batch in enumerate(batches):
        if len(batch) == 0:
            continue
        t = batch.columns["time_received"].astype(np.int64)
        units = t // unit_seconds * unit_seconds
        for unit in np.unique(units).tolist():
            rows = np.flatnonzero(units == unit)
            if open_unit is None:
                open_unit = unit
            elif unit > open_unit:
                if held is not None:
                    close(held, i)
                    held = None
                if lateness > 0:
                    held = open_unit
                else:
                    close(open_unit, i)
                open_unit = unit
            if unit == held:
                folded += len(rows)
            elif unit != open_unit:
                dropped += len(rows)
                continue
            admitted.setdefault(unit, []).append(
                {k: v[rows] for k, v in batch.columns.items()})
        watermark = max(int(t.max()), watermark or 0)
        if held is not None and watermark >= held + unit_seconds + lateness:
            close(held, i)
            held = None
    for unit in (held, open_unit):
        if unit is not None:
            close(unit, len(batches))
    units_out = {}
    for unit, parts in admitted.items():
        cols = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
        units_out[unit] = exact_groupby(
            FlowBatch(cols), list(key_cols), list(value_cols),
            timeslot=False)
    return {"units": units_out, "dropped": dropped, "folded": folded,
            "closed_at": closed_at, "order": order}
