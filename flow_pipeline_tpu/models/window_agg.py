"""Exact windowed aggregation model — BASELINE config #1.

Device side: per-batch exact partial aggregates via ``ops.sort_groupby``
keyed on (timeslot, *key columns). Host side: a window store merges partials
into one WindowStore a timeslot (sorted key rows beside a uint64 sums array)
and flushes closed windows.

Semantics match the reference's flows_5m materialized view exactly
(5-minute tumbling windows over TimeReceived, keys (SrcAS, DstAS, EType),
sums of Bytes/Packets plus count — ref: compose/clickhouse/create.sh:92-110),
with a watermark: a window flushes once the stream has advanced
``allowed_lateness`` seconds past its end (the reference's analogue is
SummingMergeTree merge-time finalization, which is also not instantaneous —
ref: README.md:164-183 OPTIMIZE TABLE).

Late-data semantics: rows arriving for an already-flushed window reopen it,
and the next flush emits the late contribution as additional PARTIAL rows
for the same (timeslot, key). Sinks must therefore merge by key — summing
partials exactly like the reference's SummingMergeTree does at merge time
(ref: compose/clickhouse/create.sh:70-90). Sinks that cannot merge should
set ``allowed_lateness`` high enough to make reopening impossible.
"""

from __future__ import annotations

# flowlint: uint64-exact
# (flows_5m promises BIT-exact uint64 sums vs the reference rollup; see
# docs/STATIC_ANALYSIS.md for what the marker enforces)

import functools
from dataclasses import dataclass, field
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import TRACER
from ..ops.hostgroup import _lex_regroup, _run_starts
from ..ops.segment import hash_groupby, sort_groupby
from ..utils.shards import local_device_blocks
from ..schema.batch import FlowBatch, lane_width
from .oracle import SECONDS_PER_SLOT


@dataclass(frozen=True)
class WindowAggConfig:
    key_cols: tuple[str, ...] = ("src_as", "dst_as", "etype")
    value_cols: tuple[str, ...] = ("bytes", "packets")
    window_seconds: int = SECONDS_PER_SLOT
    allowed_lateness: int = 0
    batch_size: int = 8192  # static shape; shorter batches are padded
    # Sampling-rate-correct serving: the reference's bps panels multiply
    # by the exporter sampling rate at query time over raw rows
    # (ref: compose/grafana/dashboards/viz.json:62 sum(bytes*sampling_
    # rate*8), viz-ch.json sum(Bytes*SamplingRate)); a pre-aggregated
    # serving table must bake that in or the information is gone. The
    # rate rides as ONE extra grouping lane (cardinality = #distinct
    # exporter rates, i.e. tiny) and flush() emits exact uint64
    # ``<value>_scaled`` columns next to the raw sums — raw flows_5m
    # parity is untouched. None disables (pre-r4 behavior). A rate of 0
    # ("unknown/unsampled", what GoFlow emits without an options
    # template) scales by 1, not 0: dropping all traffic from a panel
    # because an exporter didn't announce its rate helps nobody.
    scale_col: Optional[str] = "sampling_rate"


def group_cols(config: WindowAggConfig) -> tuple[str, ...]:
    """Grouping lanes for the device/host step: key columns plus the
    sampling-rate lane when scaled serving is on."""
    if config.scale_col:
        return (*config.key_cols, config.scale_col)
    return config.key_cols


def _build_update(config: WindowAggConfig):
    """One jitted device step: columns -> (keys, sums, counts, n_groups[,
    collided]). Cached on exactly the fields the program depends on —
    batch_size only shapes the inputs (jit re-specializes per shape
    anyway) and allowed_lateness is host-side, so neither may fragment
    the cache."""
    return _cached_update(config.window_seconds, group_cols(config),
                          config.value_cols)


def _window_keys_values(window, key_cols, value_cols, cols):
    """(timeslot, *keys) lanes + 16-bit value planes for one chunk.
    (Invalid-row masking happens downstream in hash_groupby/sort_groupby.)

    Exactness: each uint32 value column rides as two 16-bit planes so
    per-batch int32 segment sums cannot overflow (batch_size <= 32768
    guarantees plane sums < 2^31); the host recombines lo + (hi << 16)
    in uint64."""
    ts = cols["time_received"].astype(jnp.uint32)
    timeslot = ts - ts % window
    lanes = [timeslot]
    for name in key_cols:
        arr = cols[name].astype(jnp.uint32)
        if arr.ndim == 1:
            lanes.append(arr)
        else:
            lanes.extend(arr[:, i] for i in range(arr.shape[1]))
    keys = jnp.stack(lanes, axis=1)
    planes = []
    for name in value_cols:
        v = cols[name].astype(jnp.uint32)
        # flowlint: disable=uint64-discipline -- 16-bit planes: batch_size <= 32768 keeps int32 plane sums < 2^31 (exact)
        planes.append((v & jnp.uint32(0xFFFF)).astype(jnp.int32))
        # flowlint: disable=uint64-discipline -- 16-bit planes: batch_size <= 32768 keeps int32 plane sums < 2^31 (exact)
        planes.append((v >> jnp.uint32(16)).astype(jnp.int32))
    values = jnp.stack(planes, axis=1)
    return keys, values


@functools.lru_cache(maxsize=None)
def _cached_update(window_seconds: int, key_cols: tuple, value_cols: tuple):
    """Hash-grouped fast path: (keys, sums, counts, n_groups, collided).

    The collided flag is a device scalar; callers keep it lazy until
    drain time and re-run the chunk through _cached_update_exact when it
    fires (~n^2/2^65 per chunk — never observed in practice, but the
    flows_5m contract is BIT-exactness vs the reference rollup, so the
    fallback keeps the guarantee unconditional)."""
    window = jnp.uint32(window_seconds)

    @jax.jit
    def update(cols: dict, valid):
        keys, values = _window_keys_values(window, key_cols, value_cols, cols)
        return hash_groupby(keys, values, valid)

    return update


@functools.lru_cache(maxsize=None)
def _cached_update_exact(window_seconds: int, key_cols: tuple,
                         value_cols: tuple):
    """Lexicographic path: the collision fallback (and the shard-mapped
    variant's building block — parallel.sharded)."""
    window = jnp.uint32(window_seconds)

    @jax.jit
    def update(cols: dict, valid):
        keys, values = _window_keys_values(window, key_cols, value_cols, cols)
        return sort_groupby(keys, values, valid)

    return update


def _to_host(arr, stacked: bool) -> np.ndarray:
    # stacked (sharded) partials may live on non-addressable devices
    # under multi-host — read only the local shards
    return local_device_blocks(arr) if stacked else np.asarray(arr)


def _first_read(partial) -> np.ndarray:
    """The first host read a drain makes of a device partial: its
    collision flag where it has one, else its group count."""
    return _to_host(partial[4] if len(partial) == 5 else partial[3],
                    partial[0].ndim == 3)


def _distinct_rates(parts: list) -> int:
    """How many sampling rates the rows of one drain came under: the
    store is keyed by (key lanes, rate), the rate last. One compare where
    every row has the first row's rate (an unsampled stream), a sort
    only where they differ."""
    lanes = [p[:, -1] for p in parts if len(p)]
    if not lanes:
        return 0
    first = lanes[0][0]
    if all((lane == first).all() for lane in lanes):
        return 1
    return len(np.unique(np.concatenate(lanes)))


def _packed(keys: np.ndarray) -> np.ndarray:
    """Each key row as one big-endian byte string: numpy orders and
    searches those bytewise, which is the rows' lexicographic order."""
    return np.ascontiguousarray(keys, ">u4").view(
        np.dtype((np.void, 4 * keys.shape[1]))).ravel()


def _insert_rows(arr: np.ndarray, at: np.ndarray,
                 rows: np.ndarray) -> np.ndarray:
    """``np.insert(arr, at, rows, axis=0)`` moving each row as one
    item, not lane by lane."""
    row = np.dtype((np.void, arr.itemsize * arr.shape[1]))
    return np.insert(arr.view(row).ravel(), at,
                     np.ascontiguousarray(rows).view(row).ravel()
                     ).view(arr.dtype).reshape(-1, arr.shape[1])


def _sorted_run(keys, sums) -> tuple[np.ndarray, np.ndarray]:
    """Rows in any order, a key perhaps more than once, as unique rows
    in lexicographic order with the sums of equal keys added: one sort."""
    keys = np.ascontiguousarray(keys, np.uint32)
    sums = np.asarray(sums, np.uint64)
    if not len(keys):
        return keys, sums
    order, starts = _lex_regroup(keys)
    return keys[order[starts]], np.add.reduceat(sums[order], starts, axis=0)


class WindowStore:
    """One window's groups as two arrays: ``key_rows`` [G, lanes] uint32,
    rows unique and in lexicographic order, and ``sums`` [G, nvals + 1]
    uint64 (values, then count) row for row beside them. The form the
    checkpoint writes, the mesh ships and a close turns into rows.

    ``key_rows`` is never written in place (an insert replaces it);
    ``sums`` is, by every fold. Read like a mapping of key tuples to sums
    rows: ``len``, ``[key]``, iteration over the keys, ``items()``."""

    def __init__(self, key_rows: np.ndarray, sums: np.ndarray):
        """Adopts ``key_rows`` (C-contiguous, sorted, unique) and
        ``sums``."""
        self.key_rows = key_rows
        self.sums = sums
        self._search = None  # _packed(key_rows), once a fold needs it

    @classmethod
    def from_rows(cls, keys, sums) -> "WindowStore":
        """A store of rows in any order (_sorted_run)."""
        return cls(*_sorted_run(keys, sums))

    def _find(self, keys: np.ndarray, packed: np.ndarray):
        """Where each of ``keys`` stands or would stand, and whether it
        is there: a binary search on the packed rows, then the keys
        themselves compared."""
        if self._search is None:
            self._search = _packed(self.key_rows)
        pos = np.searchsorted(self._search, packed)
        # a key past the last row compares with the last, and differs
        at = np.minimum(pos, len(self.key_rows) - 1)
        return pos, (self.key_rows[at] == keys).all(axis=1)

    def merge(self, keys: np.ndarray, sums: np.ndarray) -> int:
        """Add a sorted run of unique rows: one indexed add into the
        rows that are there (unique keys, so no index repeats) and one
        insert of those that are not. Returns how many were inserted."""
        packed = _packed(keys)
        if not len(self):
            self.key_rows, self.sums, self._search = keys, sums, packed
            return len(keys)
        pos, hit = self._find(keys, packed)
        self.sums[pos[hit]] += sums[hit]
        if hit.all():
            return 0
        new = ~hit
        at = pos[new]
        self.key_rows = _insert_rows(self.key_rows, at, keys[new])
        self.sums = _insert_rows(self.sums, at, sums[new])
        self._search = np.insert(self._search, at, packed[new])
        return len(at)

    def snapshot(self) -> tuple[np.ndarray, np.ndarray]:
        """(key rows, sums) that no later fold writes into."""
        return self.key_rows, self.sums.copy()

    def __len__(self) -> int:
        return len(self.key_rows)

    def __iter__(self) -> Iterator[tuple]:
        return map(tuple, self.key_rows.tolist())

    def items(self) -> Iterator[tuple[tuple, np.ndarray]]:
        return zip(self, self.sums)

    def __getitem__(self, key: tuple) -> np.ndarray:
        """The sums row of one key tuple (a view: the fold's own row)."""
        row = np.array([key], np.uint32)
        if len(self) and row.shape[1] == self.key_rows.shape[1]:
            pos, hit = self._find(row, _packed(row))
            if hit[0]:
                return self.sums[pos[0]]
        raise KeyError(key)


# Device partials queued before add_partial forces a host fold: the bound
# for callers that never probe (a flush-free update() loop, the sharded
# paths). It caps what the queue pins: each pending partial
# holds ~batch_size padded rows of keys+sums+counts on the device (~10
# int32 lanes: 1.4 MB at 32768 rows, per chip) and one collision-fallback
# closure (the single-chip paths stash HOST numpy columns, no HBM; the
# sharded paths retain their global device column refs, about another 1x
# the partial's footprint per chip). The worker's per-batch probe
# (pop_closed) keeps the queue far below it: a partial queued with a
# host-known slot bound is folded one step behind (two pending at most),
# one queued without is folded by the probe that follows it.
DRAIN_PENDING_MAX = 32


class WindowAggregator:
    """Streaming exact aggregator: update(batch) per batch, flush() yields
    finalized window rows."""

    def __init__(self, config: WindowAggConfig = WindowAggConfig()):
        if config.batch_size > 32768:
            raise ValueError(
                "batch_size must be <= 32768 (int32 exactness of the 16-bit "
                "value planes)"
            )
        self.config = config
        self._update = _build_update(config)
        # open windows: timeslot -> its groups
        self.windows: dict[int, WindowStore] = {}
        self.watermark = 0  # max time_received seen
        # device partials not yet folded into `windows`, oldest first:
        # (partial, fallback, min_slot). jax dispatch is async, so a
        # partial stays a device array until a reader needs it; min_slot
        # is the host-known lower bound on its timeslots (None: unknown)
        self._pending_partials: list = []
        # host-grouped rows not yet folded (engine.hostfused's path),
        # with the min timeslot seen so the per-batch flush probe can
        # prove "nothing closable" without forcing a fold
        self._pending_host: list = []
        self._min_pending_slot: Optional[int] = None
        # flowmesh capture seam (mesh/member.py): when set, pop_closed
        # hands the popped (slot, store) pairs to the hook and reports
        # nothing closable locally — per-shard partial stores merge
        # network-wide at the coordinator. None keeps single-worker
        # behavior byte-identical.
        self.capture = None

    @property
    def store_key_lanes(self) -> int:
        """Width of the window stores' key rows (excludes the timeslot,
        which ``windows`` is keyed by) — restore uses this to reject checkpoints
        written under a different grouping layout (e.g. pre-sampling
        builds without the rate lane)."""
        return sum(lane_width(n) for n in self.config.key_cols) + (
            1 if self.config.scale_col else 0)

    def update(self, batch: FlowBatch) -> None:
        if len(batch) == 0:
            return
        bs = self.config.batch_size
        behind = len(batch) >= bs
        for start in range(0, len(batch), bs):  # chunk arbitrary batch sizes
            self._update_chunk(batch.slice(start, start + bs), behind)
        wm = int(batch.columns["time_received"].max())
        if wm > self.watermark:
            self.watermark = wm

    def _update_chunk(self, batch: FlowBatch, behind: bool) -> None:
        padded, mask = batch.pad_to(self.config.batch_size)
        host_cols = padded.device_columns(
            ["time_received", *group_cols(self.config),
             *self.config.value_cols]
        )
        cols = {name: jnp.asarray(arr) for name, arr in host_cols.items()}
        valid = jnp.asarray(mask)
        self.add_partial(self._update(cols, valid),
                         fallback=self._exact_fallback(host_cols, mask),
                         min_slot=self._min_slot(host_cols, mask, behind))

    def _min_slot(self, host_cols: dict, mask,
                  behind: bool) -> Optional[int]:
        """The slot bound to queue one chunk's partial with (add_partial).

        ``behind``: the polled batch filled a whole device step, so the
        source held at least that much and the step bounds the loop. The
        bound is then the minimum over the chunk's valid rows (late rows
        included) of the same uint32 words the device floors to this
        model's window, and the drain lags. A part-full batch means the
        source ran dry: the loop keeps up, and polling again before its
        step has finished would only cut the traffic into more, emptier
        padded steps (and checkpoints, which count batches). It gets no
        bound, so the probe waits for its step as it always did."""
        if not behind:
            return None
        lo = int(host_cols["time_received"][mask].astype(np.uint32).min())
        return lo - lo % self.config.window_seconds

    def _exact_fallback(self, host_cols: dict, mask):
        """Deferred exact recompute for one chunk. Closes over the HOST
        numpy columns (not the device arrays) so pending fallbacks cost
        host memory, not HBM — the device budget DRAIN_PENDING_MAX is
        sized for counts only the small partials."""
        exact = _cached_update_exact(self.config.window_seconds,
                                     group_cols(self.config),
                                     self.config.value_cols)

        def run():
            cols = {k: jnp.asarray(v) for k, v in host_cols.items()}
            return exact(cols, jnp.asarray(mask))

        return run

    def add_partial(self, partial, fallback=None,
                    min_slot: Optional[int] = None) -> None:
        """Queue one device partial — (keys, sums, counts, n) exact, or
        (keys, sums, counts, n, collided) hash-grouped — for the next
        drain. ``fallback`` is a zero-arg callable producing the EXACT
        partial for the same chunk; it runs at drain time iff the
        chunk's (lazy, device-resident) collision flag fires, keeping
        flows_5m bit-exact without syncing per chunk. ``min_slot`` is a
        lower bound on the partial's timeslots that the caller knows on
        the host (_min_slot; None: unknown, or a loop that is keeping
        up); with it the per-batch flush probe proves
        "nothing closable" without reading the device and folds this
        partial one step late, without it the probe drains everything.
        Single entry point for both the per-model path and the fused
        pipeline, so the deferral bound lives in one place: a flush-free
        caller (huge update() loops) must not pin unbounded padded
        buffers on device."""
        self._pending_partials.append((partial, fallback, min_slot))
        if len(self._pending_partials) >= DRAIN_PENDING_MAX:
            self._drain()

    def _drain(self) -> None:
        """Fold everything pending into ``windows``. Every reader of the
        store (a close, a forced flush, the checkpoint, the query and
        mesh surfaces) calls this first."""
        if self._pending_host:
            pending_h, self._pending_host = self._pending_host, []
            self._min_pending_slot = None
            self._fold_rows(
                np.concatenate([k for k, _ in pending_h]),
                np.concatenate([v for _, v in pending_h]))
        pending, self._pending_partials = self._pending_partials, []
        self._fold_partials(pending)

    def _drain_lagged(self) -> None:
        """The probe's drain when nothing is closable: fold every device
        partial but the newest. The host read then blocks only until
        the step BEFORE the one just dispatched has finished, so the
        copy, the fold and the next batch's preparation run under that
        step; only rows of an open window wait, for one batch."""
        pending = self._pending_partials[:-1]
        del self._pending_partials[:-1]
        self._fold_partials(pending)

    def _fold_partials(self, pending: list) -> None:
        """Fold partials already taken off the queue, oldest first."""
        if not pending:
            return
        with TRACER.span("wagg_wait", folded=len(pending),
                         left=len(self._pending_partials)):
            # the first host read of the oldest partial: blocks until the
            # device step that produced it has finished
            head = _first_read(pending[0][0])
        all_keys, all_sums, all_counts = [], [], []
        with TRACER.span("wagg_d2h") as span:
            nbytes = 0
            for i, (partial, fallback, _) in enumerate(pending):
                if i:
                    head = _first_read(partial)
                nbytes += head.nbytes
                keys, sums, counts, n = partial[:4]
                if len(partial) == 5:
                    if bool(np.any(head)):
                        # a 64-bit grouping-hash collision (~2^-64/chunk):
                        # recompute this chunk lexicographically
                        if fallback is None:
                            raise RuntimeError(
                                "hash-grouped partial collided and no "
                                "exact fallback was provided")
                        keys, sums, counts, n = fallback()[:4]
                    ns = _to_host(n, keys.ndim == 3)
                    nbytes += ns.nbytes
                else:
                    ns = head
                # stacked per-chip partials (sharded variant), multi-host:
                # each process can only read ITS devices' shards, and only
                # needs to — the per-chip partials are independent, and
                # each host folds its own share into its window store
                # (partial rows merge downstream by key, the
                # consumer-group contract; see parallel.multihost).
                # Slices happen on the HOST, after the transfer: keys[:g]
                # on the device is a fresh XLA program for every distinct
                # g, so compilations would grow with the chunk count
                stacked = keys.ndim == 3
                keys_np = _to_host(keys, stacked)
                sums_np = _to_host(sums, stacked)
                counts_np = _to_host(counts, stacked)
                nbytes += keys_np.nbytes + sums_np.nbytes + counts_np.nbytes
                if not stacked:
                    ns, keys_np, sums_np, counts_np = (
                        ns[None], keys_np[None], sums_np[None],
                        counts_np[None])
                for d in range(keys_np.shape[0]):
                    g = int(ns[d])
                    all_keys.append(keys_np[d, :g])
                    all_sums.append(sums_np[d, :g])
                    all_counts.append(counts_np[d, :g])
            span["bytes"] = nbytes
        # counted outside the span that times the fold, and only for a
        # recorder that keeps it
        rates = (_distinct_rates(all_keys)
                 if self.config.scale_col is not None and TRACER.recording
                 else 0)
        with TRACER.span("wagg_fold") as span:
            keys = np.concatenate(all_keys)
            span["groups"] = len(keys)
            if rates:
                span["rates"] = rates
            span["inserted"] = self._merge_partials(
                keys, np.concatenate(all_sums), np.concatenate(all_counts))
            span["store_groups"] = sum(map(len, self.windows.values()))

    def _merge_partials(self, keys, plane_sums, counts) -> int:
        """Fold device partial aggregates (keys + 16-bit value planes +
        counts) into the per-window stores; returns _fold_rows' count."""
        n = keys.shape[0]
        if n == 0:
            return 0
        keys = keys.astype(np.uint32)
        plane_sums = plane_sums.astype(np.uint64)
        counts = counts.astype(np.uint64)
        # recombine the (lo, hi) 16-bit planes of each value column
        nvals = len(self.config.value_cols)
        vals = np.empty((n, nvals + 1), dtype=np.uint64)
        for j in range(nvals):
            vals[:, j] = plane_sums[:, 2 * j] + (
                plane_sums[:, 2 * j + 1] << np.uint64(16))
        vals[:, nvals] = counts
        return self._fold_rows(keys, vals)

    def add_host_rows(self, keys, sums, counts) -> None:
        """Queue host-grouped EXACT rows for the window store.

        The CPU-backend pipeline (ops.hostgroup / engine.hostfused) groups
        batches on the host in full uint64 — no 16-bit planes, no device
        partial queue, no collision fallback — so its rows skip
        add_partial entirely. ``keys`` [R, 1 + key lanes] uint32 with the
        timeslot lane FIRST (same layout the device partials use),
        ``sums`` [R, nvals] uint64, ``counts`` [R] integer.

        Rows are buffered and folded at the next drain (flush, snapshot,
        or every DRAIN_PENDING_MAX chunks): one lexsort over the whole
        backlog beats per-chunk merges the same way the device
        partial queue does, at a few MB of host memory."""
        expect = 1 + self.store_key_lanes
        if keys.ndim != 2 or keys.shape[1] != expect:
            raise ValueError(
                f"add_host_rows keys must be [R, {expect}] "
                f"([timeslot, *key lanes"
                f"{', rate' if self.config.scale_col else ''}]) for this "
                f"config; got {keys.shape}")
        vals = np.concatenate(
            [sums.astype(np.uint64),
             counts.astype(np.uint64)[:, None]], axis=1)
        self._pending_host.append((keys.astype(np.uint32), vals))
        if len(keys):
            lo = int(keys[:, 0].min())
            if self._min_pending_slot is None or lo < self._min_pending_slot:
                self._min_pending_slot = lo
        if len(self._pending_host) >= DRAIN_PENDING_MAX:
            self._drain()

    def _fold_rows(self, keys, vals) -> int:
        """Merge (slot, key) rows + uint64 value/count columns into the
        windows' stores; returns how many of the rows were new to theirs.

        The drain's rows (several slots, a key once a folded partial)
        become one sorted run of unique rows with ONE lexsort + boundary
        reduceat; each slot's stretch of it (one or two a drain) then
        merges into that slot's store with array operations alone
        (WindowStore.merge)."""
        keys, sums = _sorted_run(keys, vals)
        # in (slot, key) order: where each slot's rows start
        slots, first = np.unique(keys[:, 0], return_index=True)
        ends = [*first[1:].tolist(), len(keys)]
        inserted = 0
        for slot, a, b in zip(slots.tolist(), first.tolist(), ends):
            rows = np.ascontiguousarray(keys[a:b, 1:])
            store = self.windows.get(slot)
            if store is None:
                self.windows[slot] = WindowStore(rows, sums[a:b])
                inserted += b - a
            else:
                inserted += store.merge(rows, sums[a:b])
        return inserted

    def closed_slots(self) -> list[int]:
        self._drain()
        limit = self.watermark - self.config.allowed_lateness
        return sorted(
            s for s in self.windows if s + self.config.window_seconds <= limit
        )

    def _nothing_closable(self) -> bool:
        """Cheap proof that flush(force=False) would emit nothing, WITHOUT
        reading the device or folding the pending queues. flush() runs
        after every batch but windows close hundreds of batches apart,
        and a read of the newest partial waits for the step just
        dispatched. Host-grouped rows carry their min slot, and so does
        a device partial queued with one; a device partial without is
        opaque until synced, so it means "maybe closable"."""
        bounds = [lo for _, _, lo in self._pending_partials]
        if None in bounds:
            return False
        if self._min_pending_slot is not None:
            bounds.append(self._min_pending_slot)
        if self.windows:
            bounds.append(min(self.windows))
        if not bounds:
            return True
        limit = self.watermark - self.config.allowed_lateness
        return min(bounds) + self.config.window_seconds > limit

    def pop_closed(self, force: bool = False
                   ) -> list[tuple[int, WindowStore]]:
        """Detach finalized windows (all, if force) as (slot, store)
        pairs. The popped stores are exclusively the caller's — late rows
        for them REOPEN fresh stores, emitted as additional partials —
        so row building (rows_from_stores) can run on another thread
        (ingest.flush) while updates continue."""
        if not force and self._nothing_closable():
            self._drain_lagged()
            return []
        self._drain()
        slots = sorted(self.windows) if force else self.closed_slots()
        popped = [(slot, self.windows.pop(slot)) for slot in slots]
        if self.capture is not None:
            self.capture(popped)  # mesh member: stores merge upstream
            return []
        return popped

    def flush(self, force: bool = False) -> dict[str, np.ndarray]:
        """Pop finalized windows (all, if force) as columnar rows.

        With ``scale_col`` set the window store is keyed by
        (*key lanes, sampling_rate); flush folds the per-rate subgroups
        back to the reference key shape and emits exact uint64
        ``<value>_scaled`` columns (sum over rates of sum(value) * rate,
        rate 0 treated as 1) alongside the raw sums — the serving-side
        equivalent of the reference's query-time
        ``sum(Bytes*SamplingRate)``. With ``scale_col=None`` the
        ``*_scaled`` columns are STILL emitted, equal to the raw sums —
        the sink schema (sink/ddl.py flows_5m) is fixed, and a deployment
        that disables scaling must not silently write NULLs into the
        scaled columns its dashboards sum over (ADVICE r4)."""
        return rows_from_stores(self.config, self.pop_closed(force))


def wagg_rows(store: WindowStore, config: WindowAggConfig, k: int,
              slot: int) -> dict[str, np.ndarray]:
    """Emitted rows for ONE merged window store — the wagg family's
    rows hook (families/registry.py), signature-compatible with the
    ranked families' ``*_top_rows`` so the coordinator's merge loop is
    kind-agnostic. ``k`` is unused: wagg emits every exact group."""
    return rows_from_stores(config, [(slot, store)])


def rows_from_stores(config: WindowAggConfig,
                     stores: list[tuple[int, WindowStore]]
                     ) -> dict[str, np.ndarray]:
    """Columnar flush rows from popped (slot, store) pairs — the second
    half of flush(), a pure function so the ingest flusher can run it off
    the worker thread. A call that has stores to turn into rows (a
    close, not the per-batch probe that popped nothing) is one
    ``wagg_rows`` span."""
    if not stores:
        return _rows_from_stores(config, stores)
    with TRACER.span("wagg_rows") as span:
        rows = _rows_from_stores(config, stores)
        span["rows"] = len(rows["timeslot"])
    return rows


def _rows_from_stores(config: WindowAggConfig,
                      stores: list[tuple[int, WindowStore]]
                      ) -> dict[str, np.ndarray]:
    """From each store's arrays as they are: its rows stand in key
    order already, so the rows of one reference key (the rate lane is
    the last) are neighbours and fold with one reduceat, no sort."""
    scaled = config.scale_col is not None
    nvals = len(config.value_cols)
    ts_parts, key_parts, val_parts, scaled_parts = [], [], [], []
    for slot, store in stores:
        if not len(store):
            continue
        keys, vals = store.key_rows.astype(np.uint64), store.sums
        if scaled:
            base, rate = keys[:, :-1], np.maximum(keys[:, -1], 1)
            svals = vals[:, :nvals] * rate[:, None]
            # fold per-rate subgroups back to the reference key shape
            starts = _run_starts(base)
            key_arr = base[starts]
            val_arr = np.add.reduceat(vals, starts, axis=0)
            scaled_arr = np.add.reduceat(svals, starts, axis=0)
        else:
            # unscaled: scaled sums == raw sums (rate treated as 1)
            key_arr = keys
            val_arr = vals
            scaled_arr = val_arr[:, :nvals].copy()
        ts_parts.append(np.full(len(key_arr), slot, np.uint64))
        key_parts.append(key_arr)
        val_parts.append(val_arr)
        scaled_parts.append(scaled_arr)
    if not ts_parts:
        empty = {"timeslot": np.zeros(0, np.uint64)}
        for name in config.value_cols + ("count",):
            empty[name] = np.zeros(0, np.uint64)
        for name in config.key_cols:
            empty[name] = np.zeros(0, np.uint64)
        for name in config.value_cols:
            empty[f"{name}_scaled"] = np.zeros(0, np.uint64)
        return empty
    key_arr = np.concatenate(key_parts)
    val_arr = np.concatenate(val_parts)
    scaled_arr = np.concatenate(scaled_parts)
    out = {"timeslot": np.concatenate(ts_parts)}
    col = 0
    for name in config.key_cols:
        width = lane_width(name)
        if width == 1:
            out[name] = key_arr[:, col]
        else:
            out[name] = key_arr[:, col : col + 4]
        col += width
    for j, name in enumerate(config.value_cols):
        out[name] = val_arr[:, j]
    out["count"] = val_arr[:, nvals]
    for j, name in enumerate(config.value_cols):
        out[f"{name}_scaled"] = scaled_arr[:, j]
    return out
