"""Count-min sketch ops.

The CMS is the framework's replacement for ClickHouse's SummingMergeTree
when key cardinality is too high for exact aggregation (the 38-byte 5-tuple
space; ref north star: BASELINE.json). Layout is TPU-first:

- counts: [planes, depth, width] float32. ``planes`` are the metrics
  (bytes, packets, count). float32 keeps scatter-adds on native lanes;
  integer sums stay exact below 2^24 per cell per batch and the parity gate
  is 1%, far above float32's relative error. ``width`` should be a multiple
  of 128 (lane tiling).
- Updates are pre-aggregated: callers first collapse the batch to unique
  keys (ops.segment.sort_groupby), so each key touches each depth row once
  per batch. This slashes scatter conflicts and makes conservative update
  meaningful within a batch.
- Merge across chips is element-wise sum (count-min is a commutative
  monoid), i.e. a plain ``psum`` over the mesh — the ICI replacement for
  ClickHouse's merge-time partial-sum combine.

Bucket choice per depth uses the murmur3 word-lane hash (schema.keys) with
a distinct seed per row.

The live bound. A device group-by hands over N group SLOTS of which only
some are real (ops.segment: a batch of N rows yields N slots; ~30 % of
them hold a group on the benchmark's stream), and on a TPU a gather or a
scatter costs by the index (a gather 13 ns each on a v5e, a scatter-max
into a row of 2^16 cells ~50 ns for a real slot), real or not. Callers
that know where their real rows end (models.heavy_hitter._apply_grouped:
1 + the index of the last valid row) pass that as ``n_live``, and the
bound serves both halves of the conservative update: its pre-update
estimate gathers only the chunks of rows below it, and its scatters drop
every slot that holds no group: those at or beyond the bound and the
holes below it, which is what ``valid`` False says. Skipping a padding
slot changes no bit of the state, because such a slot is a no-op either
way: its addends are 0 (``valid`` is False), so its ceiling is its own
estimate, which is the min of the cells it would raise; and with the
estimate left at 0 the ceiling is 0, which raises no cell either, since
cells are sums of non-negative addends and never below 0.
"""

from __future__ import annotations

# flowlint: uint64-exact
# (bucket hashing must stay exact unsigned arithmetic — a signed cast
# here skews every estimate; see docs/STATIC_ANALYSIS.md)

import jax.numpy as jnp
from jax import lax

from ..schema.keys import hash_words


def cms_init(planes: int, depth: int, width: int) -> jnp.ndarray:
    """Fresh sketch. width should be a multiple of 128."""
    return jnp.zeros((planes, depth, width), dtype=jnp.float32)


def cms_buckets(keys, depth: int, width: int):
    """Per-depth bucket indices for key word-lanes.

    keys: [N, W] uint32 lanes. Returns [depth, N] int32 in [0, width).
    Seeds 0..depth-1 give independent rows."""
    cols = []
    for d in range(depth):  # depth is small + static: unrolled
        h = hash_words(keys, seed=d)
        # flowlint: disable=uint64-discipline -- bucket INDICES in [0, width < 2^31), not counters; scatter wants int32
        cols.append((h % jnp.uint32(width)).astype(jnp.int32))
    return jnp.stack(cols, axis=0)


def cms_add(counts, keys, values, valid=None):
    """Linear (mergeable) update with pre-aggregated per-key values.

    counts: [P, D, W] float32 sketch.
    keys:   [N, W_k] uint32 unique key lanes.
    values: [N, P] per-key addends (cast to float32).
    valid:  [N] bool mask (e.g. rows < n_groups from sort_groupby).
    """
    p, d, w = counts.shape
    buckets = cms_buckets(keys, d, w)  # [D, N]
    vals = values.astype(jnp.float32)
    if valid is not None:
        vals = jnp.where(valid[:, None], vals, 0.0)
    for di in range(d):
        # [P, N] scatter-add into row di
        counts = counts.at[:, di, buckets[di]].add(vals.T)
    return counts


# Rows a trip of cms_query's bounded form gathers. From a sweep on a v5e
# at N = 32,768 (PERF.md §6, PR 37): a trip costs ~1.4 us beside its
# gathers, so what a chunk costs is the padding it gathers past the bound
# (half a chunk a family on average): 512 to 8,192 were read, and a batch
# whose slots are all real pays 0.5 % of the step for its 32 trips.
LIVE_CHUNK = 1024


def _depth_min(counts, buckets):
    """[P, n] min over depth rows of the cells at ``buckets`` [D, n]."""
    ests = [counts[:, di, buckets[di]] for di in range(counts.shape[1])]
    return jnp.min(jnp.stack(ests, axis=0), axis=0)


def cms_query(counts, keys, n_live=None):
    """Point estimate: min over depth rows. Returns [N, P] float32 (upper
    bound of the true sums for linear updates).

    ``n_live`` (traced int32 scalar, optional): every row a caller reads
    lies below it. Only the LIVE_CHUNK-row chunks that hold such rows are
    gathered, in a loop whose trip count is data, and rows at or beyond
    the bound come back as 0. Where N <= LIVE_CHUNK the plain gather
    stays, which reads every row."""
    p, d, w = counts.shape
    n = keys.shape[0]
    buckets = cms_buckets(keys, d, w)  # [D, N]
    if n_live is None or n <= LIVE_CHUNK:
        return _depth_min(counts, buckets).T  # [N, P]

    def chunk(i, est):
        # the last chunk of an N that LIVE_CHUNK does not divide is
        # clamped to end at N, by the slice and the update alike
        start = i * LIVE_CHUNK
        b = lax.dynamic_slice(buckets, (0, start), (d, LIVE_CHUNK))
        return lax.dynamic_update_slice(est, _depth_min(counts, b),
                                        (0, start))

    trips = (n_live + (LIVE_CHUNK - 1)) // LIVE_CHUNK
    est = lax.fori_loop(0, trips, chunk, jnp.zeros((p, n), counts.dtype))
    return jnp.where(lax.iota(jnp.int32, n) < n_live, est, 0.0).T


def cms_add_conservative(counts, keys, values, valid=None, n_live=None):
    """Conservative update: raise each cell only to (current min estimate +
    addend). Tighter estimates than linear add; still an upper bound. Merge
    by + remains a valid upper bound but loses the CU tightness.

    Same shapes as cms_add. Keys must be unique within the call (use
    sort_groupby first) — duplicate keys would under-count. ``n_live``:
    every ``valid`` row lies below it (the module docstring's live
    bound): the estimate is gathered below it alone. A row whose
    ``valid`` is False is in no row's scatter-max; without ``valid``
    every row is. Either way the state that comes back is the same bit
    for bit as from the plain update over every row.
    """
    p, d, w = counts.shape
    buckets = cms_buckets(keys, d, w)  # [D, N]
    vals = values.astype(jnp.float32)
    if valid is not None:
        vals = jnp.where(valid[:, None], vals, 0.0)
    # current estimate before update
    est = cms_query(counts, keys, n_live)  # [N, P]
    target = est + vals  # [N, P] the CU ceiling for this key
    if valid is not None:
        # A slot that holds no group leaves the scatter: its index goes to
        # `w`, out of range HIGH, which mode="drop" discards (a negative
        # index would wrap before the check).
        buckets = jnp.where(valid[None, :], buckets, w)
    for di in range(d):
        # cell must become at least `target`, but never decrease.
        counts = counts.at[:, di, buckets[di]].max(target.T, mode="drop")
    return counts


def cms_merge(*sketches):
    """Combine per-shard sketches (element-wise sum)."""
    out = sketches[0]
    for s in sketches[1:]:
        out = out + s
    return out


def cms_relative_error(depth: int, width: int, total: float) -> float:
    """Standard CMS guarantee: err <= e/width * total with prob 1-e^-depth."""
    import math

    return math.e / width * total
