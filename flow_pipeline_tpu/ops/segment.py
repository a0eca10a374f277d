"""Sort-based exact groupby on device.

TPU has no efficient general scatter-with-conflicts; the idiomatic exact
grouping is: lexicographic multi-key sort (``lax.sort`` with num_keys=W,
O(n log^2 n) bitonic network, all MXU/VPU-friendly) -> boundary detection ->
segment reductions. Shapes are static: a batch of N rows yields N segment
slots with a scalar count of how many are real.

This one op gives the framework exact per-batch partial aggregates, which
the host (or a psum across chips) merges per window — the same
partial-merge trick ClickHouse's SummingMergeTree uses at merge time
(ref: compose/clickhouse/create.sh:70-90), but batched and on-device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def sort_groupby(keys, values, valid):
    """Exact groupby-sum of ``values`` by row-tuples of ``keys``.

    Args:
      keys:   [N, W] integer lanes (bit-cast to uint32), lexicographic key.
      values: [N, V] int32 per-row addends (e.g. bytes, packets).
      valid:  [N] bool; invalid rows contribute nothing.

    Returns:
      unique_keys: [N, W] uint32 — row i < n_groups holds the i-th group key.
      sums:        [N, V] int32 — per-group value sums.
      counts:      [N] int32 — per-group row counts.
      n_groups:    [] int32 — number of real groups; rows >= n_groups are
                   padding (keys all-1s, sums/counts zero).

    Caveat: invalid rows are sent to the all-0xFFFFFFFF key, so a *valid*
    row whose whole key tuple is all-1s (e.g. the ff..ff address in a raw
    address-keyed layout) lands in the same sorted segment as the padding
    rows. That is still correct: padding rows contribute 0 to sums/counts,
    so the group survives the ``counts > 0`` reality test with exact values
    and its reported key IS the all-1s tuple. The only residual ambiguity
    is that such a group is indistinguishable from padding by key alone —
    reality is judged by counts, never by key. (Consumers that DO use the
    sentinel key as an empty-slot marker — ops.topk — cannot represent it
    and drop it explicitly; see topk_merge.)
    """
    n, w = keys.shape
    ku = keys.astype(jnp.uint32)
    sentinel = jnp.uint32(0xFFFFFFFF)
    ku = jnp.where(valid[:, None], ku, sentinel)
    vals = jnp.where(valid[:, None], values.astype(jnp.int32), 0)
    cnt = valid.astype(jnp.int32)

    # Payload rides as ONE iota lane, then a post-sort gather: the sort
    # network's cost scales with operand count, while gathers are ~free
    # (measured 20.8ms -> 17.5ms for the 11-lane master sort at 16k rows).
    operands = [ku[:, i] for i in range(w)] + [lax.iota(jnp.int32, n)]
    sorted_ops = lax.sort(operands, num_keys=w)
    perm = sorted_ops[w]
    sk = jnp.stack(sorted_ops[:w], axis=1)  # [N, W] sorted keys
    sv = vals[perm]  # [N, V]
    sc = cnt[perm]  # [N]

    prev = jnp.concatenate([jnp.full((1, w), sentinel, jnp.uint32), sk[:-1]], axis=0)
    is_boundary = jnp.any(sk != prev, axis=1)
    is_boundary = is_boundary.at[0].set(True)
    seg_ids = jnp.cumsum(is_boundary.astype(jnp.int32)) - 1  # [N]

    sums = jax.ops.segment_sum(sv, seg_ids, num_segments=n)
    counts = jax.ops.segment_sum(sc, seg_ids, num_segments=n)
    # Keys are constant within a segment: max == the key.
    unique_keys = jax.ops.segment_max(sk, seg_ids, num_segments=n)

    # A group is real iff it holds at least one valid row. Judging by
    # counts (not by key != sentinel) keeps a valid all-1s key tuple
    # countable: its rows share a segment with padding, but padding adds 0
    # to counts/sums. All-padding groups have counts == 0 and sort last,
    # so real groups occupy a contiguous prefix and n_groups is exact.
    group_real = counts > 0
    n_groups = jnp.sum(group_real.astype(jnp.int32))
    sums = jnp.where(group_real[:, None], sums, 0)
    unique_keys = jnp.where(group_real[:, None], unique_keys, sentinel)
    return unique_keys, sums, counts, n_groups


def presorted_segments(sorted_keys):
    """Segment ids for rows ALREADY in lexicographic key order.

    The boundary-detect + prefix-sum half of sort_groupby, factored out so
    one multi-key sort can serve several groupbys: rows sorted by key
    lanes (k1..kn) are, by lexicographic order, also grouped by every
    PREFIX (k1..kj) — pass ``sorted_keys[:, :j]`` to group by the prefix
    without re-sorting (engine.fused shares one 11-lane sort between the
    5-tuple and src-address models this way).

    Args: sorted_keys [N, W] uint32. Returns seg_ids [N] int32.
    """
    n, w = sorted_keys.shape
    sentinel = jnp.uint32(0xFFFFFFFF)
    prev = jnp.concatenate(
        [jnp.full((1, w), sentinel, jnp.uint32), sorted_keys[:-1]], axis=0
    )
    is_boundary = jnp.any(sorted_keys != prev, axis=1)
    is_boundary = is_boundary.at[0].set(True)
    return jnp.cumsum(is_boundary.astype(jnp.int32)) - 1


def presorted_groupby_float(sorted_keys, sorted_vals, sorted_cnt, width=None):
    """Groupby of presorted float payload rows by the first ``width`` key
    lanes. Same return contract as sort_groupby_float: (uniq [N,width]
    uint32, sums [N,P] float32, counts [N] int32), reality judged by
    counts > 0 (see sort_groupby's sentinel caveat)."""
    n = sorted_keys.shape[0]
    sk = sorted_keys if width is None else sorted_keys[:, :width]
    seg_ids = presorted_segments(sk)
    sums = jax.ops.segment_sum(sorted_vals, seg_ids, num_segments=n)
    counts = jax.ops.segment_sum(sorted_cnt, seg_ids, num_segments=n)
    uniq = jax.ops.segment_max(sk, seg_ids, num_segments=n)
    real = counts > 0
    sums = jnp.where(real[:, None], sums, 0.0)
    uniq = jnp.where(real[:, None], uniq, jnp.uint32(0xFFFFFFFF))
    counts = jnp.where(real, counts, 0)
    return uniq, sums, counts


# numpy, NOT jnp: a module-level jnp constant would initialize the JAX
# backend at import time (breaking jax.distributed.initialize ordering
# in multi-host workers — engine modules import this one transitively)
_SENTINEL = np.uint32(0xFFFFFFFF)

# Two decorrelated odd multipliers (golden-ratio / murmur-style constants)
# for the paired 32-bit mixes that form the 64-bit grouping hash.
_HASH_MULT = (0x9E3779B1, 0x85EBCA77)
_HASH_SEED = (0x2545F491, 0x27220A95)


def _fmix32(h):
    """murmur3 finalizer: full-avalanche 32-bit mix."""
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> jnp.uint32(16))


def hash_lanes(keys):
    """Two independent 32-bit mixes of each [N, W] uint32 key row.

    Together they form a 64-bit grouping hash: the probability that two
    DISTINCT key tuples in one batch agree on both lanes is ~n^2/2^65
    (~1e-11 at n=32k). Lane-count independence is what makes hash-grouped
    sorts cheap: ``lax.sort`` cost scales with operand count, so sorting
    (h1, h2) beats sorting the raw 4-11 key lanes ~2-4x on both CPU and
    the TPU bitonic network.

    Returns (h1, h2), each [N] uint32.
    """
    n, w = keys.shape
    ku = keys.astype(jnp.uint32)
    out = []
    for mult, seed in zip(_HASH_MULT, _HASH_SEED):
        h = jnp.full(n, seed, jnp.uint32)
        m = jnp.uint32(mult)
        for i in range(w):
            h = (h ^ ku[:, i]) * m
            h = ((h << jnp.uint32(13)) | (h >> jnp.uint32(19)))  # rotl 13
        out.append(_fmix32(h))
    return out[0], out[1]


def hash_sort(keys, valid):
    """Sort rows by the 64-bit hash of their key tuple.

    The cheap half of hash_groupby, factored out so callers with custom
    payload plumbing (engine.fused's dual-mask dst family) can ride one
    hash sort. Invalid rows hash to the all-1s sentinel pair and sort
    last, exactly like sort_groupby's sentinel keys.

    Returns (sorted_hashes [N, 2] uint32, perm [N] int32): gather any
    per-row payload with ``payload[perm]``.
    """
    n = keys.shape[0]
    h1, h2 = hash_lanes(keys)
    h1 = jnp.where(valid, h1, _SENTINEL)
    h2 = jnp.where(valid, h2, _SENTINEL)
    out = lax.sort([h1, h2, lax.iota(jnp.int32, n)], num_keys=2)
    return jnp.stack(out[:2], axis=1), out[2]


def _hash_grouped(sorted_hashes, sorted_keys, sorted_vals, sorted_cnt,
                  detect: bool):
    """Segment reductions over rows already hash-sorted.

    ``sorted_keys`` are the ORIGINAL key lanes gathered through the sort
    permutation (invalid rows replaced by the sentinel tuple). Group
    identity is judged on the hash pair; the reported unique key is the
    per-group segment_min of the real keys, so padding (all-sentinel)
    never wins a mixed group. With ``detect`` the returned flag is True
    iff some group contained two DIFFERENT real key tuples — a 64-bit
    hash collision — letting exactness-critical callers fall back to the
    lexicographic path for that batch.
    """
    n = sorted_hashes.shape[0]
    seg_ids = presorted_segments(sorted_hashes)
    sums = jax.ops.segment_sum(sorted_vals, seg_ids, num_segments=n)
    counts = jax.ops.segment_sum(sorted_cnt, seg_ids, num_segments=n)
    uniq = jax.ops.segment_min(sorted_keys, seg_ids, num_segments=n)
    real = counts > 0
    sums = jnp.where(real[:, None], sums, jnp.zeros_like(sums[:1]))
    uniq = jnp.where(real[:, None], uniq, _SENTINEL)
    counts = jnp.where(real, counts, 0)
    if not detect:
        return uniq, sums, counts, None
    rep_rows = uniq[seg_ids]  # [N, W] group representative per row
    mismatch = jnp.any(sorted_keys != rep_rows, axis=1) & (sorted_cnt > 0)
    return uniq, sums, counts, jnp.any(mismatch)


def hash_groupby_float(keys, values, valid, detect: bool = False):
    """sort_groupby_float semantics via the 64-bit hash sort.

    Same return contract as sort_groupby_float — (unique_keys [N, W]
    uint32, sums [N, P] float32, counts [N] int32), reality judged by
    counts > 0 — but groups are ordered by hash, not lexicographically
    (no consumer in this framework orders by key), and two distinct
    tuples colliding in the full 64-bit hash (~n^2/2^65 per batch) are
    merged into one group whose reported key is the lane-wise min. The
    approximate models (heavy-hitter tables, whose CMS planes already
    merge colliding keys by design) absorb that; exactness-contract
    callers pass detect=True and re-run the batch through
    sort_groupby(_float) when the returned flag fires.

    With detect=True returns (uniq, sums, counts, collided: bool scalar).
    """
    ku = jnp.where(valid[:, None], keys.astype(jnp.uint32), _SENTINEL)
    fv = jnp.where(valid[:, None], values.astype(jnp.float32), 0.0)
    cnt = valid.astype(jnp.int32)
    sh, perm = hash_sort(keys, valid)
    uniq, sums, counts, collided = _hash_grouped(
        sh, ku[perm], fv[perm], cnt[perm], detect)
    if detect:
        return uniq, sums, counts, collided
    return uniq, sums, counts


def hash_groupby(keys, values, valid):
    """sort_groupby semantics (int32 planes + n_groups) via the hash sort,
    plus a collision flag — the exact aggregator's fast path.

    Returns (unique_keys, sums, counts, n_groups, collided). Real groups
    occupy a contiguous slot prefix exactly as in sort_groupby (padding
    hashes to the sentinel pair and sorts last), so the host's
    ``[:n_groups]`` prefix slice keeps working. Callers MUST honor ``collided`` (re-run
    via sort_groupby) to preserve bit-exactness; see hash_groupby_float
    for the probability argument.
    """
    ku = jnp.where(valid[:, None], keys.astype(jnp.uint32), _SENTINEL)
    vals = jnp.where(valid[:, None], values.astype(jnp.int32), 0)
    cnt = valid.astype(jnp.int32)
    sh, perm = hash_sort(keys, valid)
    uniq, sums, counts, collided = _hash_grouped(
        sh, ku[perm], vals[perm], cnt[perm], True)
    n_groups = jnp.sum((counts > 0).astype(jnp.int32))
    return uniq, sums, counts, n_groups, collided


def sort_rows_float(keys, values, valid):
    """Lexicographic multi-key sort with float payload riding along — the
    sort half of sort_groupby_float. Invalid rows get all-sentinel keys
    (they sort last) and zeroed payload/count.

    Returns (sorted_keys [N,W] uint32, sorted_vals [N,P] float32,
    sorted_cnt [N] int32); feed to presorted_groupby_float (optionally
    per key prefix) to finish the groupby."""
    n, w = keys.shape
    sentinel = jnp.uint32(0xFFFFFFFF)
    ku = jnp.where(valid[:, None], keys.astype(jnp.uint32), sentinel)
    fv = jnp.where(valid[:, None], values.astype(jnp.float32), 0.0)
    cnt = valid.astype(jnp.int32)
    # iota payload + post-sort gather (see sort_groupby): cheaper than
    # carrying every value plane through the sort network
    operands = [ku[:, i] for i in range(w)] + [lax.iota(jnp.int32, n)]
    sorted_ops = lax.sort(operands, num_keys=w)
    perm = sorted_ops[w]
    sk = jnp.stack(sorted_ops[:w], axis=1)
    return sk, fv[perm], cnt[perm]


def sort_groupby_float(keys, values, valid):
    """sort_groupby with float32 value planes.

    Value magnitudes beyond int32 (saturated uint32 byte counters, float
    sketch sums) can't ride the int32 path; here the float planes travel
    through the multi-key sort as bit-cast int32 payload lanes and are
    segment-summed in float domain. Same return contract as sort_groupby
    but sums is float32 and the n_groups scalar is replaced by per-row
    ``counts > 0`` validity (the all-sentinel group is zeroed).

    Returns (unique_keys [N,W] uint32, sums [N,P] float32, counts [N] int32).
    """
    # counts>0 alone decides reality (see sort_groupby): a valid all-1s
    # key shares the padding segment but padding contributes 0 to counts,
    # so the group — and its exact float sums — survive.
    return presorted_groupby_float(*sort_rows_float(keys, values, valid))
