"""Host-side exact groupby for CPU-backend deployments.

On a TPU the fused per-batch step pre-aggregates with the device sort
network (ops.segment / engine.fused) — the idiomatic choice there, since
host<->HBM round trips cost more than the sort. On a CPU-only box the
trade inverts: the "device" IS the host, XLA:CPU lowers ``lax.sort`` to a
single-threaded comparison sort (~11 ms for 32k rows x 2 hash lanes on
one core, measured), while numpy's introsort over one u64 hash lane does
the same grouping in ~0.6 ms. So the CPU engine groups HERE, in numpy,
and ships only the compact group tables to the XLA step (CMS updates,
top-K table merges, dense scatters) — engine.hostfused wires it up.

Exactness: grouping identity starts from the 64-bit key hash (same
constants as ops.segment.hash_lanes' pair, composed into one u64), but
unlike the device path the result is ALWAYS exact — a full-key
verification pass catches hash collisions and re-sorts lexicographically
(numpy has no static-shape constraint, so the fallback is synchronous
and cheap instead of a deferred device flag).
"""

from __future__ import annotations

import numpy as np

# Same decorrelated multiplier/seed pairs as ops.segment (_HASH_MULT /
# _HASH_SEED) so host and device grouping hash identically — not load-
# bearing (each path verifies or flags its own collisions) but it keeps
# cross-path debugging sane.
_MULTS = (np.uint32(0x9E3779B1), np.uint32(0x85EBCA77))
_SEEDS = (np.uint32(0x2545F491), np.uint32(0x27220A95))


def hash_u64(lanes: np.ndarray) -> np.ndarray:
    """[N, W] uint32 key lanes -> [N] uint64 murmur-style hash.

    Two independent 32-bit mixes (rotl-13 lane fold + fmix32 finalizer,
    mirroring ops.segment.hash_lanes) packed high/low into one u64 so a
    single ``np.argsort`` orders rows by the full 64-bit identity.
    """
    n, w = lanes.shape
    out = []
    with np.errstate(over="ignore"):  # uint32 wraparound is the algorithm
        for mult, seed in zip(_MULTS, _SEEDS):
            h = np.full(n, seed, np.uint32)
            for i in range(w):
                h = (h ^ lanes[:, i]) * mult
                h = (h << np.uint32(13)) | (h >> np.uint32(19))
            h ^= h >> np.uint32(16)
            h *= np.uint32(0x85EBCA6B)
            h ^= h >> np.uint32(13)
            h *= np.uint32(0xC2B2AE35)
            h ^= h >> np.uint32(16)
            out.append(h)
    return (out[0].astype(np.uint64) << np.uint64(32)) | out[1]


def native_group_available() -> bool:
    """Whether the native hash-group kernel (native.hash_group: same
    64-bit hash, radix sort + collision verify in one C pass) can serve
    as grouping backend. Callers opt in per call via ``native=True``
    (WorkerConfig.ingest_native_group, which the CLI sets); the
    pure-numpy path stays the reference
    implementation the oracle tests pin down."""
    from .. import native

    return native.group_available()


def _empty_groups(w: int, planes: list[np.ndarray]):
    return (np.zeros((0, w), np.uint32),
            [np.zeros((0,) + p.shape[1:],
                      np.float64 if np.issubdtype(p.dtype, np.floating)
                      else np.uint64) for p in planes],
            np.zeros(0, np.int64))


def _run_starts(sorted_lanes: np.ndarray) -> np.ndarray:
    """Where each run of equal rows starts, in rows already sorted."""
    boundary = np.empty(sorted_lanes.shape[0], dtype=bool)
    boundary[0] = True
    np.any(sorted_lanes[1:] != sorted_lanes[:-1], axis=1, out=boundary[1:])
    return np.flatnonzero(boundary)


def _lex_regroup(lanes: np.ndarray):
    """Exact lexicographic grouping — the 64-bit-collision fallback."""
    perm = np.lexsort(lanes.T[::-1])
    return perm, _run_starts(lanes[perm])


def grouping_perm(lanes: np.ndarray, exact: bool, h: np.ndarray = None,
                  native: bool = False):
    """Row permutation + group starts for hash grouping of ``lanes``.

    The factored-out heart of group_by_key, reused by the sharded path
    (ingest.shard, which precomputes ``h`` per shard) and anything else
    that wants the grouping without the sums. Returns (perm, starts).
    """
    n = lanes.shape[0]
    if native and h is None:
        from .. import native as native_lib

        if native_lib.group_available():  # else: numpy fallback below
            perm, starts, collided = native_lib.hash_group(lanes)
            if exact and collided:
                return _lex_regroup(lanes)
            return perm, starts
    if h is None:
        h = hash_u64(lanes)
    perm = np.argsort(h)  # introsort; stability irrelevant (identity = hash)
    sh = h[perm]
    boundary = np.empty(n, dtype=bool)
    boundary[0] = True
    np.not_equal(sh[1:], sh[:-1], out=boundary[1:])
    starts = np.flatnonzero(boundary)
    if exact:
        # verify every row against its group's representative key; fall
        # back to the full lexicographic sort on a 64-bit hash collision
        # (~n^2/2^65 per batch) — exactness is unconditional on this path
        sl = lanes[perm]
        seg = np.cumsum(boundary) - 1
        if (sl != sl[starts][seg]).any():
            return _lex_regroup(lanes)
    return perm, starts


def reduce_groups(lanes: np.ndarray, planes: list[np.ndarray],
                  perm: np.ndarray, starts: np.ndarray):
    """(uniq, sums, counts) for a grouping permutation from grouping_perm.

    Each plane is summed per group with ``np.add.reduceat`` in float64
    (floating inputs) or uint64 (integer inputs) — callers cast the
    results down themselves."""
    n = perm.shape[0]
    uniq = lanes[perm[starts]]
    counts = np.diff(np.append(starts, n)).astype(np.int64)
    sums = []
    for p in planes:
        acc_dtype = (np.float64 if np.issubdtype(p.dtype, np.floating)
                     else np.uint64)
        sums.append(np.add.reduceat(p[perm].astype(acc_dtype), starts,
                                    axis=0))
    return uniq, sums, counts


def group_by_key(lanes: np.ndarray, planes: list[np.ndarray],
                 exact: bool = True, native: bool = False):
    """Groupby-sum of ``planes`` by row-tuples of ``lanes``.

    Args:
      lanes:  [N, W] uint32 key lanes.
      planes: list of [N] or [N, P] arrays, summed per group
              (see reduce_groups for the accumulator dtypes).
      exact:  verify every row against its group's representative key and
              fall back to a full lexicographic sort on a 64-bit hash
              collision (~n^2/2^65 per batch). Exactness-contract callers
              (flows_5m) keep the default; sketch callers pass False and
              accept the same merge-two-tuples failure mode their device
              twin (ops.segment.hash_groupby_float) documents — skipping
              the verify saves the [N, W] gather+compare (~15% of the
              groupby at 12 lanes).
      native: use the C hash-group kernel when built (collision verify is
              free there, so ``exact`` costs nothing extra); silently
              numpy when the library is missing — callers gate defaults
              on native_group_available().

    Returns (uniq [G, W] uint32, sums list matching ``planes``,
    counts [G] int64). Group order is hash order (arbitrary but
    deterministic); no consumer in this framework orders by key.
    """
    n, w = lanes.shape
    if n == 0:
        return _empty_groups(w, planes)
    perm, starts = grouping_perm(lanes, exact, native=native)
    return reduce_groups(lanes, planes, perm, starts)


def select_lanes(key_cols: tuple, widths: dict[str, int],
                 subset: tuple) -> list[int]:
    """Lane indices of ``subset`` columns inside the concatenated lane
    layout of ``key_cols`` (addresses occupy ``widths[name]`` lanes).
    Raises KeyError when a subset column is absent — callers decide
    between cascading from a parent group table and grouping raw rows."""
    offsets = {}
    off = 0
    for name in key_cols:
        offsets[name] = off
        off += widths[name]
    out: list[int] = []
    for name in subset:
        start = offsets[name]  # KeyError -> not a subset
        out.extend(range(start, start + widths[name]))
    return out
