"""Invertible-sketch ops — the jnp twin of the invertible heavy-hitter
family (-hh.sketch=invertible).

The invertible sketch (PAPERS.md 1910.10441's recover-keys-from-the-
sketch model, linearized onto the uint64-exact envelope) replaces the
whole admission path — top-K candidate table, admission CMS queries,
table prefilter — with ONE pure per-bucket fold over the same murmur3
buckets ops.cms uses:

    cms[p, d, b]    += addend_u64(vals[p])           (all planes, plain)
    keysum[d, b, l] += key[l] * cnt                  (wrap mod 2^64)
    keycheck[d, b]  += inv_key_hash(key) * cnt       (wrap mod 2^64)

Every cell is a plain uint64 wrap sum, so the state is LINEAR in the
stream: merge across shards/chips is an element-wise u64 sum, and heavy
keys are recovered from the sketch itself at window close by peeling
pure buckets (``inv_decode``). Conservative update is deliberately not
offered — decode divides by the count cell, which must be the bucket's
exact sum.

dtype note: the key-recovery planes are uint64 BY CONSTRUCTION (a lane
times a count does not fit any smaller exact dtype), so this module
requires jax x64 mode (``jax.enable_x64`` or the
``jax_enable_x64`` config) — the init helper raises a clear error
otherwise. The production home of this family is the host dataplane
(hostsketch/engine.py numpy twin + native/hostsketch.cc, reached
through ``ff_fused_update``); this jnp twin is the parity reference for
x64-enabled devices and tests/test_invsketch.py pins all three
bit-exact.
"""

from __future__ import annotations

# flowlint: uint64-exact
# (every plane is an exact unsigned monoid; one signed cast or float
# promotion and decode's divide-and-verify arithmetic is garbage)
# flowlint: lock-checked
# (pure functions over immutable jnp arrays — no shared state, no
# locks; the marker pins that discipline machine-checked)

import jax.numpy as jnp
import numpy as np

from ..schema.keys import hash_words
from .cms import cms_buckets

# Checksum-hash protocol constants — mirrored bit-for-bit by
# hostsketch/engine.py np_inv_key_hash and native inv_key_hash.
INV_HASH_SEED = 0x9E3779B97F4A7C15
INV_HASH_M1 = 0xFF51AFD7ED558CCD
INV_HASH_M2 = 0xC4CEB9FE1A85EC53

# Largest float32 strictly below 2^64 (hostsketch.state._U64_CAP's twin).
_U64_CAP = jnp.float32(1.8446742e19)


def _require_x64(arr) -> None:
    if arr.dtype != jnp.uint64:
        raise TypeError(
            "invertible-sketch planes must be uint64; enable jax x64 "
            "mode (jax.enable_x64) — without it jnp "
            "silently downcasts to uint32 and every cell past 2^32 is "
            f"garbage (got {arr.dtype})")


def inv_init(planes: int, depth: int, width: int, key_width: int):
    """Fresh invertible state: (cms [P, D, W], keysum [D, W, kw],
    keycheck [D, W]) — all uint64 zeros."""
    cms = jnp.zeros((planes, depth, width), dtype=jnp.uint64)
    _require_x64(cms)
    return (cms,
            jnp.zeros((depth, width, key_width), dtype=jnp.uint64),
            jnp.zeros((depth, width), dtype=jnp.uint64))


def inv_key_hash(keys) -> jnp.ndarray:
    """[N] uint64 checksum hash over [N, W] uint32 key lanes (wrap
    arithmetic mod 2^64)."""
    h = jnp.full(keys.shape[0], INV_HASH_SEED, dtype=jnp.uint64)
    _require_x64(h)
    for lane in range(keys.shape[1]):
        h = h ^ keys[:, lane].astype(jnp.uint64)
        h = h * jnp.uint64(INV_HASH_M1)
        h = h ^ (h >> jnp.uint64(33))
    h = h * jnp.uint64(INV_HASH_M2)
    h = h ^ (h >> jnp.uint64(29))
    return h


def _addend_u64(vals) -> jnp.ndarray:
    """f32 addends -> u64 with the hostsketch clamp (negatives/NaN
    contribute nothing; at/past 2^64 — inf included — clamps to
    UINT64_MAX exactly like native addend_u64)."""
    v = vals.astype(jnp.float32)
    v = jnp.where(jnp.isnan(v) | (v <= 0), jnp.float32(0.0), v)
    big = v >= jnp.float32(2.0**64)
    v = jnp.minimum(v, _U64_CAP)
    return jnp.where(big, jnp.uint64(0xFFFFFFFFFFFFFFFF),
                     v.astype(jnp.uint64))


def inv_update(cms, keysum, keycheck, keys, values, valid=None):
    """One pre-aggregated update step (jit-able): the jnp twin of
    np_inv_update / native hs_inv_update.

    keys [N, kw] uint32 unique key rows; values [N, P] addends with the
    count plane LAST; valid [N] bool mask. Returns the new
    (cms, keysum, keycheck)."""
    _require_x64(cms)
    p, d, w = cms.shape
    buckets = cms_buckets(keys, d, w)  # [D, N] — the CMS bucket scheme
    add = _addend_u64(values)
    if valid is not None:
        add = jnp.where(valid[:, None], add, jnp.uint64(0))
    cnt = add[:, -1]
    check = inv_key_hash(keys) * cnt
    lanes_u64 = keys.astype(jnp.uint64) * cnt[:, None]
    for di in range(d):
        cms = cms.at[:, di, buckets[di]].add(add.T)
        keysum = keysum.at[di, buckets[di], :].add(lanes_u64)
        keycheck = keycheck.at[di, buckets[di]].add(check)
    return cms, keysum, keycheck


def inv_merge(*states):
    """Combine per-shard invertible states: element-wise u64 wrap sum of
    every plane — the whole mesh-merge story for this family."""
    cms, keysum, keycheck = states[0]
    for c, ks, kc in states[1:]:
        cms = cms + c
        keysum = keysum + ks
        keycheck = keycheck + kc
    return cms, keysum, keycheck


def inv_decode(cms, keysum, keycheck):
    """Heavy-key recovery by peeling pure buckets — the jnp twin of
    np_inv_decode (vectorized purity scan per round in jnp; the
    peel-round loop is data-dependent and runs on the host). Returns
    numpy (keys [K, kw] u32, vals [K, P] u64) in canonical
    lexicographic key order — array-equal to the numpy and native
    decodes (the recoverable set is peel-order independent)."""
    _require_x64(cms)
    p, depth, width = cms.shape
    kw = keysum.shape[2]
    out_keys: list[np.ndarray] = []
    out_vals: list[np.ndarray] = []
    seen: set[bytes] = set()
    cand = np.asarray(cms[-1] != 0)
    while cand.any():
        cnt = cms[-1]  # [D, W]
        safe = jnp.where(cnt != 0, cnt, jnp.uint64(1))
        q = keysum // safe[:, :, None]  # [D, W, kw]
        ok = (cnt != 0) & (q * safe[:, :, None] == keysum).all(axis=2) \
            & (q <= jnp.uint64(0xFFFFFFFF)).all(axis=2)
        qk = q.astype(jnp.uint32)
        cols = jnp.arange(width, dtype=jnp.uint32)
        for di in range(depth):
            row_keys = qk[di]  # [W, kw]
            h = inv_key_hash(row_keys) * safe[di]
            ok = ok.at[di].set(
                ok[di] & (h == keycheck[di])
                & (hash_words(row_keys, seed=di)
                   % jnp.uint32(width) == cols))
        ok_np = np.asarray(ok) & cand
        d_idx, b_idx = np.nonzero(ok_np)
        if not len(d_idx):
            break
        dec = np.asarray(qk)[d_idx, b_idx]  # [m, kw]
        kview = np.ascontiguousarray(dec).view(
            [("", np.uint32)] * kw).reshape(-1)
        _, first = np.unique(kview, return_index=True)
        picked = [i for i in sorted(first)
                  if kview[i].tobytes() not in seen]
        if not picked:
            break
        for i in picked:
            seen.add(kview[i].tobytes())
        picked = np.asarray(picked)
        dec_keys = np.ascontiguousarray(dec[picked])
        cms_np = np.asarray(cms)
        dec_vals = np.stack(
            [cms_np[pi, d_idx[picked], b_idx[picked]] for pi in range(p)],
            axis=1)
        out_keys.append(dec_keys)
        out_vals.append(dec_vals)
        # peel: subtract each decoded key's exact contribution from its
        # bucket in every depth row (wrap), then rescan touched buckets
        jkeys = jnp.asarray(dec_keys)
        jvals = jnp.asarray(dec_vals)
        dcnt = jvals[:, -1]
        check = inv_key_hash(jkeys) * dcnt
        lanes_u64 = jkeys.astype(jnp.uint64) * dcnt[:, None]
        touched = np.zeros((depth, width), bool)
        for di in range(depth):
            bb = hash_words(jkeys, seed=di) % jnp.uint32(width)
            cms = cms.at[:, di, bb].add(
                jnp.uint64(0) - jvals.T)  # wrap subtract
            keysum = keysum.at[di, bb, :].add(jnp.uint64(0) - lanes_u64)
            keycheck = keycheck.at[di, bb].add(jnp.uint64(0) - check)
            touched[di, np.asarray(bb)] = True
        cand = touched & np.asarray(cms[-1] != 0)
    if not out_keys:
        return (np.zeros((0, kw), np.uint32), np.zeros((0, p), np.uint64))
    keys = np.concatenate(out_keys)
    vals = np.concatenate(out_vals)
    order = np.lexsort(keys.T[::-1])
    return (np.ascontiguousarray(keys[order]),
            np.ascontiguousarray(vals[order]))
