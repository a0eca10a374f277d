"""Distinct-count (spread) sketch ops — the jnp twin of the flowspread
family (-spread.enabled).

flowspread answers "how many DISTINCT elements did this key touch?" —
the cardinality companion to the volume sketches: superspreaders
(src -> distinct dst addrs) and port scans (src -> distinct dst ports).
The reference points are the streaming spread top-K surface of
PAPERS.md 2511.16797 and the compact register layouts of 2504.16896;
the layout here is a CMS-of-HLLs over the estate's murmur3 bucket
discipline:

    regs: [depth, width, m] uint8      (m registers per bucket)
    bucket_d(key) = hash_words(key_lanes, seed=d) % width   (ops.cms twin)
    r             = hash_words(elem_lanes, SPREAD_REG_SEED) % m
    rho           = clz32(hash_words(elem_lanes, SPREAD_RHO_SEED)) + 1
    update:  regs[d, bucket_d, r] = max(regs[d, bucket_d, r], rho)

Every update is an integer element-wise max, which makes the state a
commutative, associative, IDEMPOTENT monoid:

  - merge across shards/workers is element-wise u8 max — exact by
    construction (max(max(A,B),C) = max over the union), the spread
    mirror of the CMS u64 sum monoid;
  - update order cannot change the state, and duplicate elements are
    free (idempotence), so pre-grouping the batch to unique
    (key, element) pairs is bit-identical to raw row-at-a-time updates;
  - all arithmetic is uint32 hashing + uint8 max — no floats in the
    state, so the three twins (this module, hostsketch/engine.py
    np_spread_*, native hs_spread_update) are trivially bit-exact and,
    unlike ops.invsketch, NO x64 mode is needed.

Estimation (``spread_estimate``) is decode-at-read, host-side float64:
standard HLL harmonic mean with linear-counting small-range correction,
then min over depth rows (each row is an independent estimate; min
bounds bucket-collision inflation, the cardinality analogue of the
count-min min). Only the u8 register state needs three-way parity —
every serve path (worker, mesh coordinator, delta-fed gateway) decodes
through this ONE numpy function, so byte-identical registers give
byte-identical /query/spread answers.
"""

from __future__ import annotations

# flowlint: uint64-exact
# (register updates are pure uint32 hash -> uint8 max arithmetic; a
# signed cast or float promotion breaks three-way twin parity)
# flowlint: lock-checked
# (pure functions over immutable jnp arrays — no shared state, no
# locks; the marker pins that discipline machine-checked)

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..schema.keys import hash_words
from . import topk as topk_ops
from .cms import cms_buckets
from .segment import hash_lanes, sort_groupby_float

# Element-hash protocol constants — mirrored bit-for-bit by
# hostsketch/engine.py np_spread_update and native hs_spread_update.
# Both are far outside the per-depth bucket seed range 0..depth-1, so
# the register-index and rho streams are independent of the bucket rows.
SPREAD_REG_SEED = 0x9E3779B9
SPREAD_RHO_SEED = 0x85EBCA6B

# rho for a zero hash: all 32 bits "leading zeros" + 1. With uint8
# registers saturation is unreachable (rho <= 33 << 255) but merge/max
# stays well-defined at 255 anyway (tests pin the edge).
SPREAD_RHO_ZERO = 33


def spread_init(depth: int, width: int, m: int) -> jnp.ndarray:
    """Fresh register planes: [depth, width, m] uint8 zeros."""
    return jnp.zeros((depth, width, m), dtype=jnp.uint8)


def _bit_length_u32(h):
    """Vectorized integer bit_length of uint32 (0 -> 0), by binary
    search over shifts — identical integer steps in all three twins."""
    h = h.astype(jnp.uint32)
    n = jnp.zeros(h.shape, dtype=jnp.uint32)
    for shift in (16, 8, 4, 2, 1):
        big = (h >> jnp.uint32(shift)) != 0
        n = jnp.where(big, n + jnp.uint32(shift), n)
        h = jnp.where(big, h >> jnp.uint32(shift), h)
    return n + jnp.where(h != 0, jnp.uint32(1), jnp.uint32(0))


def spread_reg_rho(elems, m: int):
    """(register index [N] int32, rho [N] uint32) of element lanes
    ``elems`` [N, W_e]: the element-hash protocol above."""
    # flowlint: disable=uint64-discipline -- register INDICES in [0, m < 2^31); scatter wants int32
    r = (hash_words(elems, seed=SPREAD_REG_SEED)
         % jnp.uint32(m)).astype(jnp.int32)
    h2 = hash_words(elems, seed=SPREAD_RHO_SEED)
    return r, jnp.uint32(SPREAD_RHO_ZERO) - _bit_length_u32(h2)


def spread_update(regs, keys, elems, valid=None):
    """Scatter-max update with (key, element) rows.

    regs:  [D, W, m] uint8 register planes.
    keys:  [N, W_k] uint32 key lanes.
    elems: [N, W_e] uint32 element lanes (counted dimension).
    valid: [N] bool mask (padded rows contribute rho=0, a no-op under
           max since registers are >= 0).
    """
    d, w, m = regs.shape
    buckets = cms_buckets(keys, d, w)  # [D, N] int32
    r, rho = spread_reg_rho(elems, m)
    rho = rho.astype(jnp.uint8)
    if valid is not None:
        rho = jnp.where(valid, rho, jnp.uint8(0))
    for di in range(d):
        regs = regs.at[di, buckets[di], r].max(rho)
    return regs


# ---------------------------------------------------------------------------
# The planes as a device step holds them (engine/fused.py, under
# -spread.enabled on the device backend): ONE flat [D * W * m] array of
# DEVICE_REG_DTYPE, so that an update is one 1-D scatter-max and no
# reshape stands between two steps (on a TPU a [D, W, m] -> flat
# reshape is a relayout, a copy of the whole plane). Every host-side
# form (checkpoint, snapshot, mesh payload, decode) stays [D, W, m]
# uint8: ``device_regs`` and ``host_regs`` are the only two crossings.
# int32, not uint8: the v5e scatters whole words (PERF.md 6, PR 47 has
# both readings); the values are the same integers either way, so the
# cast at the boundary loses nothing and the registers stay the numpy
# twin's bit for bit.

DEVICE_REG_DTYPE = jnp.int32


def device_regs(regs: np.ndarray):
    """[D, W, m] uint8 on the host -> the flat device plane (the bytes
    cross once; the widening runs on the device)."""
    flat = np.ascontiguousarray(regs, np.uint8).reshape(-1)
    return jnp.asarray(flat).astype(DEVICE_REG_DTYPE)


@functools.partial(jax.jit, static_argnames=("shape",))
def host_regs(flat, *, shape: tuple):
    """The flat device plane -> [D, W, m] uint8, still on the device:
    what a checkpoint, a publish or a close then copies to the host (a
    quarter of the plane's bytes)."""
    return flat.astype(jnp.uint8).reshape(shape)


def spread_scatter(flat, shape: tuple, keys, elems, valid):
    """``spread_update`` on the flat device plane: ONE scatter-max over
    every depth row. A row whose ``valid`` is False leaves the scatter
    (its index goes out of range HIGH, which mode="drop" discards, as
    ops.cms.cms_add_conservative does it). Into a plane of this size
    the v5e pays by the index handed over, real or dropped, ~12 ns
    each, and a loop over the chunks under a live bound costs more than
    it saves (0.78 ms a call for 65,536 indices against 1.89 for five
    trips of 4,096: PERF.md 6, PR 47), so the step hands it the batch's
    rows as they are: under max a duplicate is a no-op."""
    d, w, m = shape
    buckets = cms_buckets(keys, d, w)  # [D, N] int32
    r, rho = spread_reg_rho(elems, m)
    # int32 arithmetic on int32 bucket/register INDICES: the cells number
    # below 2^31 (models.spread.spread_init refuses more)
    rows = jnp.arange(d, dtype=buckets.dtype)[:, None] * w + buckets
    idx = jnp.where(valid[None, :], rows * m + r[None, :], d * w * m)
    vals = jnp.broadcast_to(rho.astype(flat.dtype)[None, :], idx.shape)
    return flat.at[idx.reshape(-1)].max(vals.reshape(-1), mode="drop")


def spread_estimate_device(rows):
    """``spread_estimate`` of register rows [..., m] on the device, in
    float32: the raw HLL estimate with the linear-counting small-range
    correction (no large-range one: it starts at 2^32 / 30). Only the
    candidate table's admission reads it (``spread_table_admit``); what
    a close or a query reports is ``spread_estimate``'s float64 on the
    host, as ever. Written so that the chip's transcendentals do not
    show: 2^-register is put together from its exponent bits (exact;
    the v5e's ``exp2`` is not), and the linear count is
    m * log1p((m - zeros) / zeros), whose argument is small where
    log(m / zeros) takes the logarithm of a number next to 1 (with
    ``exp2`` and ``log`` the v5e read 1.2x10^-4 off the host's float64
    where the CPU read 2x10^-5: chip_smoke.py, PERF.md 6, PR 47)."""
    m = rows.shape[-1]
    # float32 2^-r: sign 0, exponent 127 - r, mantissa 0 (a register
    # past 126, which no hash gives, reads 0.0 where float64 has 2^-127)
    # flowlint: disable=uint64-discipline -- REGISTERS, u8 values in [0, 255] (the device plane holds them as int32 already); an exponent field wants int32
    expo = jnp.maximum(127 - rows.astype(jnp.int32), 0)
    pow2 = jax.lax.bitcast_convert_type(expo << 23, jnp.float32)
    est = jnp.float32(_hll_alpha(m) * m * m) / jnp.sum(pow2, axis=-1)
    zeros = jnp.sum(rows == 0, axis=-1)
    z = jnp.maximum(zeros, 1).astype(jnp.float32)
    lc = m * jnp.log1p((m - z) / z)
    return jnp.where((est <= 2.5 * m) & (zeros > 0), lc, est)


def spread_decode_device(flat, shape: tuple, keys):
    """[N] float32: ``spread_decode`` of the flat device plane for the
    key lanes ``keys`` [N, W_k]: each key's bucket row a depth row,
    estimated, and the least over depth taken. The rows are taken from a
    [D * W, m] view of the plane, which on the v5e is a copy of it (the
    relayout ``device_regs`` speaks of) and still the cheapest form
    measured there: 0.47 ms for 32,768 keys of 2 x 32,768 x 256 planes,
    copy, gather and estimate, against 78 ms for m-wide slices taken
    from the flat plane itself, a loop of one slice a trip (PERF.md 6,
    PR 47)."""
    d, w, m = shape
    buckets = cms_buckets(keys, d, w)  # [D, N] int32
    rows = jnp.arange(d, dtype=buckets.dtype)[:, None] * w + buckets
    return spread_estimate_device(
        flat.reshape(d * w, m)[rows]).min(axis=0)


def spread_table_admit(table_keys, table_metric, cand_keys, cand_est,
                       cand_valid):
    """The candidate table's fold on the device: a key the batch holds
    is worth its spread as the registers decode it NOW (``cand_est``:
    ``spread_decode_device`` after the batch's scatter, so it covers all
    the key has shown since the window opened, whenever it enters); a
    resident the batch does not hold keeps what it was worth when last
    seen; the ``capacity`` largest stay. A decoded spread never falls
    (the registers only rise), so neither does the table's last place,
    and a key the table lacks at the close decoded, when last seen, to
    less than that place: the table holds the window's ``capacity``
    largest sources by decoded spread however slowly one of them grew.
    (The host twin, hostsketch.engine.np_spread_table_merge, ranks by
    the accumulated count of a key's pairs a batch, the busiest sources
    first, and admits by the batch's count alone: a spreader that shows
    a target or two a batch can stay outside it. ROADMAP B-mech 2.)

    Past 2 x capacity candidates only the residents and the largest
    others go to the merge (models.heavy_hitter._apply_grouped's
    prefilter): what it leaves out decodes below 2 x capacity keys of
    this batch alone. The metric only admits; what a close reports is
    decoded from the registers on the host."""
    c = table_keys.shape[0]
    est = cand_est.astype(jnp.float32)
    if cand_keys.shape[0] > 2 * c:
        th, _ = hash_lanes(table_keys)
        gh, _ = hash_lanes(cand_keys)
        resident = (th[:, None] == gh[None, :]).any(axis=0) & cand_valid
        rank = jnp.where(cand_valid, est, -jnp.inf)
        _, sel = jax.lax.top_k(jnp.where(resident, jnp.inf, rank), 2 * c)
        cand_keys, est, cand_valid = (cand_keys[sel], est[sel],
                                      cand_valid[sel])
    # ops.topk.topk_merge with max where it sums: a key has at most one
    # row of each kind, so a group's two sums are what the table held
    # and what the batch decodes
    sentinel = topk_ops.SENTINEL
    valid = jnp.concatenate([
        jnp.any(table_keys != sentinel, axis=1),
        cand_valid & jnp.any(cand_keys != sentinel, axis=1)])
    zt, zc = jnp.zeros_like(table_metric), jnp.zeros_like(est)
    uniq, sums, counts = sort_groupby_float(
        jnp.concatenate([table_keys, cand_keys.astype(jnp.uint32)]),
        jnp.stack([jnp.concatenate([table_metric, zc]),
                   jnp.concatenate([zt, est])], axis=1), valid)
    real = counts > 0
    worth = jnp.where(real, jnp.maximum(sums[:, 0], sums[:, 1]), -jnp.inf)
    top = jnp.argsort(-worth)[:c]
    return (jnp.where(real[top][:, None], uniq[top], sentinel),
            jnp.where(real[top], worth[top], 0.0))


def spread_merge(*states):
    """Element-wise max fold — the exact merge monoid (commutative,
    associative, idempotent)."""
    out = states[0]
    for s in states[1:]:
        out = jnp.maximum(out, s)
    return out


# ---------------------------------------------------------------------------
# Decode — host-side float64, shared by EVERY serve path. Pure function
# of the u8 registers; numpy on purpose (deterministic float64 ops, no
# XLA fusion reordering), so identical registers decode to identical
# bytes on worker, mesh coordinator and gateway replicas alike.

def _hll_alpha(m: int) -> float:
    if m == 16:
        return 0.673
    if m == 32:
        return 0.697
    if m == 64:
        return 0.709
    return 0.7213 / (1.0 + 1.079 / m)


_TWO32 = float(1 << 32)


def spread_estimate(rows: np.ndarray) -> np.ndarray:
    """HLL estimate per register row.

    rows: [..., m] uint8 registers. Returns [...] float64: harmonic-mean
    raw estimate with linear-counting small-range correction (E <= 2.5m
    with empty registers present) and the 32-bit large-range correction.
    """
    rows = np.asarray(rows, dtype=np.uint8)
    m = rows.shape[-1]
    alpha = _hll_alpha(m)
    # flowlint: disable=uint64-discipline -- u8 register VALUES in [0, 255] widened for negation; ldexp exponents, not counters
    inv = np.ldexp(1.0, -rows.astype(np.int64))  # exact 2^-reg in f64
    est = alpha * m * m / np.sum(inv, axis=-1)
    zeros = np.count_nonzero(rows == 0, axis=-1)
    small = (est <= 2.5 * m) & (zeros > 0)
    with np.errstate(divide="ignore"):
        lc = m * np.log(np.where(zeros > 0, m / np.maximum(zeros, 1), 1.0))
    est = np.where(small, lc, est)
    large = est > _TWO32 / 30.0
    est = np.where(large, -_TWO32 * np.log1p(-np.minimum(est, _TWO32 * 0.99999)
                                             / _TWO32), est)
    return est


def spread_decode(regs: np.ndarray, buckets: np.ndarray) -> np.ndarray:
    """Point estimates for pre-hashed buckets: min over depth rows.

    regs: [D, W, m] uint8. buckets: [D, N] integer bucket indices.
    Returns [N] float64 spread estimates.
    """
    regs = np.asarray(regs)
    d = regs.shape[0]
    ests = [spread_estimate(regs[di, np.asarray(buckets[di])])
            for di in range(d)]
    return np.minimum.reduce(ests)
