"""Pallas CMS update kernels: scatter as dense tile work on the VPU.

XLA lowers ``counts.at[buckets].add(v)`` / ``.max(v)`` to scatters, which
the TPU executes with serialized conflict handling. Both CMS updates can
instead be written as dense per-tile work with no conflicts at all:

    cell[p, d, w] (+= | max=) over keys n with bucket[d, n] == w
                              of addend[p, n]

One kernel serves both updates (``combine`` is add or max). A grid cell
owns ``tile`` sketch columns of every depth row and streams the keys
through in ``chunk``-sized blocks: per block it builds the
``[tile, chunk]`` membership mask of one depth row against a SUBLANE
iota — columns on sublanes, keys on lanes, so the 1-D bucket and addend
rows are used in the lane layout they arrive in and never relaid out —
and folds the masked addends elementwise into a ``[tile, chunk]``
accumulator per (plane, depth row). After the last block the accumulator
is reduced over lanes and turned into a lane-major row through an
identity mask (a select and a sublane reduce: no transpose), then
combined into the sketch block, which stays resident in VMEM for the
whole key stream.

Exactness: adds are f32 VPU adds of the addends themselves (no MXU
pass, so no bf16 rounding) — integer-valued sums stay exact below 2^24
per cell, the contract ops/cms.py states; max is order-free. Both match
``ops.cms.cms_add`` / ``cms_add_conservative`` bit for bit on that
envelope. They use the SAME bucket scheme (cms_buckets), so they are
drop-in replacements on the same sketch state and ops.cms.cms_query
serves either path.

``interpret`` is for tests (tests/test_cms_pallas.py, CPU); chip_smoke.py
compiles both kernels at the processor's default shapes on the chip and
checks them against the XLA twins.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .cms import cms_buckets, cms_query

_LANE = 128  # TPU lane width; tiles and chunks are multiples of this


def _scatter_kernel(buckets_ref, addend_ref, counts_ref, out_ref, acc_ref,
                    *, tile: int, chunk: int, is_max: bool):
    """Grid cell (j, k): fold key block k into columns [j*tile,
    (j+1)*tile) of every (plane, depth row).

    buckets_ref [D, chunk] int32, addend_ref [P, chunk] f32 (keys on
    lanes), counts_ref/out_ref [P, D, tile], acc_ref [P*D, tile, chunk].
    Padding keys carry bucket -1 (no column matches); invalid keys carry
    a 0 addend, inert under both add and max (cells are >= 0)."""
    j, k = pl.program_id(0), pl.program_id(1)
    p, d, _ = out_ref.shape
    combine = jnp.maximum if is_max else jnp.add
    fold = jnp.max if is_max else jnp.sum

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cols = j * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, chunk), 0)
    for di in range(d):  # depth and planes are small + static: unrolled
        mask = cols == buckets_ref[di:di + 1, :]  # [tile, chunk]
        for pi in range(p):
            row = pi * d + di
            acc_ref[row] = combine(
                acc_ref[row],
                jnp.where(mask, addend_ref[pi:pi + 1, :], 0.0))

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        eye = (jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1))
        for di in range(d):
            for pi in range(p):
                col = fold(acc_ref[pi * d + di], axis=1,
                           keepdims=True)  # [tile, 1]
                upd = fold(jnp.where(eye, col, 0.0), axis=0,
                           keepdims=True)  # [1, tile]
                out_ref[pi, di:di + 1, :] = combine(
                    counts_ref[pi, di:di + 1, :], upd)


def _scatter_call(counts, buckets, addend, *, tile: int, chunk: int,
                  is_max: bool, interpret: bool):
    """counts [P, D, W] (+= | max=) addend [P, N] at buckets [D, N]."""
    p, d, w = counts.shape
    n = buckets.shape[1]
    if w % tile:
        raise ValueError(f"width {w} must be a multiple of tile {tile}")
    if tile % _LANE or chunk % _LANE:
        raise ValueError(
            f"tile {tile} and chunk {chunk} must be multiples of {_LANE}")
    if n % chunk:
        # pad the streamed dimension to a chunk multiple with inert keys
        # so chunk stays lane-aligned for ANY batch size
        pad = chunk - n % chunk
        buckets = jnp.pad(buckets, ((0, 0), (0, pad)), constant_values=-1)
        addend = jnp.pad(addend, ((0, 0), (0, pad)))
    grid = (w // tile, buckets.shape[1] // chunk)
    return pl.pallas_call(
        functools.partial(_scatter_kernel, tile=tile, chunk=chunk,
                          is_max=is_max),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d, chunk), lambda j, k: (0, k)),
            pl.BlockSpec((p, chunk), lambda j, k: (0, k)),
            pl.BlockSpec((p, d, tile), lambda j, k: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((p, d, tile), lambda j, k: (0, 0, j)),
        out_shape=jax.ShapeDtypeStruct(counts.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((p * d, tile, chunk), jnp.float32)],
        input_output_aliases={2: 0},  # update the sketch in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(buckets, addend, counts)


@functools.partial(jax.jit,
                   static_argnames=("tile", "chunk", "interpret"))
def cms_add_pallas(counts, keys, values, valid=None, *, tile: int = 256,
                   chunk: int = 256, interpret: bool = False):
    """Linear CMS update; drop-in for ops.cms.cms_add (same bucket
    scheme, same state, query with ops.cms.cms_query)."""
    _, d, w = counts.shape
    vals = values.astype(jnp.float32)
    if valid is not None:
        vals = jnp.where(valid[:, None], vals, 0.0)
    buckets = cms_buckets(keys, d, w)  # [D, N], hashed exactly once
    return _scatter_call(counts, buckets, vals.T, tile=tile, chunk=chunk,
                         is_max=False, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("tile", "chunk", "interpret"))
def cms_add_conservative_pallas(counts, keys, values, valid=None, *,
                                tile: int = 256, chunk: int = 256,
                                interpret: bool = False):
    """Conservative CMS update; drop-in for ops.cms.cms_add_conservative.

    The current-estimate gather runs in XLA (gathers do not serialize);
    only the conflict-prone scatter-max is the Pallas kernel. Keys must
    be unique within the call (sort_groupby first), matching the XLA
    path's contract."""
    _, d, w = counts.shape
    buckets = cms_buckets(keys, d, w)  # [D, N]
    est = cms_query(counts, keys)  # [N, P]
    target = est + values.astype(jnp.float32)  # the CU ceiling per key
    if valid is not None:
        # invalid rows must not raise any cell (their est alone could);
        # a 0 target is inert — cells are >= 0 and only move via max
        target = jnp.where(valid[:, None], target, 0.0)
    return _scatter_call(counts, buckets, target.T, tile=tile, chunk=chunk,
                         is_max=True, interpret=interpret)
