"""Host-resident heavy-hitter state and its device-state conversions.

The host engine keeps CMS counters in uint64 (the exact monoid — u64
addition is associative, which is what makes the threaded native update
deterministic for free) and the top-K table in the device layout
(uint32 keys, float32 values: table values accumulate by single f32
adds per round on BOTH paths, so keeping f32 here makes table parity
unconditional). Conversions to/from the device ``HHState`` are lossless
on the uint64-exact envelope:

- u64 -> f32 export is exact while cells stay below 2^24 — the same
  envelope inside which the device's own f32 accumulation is exact;
- f32 -> u64 import is exact for every integer-valued f32 cell, which
  the device path produces by construction (counters are integer sums
  of integer-valued addends).

Out-of-envelope values clamp instead of corrupting (NaN/negative -> 0,
overflow -> the largest f32 below 2^64), so a restore from a hot
device sketch never produces garbage counters.
"""

from __future__ import annotations

# flowlint: uint64-exact
# (the whole point of this state is exact unsigned counters; a signed
# cast here silently re-introduces the float error the engine removes)

from dataclasses import dataclass

import numpy as np

from ..models.heavy_hitter import HeavyHitterConfig, HHState, key_width

# Largest float32 strictly below 2^64 — the clamp for out-of-envelope
# device cells on import (astype(u64) of +/-inf or >=2^64 is undefined).
_U64_CAP = np.float32(1.8446742e19)


@dataclass
class HostHHState:
    """One family's host-resident sketch state (engine-owned buffers)."""

    cms: np.ndarray         # [P+1, depth, width] uint64, C-contiguous
    table_keys: np.ndarray  # [capacity, key_width] uint32, C-contiguous
    table_vals: np.ndarray  # [capacity, P+1] float32, C-contiguous


@dataclass
class HostInvState:
    """One family's host-resident INVERTIBLE sketch state
    (-hh.sketch=invertible): the count/value planes plus the
    key-recovery planes, all plain u64 wrap sums — linear in the
    stream, so shards merge by element-wise u64 addition and heavy keys
    decode from the sketch itself at window close
    (hostsketch.engine.np_inv_decode / native hs_inv_decode). There is
    NO candidate table: the admission machinery does not exist for this
    family."""

    cms: np.ndarray       # [P+1, depth, width] uint64, C-contiguous
    keysum: np.ndarray    # [depth, width, key_width] uint64
    keycheck: np.ndarray  # [depth, width] uint64


def host_hh_init(config: HeavyHitterConfig) -> HostHHState:
    planes = len(config.value_cols) + 1  # + count plane
    w = key_width(config)
    return HostHHState(
        cms=np.zeros((planes, config.depth, config.width), np.uint64),
        table_keys=np.full((config.capacity, w), 0xFFFFFFFF, np.uint32),
        table_vals=np.zeros((config.capacity, planes), np.float32),
    )


def host_inv_init(config: HeavyHitterConfig) -> HostInvState:
    planes = len(config.value_cols) + 1  # + count plane
    w = key_width(config)
    return HostInvState(
        cms=np.zeros((planes, config.depth, config.width), np.uint64),
        keysum=np.zeros((config.depth, config.width, w), np.uint64),
        keycheck=np.zeros((config.depth, config.width), np.uint64),
    )


def is_inv_state(state) -> bool:
    """Whether any sketch-state form (HostInvState, the model-facing
    InvState, or a checkpoint/mesh field dict) is an invertible-family
    state — the one dispatch rule every cross-boundary consumer
    (checkpoint restore, mesh codec/merge, sketchwatch) shares."""
    if isinstance(state, dict):
        return "keysum" in state
    return hasattr(state, "keysum")


def is_spread_state(state) -> bool:
    """Whether any sketch-state form (the model-facing SpreadState or a
    checkpoint/mesh field dict) is a flowspread distinct-count state —
    the dispatch rule checkpoint restore and the mesh codec share. Every
    form that leaves a spread model is the canonical one (u8 registers +
    u32 candidate keys: the exact max monoid, like the invertible
    family's u64 planes), whether its open state lives on the device or
    in host numpy (models/spread.py), so there is no layout conversion
    to make here."""
    if isinstance(state, dict):
        return "regs" in state
    return hasattr(state, "regs")


def _cms_to_u64(cms) -> np.ndarray:
    a = np.asarray(cms, dtype=np.float32)
    # fast path: healthy sketches (finite, in [0, 2^64) — every cell the
    # device path produces by construction) convert in ONE pass; NaN/inf
    # comparisons are False, so any pathological cell routes to the
    # clamping slow path below
    lo, hi = a.min(initial=np.float32(0.0)), a.max(initial=np.float32(0.0))
    if np.float32(0.0) <= lo and hi <= _U64_CAP:
        return np.ascontiguousarray(a.astype(np.uint64))
    with np.errstate(invalid="ignore"):
        a = np.nan_to_num(a, nan=0.0, posinf=float(_U64_CAP), neginf=0.0)
        a = np.clip(a, np.float32(0.0), _U64_CAP)
    return np.ascontiguousarray(a.astype(np.uint64))


def frozen_cms(state) -> np.ndarray:
    """The CMS planes of any sketch-state form (device HHState, host
    HostHHState, a checkpoint field-dict, or bare planes) as a FRESH
    uint64 array — the canonical exact-monoid layout every
    cross-boundary consumer shares (the flowmesh codec's merge
    payloads, flowserve's frozen per-key-estimate planes). Always
    copies: callers publish the result to readers that outlive the
    engine's in-place mutation."""
    if isinstance(state, (HostHHState, HostInvState)):
        return state.cms.copy()
    if not isinstance(state, np.ndarray):
        state = state["cms"] if isinstance(state, dict) else state.cms
    a = np.asarray(state)
    if a.dtype == np.uint64:
        # invertible states (and already-frozen payloads) carry exact
        # u64 planes — routing them through the f32 conversion would
        # destroy every cell past 2^24
        return np.ascontiguousarray(a).copy()
    return _cms_to_u64(a)


def _u64_leaf(a) -> np.ndarray:
    """A fresh C-contiguous uint64 copy of an (already-u64) array leaf —
    the invertible planes never round-trip through float."""
    out = np.ascontiguousarray(np.asarray(a), dtype=np.uint64)
    return out.copy() if out is a or not out.flags["OWNDATA"] else out


def from_device_state(state):
    """Import a model-facing state (``HHState``/``InvState``, jax or
    numpy leaves; also accepts the checkpoint loader's field-dict form)
    into engine-owned host buffers. Always copies — the engine mutates
    its state in place and must never alias arrays a LazyWindowTop or
    checkpoint may still read."""
    if is_inv_state(state):
        if isinstance(state, dict):
            cms, ks, kc = state["cms"], state["keysum"], state["keycheck"]
        else:
            cms, ks, kc = state.cms, state.keysum, state.keycheck
        return HostInvState(cms=_u64_leaf(cms), keysum=_u64_leaf(ks),
                            keycheck=_u64_leaf(kc))
    if isinstance(state, dict):  # engine.checkpoint decodes NamedTuples so
        cms, tk, tv = (state["cms"], state["table_keys"],
                       state["table_vals"])
    else:
        cms, tk, tv = state.cms, state.table_keys, state.table_vals
    return HostHHState(
        cms=_cms_to_u64(cms),
        table_keys=np.ascontiguousarray(np.asarray(tk), dtype=np.uint32)
        .copy(),
        table_vals=np.ascontiguousarray(np.asarray(tv), dtype=np.float32)
        .copy(),
    )


def to_device_state(host):
    """Export engine state as a model-facing state with fresh numpy
    leaves (consumed by model.top()/top_lazy(), checkpoints, and a
    backend switch back to the jitted path). Invertible families export
    an ``InvState`` — host-resident u64 by design (there is no f32
    device layout for the key-recovery planes; the exact monoid IS the
    canonical form)."""
    if isinstance(host, HostInvState):
        from ..models.heavy_hitter import InvState

        return InvState(
            cms=host.cms.copy(),
            keysum=host.keysum.copy(),
            keycheck=host.keycheck.copy(),
        )
    return HHState(
        cms=host.cms.astype(np.float32),
        table_keys=host.table_keys.copy(),
        table_vals=host.table_vals.copy(),
    )
