"""flowmesh merge codec: serialized per-window sketch/aggregate state.

Contributions cross the mesh (member -> coordinator) as one framed byte
envelope: a JSON structure tree plus an in-memory ``.npz`` archive of
every array leaf — the same no-pickle split engine.checkpoint uses for
durable snapshots, so a payload is safe to accept from another trust
domain and survives encode -> decode BIT-exactly on the uint64
envelope (dtype + shape + every word preserved; tests/test_mesh.py
round-trips u64 extremes and hostsketch engine state).

The canonical heavy-hitter payload keeps the CMS in **uint64** (the
exact merge monoid — element sums cannot lose counts the way float
addition can), converting device f32 sketches through hostsketch's
proven clamp conversions. Table keys stay uint32, table values float32
(the device accumulation dtype — merging sums them per key, which for
key-hash-sharded streams is a disjoint union and therefore exact).
"""

from __future__ import annotations

import io
import json

import numpy as np

from ..engine.checkpoint import _decode, _encode
from ..families import registry
from ..hostsketch.state import (HostHHState, frozen_cms, is_inv_state)

MAGIC = b"FMSH1\n"


def encode(obj) -> bytes:
    """Nested dicts/lists/tuples/scalars/arrays -> framed bytes."""
    arrays: dict[str, np.ndarray] = {}
    meta = json.dumps(_encode(obj, arrays, "r")).encode()
    buf = io.BytesIO()
    # savez (uncompressed): payloads are hot-path window state, and the
    # arrays (CMS planes) are incompressible counter noise anyway
    np.savez(buf, **arrays)
    return MAGIC + len(meta).to_bytes(8, "little") + meta + buf.getvalue()


def decode(data: bytes):
    """Framed bytes -> the original structure with numpy array leaves."""
    if not data.startswith(MAGIC):
        raise ValueError("not a flowmesh payload (bad magic)")
    off = len(MAGIC)
    meta_len = int.from_bytes(data[off:off + 8], "little")
    off += 8
    meta = json.loads(data[off:off + meta_len].decode())
    blob = data[off + meta_len:]
    arrays = np.load(io.BytesIO(blob)) if blob else {}
    return _decode(meta, arrays)


# ---- model-state capture --------------------------------------------------
#
# One payload shape per model kind, all plain numpy (no jax arrays cross
# the mesh). ``kind`` tags dispatch the coordinator-side merge.


def _u64_plane(a) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(a), dtype=np.uint64).copy()


def hh_payload(state) -> dict:
    """Device/host HHState (or checkpoint field-dict) -> canonical
    uint64-CMS payload. Accepts jax or numpy leaves; always copies
    (frozen_cms is the shared hostsketch export seam).

    Invertible-family states (InvState / HostInvState / field dicts
    with key-recovery planes) ship as ``kind="hh_inv"``: the three u64
    plane sets verbatim — self-contained and LINEAR, so the
    coordinator's merge is a plain element-wise u64 sum (merge_hh
    dispatches on the kind) and there is no extracted table to ship
    until the merged window is decoded at close."""
    if is_inv_state(state):
        if isinstance(state, dict):
            ks, kc = state["keysum"], state["keycheck"]
        else:
            ks, kc = state.keysum, state.keycheck
        return {"kind": "hh_inv", "cms": frozen_cms(state),
                "keysum": _u64_plane(ks), "keycheck": _u64_plane(kc)}
    if isinstance(state, HostHHState):
        return {"kind": "hh", "cms": frozen_cms(state),
                "table_keys": state.table_keys.copy(),
                "table_vals": state.table_vals.copy()}
    if isinstance(state, dict):
        tk, tv = state["table_keys"], state["table_vals"]
    else:
        tk, tv = state.table_keys, state.table_vals
    return {
        "kind": "hh",
        "cms": frozen_cms(state),
        "table_keys": np.ascontiguousarray(np.asarray(tk),
                                           dtype=np.uint32).copy(),
        "table_vals": np.ascontiguousarray(np.asarray(tv),
                                           dtype=np.float32).copy(),
    }


def wagg_payload(store) -> dict:
    """One window's store (models.window_agg.WindowStore) -> columnar
    (keys [G, L] uint32, vals [G, V] uint64) payload: its two arrays,
    the sums copied so that a fold after this does not reach them."""
    keys, vals = store.snapshot()
    return {"kind": "wagg", "keys": keys, "vals": vals}


def dense_payload(totals) -> dict:
    """Dense accumulator planes -> payload (int64: the (lo, hi) int32
    planes sum across members, and int64 headroom makes N-member merge
    overflow a non-issue before renormalization)."""
    return {"kind": "dense",
            "totals": np.asarray(totals).astype(np.int64)}


def spread_payload(state) -> dict:
    """SpreadState (or checkpoint field dict) -> canonical spread
    payload. The u8 register planes are already the exact max-monoid
    canonical form (models/spread.py), so the payload ships them
    verbatim; the candidate table rides as u32 keys + f32 admission
    metric, exactly like the hh table legs."""
    if isinstance(state, dict):
        regs, tk, tm = (state["regs"], state["table_keys"],
                        state["table_metric"])
    else:
        regs, tk, tm = state.regs, state.table_keys, state.table_metric
    return {
        "kind": "spread",
        "regs": np.ascontiguousarray(np.asarray(regs),
                                     dtype=np.uint8).copy(),
        "table_keys": np.ascontiguousarray(np.asarray(tk),
                                           dtype=np.uint32).copy(),
        "table_metric": np.ascontiguousarray(np.asarray(tm),
                                             dtype=np.float32).copy(),
    }


def capture_model(model) -> dict:
    """State payload for one windowed model (the object WindowedHeavyHitter
    wraps): the family registry maps the model's snapshot_kind tag to
    its payload hook and state attribute."""
    kind = getattr(model, "snapshot_kind", None)
    fam = registry.family_for_snapshot(kind) if kind else None
    if fam is None or fam.payload is None or fam.state_attr is None:
        raise TypeError(f"no mesh payload for model kind {kind!r}")
    return registry.hook(fam, "payload")(getattr(model, fam.state_attr))
