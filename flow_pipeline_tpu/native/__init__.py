"""Native (C++) host-path acceleration.

The host hot path — bulk protobuf decode into columnar batches — dominates at
≥1M flows/sec (the reference's analogue is ClickHouse's C++ Kafka/Protobuf
engine, ref: compose/clickhouse/create.sh:5-34). ``libflowdecode.so`` decodes a
length-prefixed FlowMessage stream straight into struct-of-arrays buffers;
this module loads it via ctypes and falls back to pure Python when unbuilt.

Build with ``make native`` once ``native/`` (flowdecode.cc + Makefile) lands;
until then ``available()`` is False and the pure-Python codec is used.
"""

from __future__ import annotations

import ctypes
import os
from dataclasses import dataclass
from typing import Optional

import numpy as np

_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

_HERE = os.path.dirname(os.path.abspath(__file__))
_SEARCH = [
    os.path.join(_HERE, "libflowdecode.so"),
    os.path.join(_HERE, "..", "..", "native", "libflowdecode.so"),
]

# Loader override for instrumented builds (`make -C native san` / `tsan`
# produce libflowdecode_{san,tsan}.so): FLOWDECODE_LIB points the ctypes
# loader at an explicit .so. The override is STRICT — if the named
# library cannot be loaded we raise instead of quietly falling back to
# the regular build, because the only reason to set it is a sanitizer
# run (tools/flowlint/native_stress.py) and a silent fallback would fake
# a clean pass with uninstrumented code.
_LIB_ENV = "FLOWDECODE_LIB"


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    override = os.environ.get(_LIB_ENV)
    if override:
        # raise WITHOUT latching _TRIED: a failed strict override must
        # stay loud on every call — latching would let a caller that
        # swallowed the first error fall through to "no native library"
        # and silently run uninstrumented code with the override set
        if not os.path.exists(override):
            raise RuntimeError(
                f"{_LIB_ENV}={override} does not exist (build it with "
                "`make -C native san` / `tsan`)")
        try:
            lib = ctypes.CDLL(override)
        except OSError as e:
            raise RuntimeError(
                f"{_LIB_ENV}={override} failed to load: {e} (sanitizer "
                "builds need their runtime preloaded — see "
                "tools/flowlint/native_stress.py)") from e
        _LIB = _bind(lib)
        _TRIED = True
        return _LIB
    _TRIED = True
    for path in _SEARCH:
        if os.path.exists(path):
            try:
                lib = ctypes.CDLL(path)
            except OSError:
                continue
            _LIB = _bind(lib)
            break
    return _LIB


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    """Attach the C ABI signatures (shared by the default search path and
    the FLOWDECODE_LIB override)."""
    lib.flow_decode_stream.restype = ctypes.c_longlong
    lib.flow_decode_stream.argtypes = [
        ctypes.c_char_p,
        ctypes.c_longlong,
        ctypes.POINTER(ctypes.c_void_p),  # column buffer pointers
        ctypes.c_longlong,  # capacity (rows)
    ]
    lib.flow_count_frames.restype = ctypes.c_longlong
    lib.flow_count_frames.argtypes = [ctypes.c_char_p, ctypes.c_longlong]
    lib.flow_encode_stream.restype = ctypes.c_longlong
    lib.flow_encode_stream.argtypes = [
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.c_longlong,
        ctypes.c_char_p,
        ctypes.c_longlong,
    ]
    if hasattr(lib, "flow_hash_group"):  # pre-r6 .so lacks it
        lib.flow_hash_group.restype = ctypes.c_longlong
        lib.flow_hash_group.argtypes = [
            ctypes.c_void_p,  # [n, w] uint32 lanes
            ctypes.c_longlong,
            ctypes.c_longlong,
            ctypes.c_void_p,  # [n] int32 perm out
            ctypes.c_void_p,  # [n] int32 starts out
            ctypes.POINTER(ctypes.c_int32),  # collided out
            ctypes.c_void_p,  # [FF_STATS_LEN] int64 stats (NULL = off)
        ]
    if hasattr(lib, "flow_hash_group_mt"):  # pre-r19 .so lacks it
        lib.flow_hash_group_mt.restype = ctypes.c_longlong
        lib.flow_hash_group_mt.argtypes = [
            ctypes.c_void_p,  # [n, w] uint32 lanes
            ctypes.c_longlong,
            ctypes.c_longlong,
            ctypes.c_void_p,  # [n] int32 perm out
            ctypes.c_void_p,  # [n] int32 starts out
            ctypes.POINTER(ctypes.c_int32),  # collided out
            ctypes.c_int,     # threads
            ctypes.c_void_p,  # [FF_STATS_LEN] int64 stats (NULL = off)
        ]
    if hasattr(lib, "hs_cms_update"):  # pre-r8 .so lacks the sketch engine
        lib.hs_cms_update.restype = ctypes.c_longlong
        lib.hs_cms_update.argtypes = [
            ctypes.c_void_p,  # [P, D, W] uint64 sketch (in place)
            ctypes.c_longlong, ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # [n, kw] uint32 keys
            ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # [n, P] float32 addends
            ctypes.c_void_p,  # [n] uint8 valid (NULL = all)
            ctypes.c_int,     # conservative
            ctypes.c_int,     # threads
            ctypes.c_void_p,  # [FF_STATS_LEN] int64 stats (NULL = off)
        ]
        lib.hs_cms_query.restype = ctypes.c_longlong
        lib.hs_cms_query.argtypes = [
            ctypes.c_void_p,  # [P, D, W] uint64 sketch
            ctypes.c_longlong, ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # [n, kw] uint32 keys
            ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # [n, P] float32 out
            ctypes.c_int,     # threads
            ctypes.c_void_p,  # [FF_STATS_LEN] int64 stats (NULL = off)
        ]
        lib.hs_hh_prefilter.restype = ctypes.c_longlong
        lib.hs_hh_prefilter.argtypes = [
            ctypes.c_void_p,  # [cap, kw] uint32 table keys
            ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # [n, kw] uint32 candidate keys
            ctypes.c_void_p,  # [n, P] float32 sums (plane 0 ranks)
            ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # [2*cap] int32 selection out
            ctypes.c_int,     # threads
            ctypes.c_void_p,  # [FF_STATS_LEN] int64 stats (NULL = off)
        ]
        lib.hs_topk_merge.restype = ctypes.c_longlong
        lib.hs_topk_merge.argtypes = [
            ctypes.c_void_p,  # [cap, kw] uint32 table keys (in place)
            ctypes.c_void_p,  # [cap, P] float32 table vals (in place)
            ctypes.c_longlong, ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # [n, kw] uint32 candidate keys
            ctypes.c_void_p,  # [n, P] float32 batch sums
            ctypes.c_void_p,  # [n, P] float32 CMS estimates
            ctypes.c_void_p,  # [n] uint8 valid (NULL = all)
            ctypes.c_longlong,
            ctypes.c_void_p,  # [FF_STATS_LEN] int64 stats (NULL = off)
        ]
    if hasattr(lib, "hs_inv_update"):  # pre-r16 .so lacks the invertible
        lib.hs_inv_update.restype = ctypes.c_longlong
        lib.hs_inv_update.argtypes = [
            ctypes.c_void_p,  # [P, D, W] uint64 count/value planes (in place)
            ctypes.c_longlong, ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # [D, W, kw] uint64 keysum planes (in place)
            ctypes.c_void_p,  # [D, W] uint64 checksum plane (in place)
            ctypes.c_void_p,  # [n, kw] uint32 keys
            ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # [n, P] float32 addends (count plane last)
            ctypes.c_void_p,  # [n] uint8 valid (NULL = all)
            ctypes.c_int,     # threads
            ctypes.c_void_p,  # [FF_STATS_LEN] int64 stats (NULL = off)
        ]
        lib.hs_inv_decode.restype = ctypes.c_longlong
        lib.hs_inv_decode.argtypes = [
            ctypes.c_void_p,  # [P, D, W] uint64 count/value planes
            ctypes.c_longlong, ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # [D, W, kw] uint64 keysum planes
            ctypes.c_void_p,  # [D, W] uint64 checksum plane
            ctypes.c_longlong,
            ctypes.c_void_p,  # [D*W, kw] uint32 decoded keys out
            ctypes.c_void_p,  # [D*W, P] uint64 decoded sums out
            ctypes.c_void_p,  # [FF_STATS_LEN] int64 stats (NULL = off)
        ]
    if hasattr(lib, "hs_spread_update"):  # pre-r21 .so lacks flowspread
        lib.hs_spread_update.restype = ctypes.c_longlong
        lib.hs_spread_update.argtypes = [
            ctypes.c_void_p,  # [D, W, m] uint8 register planes (in place)
            ctypes.c_longlong, ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # [n, kw] uint32 key lanes
            ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # [n, ew] uint32 element lanes
            ctypes.c_longlong,
            ctypes.c_void_p,  # [n] uint8 valid (NULL = all)
            ctypes.c_int,     # threads
            ctypes.c_void_p,  # [FF_STATS_LEN] int64 stats (NULL = off)
        ]
    if hasattr(lib, "ff_group_sum"):  # pre-r10 .so lacks the fused plane
        lib.ff_group_sum.restype = ctypes.c_longlong
        lib.ff_group_sum.argtypes = [
            ctypes.c_void_p,  # [n, w] uint32 lanes
            ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # [n, p] uint64 value planes
            ctypes.c_longlong,
            ctypes.c_void_p,  # [n, w] uint32 uniq out
            ctypes.c_void_p,  # [n, p] uint64 sums out
            ctypes.c_void_p,  # [n] int64 counts out
            ctypes.c_void_p,  # [FF_STATS_LEN] int64 stats (NULL = off)
        ]
    if hasattr(lib, "ff_group_sum_mt"):  # pre-r19 .so lacks it
        lib.ff_group_sum_mt.restype = ctypes.c_longlong
        lib.ff_group_sum_mt.argtypes = [
            ctypes.c_void_p,  # [n, w] uint32 lanes
            ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # [n, p] uint64 value planes
            ctypes.c_longlong,
            ctypes.c_void_p,  # [n, w] uint32 uniq out
            ctypes.c_void_p,  # [n, p] uint64 sums out
            ctypes.c_void_p,  # [n] int64 counts out
            ctypes.c_int,     # threads
            ctypes.c_void_p,  # [FF_STATS_LEN] int64 stats (NULL = off)
        ]
    if hasattr(lib, "ff_build_lanes"):  # pre-r19 .so lacks lane building
        lib.ff_build_lanes.restype = ctypes.c_longlong
        lib.ff_build_lanes.argtypes = [
            ctypes.POINTER(ctypes.c_void_p),  # [ncols] column buffers
            ctypes.c_void_p,  # [ncols] uint8 is64
            ctypes.c_void_p,  # [ncols] int64 widths (1 or 4)
            ctypes.c_void_p,  # [ncols] uint32 slot mods (NULL = none)
            ctypes.c_longlong, ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # [n, wtotal] uint32 lanes out
            ctypes.c_int,     # threads
            ctypes.c_void_p,  # [FF_STATS_LEN] int64 stats (NULL = off)
        ]
        lib.ff_build_planes.restype = ctypes.c_longlong
        lib.ff_build_planes.argtypes = [
            ctypes.POINTER(ctypes.c_void_p),  # [p] scalar column buffers
            ctypes.c_void_p,  # [p] uint8 is64
            ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # scale column (NULL = none; f32 mode only)
            ctypes.c_int,     # scale_is64
            ctypes.c_void_p,  # [n, p] float32 out (XOR with out_u64)
            ctypes.c_void_p,  # [n, p] uint64 out (the wagg layout)
            ctypes.c_int,     # threads
            ctypes.c_void_p,  # [FF_STATS_LEN] int64 stats (NULL = off)
        ]
    if hasattr(lib, "ff_fused_update"):
        lib.ff_fused_update.restype = ctypes.c_longlong
        lib.ff_fused_update.argtypes = [
            ctypes.c_void_p,  # [n, w] uint32 root lanes
            ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # [n, p] float32 value planes
            ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # [nf] int64 parent
            ctypes.c_void_p,  # [sel_off[nf]] int64 child lane selections
            ctypes.c_void_p,  # [nf+1] int64 sel offsets
            ctypes.c_void_p,  # [nf] int64 depth
            ctypes.c_void_p,  # [nf] int64 width
            ctypes.c_void_p,  # [nf] int64 capacity
            ctypes.c_void_p,  # [nf] uint8 conservative
            ctypes.c_void_p,  # [nf] uint8 prefilter
            ctypes.c_void_p,  # [nf] uint8 admission==plain
            ctypes.POINTER(ctypes.c_void_p),  # [nf] cms buffers
            ctypes.POINTER(ctypes.c_void_p),  # [nf] table key buffers
            ctypes.POINTER(ctypes.c_void_p),  # [nf] table val buffers
            ctypes.c_int,     # do_sketch
            ctypes.c_longlong,  # ddos parent family (-1 = none)
            ctypes.c_void_p,  # [ddos_sel_w] int64 ddos lane selection
            ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_void_p,  # [n, ddos_sel_w] uint32 ddos keys out
            ctypes.c_void_p,  # [n] float32 ddos sums out
            ctypes.c_int,     # threads
            ctypes.c_void_p,  # [FF_STATS_LEN] int64 stats (NULL = off)
            # r16 invertible trailer (safe past a pre-r16 .so: extra
            # cdecl args are ignored, and invertible trees are gated on
            # the hs_inv_update export which only r16+ builds carry)
            ctypes.c_void_p,  # [nf] uint8 invertible flags (NULL = none)
            ctypes.POINTER(ctypes.c_void_p),  # [nf] keysum buffers
            ctypes.POINTER(ctypes.c_void_p),  # [nf] keycheck buffers
        ]
    return lib


def available() -> bool:
    return _load() is not None


# ---- flowtrace phase counters ----------------------------------------------
#
# Every groupby/sketch kernel takes an optional trailing int64 stats
# buffer it ACCUMULATES per-phase wall nanoseconds and row/group counts
# into — the in-kernel attribution that the single-pass fused dataplane
# erased from the Python-side stage timers. Slot layout mirrors the
# FF_STAT_* enum in native/ffstat.h (the C side is authoritative;
# tests/test_flowtrace.py pins the two in sync via behavior).
FF_STATS_LEN = 16
FF_STAT_SLOTS = {
    "radix": 0,      # LSD radix passes incl. the row-hash pass (ns)
    "refine": 1,     # run refinement + group boundary scan (ns)
    "regroup": 2,    # cascade regroup: gather + group + fold (ns)
    "cms": 3,        # hs_cms_update (ns)
    "prefilter": 4,  # hs_hh_prefilter (ns)
    "topk": 5,       # hs_cms_query (admission est) + hs_topk_merge (ns)
    "fold": 6,       # root group-table accumulation (ns)
    "inv": 10,       # hs_inv_update / hs_inv_decode (the invertible
                     # family's whole sketch fold — no admission phases)
    "lanes": 11,     # ff_build_lanes / ff_build_planes: native lane
                     # building off the decoded columns (r19 flowspeed)
    "spread": 12,    # hs_spread_update (the flowspread distinct-count
                     # family's register fold — r21)
}
FF_STAT_PHASES = tuple(FF_STAT_SLOTS)  # ns-valued phase slots, in order
FF_STAT_ROWS = 7
FF_STAT_GROUPS = 8
FF_STAT_RADIX_PASSES = 9


def new_stats() -> np.ndarray:
    """A zeroed stats buffer kernels accumulate into (reusable across
    calls — callers zero or diff it themselves)."""
    return np.zeros(FF_STATS_LEN, np.int64)


def _stats_ptr(stats):
    """Validated ctypes arg for an optional stats buffer."""
    if stats is None:
        return None
    assert stats.dtype == np.int64 and stats.flags["C_CONTIGUOUS"] \
        and stats.shape == (FF_STATS_LEN,)
    return _c_arr(stats)


# Feature -> witness symbol: the capability surface operators and the
# degradation report key off. Each entry marks an .so generation (r1
# decode, r6 group, r8 sketch, r10 fused) — a stale build silently
# lacking the newer symbols is exactly what missing_features() exists
# to make loud (gauge + startup warning, engine/hostfused.py).
_FEATURE_SYMBOLS = {
    "decode": "flow_decode_stream",
    "group": "flow_hash_group",
    "sketch": "hs_cms_update",
    "fused": "ff_fused_update",
    "invsketch": "hs_inv_update",
    # r19 flowspeed: native lane building off the decoded columns +
    # the threaded groupby (one .so generation — witness either)
    "lanes": "ff_build_lanes",
    # r21 flowspread: the distinct-count register fold
    "spread": "hs_spread_update",
}


def capabilities() -> dict:
    """Per-feature availability of the loaded library ({} keys always
    present; all False when no library loads at all)."""
    lib = _load()
    return {feat: bool(lib is not None and hasattr(lib, sym))
            for feat, sym in _FEATURE_SYMBOLS.items()}


def missing_features() -> list[str]:
    """Features the loaded (or absent) library cannot serve — what a
    startup banner should name before any fallback quietly engages."""
    return [feat for feat, ok in capabilities().items() if not ok]


# Column order shared with native/flowdecode.cc — scalar uint32 columns in
# schema order, then the three [N,4] address columns.
def _column_order():
    from ..schema.batch import COLUMNS, ADDR_COLUMNS

    return list(COLUMNS), list(ADDR_COLUMNS)


def decode_stream(data: bytes, capacity_hint: int = 0):
    """Decode length-prefixed FlowMessage frames into a FlowBatch using the
    native library. Raises RuntimeError if the library is not built."""
    from ..schema.batch import FlowBatch

    lib = _load()
    if lib is None:
        raise RuntimeError("libflowdecode.so not built; run `make native`")
    # Exact row count via a cheap native scan of the length prefixes (a frame
    # can be as small as 1 byte — an all-default message).
    cap = capacity_hint or max(1, int(lib.flow_count_frames(data, len(data))))
    batch = FlowBatch.empty(cap)
    scalar_names, addr_names = _column_order()
    ptrs = (ctypes.c_void_p * (len(scalar_names) + len(addr_names)))()
    for i, name in enumerate(scalar_names + addr_names):
        arr = batch.columns[name]
        assert arr.flags["C_CONTIGUOUS"]
        ptrs[i] = arr.ctypes.data_as(ctypes.c_void_p).value
    n = lib.flow_decode_stream(data, len(data), ptrs, cap)
    if n < 0:
        raise ValueError(f"native decode failed at frame {-n - 1}")
    return batch.slice(0, int(n))


def group_available() -> bool:
    """Whether the loaded library exports the hash-group kernel (an .so
    built before r6 decodes fine but cannot group)."""
    lib = _load()
    return lib is not None and hasattr(lib, "flow_hash_group")


def hash_group(lanes: np.ndarray, stats: Optional[np.ndarray] = None,
               threads: int = 1):
    """Native hash-grouping of [N, W] uint32 key lanes.

    Computes the same 64-bit row hash as ops.hostgroup.hash_u64, radix-
    sorts it, and verifies lane equality within each hash group in one
    C pass. Returns (perm [N] int32, starts [G] int32, collided bool) —
    identical contract (and identical group order) to the numpy path, so
    callers can switch per batch. ``threads`` > 1 routes through the
    r19 flow_hash_group_mt kernel (per-key-range partitioning,
    per-partition stable sort) whose output is BIT-IDENTICAL to the
    serial kernel at any thread count; a pre-r19 library quietly serves
    the serial path. Raises RuntimeError when the library is missing or
    too old (callers gate on group_available())."""
    lib = _load()
    if lib is None or not hasattr(lib, "flow_hash_group"):
        raise RuntimeError("libflowdecode.so missing flow_hash_group; "
                           "run `make native`")
    lanes = np.ascontiguousarray(lanes, dtype=np.uint32)
    n, w = lanes.shape
    perm = np.empty(n, np.int32)
    starts = np.empty(max(n, 1), np.int32)
    collided = ctypes.c_int32(0)
    if threads > 1 and hasattr(lib, "flow_hash_group_mt"):
        g = lib.flow_hash_group_mt(
            lanes.ctypes.data_as(ctypes.c_void_p), n, w,
            perm.ctypes.data_as(ctypes.c_void_p),
            starts.ctypes.data_as(ctypes.c_void_p),
            ctypes.byref(collided), int(threads),
            _stats_ptr(stats),
        )
    else:
        g = lib.flow_hash_group(
            lanes.ctypes.data_as(ctypes.c_void_p), n, w,
            perm.ctypes.data_as(ctypes.c_void_p),
            starts.ctypes.data_as(ctypes.c_void_p),
            ctypes.byref(collided),
            _stats_ptr(stats),
        )
    if g < 0:
        raise ValueError("flow_hash_group failed (batch too large?)")
    return perm, starts[:g], bool(collided.value)


def sketch_available() -> bool:
    """Whether the loaded library exports the hostsketch engine (an .so
    built before r8 decodes and groups fine but cannot sketch)."""
    lib = _load()
    return lib is not None and hasattr(lib, "hs_cms_update")


def _c_arr(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.c_void_p)


def hs_cms_update(cms: np.ndarray, keys: np.ndarray, vals: np.ndarray,
                  valid, conservative: bool, threads: int = 1,
                  stats: Optional[np.ndarray] = None) -> None:
    """Native uint64 CMS update (plain or conservative) in place.

    cms [P, D, W] uint64 C-contiguous; keys [n, kw] uint32; vals [n, P]
    float32; valid [n] bool or None. Deterministic for any thread count
    (see native/hostsketch.cc). Raises on degenerate shapes."""
    lib = _load()
    if lib is None or not hasattr(lib, "hs_cms_update"):
        raise RuntimeError("libflowdecode.so missing hostsketch engine; "
                           "run `make native`")
    assert cms.dtype == np.uint64 and cms.flags["C_CONTIGUOUS"]
    p, d, w = cms.shape
    keys = np.ascontiguousarray(keys, dtype=np.uint32)
    vals = np.ascontiguousarray(vals, dtype=np.float32)
    n, kw = keys.shape
    vptr = None
    if valid is not None:
        valid = np.ascontiguousarray(valid, dtype=np.uint8)
        vptr = _c_arr(valid)
    rc = lib.hs_cms_update(_c_arr(cms), p, d, w, _c_arr(keys), n, kw,
                           _c_arr(vals), vptr, int(bool(conservative)),
                           int(threads), _stats_ptr(stats))
    if rc != 0:
        raise ValueError(f"hs_cms_update failed (rc={rc}): degenerate "
                         f"shape planes={p} depth={d} width={w}")


def hs_cms_query(cms: np.ndarray, keys: np.ndarray, threads: int = 1,
                 stats: Optional[np.ndarray] = None) -> np.ndarray:
    """Native CMS point query: [n, P] float32 min-over-depth estimates."""
    lib = _load()
    if lib is None or not hasattr(lib, "hs_cms_query"):
        raise RuntimeError("libflowdecode.so missing hostsketch engine; "
                           "run `make native`")
    assert cms.dtype == np.uint64 and cms.flags["C_CONTIGUOUS"]
    p, d, w = cms.shape
    keys = np.ascontiguousarray(keys, dtype=np.uint32)
    n, kw = keys.shape
    out = np.empty((n, p), np.float32)
    rc = lib.hs_cms_query(_c_arr(cms), p, d, w, _c_arr(keys), n, kw,
                          _c_arr(out), int(threads), _stats_ptr(stats))
    if rc != 0:
        raise ValueError(f"hs_cms_query failed (rc={rc})")
    return out


def hs_hh_prefilter(table_keys: np.ndarray, cand_keys: np.ndarray,
                    cand_sums: np.ndarray, threads: int = 1,
                    stats: Optional[np.ndarray] = None) -> np.ndarray:
    """Native table-aware candidate prefilter: selected row indices in
    (metric desc, index asc) order — lax.top_k's tie-break. Returns
    [min(n, 2*cap)] int32."""
    lib = _load()
    if lib is None or not hasattr(lib, "hs_hh_prefilter"):
        raise RuntimeError("libflowdecode.so missing hostsketch engine; "
                           "run `make native`")
    table_keys = np.ascontiguousarray(table_keys, dtype=np.uint32)
    cand_keys = np.ascontiguousarray(cand_keys, dtype=np.uint32)
    cand_sums = np.ascontiguousarray(cand_sums, dtype=np.float32)
    cap, kw = table_keys.shape
    n, planes = cand_sums.shape
    sel = np.empty(2 * cap, np.int32)
    m = lib.hs_hh_prefilter(_c_arr(table_keys), cap, kw, _c_arr(cand_keys),
                            _c_arr(cand_sums), n, planes, _c_arr(sel),
                            int(threads), _stats_ptr(stats))
    if m < 0:
        raise ValueError(f"hs_hh_prefilter failed (rc={m})")
    return sel[:m]


def hs_topk_merge(table_keys: np.ndarray, table_vals: np.ndarray,
                  cand_keys: np.ndarray, cand_sums: np.ndarray,
                  cand_est: np.ndarray, valid,
                  stats: Optional[np.ndarray] = None) -> int:
    """Native space-saving admission merge, in place on the table buffers
    (ops.topk.topk_merge_est semantics — pass cand_est=cand_sums for the
    'plain' batch-sum merge). Returns the number of real rows."""
    lib = _load()
    if lib is None or not hasattr(lib, "hs_topk_merge"):
        raise RuntimeError("libflowdecode.so missing hostsketch engine; "
                           "run `make native`")
    assert table_keys.dtype == np.uint32 and \
        table_keys.flags["C_CONTIGUOUS"]
    assert table_vals.dtype == np.float32 and \
        table_vals.flags["C_CONTIGUOUS"]
    cap, kw = table_keys.shape
    planes = table_vals.shape[1]
    cand_keys = np.ascontiguousarray(cand_keys, dtype=np.uint32)
    cand_sums = np.ascontiguousarray(cand_sums, dtype=np.float32)
    cand_est = np.ascontiguousarray(cand_est, dtype=np.float32)
    n = cand_keys.shape[0]
    vptr = None
    if valid is not None:
        valid = np.ascontiguousarray(valid, dtype=np.uint8)
        vptr = _c_arr(valid)
    rc = lib.hs_topk_merge(_c_arr(table_keys), _c_arr(table_vals),
                           cap, kw, planes, _c_arr(cand_keys),
                           _c_arr(cand_sums), _c_arr(cand_est), vptr, n,
                           _stats_ptr(stats))
    if rc < 0:
        raise ValueError(f"hs_topk_merge failed (rc={rc}): degenerate "
                         f"shape cap={cap} kw={kw} planes={planes}")
    return int(rc)


def inv_available() -> bool:
    """Whether the loaded library exports the invertible sketch kernels
    (an .so built before r16 serves the table family fine but cannot
    run -hh.sketch=invertible natively)."""
    lib = _load()
    return lib is not None and hasattr(lib, "hs_inv_update")


def hs_inv_update(cms: np.ndarray, keysum: np.ndarray,
                  keycheck: np.ndarray, keys: np.ndarray,
                  vals: np.ndarray, valid, threads: int = 1,
                  stats: Optional[np.ndarray] = None) -> None:
    """Native invertible-sketch update in place — one pure per-bucket
    fold (u64 count/value planes + key-recovery planes), no admission
    machinery. cms [P, D, W] u64; keysum [D, W, kw] u64; keycheck
    [D, W] u64; keys [n, kw] u32; vals [n, P] f32 (count plane LAST).
    Deterministic for any thread count (plain wrap adds are order-free;
    see native/hostsketch.cc). Raises on degenerate shapes."""
    lib = _load()
    if lib is None or not hasattr(lib, "hs_inv_update"):
        raise RuntimeError("libflowdecode.so missing the invertible "
                           "sketch kernels; run `make native`")
    assert cms.dtype == np.uint64 and cms.flags["C_CONTIGUOUS"]
    assert keysum.dtype == np.uint64 and keysum.flags["C_CONTIGUOUS"]
    assert keycheck.dtype == np.uint64 and keycheck.flags["C_CONTIGUOUS"]
    p, d, w = cms.shape
    keys = np.ascontiguousarray(keys, dtype=np.uint32)
    vals = np.ascontiguousarray(vals, dtype=np.float32)
    n, kw = keys.shape
    assert keysum.shape == (d, w, kw) and keycheck.shape == (d, w)
    vptr = None
    if valid is not None:
        valid = np.ascontiguousarray(valid, dtype=np.uint8)
        vptr = _c_arr(valid)
    rc = lib.hs_inv_update(_c_arr(cms), p, d, w, _c_arr(keysum),
                           _c_arr(keycheck), _c_arr(keys), n, kw,
                           _c_arr(vals), vptr, int(threads),
                           _stats_ptr(stats))
    if rc != 0:
        raise ValueError(f"hs_inv_update failed (rc={rc}): degenerate "
                         f"shape planes={p} depth={d} width={w} kw={kw}")


def hs_inv_decode(cms: np.ndarray, keysum: np.ndarray,
                  keycheck: np.ndarray,
                  stats: Optional[np.ndarray] = None):
    """Native heavy-key recovery from an invertible sketch (IBLT-style
    peel over pure buckets; inputs read-only). Returns (keys [K, kw]
    u32, vals [K, P] u64) in the kernel's peel order — callers
    canonicalize (hostsketch.engine lex-sorts before ranking)."""
    lib = _load()
    if lib is None or not hasattr(lib, "hs_inv_decode"):
        raise RuntimeError("libflowdecode.so missing the invertible "
                           "sketch kernels; run `make native`")
    assert cms.dtype == np.uint64 and cms.flags["C_CONTIGUOUS"]
    assert keysum.dtype == np.uint64 and keysum.flags["C_CONTIGUOUS"]
    assert keycheck.dtype == np.uint64 and keycheck.flags["C_CONTIGUOUS"]
    p, d, w = cms.shape
    kw = keysum.shape[2]
    assert keysum.shape == (d, w, kw) and keycheck.shape == (d, w)
    keys_out = np.empty((d * w, kw), np.uint32)
    vals_out = np.empty((d * w, p), np.uint64)
    n = lib.hs_inv_decode(_c_arr(cms), p, d, w, _c_arr(keysum),
                          _c_arr(keycheck), kw, _c_arr(keys_out),
                          _c_arr(vals_out), _stats_ptr(stats))
    if n < 0:
        raise ValueError(f"hs_inv_decode failed (rc={n})")
    n = int(n)
    return keys_out[:n], vals_out[:n]


def spread_available() -> bool:
    """Whether the loaded library exports the flowspread register fold
    (an .so built before r21 serves every other family fine but cannot
    run -spread.* natively — the numpy twin serves, bit-identically)."""
    lib = _load()
    return lib is not None and hasattr(lib, "hs_spread_update")


def hs_spread_update(regs: np.ndarray, keys: np.ndarray,
                     elems: np.ndarray, threads: int = 1,
                     stats: Optional[np.ndarray] = None,
                     valid=None) -> None:
    """Native distinct-count register update in place — the threaded
    twin of hostsketch.engine.np_spread_update (u8 scatter-max over
    per-depth-owned register blocks; deterministic at any thread count
    since max is order-free — see native/hostsketch.cc). regs [D, W, m]
    u8 C-contiguous; keys [n, kw] u32; elems [n, ew] u32. Raises on
    degenerate shapes."""
    lib = _load()
    if lib is None or not hasattr(lib, "hs_spread_update"):
        raise RuntimeError("libflowdecode.so missing the flowspread "
                           "kernel; run `make native`")
    assert regs.dtype == np.uint8 and regs.flags["C_CONTIGUOUS"]
    d, w, m = regs.shape
    keys = np.ascontiguousarray(keys, dtype=np.uint32)
    elems = np.ascontiguousarray(elems, dtype=np.uint32)
    n, kw = keys.shape
    ew = elems.shape[1]
    assert elems.shape[0] == n
    vptr = None
    if valid is not None:
        valid = np.ascontiguousarray(valid, dtype=np.uint8)
        vptr = _c_arr(valid)
    rc = lib.hs_spread_update(_c_arr(regs), d, w, m, _c_arr(keys), n, kw,
                              _c_arr(elems), ew, vptr, int(threads),
                              _stats_ptr(stats))
    if rc != 0:
        raise ValueError(f"hs_spread_update failed (rc={rc}): degenerate "
                         f"shape depth={d} width={w} m={m} kw={kw} ew={ew}")


def fused_available() -> bool:
    """Whether the loaded library exports the fused dataplane (an .so
    built before r10 decodes, groups and sketches fine but cannot run
    the single-pass group->cascade->sketch update)."""
    lib = _load()
    return lib is not None and hasattr(lib, "ff_fused_update")


def group_sum(lanes: np.ndarray, vals: np.ndarray,
              stats: Optional[np.ndarray] = None, threads: int = 1):
    """Single-pass exact groupby-sum (ff_group_sum): the native twin of
    ops.hostgroup.group_by_key(exact=True) over integer planes.

    lanes [n, w] uint32; vals [n, p] uint64. Returns (uniq [G, w] u32,
    sums [G, p] u64, counts [G] i64), or None on a 64-bit hash collision
    between distinct key rows — the caller re-groups lexicographically,
    the same contract the numpy path honors. ``threads`` > 1 rides the
    r19 ff_group_sum_mt kernel (threaded grouping + per-group-range u64
    fold — exact integer sums, bit-identical at any thread count); a
    pre-r19 library quietly serves the serial kernel."""
    lib = _load()
    if lib is None or not hasattr(lib, "ff_group_sum"):
        raise RuntimeError("libflowdecode.so missing the fused dataplane; "
                           "run `make native`")
    lanes = np.ascontiguousarray(lanes, dtype=np.uint32)
    vals = np.ascontiguousarray(vals, dtype=np.uint64)
    n, w = lanes.shape
    p = vals.shape[1]
    if vals.shape[0] != n:
        # C iterates vals by lane row count — a shorter vals would read
        # out of bounds, and no rc can report it after the fact
        raise ValueError(f"lanes rows ({n}) != vals rows "
                         f"({vals.shape[0]})")
    uniq = np.empty((n, w), np.uint32)
    sums = np.empty((n, p), np.uint64)
    counts = np.empty(max(n, 1), np.int64)
    if threads > 1 and hasattr(lib, "ff_group_sum_mt"):
        g = lib.ff_group_sum_mt(_c_arr(lanes), n, w, _c_arr(vals), p,
                                _c_arr(uniq), _c_arr(sums),
                                _c_arr(counts), int(threads),
                                _stats_ptr(stats))
    else:
        g = lib.ff_group_sum(_c_arr(lanes), n, w, _c_arr(vals), p,
                             _c_arr(uniq), _c_arr(sums), _c_arr(counts),
                             _stats_ptr(stats))
    if g == -2:
        return None  # 64-bit collision: caller takes the exact fallback
    if g < 0:
        raise ValueError(f"ff_group_sum failed (rc={g})")
    g = int(g)
    return uniq[:g], sums[:g], counts[:g]


# ---- native lane building off the decoded columns (r19 flowspeed) ----------


def lanes_available() -> bool:
    """Whether the loaded library exports the lane-building kernels (an
    .so built before r19 runs the fused dataplane fine but builds its
    lanes in numpy — engine/hostfused.py's bit-exact twins)."""
    lib = _load()
    return lib is not None and hasattr(lib, "ff_build_lanes")


def _lane_cols(columns):
    """(ptr array, is64, widths, contiguous keepalives) for a list of
    decoded columns — [n] u32 / [n] u64 scalars or [n, 4] u32 words."""
    keep = []
    ptrs = (ctypes.c_void_p * len(columns))()
    is64 = np.zeros(len(columns), np.uint8)
    widths = np.empty(len(columns), np.int64)
    for i, col in enumerate(columns):
        a = np.ascontiguousarray(col)
        if a.ndim == 2:
            if a.shape[1] != 4 or a.dtype != np.uint32:
                raise ValueError(
                    f"column {i}: 2-D lanes must be [n, 4] uint32, got "
                    f"{a.shape} {a.dtype}")
            widths[i] = 4
        elif a.dtype == np.uint64:
            is64[i] = 1
            widths[i] = 1
        else:
            a = np.ascontiguousarray(a, dtype=np.uint32)
            widths[i] = 1
        keep.append(a)
        ptrs[i] = a.ctypes.data_as(ctypes.c_void_p).value
    # must hold even under python -O: the C kernels read cols[c][r] for
    # every r < n taken from column 0 — a shorter column would be read
    # past its end (heap overread), not caught
    for i, a in enumerate(keep[1:], start=1):
        if a.shape[0] != keep[0].shape[0]:
            raise ValueError(
                f"column {i}: {a.shape[0]} rows, column 0 has "
                f"{keep[0].shape[0]} — all columns must share n")
    return ptrs, is64, widths, keep


def build_lanes(columns, mods=None, threads: int = 1,
                stats: Optional[np.ndarray] = None) -> np.ndarray:
    """[n, W] uint32 key lanes built natively off decoded columns — the
    C twin of engine/hostfused.py _key_lanes_into (u64 saturation, [n,4]
    address words copied through, optional per-column slot transform
    ``v - v % mods[i]`` for the wagg slot lane). Raises RuntimeError on
    a pre-r19 library (callers gate on lanes_available())."""
    lib = _load()
    if lib is None or not hasattr(lib, "ff_build_lanes"):
        raise RuntimeError("libflowdecode.so missing the lane-building "
                           "kernels; run `make native`")
    ptrs, is64, widths, keep = _lane_cols(columns)
    n = keep[0].shape[0]
    wtotal = int(widths.sum())
    out = np.empty((n, wtotal), np.uint32)
    mods_arr = None
    if mods is not None:
        mods_arr = np.ascontiguousarray(mods, dtype=np.uint32)
        if mods_arr.shape != (len(columns),):
            # must hold even under python -O: a short mods array would
            # send ff_build_lanes reading past its end
            raise ValueError(
                f"mods must have one entry per column "
                f"({len(columns)}), got shape {mods_arr.shape}")
    rc = lib.ff_build_lanes(
        ptrs, _c_arr(is64), _c_arr(widths),
        _c_arr(mods_arr) if mods_arr is not None else None,
        len(keep), n, wtotal, _c_arr(out), int(threads),
        _stats_ptr(stats))
    del keep
    if rc != 0:
        raise ValueError(f"ff_build_lanes failed (rc={rc})")
    return out


def build_planes_f32(columns, scale=None, threads: int = 1,
                     stats: Optional[np.ndarray] = None) -> np.ndarray:
    """[n, P] float32 value planes built natively — the C twin of
    _value_planes_np (u32 saturation, u32->f32 cast, one f32 multiply
    by max(scale, 1) per cell)."""
    lib = _load()
    if lib is None or not hasattr(lib, "ff_build_planes"):
        raise RuntimeError("libflowdecode.so missing the lane-building "
                           "kernels; run `make native`")
    ptrs, is64, widths, keep = _lane_cols(columns)
    if (widths != 1).any():
        raise ValueError("value planes take scalar columns only")
    n = keep[0].shape[0]
    out = np.empty((n, len(keep)), np.float32)
    sptr = None
    s64 = 0
    if scale is not None:
        s = np.ascontiguousarray(scale)
        if s.dtype == np.uint64:
            s64 = 1
        else:
            s = np.ascontiguousarray(s, dtype=np.uint32)
        if s.shape[0] != n:
            # same overread class as the mods/column checks above
            raise ValueError(
                f"scale has {s.shape[0]} rows, columns have {n}")
        keep.append(s)
        sptr = _c_arr(s)
    rc = lib.ff_build_planes(ptrs, _c_arr(is64), len(is64), n, sptr,
                             s64, _c_arr(out), None, int(threads),
                             _stats_ptr(stats))
    del keep
    if rc != 0:
        raise ValueError(f"ff_build_planes failed (rc={rc})")
    return out


def build_planes_u64(columns, threads: int = 1,
                     stats: Optional[np.ndarray] = None) -> np.ndarray:
    """[n, P] uint64 value planes saturated at U32_MAX — the C twin of
    _wagg_rows' ``np.minimum(col, U32_MAX)`` plane stack (the exact
    flows_5m substrate)."""
    lib = _load()
    if lib is None or not hasattr(lib, "ff_build_planes"):
        raise RuntimeError("libflowdecode.so missing the lane-building "
                           "kernels; run `make native`")
    ptrs, is64, widths, keep = _lane_cols(columns)
    if (widths != 1).any():
        raise ValueError("value planes take scalar columns only")
    n = keep[0].shape[0]
    out = np.empty((n, len(keep)), np.uint64)
    rc = lib.ff_build_planes(ptrs, _c_arr(is64), len(is64), n, None, 0,
                             None, _c_arr(out), int(threads),
                             _stats_ptr(stats))
    del keep
    if rc != 0:
        raise ValueError(f"ff_build_planes failed (rc={rc})")
    return out


@dataclass(frozen=True)
class FusedPlan:
    """Static per-tree parameter block for fused_update — built once per
    pipeline from engine/hostfused.py's _fam_plan (hostsketch/pipeline),
    reused every chunk. Family 0 is the tree's root ("own") family;
    parents precede children."""

    parent: np.ndarray            # [nf] int64; -1 = root
    sel: np.ndarray               # [sel_off[nf]] int64 child lane picks
    sel_off: np.ndarray           # [nf+1] int64
    depth: np.ndarray             # [nf] int64
    width: np.ndarray             # [nf] int64
    cap: np.ndarray               # [nf] int64
    conservative: np.ndarray      # [nf] uint8
    prefilter: np.ndarray         # [nf] uint8
    admission_plain: np.ndarray   # [nf] uint8
    ddos_parent: int = -1         # family index, -1 = no ddos side table
    ddos_sel: Optional[np.ndarray] = None  # [ddos_sel_w] int64
    ddos_plane: int = -1
    # [nf] uint8 — families running -hh.sketch=invertible (their states
    # are HostInvState; the admission path is never entered for them).
    # None = all-table, the pre-r16 plan shape.
    invertible: Optional[np.ndarray] = None


def fused_update(lanes: np.ndarray, vals: np.ndarray, plan: FusedPlan,
                 states, do_sketch: bool, do_ddos: bool = True,
                 threads: int = 1,
                 stats: Optional[np.ndarray] = None):
    """One fused group->cascade->sketch pass over a chunk's root-family
    lanes (ff_fused_update): every family's CMS/prefilter/top-K state in
    ``states`` (HostHHState per family, plan order) is updated IN PLACE;
    the only surfaced output is the DDoS per-dst side table.

    lanes [n, w] uint32; vals [n, p] float32 (pre-scaled value planes —
    the count plane is appended natively). ``do_sketch=False`` runs the
    grouping only (late parts that still need the ddos table); states
    may then be None. ``do_ddos=False`` skips the plan's per-dst cascade
    (native regroup + output buffers) when the caller would discard the
    table — a late ddos sub-window. Returns (ddos_uniq [G, dw] u32,
    ddos_sums [G] f32) or None when no ddos table was produced."""
    lib = _load()
    if lib is None or not hasattr(lib, "ff_fused_update"):
        raise RuntimeError("libflowdecode.so missing the fused dataplane; "
                           "run `make native`")
    lanes = np.ascontiguousarray(lanes, dtype=np.uint32)
    vals = np.ascontiguousarray(vals, dtype=np.float32)
    n, w = lanes.shape
    p = vals.shape[1]
    if vals.shape[0] != n:
        # the fused pass folds vals rows into in-place sketch state by
        # lane row index — reject the mismatch before any state is
        # touched (same contract as the oob lane-selection check)
        raise ValueError(f"lanes rows ({n}) != vals rows "
                         f"({vals.shape[0]})")
    parent = np.ascontiguousarray(plan.parent, dtype=np.int64)
    sel = np.ascontiguousarray(plan.sel, dtype=np.int64)
    sel_off = np.ascontiguousarray(plan.sel_off, dtype=np.int64)
    depth = np.ascontiguousarray(plan.depth, dtype=np.int64)
    width = np.ascontiguousarray(plan.width, dtype=np.int64)
    cap = np.ascontiguousarray(plan.cap, dtype=np.int64)
    conserv = np.ascontiguousarray(plan.conservative, dtype=np.uint8)
    prefilter = np.ascontiguousarray(plan.prefilter, dtype=np.uint8)
    plain = np.ascontiguousarray(plan.admission_plain, dtype=np.uint8)
    nf = parent.shape[0]
    cms_ptrs = (ctypes.c_void_p * nf)()
    tkey_ptrs = (ctypes.c_void_p * nf)()
    tval_ptrs = (ctypes.c_void_p * nf)()
    inv_ks_ptrs = (ctypes.c_void_p * nf)()
    inv_kc_ptrs = (ctypes.c_void_p * nf)()
    inv_flags = None
    if plan.invertible is not None:
        inv_flags = np.ascontiguousarray(plan.invertible, dtype=np.uint8)
        if inv_flags.any() and not inv_available():
            # the loaded .so predates hs_inv_update — its ff_fused_update
            # also predates the invertible trailer and would silently
            # run the table path on inv state buffers
            raise RuntimeError("libflowdecode.so missing the invertible "
                              "sketch kernels; run `make native`")
    if do_sketch:
        for i, st in enumerate(states):
            assert st.cms.dtype == np.uint64 and st.cms.flags["C_CONTIGUOUS"]
            cms_ptrs[i] = st.cms.ctypes.data_as(ctypes.c_void_p).value
            if inv_flags is not None and inv_flags[i]:
                assert st.keysum.dtype == np.uint64 and \
                    st.keysum.flags["C_CONTIGUOUS"]
                assert st.keycheck.dtype == np.uint64 and \
                    st.keycheck.flags["C_CONTIGUOUS"]
                inv_ks_ptrs[i] = st.keysum.ctypes.data_as(
                    ctypes.c_void_p).value
                inv_kc_ptrs[i] = st.keycheck.ctypes.data_as(
                    ctypes.c_void_p).value
                continue
            assert st.table_keys.dtype == np.uint32 and \
                st.table_keys.flags["C_CONTIGUOUS"]
            assert st.table_vals.dtype == np.float32 and \
                st.table_vals.flags["C_CONTIGUOUS"]
            tkey_ptrs[i] = st.table_keys.ctypes.data_as(
                ctypes.c_void_p).value
            tval_ptrs[i] = st.table_vals.ctypes.data_as(
                ctypes.c_void_p).value
    ddos_keys = ddos_sums = None
    ddos_sel_ptr = None
    ddos_parent = -1
    ddos_sel_w = 0
    if do_ddos and plan.ddos_parent >= 0:
        ddos_parent = plan.ddos_parent
        ddos_sel = np.ascontiguousarray(plan.ddos_sel, dtype=np.int64)
        ddos_sel_w = ddos_sel.shape[0]
        ddos_sel_ptr = _c_arr(ddos_sel)
        ddos_keys = np.empty((max(n, 1), ddos_sel_w), np.uint32)
        ddos_sums = np.empty(max(n, 1), np.float32)
    g = lib.ff_fused_update(
        _c_arr(lanes), n, w, _c_arr(vals), p, nf,
        _c_arr(parent), _c_arr(sel), _c_arr(sel_off),
        _c_arr(depth), _c_arr(width), _c_arr(cap),
        _c_arr(conserv), _c_arr(prefilter), _c_arr(plain),
        cms_ptrs, tkey_ptrs, tval_ptrs, int(bool(do_sketch)),
        ddos_parent, ddos_sel_ptr, ddos_sel_w,
        plan.ddos_plane if ddos_parent >= 0 else -1,
        _c_arr(ddos_keys) if ddos_keys is not None else None,
        _c_arr(ddos_sums) if ddos_sums is not None else None,
        int(threads), _stats_ptr(stats),
        _c_arr(inv_flags) if inv_flags is not None else None,
        inv_ks_ptrs, inv_kc_ptrs)
    if g < 0:
        raise ValueError(f"ff_fused_update failed (rc={g}): degenerate "
                         f"shape n={n} w={w} p={p} nf={nf}")
    if ddos_parent < 0:
        return None
    g = int(g)
    return ddos_keys[:g], ddos_sums[:g]


def encode_stream(batch, out_capacity: int = 0) -> bytes:
    """Encode a FlowBatch to length-prefixed frames using the native library.

    Byte-identical to the pure-Python encoder except for all-zero addresses:
    the columnar form cannot distinguish an absent address from ``::``, and
    the native encoder omits such fields (proto3 decoders treat both the
    same; the stream is smaller)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("libflowdecode.so not built; run `make native`")
    scalar_names, addr_names = _column_order()
    n = len(batch)
    # Worst case ~ 27 fields * (2 tag + 5 varint) + addresses + prefix.
    cap = out_capacity or (n * 256 + 16)
    out = ctypes.create_string_buffer(cap)
    ptrs = (ctypes.c_void_p * (len(scalar_names) + len(addr_names)))()
    keepalive = []  # hold contiguous copies for the duration of the call
    for i, name in enumerate(scalar_names + addr_names):
        arr = np.ascontiguousarray(batch.columns[name])
        keepalive.append(arr)
        ptrs[i] = arr.ctypes.data_as(ctypes.c_void_p).value
    written = lib.flow_encode_stream(ptrs, n, out, cap)
    del keepalive
    if written < 0:
        raise ValueError("native encode: output buffer too small")
    return out.raw[: int(written)]
