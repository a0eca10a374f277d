"""What one slide's fold needs from the chip, counted from shapes alone,
beside ``roofline.py``'s peaks.

A sliding window of K = W / S sub-windows folds, at every slide and for
every ranked table, K sub-window states into one: K states read, one
written, whatever implements the fold. A sketch family's state is its
count-min planes (``PLANES`` x ``DEPTH`` x width words) and its candidate
table (capacity rows of key lanes + ``PLANES`` values); a port table's is
its (lo, hi) int32 planes over the 2^16 domain. No floating-point work is
counted (a sum and a merge of small sorted tables): the bound is HBM
bytes, and a share near 0 says the fold is bound by the serial chain of
K - 1 table merges, not by bandwidth; it cannot honestly pass 100 %.
"""

from __future__ import annotations

from benchmark.mesh_roofline import (DEPTH, PLANES, PORT_DOMAIN, WORD,
                                     _lanes)
from benchmark.roofline import PEAKS, _FAMILY_KEYS, _flag

WINDOW_SECONDS = 300  # models/oracle.py::SECONDS_PER_SLOT


def state_bytes(config: dict) -> dict:
    """{table kind: [bytes of one sub-window state, a table]}."""
    flags = config["processor_flags"]
    width = _flag(flags, "sketch.width", 1 << 16)
    capacity = _flag(flags, "sketch.capacity", 1024)
    families = [k for name, keys in _FAMILY_KEYS.items()
                if _flag(flags, name, True) for k in keys]
    return {
        "hh": [PLANES * DEPTH * width * WORD
               + capacity * (_lanes(keys) + PLANES) * WORD
               for keys in families],
        "dense": [PORT_DOMAIN * PLANES * 2 * WORD] * (
            2 if _flag(flags, "model.ports", True) else 0),
    }


def fold_bytes(config: dict) -> int:
    """HBM bytes the fold programs of one slide move: every table's K
    states read and one written."""
    slide = _flag(config["processor_flags"], "window.slide", 0)
    k = WINDOW_SECONDS // slide if slide else 1
    return (k + 1) * sum(b for states in state_bytes(config).values()
                         for b in states)


def fold_least_seconds(config: dict, device_kind: str):
    """(least seconds for one slide's fold programs, which bound)."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device_kind {device_kind!r}: add it "
                       f"to benchmark/roofline.py with its source")
    return (fold_bytes(config) / PEAKS[device_kind]["hbm_bytes_per_s"],
            "hbm_bytes")
