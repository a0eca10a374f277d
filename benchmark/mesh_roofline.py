"""What the sharded programs of ``-processor.mesh`` need from one chip,
counted from shapes alone, beside ``roofline.py``'s peaks.

``mesh_update_bytes``: the HBM traffic of the update programs a chip
runs for one global step (its shard: ``-processor.batch`` rows), counted
term for term as ``roofline.fused_step_bytes`` counts the fused step,
but a program a model: every model reads its own input lanes (they are
sharded to the device once a model, not once a batch), and the three
heavy-hitter families sort on their own.

``mesh_merge_bytes``: what one window close moves on a chip. Over ICI,
in a ring of ``n`` chips: a ``psum`` of ``S`` bytes sends ``2 (n-1)/n
S`` from each chip, an ``all_gather`` of a shard of ``S`` bytes sends
``(n-1) S``. Over HBM: every merged array read and written once, every
gathered table read once by the ``topk_merge`` fold.

Neither counts floating-point work: no program here has a matrix
product. A share near 0 says latency and serial dependence bound the
programs, not bandwidth; it cannot honestly pass 100 %.
"""

from __future__ import annotations

from benchmark.roofline import PEAKS, _FAMILY_KEYS, _LANES, _flag

# Google Cloud documentation, "TPU v5e": 1,600 Gbit/s of inter-chip
# interconnect per chip. A 1-D ring over a 2x2 host uses part of it, so
# the least time below is a floor, and the share an upper bound.
ICI_BYTES_PER_S = {"TPU v5 lite": 1600e9 / 8}

WORD, DEPTH, PLANES = 4, 4, 3   # bytes; CMS rows; bytes, packets, count
PORT_DOMAIN = 1 << 16
DDOS_BUCKETS = 1 << 14          # models/ddos.py::DDoSConfig.n_buckets


def _sizes(config: dict) -> dict:
    flags = config["processor_flags"]
    return {
        "rows": _flag(flags, "processor.batch", 32768),
        "width": _flag(flags, "sketch.width", 1 << 16),
        "capacity": _flag(flags, "sketch.capacity", 1024),
        "chips": _flag(flags, "processor.mesh", 1),
        "families": [k for name, keys in _FAMILY_KEYS.items()
                     if _flag(flags, name, True) for k in keys],
        "ports": 2 if _flag(flags, "model.ports", True) else 0,
        "ddos": _flag(flags, "model.ddos", True),
        "flows5m": _flag(flags, "model.flows5m", True),
    }


def _lanes(cols) -> int:
    return sum(_LANES.get(c, 1) for c in cols)


def mesh_update_bytes(config: dict) -> dict:
    """{program family: HBM bytes one chip moves for one global step}."""
    s = _sizes(config)
    rows, mask = s["rows"], s["rows"]  # the valid mask: a byte a row
    out = {"hh": 0, "dense": 0, "ddos": 0, "wagg": 0}
    for keys in s["families"]:
        lanes = _lanes(keys)
        out["hh"] += (
            rows * (lanes + 3) * WORD + mask      # keys, 2 values, rate
            + 2 * rows * DEPTH * PLANES * WORD    # CMS cells touched
            + 2 * s["capacity"] * (lanes + PLANES) * WORD  # table merge
            + 2 * rows * 3 * WORD)                # hash sort operands
    out["dense"] = s["ports"] * (
        rows * 4 * WORD + mask                    # port, 2 values, rate
        + 2 * rows * PLANES * WORD)               # scatter
    if s["ddos"]:
        out["ddos"] = (rows * (4 + 2) * WORD + mask  # dst_addr, value, rate
                       + 2 * rows * 2 * WORD)     # per-dst accumulate
    if s["flows5m"]:
        out["wagg"] = (rows * 7 * WORD + mask     # time, 3 keys, rate, 2
                       + 2 * rows * 3 * WORD      # group-by sort
                       + rows * (5 + 5) * WORD)   # partial out
    return out


def mesh_merge_bytes(config: dict) -> dict:
    """{"ici": bytes a chip sends, "hbm": bytes it reads and writes} for
    the merge programs of one window close."""
    s = _sizes(config)
    n = s["chips"]
    ici = hbm = 0
    for keys in s["families"]:
        cms = DEPTH * PLANES * s["width"] * WORD
        table = s["capacity"] * (_lanes(keys) + PLANES) * WORD
        ici += 2 * (n - 1) * cms // n + (n - 1) * table
        hbm += 2 * cms + (n + 1) * table
    dense = PORT_DOMAIN * PLANES * 2 * WORD       # (lo, hi) int32 planes
    ici += s["ports"] * (2 * (n - 1) * dense // n)
    hbm += s["ports"] * 2 * dense
    return {"ici": ici, "hbm": hbm}


def _peak(table: dict, device_kind: str) -> float:
    if device_kind not in table:
        raise KeyError(f"no peak for device_kind {device_kind!r}: add it "
                       f"to benchmark/mesh_roofline.py with its source")
    return table[device_kind]


def update_least_seconds(config: dict, device_kind: str):
    """(least seconds for one chip's update programs of one global step,
    which bound applies)."""
    hbm = _peak(PEAKS, device_kind)["hbm_bytes_per_s"]
    return sum(mesh_update_bytes(config).values()) / hbm, "hbm_bytes"


def merge_least_seconds(config: dict, device_kind: str):
    """(least seconds for one close's merge programs on a chip, which
    bound applies): the slower of the two paths."""
    b = mesh_merge_bytes(config)
    ici = b["ici"] / _peak(ICI_BYTES_PER_S, device_kind)
    hbm = b["hbm"] / _peak(PEAKS, device_kind)["hbm_bytes_per_s"]
    return (ici, "ici_bytes") if ici >= hbm else (hbm, "hbm_bytes")
