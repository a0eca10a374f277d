"""Peaks of the chips, and what the fused step needs from them.

``PEAKS`` is keyed by ``device_kind`` as JAX reports it; a device that is
not in it is an error, not a default. ``fused_step_bytes`` counts, from
shapes alone, the HBM traffic the algorithm of one step needs: every
input lane read once, every sketch cell a row can touch read and written
once, every ranked-table row read and written once, the exact group-by's
partial written once, each sort's operands read and written once (at
32,768 rows a sort's operands fit on chip). It counts no floating-point
operations: the step has no matrix product, and no peak is published for
the vector unit, so the bound that applies is HBM bytes. A share near 0
says the step is bound by latency and serial dependence (sorts,
scatters), not by bandwidth; it cannot honestly pass 100 %.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM2e at 819 GB/s, per chip
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}

_LANES = {"src_addr": 4, "dst_addr": 4}
_FAMILY_KEYS = {  # ranked sketch families of the default processor
    "model.talkers": [("src_addr", "dst_addr", "src_port", "dst_port",
                       "proto")],
    "model.ips": [("src_addr",), ("dst_addr",)],
}


def _flag(flags: list, name: str, default):
    for i, f in enumerate(flags):
        if f == f"-{name}" and i + 1 < len(flags):
            return type(default)(flags[i + 1])
        if f.startswith(f"-{name}="):
            v = f.split("=", 1)[1]
            return v == "true" if isinstance(default, bool) \
                else type(default)(v)
    return default


def fused_step_bytes(config: dict) -> int:
    flags = config["processor_flags"]
    rows = _flag(flags, "processor.batch", 32768)
    width = _flag(flags, "sketch.width", 1 << 16)
    capacity = _flag(flags, "sketch.capacity", 1024)
    depth, planes = 4, 3  # CMS rows; bytes, packets, count planes
    word = 4
    cols = {"time_received", "src_as", "dst_as", "etype", "bytes",
            "packets", "sampling_rate"}
    total = 0
    families = [k for name, keys in _FAMILY_KEYS.items()
                if _flag(flags, name, True) for k in keys]
    for keys in families:
        cols.update(keys)
        lanes = sum(_LANES.get(c, 1) for c in keys)
        total += 2 * rows * depth * planes * word      # CMS cells touched
        total += 2 * capacity * (lanes + planes) * word  # table merge
        total += 2 * rows * 3 * word                   # hash sort operands
    if _flag(flags, "model.ports", True):
        cols.update(("src_port", "dst_port"))
        total += 2 * 2 * rows * planes * word          # dense scatters
    if _flag(flags, "model.ddos", True):
        cols.add("dst_addr")
        total += 2 * rows * 2 * word                   # per-dst accumulate
    if _flag(flags, "model.flows5m", True):
        total += 2 * rows * 3 * word                   # group-by sort
        total += rows * (5 + 5) * word                 # partial out
    del width  # the sketch's size does not enter: only touched cells do
    total += rows * sum(_LANES.get(c, 1) for c in cols) * word  # inputs
    return total


def fused_step_least_seconds(config: dict, device_kind: str):
    """(least seconds for one step, which bound applies)."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device_kind {device_kind!r}: add it "
                       f"to benchmark/roofline.py with its source")
    return (fused_step_bytes(config)
            / PEAKS[device_kind]["hbm_bytes_per_s"], "hbm_bytes")
