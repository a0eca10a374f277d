"""What one fused step of a heavy-hitter deployment needs from the chip,
counted from shapes alone, beside ``roofline.py``'s peaks.

``roofline.py::fused_step_bytes`` knows the default processor's families
(``model.talkers``, ``model.ips``) and a sketch whose rows a batch can
always fill; this counts a configuration's own: every ranked family its
flags build, ``-model.pairs`` among them, the members of the one sort
that nested families share (``engine/fused.py``: a hash-lane pair a
member), and a count-min row of ``-sketch.width`` cells, of which a batch
touches at most as many as it has rows. The rule is ``roofline.py``'s:
every input lane read once, every sketch cell a row can touch read and
written once, every ranked-table row read and written once, the exact
group-by's partial written once, each sort's operands read and written
once. No floating-point work is counted: the bound is HBM bytes, and a
share near 0 says the step is bound by latency and serial dependence
(sorts, scatters), not by bandwidth; it cannot honestly pass 100 %.
"""

from __future__ import annotations

from benchmark.roofline import PEAKS, _flag

WORD, DEPTH, PLANES = 4, 4, 3  # bytes; CMS rows; bytes, packets, count
_LANES = {"src_addr": 4, "dst_addr": 4}
FIVE = ("src_addr", "dst_addr", "src_port", "dst_port", "proto")
# flag -> (default, the key tuples of the ranked families it builds)
FAMILY_FLAGS = {
    "model.talkers": (True, [FIVE]),
    "model.pairs": (False, [("src_addr", "dst_addr")]),
    "model.ips": (True, [("src_addr",), ("dst_addr",)]),
}


def families(config: dict) -> list:
    flags = config["processor_flags"]
    return [keys for name, (default, tuples) in FAMILY_FLAGS.items()
            if _flag(flags, name, default) for keys in tuples]


def _chains(fams: list) -> tuple:
    """(members of the shared sort, families with a sort of their own):
    a family whose key tuple is a prefix of the longest one rides its
    sort; ``dst_addr`` alone takes the dst-keyed sort."""
    rest = [k for k in fams if k != ("dst_addr",)]
    if not rest:
        return 0, 0
    parent = max(rest, key=len)
    members = sum(parent[:len(k)] == k for k in rest)
    chained = members if members > 1 else 0
    return chained, len(rest) - chained


def hh_step_bytes(config: dict) -> int:
    flags = config["processor_flags"]
    rows = _flag(flags, "processor.batch", 32768)
    width = _flag(flags, "sketch.width", 1 << 16)
    capacity = _flag(flags, "sketch.capacity", 1024)
    fams = families(config)
    cols = {"time_received", "src_as", "dst_as", "etype", "bytes",
            "packets", "sampling_rate"}
    total = 0
    for keys in fams:
        cols.update(keys)
        lanes = sum(_LANES.get(c, 1) for c in keys)
        # CMS cells a batch can touch: a row a depth, never more than
        # the row has
        total += 2 * min(rows, width) * DEPTH * PLANES * WORD
        total += 2 * capacity * (lanes + PLANES) * WORD   # table merge
    chained, own = _chains(fams)
    if chained:
        total += 2 * rows * (2 * chained + 1) * WORD  # the shared sort
    total += own * 2 * rows * 3 * WORD                # sorts of their own
    if ("dst_addr",) in fams or _flag(flags, "model.ddos", True):
        cols.add("dst_addr")
        total += 2 * rows * 3 * WORD                  # the dst-keyed sort
    if _flag(flags, "model.ports", True):
        cols.update(("src_port", "dst_port"))
        total += 2 * 2 * rows * PLANES * WORD         # dense scatters
    if _flag(flags, "model.ddos", True):
        total += 2 * rows * 2 * WORD                  # per-dst accumulate
    if _flag(flags, "model.flows5m", True):
        total += 2 * rows * 3 * WORD                  # group-by sort
        total += rows * (5 + 5) * WORD                # partial out
    total += rows * sum(_LANES.get(c, 1) for c in cols) * WORD  # inputs
    return total


def hh_step_least_seconds(config: dict, device_kind: str):
    """(least seconds for one step, which bound applies)."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device_kind {device_kind!r}: add it "
                       f"to benchmark/roofline.py with its source")
    return (hh_step_bytes(config) / PEAKS[device_kind]["hbm_bytes_per_s"],
            "hbm_bytes")
