"""What the spread detectors' register update needs from the chip, counted
from shapes alone, beside ``roofline.py``'s peaks.

One step raises, for each detector and each of its ``-spread.depth``
rows, one register a batch row: a scatter-max of ``rows`` indices into a
plane of ``depth x width x regs`` cells. The rule is ``roofline.py``'s:
what the algorithm has to move through HBM once. An index reads its cell
and writes it back (a cell is a word on the device:
``ops/spread.py::DEVICE_REG_DTYPE``), and the index and the value streams
are read once: four words an index. The element hashes that make the
index and the value are arithmetic on lanes the step has already read
and are not counted; nor is the rest of the plane, which an update does
not touch. The bound is HBM bytes; a share near 0 says the scatter is
bound by the latency of one cell at a time, not by bandwidth, and it
cannot honestly pass 100 %.
"""

from __future__ import annotations

from benchmark.roofline import PEAKS, _flag

WORD = 4          # bytes of a cell, an index, a value on the device
DETECTORS = 2     # superspreaders, portscan (cli.py: -spread.enabled)


def scatter_bytes(config: dict) -> int:
    """HBM bytes one step's register updates need, both detectors."""
    flags = config["processor_flags"]
    if not _flag(flags, "spread.enabled", False):
        return 0
    rows = _flag(flags, "processor.batch", 32768)
    depth = _flag(flags, "spread.depth", 2)
    return DETECTORS * depth * rows * 4 * WORD


def scatter_least_seconds(config: dict, device_kind: str):
    """Least seconds for one step's register updates, or None for a
    device whose peaks ``roofline.py`` does not list (the CPU dry run: a
    CPU number never goes under a device metric's name)."""
    if device_kind not in PEAKS:
        return None
    return scatter_bytes(config) / PEAKS[device_kind]["hbm_bytes_per_s"]
