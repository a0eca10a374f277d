"""The spread detectors' register update: its least possible time over
its measured device time, in per cent. Bytes from shapes
(spread_roofline.py: a cell read and written an index, the index and
value streams once), the peak from roofline.py's table keyed by
device_kind, the time from the step's spread_regs_<detector> scopes
(spread_scopes.py), median over the step's executions in the traced
window. Source: profiler trace. Nothing on a device without listed peaks
(the CPU dry run) or for a program whose step holds no spread detector."""

from benchmark import spread_roofline, spread_scopes


def read(run):
    least_s = spread_roofline.scatter_least_seconds(
        run.cell.config, run.device.get("kind", ""))
    ms = spread_scopes.scope_ms_p50(run, "spread_regs_")
    if not least_s or not ms:
        return None
    return 100.0 * least_s / (ms / 1e3)
