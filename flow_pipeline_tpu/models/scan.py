"""Port-scan detector preset: src addr -> distinct dst ports.

A vertical port scan touches many DISTINCT destination ports with
near-zero volume per port — the exact inverse of what the byte/packet
sketches rank. Counting the distinct-port dimension per source
(models/spread.py; ops/spread.py for the register protocol) surfaces
scanners directly; this module is the preset wiring for that detector:
key/element choice, the windowed wrapper, and the metric label for the
PortScanDetected alerting rule (deploy/prometheus/alerts.yml).
"""

from __future__ import annotations

from ..models.oracle import SECONDS_PER_SLOT
from .spread import SpreadConfig, SpreadModel

# The detector's model name — the `model` label on spread_top_max and
# the name the worker registers the windowed model under.
SCAN_MODEL = "portscan"


def scan_config(depth: int = 2, width: int = 1 << 12,
                registers: int = 64, capacity: int = 512,
                batch_size: int = 8192) -> SpreadConfig:
    """src_addr -> distinct dst_port spread. The element space is only
    2^16, so the linear-counting regime covers most keys exactly; the
    default register sizing matches the superspreader preset so both
    detectors share bucket discipline and parity suites."""
    return SpreadConfig(
        key_cols=("src_addr",), elem_col="dst_port", depth=depth,
        width=width, registers=registers, capacity=capacity,
        batch_size=batch_size)


def scan_model(config: SpreadConfig | None = None,
               window_seconds: int = SECONDS_PER_SLOT,
               k: int = 64, lateness: int = 0):
    """The windowed detector: a WindowedHeavyHitter wrapper over
    SpreadModel with the alert gauge labeled for this detector."""
    from ..engine.windowed import WindowedHeavyHitter

    whh = WindowedHeavyHitter(config or scan_config(),
                              window_seconds=window_seconds, k=k,
                              model_cls=SpreadModel, lateness=lateness)
    whh.model.metric_label = SCAN_MODEL
    return whh
