"""Superspreader detector preset: src addr -> distinct dst addrs.

A superspreader is a source touching an anomalous number of DISTINCT
destinations (worm propagation, scanning botnets, spam campaigns) —
invisible to the volume sketches, whose per-key byte/packet sums a
single fat flow can dominate. The spread family counts the distinct
dimension directly (models/spread.py; ops/spread.py for the register
protocol), so this module is just the preset wiring: the key/element
choice, the windowed wrapper, and the detector's metric label for the
SuperspreaderDetected alerting rule (deploy/prometheus/alerts.yml).
"""

from __future__ import annotations

from ..models.oracle import SECONDS_PER_SLOT
from .spread import SpreadConfig, SpreadModel

# The detector's model name — the `model` label on spread_top_max and
# the name the worker registers the windowed model under.
SUPERSPREADER_MODEL = "superspreaders"


def superspreader_config(depth: int = 2, width: int = 1 << 12,
                         registers: int = 64, capacity: int = 512,
                         batch_size: int = 8192) -> SpreadConfig:
    """src_addr -> distinct dst_addr spread. Default sizing: 4096
    buckets x 64 u8 registers x 2 rows = 512 KiB of registers, ~2%
    standard error (1.04/sqrt(64)) past the linear-counting regime —
    plenty to rank spreaders whose fan-out is 100x the median."""
    return SpreadConfig(
        key_cols=("src_addr",), elem_col="dst_addr", depth=depth,
        width=width, registers=registers, capacity=capacity,
        batch_size=batch_size)


def superspreader_model(config: SpreadConfig | None = None,
                        window_seconds: int = SECONDS_PER_SLOT,
                        k: int = 64, lateness: int = 0):
    """The windowed detector: a WindowedHeavyHitter wrapper over
    SpreadModel with the alert gauge labeled for this detector."""
    from ..engine.windowed import WindowedHeavyHitter

    whh = WindowedHeavyHitter(config or superspreader_config(),
                              window_seconds=window_seconds, k=k,
                              model_cls=SpreadModel, lateness=lateness)
    whh.model.metric_label = SUPERSPREADER_MODEL
    return whh
