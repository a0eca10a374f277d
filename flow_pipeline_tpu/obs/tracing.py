"""Per-stage latency summaries (SURVEY.md §5: the reference's closest
analogue is GoFlow's per-stage latency summaries). Device traces come from
any ``jax.profiler`` session: the program's spans (``obs/trace.py``) and
the fused step's named scopes are in it.

- ``StageTimer``: host-side per-stage wall-clock accumulation exposed as
  the flow_summary_*_time_us metric family the reference dashboards chart,
  PLUS the aggregable ``flow_stage_duration_us`` histogram (cumulative
  ``le`` buckets by stage — Summary quantiles cannot be summed across
  workers; histogram buckets can, and they render as Grafana heatmaps).
"""

from __future__ import annotations

import contextlib
import time

from .metrics import REGISTRY

# Stage names are dynamic (callers mint them), and every distinct name
# registers a whole summary family plus a histogram label set — so the
# family is CAPPED exactly like r08 capped labeled summaries: beyond
# MAX_STAGES distinct names, observations fold into the single
# ``flow_summary_other_time_us`` overflow series (measured, bounded).
MAX_STAGES = 64
OVERFLOW_STAGE = "other"

STAGE_HISTOGRAM = "flow_stage_duration_us"


def register_stage_histogram():
    """The shared flow_stage_duration_us{stage=...} histogram: registered
    eagerly so /metrics (and the dashboard honesty test) sees the family
    before the first stage observation."""
    return REGISTRY.histogram(
        STAGE_HISTOGRAM,
        "per-stage wall time histogram (us; aggregable across "
        "instances, unlike the summary quantiles)")


class StageTimer:
    """Named per-stage timers -> flow_summary_<stage>_time_us summaries
    + the shared flow_stage_duration_us{stage=...} histogram."""

    def __init__(self):
        self._summaries = {}
        self._hist = register_stage_histogram()

    def _resolve(self, name: str) -> str:
        """Overflow guard: a caller minting unbounded stage names (e.g. a
        name built from input data) must not grow the metric family
        unbounded — beyond MAX_STAGES distinct names, the tail folds into
        the single overflow stage (measured, bounded)."""
        if name in self._summaries or len(self._summaries) < MAX_STAGES:
            return name
        return OVERFLOW_STAGE

    def _summary(self, name: str):
        s = self._summaries.get(name)
        if s is None:
            s = REGISTRY.summary(f"flow_summary_{name}_time_us",
                                 f"{name} stage wall time")
            self._summaries[name] = s
        return s

    def observe(self, name: str, us: float) -> None:
        """Record one measurement directly (for callers that must decide
        AFTER the fact whether a timing is worth recording, e.g. skipping
        no-op flushes that would bury real latency in the quantiles)."""
        name = self._resolve(name)
        self._summary(name).observe(us)
        self._hist.observe(us, stage=name)

    @contextlib.contextmanager
    def stage(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.observe(name, (time.perf_counter() - t0) * 1e6)
