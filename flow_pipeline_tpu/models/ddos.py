"""DDoS spike detector: per-DstAddr EWMA + quantile sketch on Packets.

BASELINE config #5: "sliding-window DDoS spike detect: per-DstAddr EWMA +
quantile-sketch on Packets". Design:

- DstAddr hashes into an [M] bucket array; each detection sub-window
  scatter-adds per-flow Packets into the bucket rates.
- At sub-window close: z-score of each bucket's rate against its EW
  mean/variance baseline (ops.ewma), AND the rate's rank against the
  population quantile sketch (ops.quantile). A bucket alarms when both
  z >= z_threshold and rate >= quantile(q) — the quantile gate suppresses
  "3 sigma above a tiny baseline" noise.
- Bucket -> address inversion: an [M, 4] witness store holding the dst of
  the largest single flow seen in the bucket this sub-window — deterministic
  under a flood even when several dsts hash-collide into one bucket (the
  alert also carries the bucket id for exact drill-down via the
  heavy-hitter model).

All state is mergeable across chips: rates and the histogram sum (psum);
the EW fold happens once per sub-window on the merged rates.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.metrics import REGISTRY
from ..ops import ewma as ewma_ops
from .held import HELD, HeldUnits
from ..ops.quantile import QuantileSketchSpec
from ..schema.batch import FlowBatch

# flowspread entropy companion (r21): normalized Shannon entropy of the
# positive bucket-rate distribution, published with its EW baseline at
# every sub-window close. A volumetric flood concentrates rate mass into
# few buckets, crushing the series toward 0 well before any single
# bucket's z-score trips — the EntropyCollapse rule
# (deploy/prometheus/alerts.yml) fires on live-vs-baseline divergence.
ENTROPY_GAUGE = ("flow_entropy",
                 "normalized Shannon entropy of per-bucket rates at the "
                 "last sub-window close (1 = uniform, -> 0 as one bucket "
                 "dominates)")
ENTROPY_BASELINE_GAUGE = ("flow_entropy_baseline",
                          "EW baseline of flow_entropy (fold weight "
                          "-ddos.entropy_alpha)")


@dataclass(frozen=True)
class DDoSConfig:
    n_buckets: int = 1 << 14  # 16384 dst buckets
    sub_window_seconds: int = 10  # detection cadence
    alpha: float = 0.3  # EW fold weight
    z_threshold: float = 4.0
    quantile: float = 0.99
    min_sigma: float = 4.0
    rel_sigma: float = 0.25  # sigma floor as a fraction of the EW mean
    warmup_windows: int = 3  # no alerts until the baseline has folded this often
    batch_size: int = 8192
    value_col: str = "packets"
    rel_err: float = 0.01
    # EW fold weight for the flow_entropy baseline (slower than the
    # rate baseline's alpha: entropy is a distribution-shape signal and
    # its baseline should ride out single-window wobble).
    entropy_alpha: float = 0.1
    # Serving-side sampling correction (see HeavyHitterConfig.scale_col):
    # rates reflect the TRUE per-dst traffic the samples represent, so a
    # 1:1000-sampled flood trips the same z-score gate an unsampled one
    # would. float32 multiply; None disables.
    scale_col: str | None = "sampling_rate"


def ddos_input_cols(config: "DDoSConfig") -> list[str]:
    """Columns the accumulate step reads."""
    out = ["dst_addr", config.value_col]
    if config.scale_col:
        out.append(config.scale_col)
    return out


def rate_entropy(rates: np.ndarray) -> tuple[float, int]:
    """(normalized Shannon entropy, active buckets) of one sub-window's
    [M] bucket rates: H = -sum(p ln p) / ln(M) over the positive
    buckets, so 1.0 is rate mass uniform across ALL buckets and the
    series collapses toward 0 as mass concentrates into few. The
    denominator is the FULL bucket count, not the active count — a
    flood aimed at two dsts spreads evenly across two buckets, which
    ln(active) normalization would score as a perfect 1.0 instead of
    the collapse it is. Fewer than two positive buckets reports 0.
    Pure float64 numpy — the host-side close path owns this."""
    rates = np.asarray(rates, np.float64)
    m = rates.size
    pos = rates[rates > 0]
    active = int(pos.size)
    if active <= 1 or m < 2:
        return 0.0, active
    p = pos / pos.sum()
    return float(-(p * np.log(p)).sum() / np.log(m)), active


class DDoSState(NamedTuple):
    mean: jnp.ndarray  # [M]
    var: jnp.ndarray  # [M]
    seen: jnp.ndarray  # [M] bool
    rates: jnp.ndarray  # [M] current sub-window accumulator
    hist: jnp.ndarray  # [B] quantile sketch of historical rates
    addrs: jnp.ndarray  # [M, 4] witness dst address per bucket
    wmax: jnp.ndarray  # [M] largest single-flow value seen this sub-window


def ddos_init(config: DDoSConfig, spec: QuantileSketchSpec) -> DDoSState:
    mean, var, seen = ewma_ops.ewma_init(config.n_buckets)
    return DDoSState(
        mean=mean,
        var=var,
        seen=seen,
        rates=jnp.zeros(config.n_buckets, jnp.float32),
        hist=spec.init(),
        addrs=jnp.zeros((config.n_buckets, 4), jnp.uint32),
        wmax=jnp.zeros(config.n_buckets, jnp.float32),
    )


def _accumulate_grouped(state: DDoSState, uniq, dsums, row_valid,
                        config: DDoSConfig):
    """Scatter pre-aggregated per-dst sums into the current sub-window.
    ``uniq`` [N,4] uint32 unique dst rows, ``dsums`` [N] float32 per-dst
    value sums, ``row_valid`` [N] bool. Shared by ddos_accumulate and the
    fused pipeline (engine.fused), which reuses the dst-keyed groupby the
    top-dst-IP model already computed."""
    buckets = ewma_ops.bucket_of(uniq, config.n_buckets)
    rates = ewma_ops.rate_accumulate(state.rates, buckets, dsums, row_valid)
    # Invalid rows go to index n_buckets: out of range HIGH, which
    # mode="drop" discards (a negative index would wrap before the check).
    safe_buckets = jnp.where(row_valid, buckets, config.n_buckets)
    masked = jnp.where(row_valid, dsums, -1.0)
    wmax = state.wmax.at[safe_buckets].max(masked, mode="drop")
    is_witness = row_valid & (masked >= wmax[buckets])
    witness_buckets = jnp.where(is_witness, buckets, config.n_buckets)
    addrs = state.addrs.at[witness_buckets].set(uniq, mode="drop")
    return state._replace(rates=rates, addrs=addrs, wmax=wmax)


@partial(jax.jit, static_argnames=("config",), donate_argnames=("state",))
def ddos_accumulate(state: DDoSState, cols: dict, valid, *, config: DDoSConfig):
    """Scatter one batch into the current sub-window.

    The batch is first collapsed to per-dst sums (sort_groupby), so the
    scatter sees each dst once — fewer conflicts AND a meaningful witness:
    the bucket's witness address is the dst with the largest per-batch SUM
    (a thousand 1-packet flood flows beat one benign 2-packet flow), not
    the largest single flow or an arbitrary last writer.
    """
    from ..ops.segment import sort_groupby_float

    dst = cols["dst_addr"].astype(jnp.uint32)
    # uint32 reinterpretation keeps saturated counters (>2^31) positive
    vals = cols[config.value_col].astype(jnp.uint32).astype(jnp.float32)
    if config.scale_col:
        vals = vals * jnp.maximum(
            cols[config.scale_col].astype(jnp.uint32).astype(jnp.float32),
            1.0)
    uniq, sums, counts = sort_groupby_float(dst, vals[:, None], valid)
    return _accumulate_grouped(state, uniq, sums[:, 0], counts > 0, config)


@partial(jax.jit, static_argnames=("config", "spec"), donate_argnames=("state",))
def ddos_close_window(state: DDoSState, *, config: DDoSConfig, spec: QuantileSketchSpec):
    """Close a sub-window: score, fold baseline, reset rates.

    Returns (new_state, z [M], rates [M]).
    """
    z = ewma_ops.zscores((state.mean, state.var, state.seen), state.rates,
                         config.min_sigma, config.rel_sigma)
    active = state.rates > 0
    hist = spec.add(state.hist, state.rates, valid=active)
    mean, var, seen = ewma_ops.ewma_fold(
        (state.mean, state.var, state.seen), state.rates, config.alpha
    )
    new_state = state._replace(
        mean=mean, var=var, seen=seen,
        rates=jnp.zeros_like(state.rates), hist=hist,
        wmax=jnp.zeros_like(state.wmax),
    )
    return new_state, z, state.rates


class DDoSDetector(HeldUnits):
    """Host wrapper: feed batches; sub-windows close on time_received.
    ``lateness`` (``-window.lateness``) holds a sub-window that rolled
    open for its late rows (``models/held.py``): its accumulators
    (rates, witnesses) are set aside in a state of their own, with the
    baselines as they stood at the roll, and scored against those at the
    deferred close, whose folded baselines the open state then takes:
    sub-window n is always scored before n + 1."""

    name = "ddos"  # in the held_close span

    def __init__(self, config: DDoSConfig = DDoSConfig(),
                 lateness: int = 0):
        self.config = config
        self.spec = QuantileSketchSpec(rel_err=config.rel_err)
        self.state = ddos_init(config, self.spec)
        self.current_sub = None  # sub-window start
        self.folds = 0  # closed sub-windows; alerts suppressed during warmup
        self.alerts: list[dict] = []  # drained by the worker per flush
        self.recent = deque(maxlen=1000)  # retained for live queries
        # Late rows (sub-window already closed and its rates reset) are
        # dropped, mirroring WindowedHeavyHitter: folding them into the
        # CURRENT sub-window would inflate its rates and can fire spurious
        # z-score alerts after a burst of late arrivals.
        self._init_held(lateness)
        # entropy anomaly signal (rate_entropy): live value and EW
        # baseline for the last closed sub-window; None until the first
        # close with >=2 active buckets folds the baseline
        self.entropy: float | None = None
        self.entropy_baseline: float | None = None
        # eager family registration: the gauges must exist on /metrics
        # from the first scrape (and for the dashboard honesty tests),
        # not only after the first sub-window closes
        REGISTRY.gauge(*ENTROPY_GAUGE)
        REGISTRY.gauge(*ENTROPY_BASELINE_GAUGE)

    def update(self, batch: FlowBatch) -> None:
        if len(batch) == 0:
            return
        # Split rows by sub-window (a batch may straddle boundaries; rows
        # must not inflate the wrong window's rates). Row order within the
        # batch is irrelevant to the scatter, so boolean selection is fine.
        times = batch.columns["time_received"].astype(np.int64)
        subs = (times // self.config.sub_window_seconds
                * self.config.sub_window_seconds)
        for sub in np.unique(subs):
            idx = np.flatnonzero(subs == sub)
            part = FlowBatch(
                {k: v[idx] for k, v in batch.columns.items()},
                batch.partition,
            )
            unit = self.admit(int(sub), len(part))
            if unit == HELD:
                self.swap_held()
                self._accumulate(part)
                self.swap_held()
            elif unit is not None:
                self._accumulate(part)
        self.advance_watermark(int(times.max()))

    # ---- models/held.py's hooks -------------------------------------------

    @property
    def _unit(self) -> int | None:
        return self.current_sub

    @property
    def _unit_seconds(self) -> int:
        return self.config.sub_window_seconds

    def _adopt(self, sub: int) -> None:
        self.current_sub = sub

    def _close_open(self) -> None:
        self._close_state()

    def _window_state(self) -> DDoSState:
        return self.state

    def _load_window_state(self, state: DDoSState) -> None:
        self.state = state

    def _fresh_state(self) -> DDoSState:
        return ddos_init(self.config, self.spec)

    def _reset_window(self) -> None:
        # buffers of its own: a step donates the state it is handed, so
        # the open state may share none with the held one. Its baselines
        # stay unread until the held sub-window's close hands it theirs
        self.state = self._fresh_state()

    def _close_held_state(self, sub: int, open_state: DDoSState):
        open_sub, self.current_sub = self.current_sub, sub
        self._close_state()  # scores what swap_held put in self.state
        self.current_sub = open_sub
        closed = self.state
        return open_state._replace(mean=closed.mean, var=closed.var,
                                   seen=closed.seen, hist=closed.hist)

    def _accumulate(self, batch: FlowBatch) -> None:
        bs = self.config.batch_size
        for start in range(0, len(batch), bs):  # chunk arbitrary batch sizes
            padded, mask = batch.slice(start, start + bs).pad_to(bs)
            cols = padded.device_columns(ddos_input_cols(self.config))
            cols = {k: jnp.asarray(v) for k, v in cols.items()}
            self.state = ddos_accumulate(
                self.state, cols, jnp.asarray(mask), config=self.config
            )

    def close_sub_window(self) -> list[dict]:
        """Score + roll: a held sub-window first, then the open one;
        returns (and records) new alerts."""
        before = len(self.alerts)
        if self.held_unit is not None:
            self.close_held()
        self._close_state()
        return self.alerts[before:]

    def _close_state(self) -> list[dict]:
        """Score the sub-window ``self.state`` accumulated, fold the
        baselines, reset the accumulators."""
        self.state, z, rates = ddos_close_window(
            self.state, config=self.config, spec=self.spec
        )
        return self._emit_alerts(z, rates, self.state.hist, self.state.addrs)

    def _fold_entropy(self, rates) -> None:
        """Publish the sub-window's rate entropy and fold its EW
        baseline. Runs on EVERY close (before the alert warmup gate) —
        the entropy series carries its own baseline and the collapse
        comparison happens rule-side, not here."""
        h, active = rate_entropy(np.asarray(rates))
        self.entropy = h
        if active > 1:
            a = self.config.entropy_alpha
            self.entropy_baseline = (
                h if self.entropy_baseline is None
                else (1.0 - a) * self.entropy_baseline + a * h)
        REGISTRY.gauge(*ENTROPY_GAUGE).set(h)
        if self.entropy_baseline is not None:
            REGISTRY.gauge(*ENTROPY_BASELINE_GAUGE).set(
                self.entropy_baseline)

    def _emit_alerts(self, z, rates, hist, addrs) -> list[dict]:
        """Shared gating + alert construction (single-chip and sharded)."""
        self._fold_entropy(rates)
        self.folds += 1
        if self.folds <= self.config.warmup_windows:
            return []
        z = np.asarray(z)
        rates = np.asarray(rates)
        gate = self.spec.quantile(np.asarray(hist), self.config.quantile)
        hot = np.nonzero(
            (z >= self.config.z_threshold) & (rates >= max(gate, 1.0))
        )[0]
        addrs = np.asarray(addrs)
        new = [
            {
                "sub_window": self.current_sub,
                "bucket": int(b),
                "dst_addr": addrs[b].astype(np.uint32),
                "rate": float(rates[b]),
                "zscore": float(z[b]),
                "baseline_quantile": float(gate),
            }
            for b in hot
        ]
        self.alerts.extend(new)
        self.recent.extend(new)
        return new
