"""Spread (distinct-count) model: per-key HLL register planes + a
ranked top-K-by-spread candidate table.

The flowspread family (ops/spread.py states the protocol and the
exactness argument) answers the cardinality questions the volume
sketches cannot: "how many DISTINCT dst addrs did this src touch?"
(superspreaders) and "how many DISTINCT dst ports?" (port scans).
Where the hh family accumulates bytes/packets per key, spread updates
per-key u8 registers from a hash of the COUNTED DIMENSION
(``elem_col``), so duplicate (key, element) pairs are free
(idempotent max) and the mesh merge is an exact element-wise max.

Two halves per update chunk:

- registers: group the chunk to unique (key, element) pairs (the max
  monoid makes this bit-identical to raw row updates), then scatter-max
  — native ``hs_spread_update`` when built, the numpy twin otherwise;
- candidate table: regroup the pairs by key; per-chunk distinct-pair
  counts accumulate into a sentinel-padded table as the ADMISSION
  metric (a union-bound upper bound on the true distinct count). The
  metric only decides which keys are tracked — reported spread values
  are always decoded from the registers at extraction
  (hostsketch.engine.np_spread_query, the one decode every serve path
  shares), so identical registers give identical answers everywhere.

Windowing rides the same wrapper as every other family:
``WindowedHeavyHitter(config, model_cls=SpreadModel)``. Concrete
detector presets live in models/superspreader.py and models/scan.py.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

from ..obs.metrics import REGISTRY
from ..schema.batch import FlowBatch, lane_width

_SENTINEL = np.uint32(0xFFFFFFFF)

# Max register-decoded spread among a model's extracted top rows —
# the alerting surface for SuperspreaderDetected / PortScanDetected
# (deploy/prometheus/alerts.yml); labeled by detector model name.
SPREAD_TOP_GAUGE = ("spread_top_max",
                    "max register-decoded spread among the extracted "
                    "top rows, per spread detector model")


@dataclass(frozen=True)
class SpreadConfig:
    key_cols: tuple[str, ...] = ("src_addr",)
    elem_col: str = "dst_addr"  # the counted dimension
    depth: int = 2
    width: int = 1 << 12  # 4096 buckets per depth row
    registers: int = 64   # m registers per bucket (u8 each)
    capacity: int = 512   # candidate table rows
    batch_size: int = 8192


class SpreadState(NamedTuple):
    """Spread sketch state — HOST-resident numpy by design (u8
    registers + u32 candidate keys; the exact max monoid IS the
    canonical form, like the invertible family's u64 planes). The
    update path mutates ``regs`` in place; readers that capture state
    (top_lazy, snapshot publishers) copy."""

    regs: np.ndarray          # [depth, width, m] uint8
    table_keys: np.ndarray    # [capacity, key_width] uint32
    table_metric: np.ndarray  # [capacity] float32 (admission metric)


def spread_key_width(config: SpreadConfig) -> int:
    return sum(lane_width(name) for name in config.key_cols)


def spread_elem_width(config: SpreadConfig) -> int:
    return lane_width(config.elem_col)


def spread_input_cols(config: SpreadConfig) -> list[str]:
    """Columns the update step reads: keys + the counted dimension."""
    return [*config.key_cols, config.elem_col]


def spread_init(config: SpreadConfig) -> SpreadState:
    if config.depth < 1 or config.width < 1 or config.registers < 2:
        raise ValueError(
            f"spread needs depth>=1, width>=1, registers>=2 "
            f"(got {config.depth}/{config.width}/{config.registers})")
    if config.elem_col in config.key_cols:
        raise ValueError(
            f"spread elem_col {config.elem_col!r} cannot be a key "
            f"column — a key always touches exactly one of itself")
    return SpreadState(
        regs=np.zeros((config.depth, config.width, config.registers),
                      np.uint8),
        table_keys=np.full((config.capacity, spread_key_width(config)),
                           _SENTINEL, np.uint32),
        table_metric=np.zeros(config.capacity, np.float32),
    )


def spread_top_from(state, config: SpreadConfig,
                    k: int) -> dict[str, np.ndarray]:
    """Top-k rows ranked by register-decoded spread, descending, with
    the stable lexicographic-key tie-break every table surface uses.
    Pure function of (regs, table_keys, table_metric) — the worker
    wrapper, the mesh coordinator merge and every serve publisher call
    THIS, so byte-identical state extracts byte-identical rows.
    Accepts SpreadState or a codec/checkpoint field dict."""
    from ..hostsketch.engine import np_spread_query

    if isinstance(state, dict):
        regs = np.asarray(state["regs"], np.uint8)
        tk = np.asarray(state["table_keys"], np.uint32)
        tm = np.asarray(state["table_metric"], np.float32)
    else:
        regs, tk, tm = state.regs, state.table_keys, state.table_metric
    kw = tk.shape[1]
    real = (tk != _SENTINEL).any(axis=1)
    keys = np.ascontiguousarray(tk[real], np.uint32)
    metric = np.asarray(tm, np.float32)[real]
    # lex-sort first, then stable argsort by -spread == (spread desc,
    # lex asc) — the (primary desc, lex asc) rule of np_topk_merge
    lex = np.lexsort(keys.T[::-1])
    keys, metric = keys[lex], metric[lex]
    spread = np_spread_query(regs, keys).astype(np.float32)
    order = np.argsort(-spread, kind="stable")[:k]
    n = len(order)
    out_keys = np.full((k, kw), _SENTINEL, np.uint32)
    out_spread = np.zeros(k, np.float32)
    out_metric = np.zeros(k, np.float32)
    out_keys[:n] = keys[order]
    out_spread[:n] = spread[order]
    out_metric[:n] = metric[order]
    valid = np.zeros(k, bool)
    valid[:n] = True
    out: dict[str, np.ndarray] = {}
    col = 0
    for name in config.key_cols:
        w = lane_width(name)
        out[name] = out_keys[:, col:col + w] if w == 4 else out_keys[:, col]
        col += w
    out["spread"] = out_spread
    out["pairs"] = out_metric
    out["valid"] = valid
    return out


class SpreadModel:
    """Host wrapper: feed batches, extract ranked-by-spread rows at
    window close. The interface triangle (update/top/top_lazy/reset +
    snapshot_kind) matches HeavyHitterModel, so the windowing wrapper,
    worker flush, checkpoint and serve layers drive it unchanged."""

    snapshot_kind = "windowed_spread"  # worker checkpoint dispatch tag

    def __init__(self, config: SpreadConfig = SpreadConfig()):
        self.config = config
        self.state = spread_init(config)
        # detector name for the alerting gauge (cli sets it; None keeps
        # extraction metric-silent, e.g. in parity tests)
        self.metric_label: str | None = None
        # eager family registration: spread_top_max must exist on
        # /metrics from the first scrape (labeled series appear when a
        # named detector publishes), not only after the first extract
        REGISTRY.gauge(*SPREAD_TOP_GAUGE)

    def update(self, batch: FlowBatch) -> None:
        """Per-model update path (the host pipeline folds prepared pair
        tables instead — bit-identical by the max monoid). Mutates the
        state arrays in place (readers that capture state copy)."""
        from ..engine.hostfused import _key_lanes_np
        from ..hostsketch.engine import (
            np_spread_table_merge,
            spread_apply_update,
        )
        from ..ops.hostgroup import group_by_key

        cfg = self.config
        kw = spread_key_width(cfg)
        bs = cfg.batch_size
        for start in range(0, len(batch), bs):
            chunk = batch.slice(start, start + bs)
            if len(chunk) == 0:
                continue
            cols = chunk.columns
            pair_lanes = _key_lanes_np(
                cols, (*cfg.key_cols, cfg.elem_col))
            pairs, _, _ = group_by_key(pair_lanes, [], exact=False)
            pairs = np.ascontiguousarray(pairs, dtype=np.uint32)
            spread_apply_update(self.state.regs, pairs[:, :kw],
                                pairs[:, kw:])
            key_uniq, _, pair_counts = group_by_key(
                np.ascontiguousarray(pairs[:, :kw]), [], exact=False)
            tk, tm = np_spread_table_merge(
                self.state.table_keys, self.state.table_metric,
                key_uniq, pair_counts.astype(np.float32))
            self.state = SpreadState(self.state.regs, tk, tm)

    def _publish(self, top: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
        if self.metric_label is not None:
            peak = float(top["spread"][0]) if top["valid"].any() else 0.0
            REGISTRY.gauge(*SPREAD_TOP_GAUGE).set(
                peak, model=self.metric_label)
        return top

    def top(self, k: int | None = None) -> dict[str, np.ndarray]:
        """Top-k rows ranked by register-decoded spread. ``spread`` is
        the HLL estimate (min over depth rows); ``pairs`` is the
        accumulated admission metric (a union-bound upper bound on the
        true distinct count, useful as a sanity cross-check)."""
        k = k or self.config.capacity
        return self._publish(spread_top_from(self.state, self.config, k))

    def top_lazy(self, k: int | None = None):
        """Zero-arg closure producing top(k) from the state captured
        NOW. The update path mutates registers in place, so the capture
        copies — once per window close, same cost class as extraction."""
        config = self.config
        k = k or config.capacity
        state = SpreadState(self.state.regs.copy(),
                            self.state.table_keys.copy(),
                            self.state.table_metric.copy())
        return lambda: self._publish(spread_top_from(state, config, k))

    def reset(self) -> None:
        self.state = spread_init(self.config)

    # ---- a window held for its late rows (models/held.py) -------------

    def window_state(self) -> SpreadState:
        return self.state

    def load_window_state(self, state: SpreadState) -> None:
        self.state = state

    @staticmethod
    def state_arrays(state: SpreadState) -> dict:
        return state._asdict()

    @staticmethod
    def state_from_arrays(arrays: dict) -> SpreadState:
        # numpy, NOT jnp: spread state is host-resident by design
        return SpreadState(
            regs=np.asarray(arrays["regs"], dtype=np.uint8),
            table_keys=np.asarray(arrays["table_keys"], dtype=np.uint32),
            table_metric=np.asarray(arrays["table_metric"],
                                    dtype=np.float32))
