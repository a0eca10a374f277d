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

Two halves per update chunk, on the host homes:

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
  (The device home's table keeps another metric, what a key's registers
  decoded to when it was last seen: ops.spread.spread_table_admit says
  why. The rows of a close are decoded from the registers either way.)

Where the state lives is the dataplane's to say (engine/dataplane.py
picks it, families/registry.py names the two homes): the host-grouped
pipelines and the per-model loop keep it in host numpy and fold it as
above; ``engine.fused.FusedPipeline`` moves it to the device
(``SpreadModel.to_device``) and updates it inside its jitted step, the
registers as one flat plane (ops/spread.py: ``device_regs``). Every
form that leaves the model (checkpoint, snapshot, mesh payload, the
rows of a close) is the host one, [depth, width, m] uint8, whichever
home the state has.

Windowing rides the same wrapper as every other family:
``WindowedHeavyHitter(config, model_cls=SpreadModel)``. Concrete
detector presets live in models/superspreader.py and models/scan.py.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

from ..obs.metrics import REGISTRY
from ..obs.trace import TRACER
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
    """Spread sketch state: u8 registers + u32 candidate keys, the
    exact max monoid's canonical form. On the host (the host-grouped
    dataplanes, the per-model loop, and every form that leaves a model)
    the fields are numpy as annotated, the update path mutates ``regs``
    in place, and readers that capture state (top_lazy, snapshot
    publishers) copy. On the device (``SpreadModel.to_device``) the
    same tuple holds jax arrays, ``regs`` flat in
    ops.spread.DEVICE_REG_DTYPE; the fused step donates and replaces it
    whole, and ``SpreadModel.host_state`` is the one way back."""

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
    if config.depth * config.width * config.registers >= 2 ** 31:
        raise ValueError(
            f"spread planes of {config.depth} x {config.width} x "
            f"{config.registers} registers pass 2^31 cells, which a "
            f"device step's int32 cell index cannot name")
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
        # True once a device pipeline owns the state (to_device): every
        # state this model then makes or adopts is the device form
        self.on_device = False
        # (the device state a host copy was made of, the copy): a
        # publish's top() and view parts, and a checkpoint that finds
        # the state unchanged, share ONE device->host copy of the planes
        self._host_of: tuple = (None, None)
        # detector name for the alerting gauge (cli sets it; None keeps
        # extraction metric-silent, e.g. in parity tests)
        self.metric_label: str | None = None
        # eager family registration: spread_top_max must exist on
        # /metrics from the first scrape (labeled series appear when a
        # named detector publishes), not only after the first extract
        REGISTRY.gauge(*SPREAD_TOP_GAUGE)

    # ---- where the state lives ---------------------------------------------

    @property
    def _shape(self) -> tuple:
        cfg = self.config
        return cfg.depth, cfg.width, cfg.registers

    def to_device(self) -> None:
        """Hand the state to a device step (engine.fused): from here on
        ``state`` is jax arrays and ``update`` is the pipeline's."""
        if not self.on_device:
            self.on_device = True
            self.state = self._placed(self.state)

    def _placed(self, host: SpreadState) -> SpreadState:
        """``host`` (numpy, the canonical form) as this model holds
        state: itself, or its device form."""
        if not self.on_device:
            return host
        import jax.numpy as jnp

        from ..ops.spread import device_regs

        return SpreadState(device_regs(host.regs),
                           jnp.asarray(host.table_keys),
                           jnp.asarray(host.table_metric))

    def leaving(self, state: SpreadState | None = None) -> SpreadState:
        """``state`` (default: the open one) in the form that leaves the
        model, [depth, width, m] uint8: numpy as it is; a device state
        as device arrays still, the registers narrowed there, so that
        whoever copies it to the host (a checkpoint's ckpt_d2h) moves a
        byte a register, once. A host copy already made of this very
        state is handed out instead."""
        state = self.state if state is None else state
        if isinstance(state.regs, np.ndarray):
            return state
        if self._host_of[0] is state.regs:
            return self._host_of[1]
        from ..ops.spread import host_regs

        return state._replace(regs=host_regs(state.regs, shape=self._shape))

    def host_state(self, state: SpreadState | None = None) -> SpreadState:
        """``state`` (default: the open one) as numpy. A device state is
        copied once for as long as it stays the model's (a step replaces
        it whole, so identity tells): the planes cross a publish once,
        not once a reader."""
        state = self.state if state is None else state
        if isinstance(state.regs, np.ndarray):
            return state
        if self._host_of[0] is not state.regs:
            host = SpreadState(*(np.asarray(x)
                                 for x in self.leaving(state)))
            self._host_of = (state.regs, host)
        return self._host_of[1]

    @property
    def canonical(self) -> SpreadState:
        """The open state in the canonical (host) form: what a mesh
        member ships (families/registry.py: ``state_attr``)."""
        return self.host_state()

    # ---- update (host homes; a device pipeline updates in its step) -------

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

        if self.on_device:
            raise RuntimeError(
                "this spread model's state is on the device: the fused "
                "step updates it (engine.fused), not update()")
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

    # ---- extraction ---------------------------------------------------------

    def _top_of(self, state: SpreadState, k: int) -> dict[str, np.ndarray]:
        """The ranked rows of ``state``: the planes to the host (where
        they are not), the register decode and the ranking, one
        ``spread_decode`` span."""
        with TRACER.span("spread_decode",
                         model=self.metric_label or "spread") as span:
            top = spread_top_from(self.host_state(state), self.config, k)
            span["rows"] = int(top["valid"].sum())
        if self.metric_label is not None:
            peak = float(top["spread"][0]) if top["valid"].any() else 0.0
            REGISTRY.gauge(*SPREAD_TOP_GAUGE).set(
                peak, model=self.metric_label)
        return top

    def top(self, k: int | None = None) -> dict[str, np.ndarray]:
        """Top-k rows ranked by register-decoded spread. ``spread`` is
        the HLL estimate (min over depth rows); ``pairs`` is the
        admission metric: on the host homes the accumulated count of
        pairs (a union-bound upper bound on the true distinct count,
        useful as a sanity cross-check), on the device what the key
        decoded to when a batch last held it (never above ``spread``)."""
        return self._top_of(self.state, k or self.config.capacity)

    def top_lazy(self, k: int | None = None):
        """Zero-arg closure producing top(k) from the state captured
        NOW. The host update path mutates registers in place, so the
        capture copies — once per window close, same cost class as
        extraction; a device state is immutable and a close replaces it
        rather than donating it, so it is captured as it is."""
        k = k or self.config.capacity
        state = self.state
        if not self.on_device:
            state = SpreadState(*(x.copy() for x in state))
        return lambda: self._top_of(state, k)

    def reset(self) -> None:
        if not self.on_device:
            self.state = spread_init(self.config)
            return
        # made on the device: no plane crosses for a fresh window
        import jax.numpy as jnp

        from ..ops.spread import DEVICE_REG_DTYPE

        cfg = self.config
        self.state = SpreadState(
            jnp.zeros(cfg.depth * cfg.width * cfg.registers,
                      DEVICE_REG_DTYPE),
            jnp.full((cfg.capacity, spread_key_width(cfg)), _SENTINEL,
                     jnp.uint32),
            jnp.zeros(cfg.capacity, jnp.float32))

    # ---- a window held for its late rows (models/held.py) -------------

    def window_state(self) -> SpreadState:
        return self.state

    def load_window_state(self, state: SpreadState) -> None:
        self.state = state

    def state_arrays(self, state: SpreadState) -> dict:
        return self.leaving(state)._asdict()

    def state_from_arrays(self, arrays: dict) -> SpreadState:
        """A checkpoint's field dict (any build's: the host form is the
        only one ever written) as this model holds state."""
        return self._placed(SpreadState(
            regs=np.asarray(arrays["regs"], dtype=np.uint8),
            table_keys=np.asarray(arrays["table_keys"], dtype=np.uint32),
            table_metric=np.asarray(arrays["table_metric"],
                                    dtype=np.float32)))
