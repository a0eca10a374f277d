"""Dense exact top-K for SMALL key domains (ports, protocols, AS-lets).

The sketch pipeline (CMS + candidate table) exists because the 5-tuple
key space is unbounded; a 16-bit port space is not. For domains that fit
in device memory, an exact dense accumulator is strictly better than any
sketch: one scatter-add per batch (vs depth scatters + a table-merge
sort), zero error, and top-K is one `lax.top_k` over the totals. This is
the TPU-first replacement for the reference's "top ports" raw-scan
panels (ref: compose/grafana/dashboards/viz.json port tables) at
O(domain) memory and O(batch) update cost.

Exactness design (same int32 discipline as models.window_agg, which
cannot use floats either): float32 scatter-adds lose integer increments
past 2^24 — a single busy port can blow through that inside one window —
so each value rides as two 16-bit planes in int32 with an explicit carry
propagation per batch:

    batch partial: scatter-add of (v & 0xFFFF, v >> 16) over <= 2^15-row
        sub-chunks — bounded by 2^15 * (2^16 - 1) = 0x7FFF8000 < 2^31,
        int32-exact;
    fold (two-stage carry): the partial's lo plane normalizes to 16 bits
        first, then adds the carried-in totals lo — hi counts 2^16
        units, so totals stay exact to 2^47 per cell (~140 TB per port
        per window). Any caller batch size is exact; sub-chunking is
        internal static slicing.

Ranking uses float32(hi)*65536 + lo (relative error ~6e-8, only capable
of swapping keys whose totals differ by less than that); the REPORTED
values are recombined exactly from the planes in uint64 on the host.

The model implements the surface WindowedHeavyHitter drives
(update/top/reset), so the window lifecycle (tumbling or sliding),
worker flushes and ranked sink tables are shared with the sketch models
unchanged.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.fold import fold_planes, named_program
from ..schema.batch import FlowBatch


@dataclass(frozen=True)
class DenseTopConfig:
    key_col: str = "src_port"
    # distinct key values; keys are ints in [0, domain). Rows whose key
    # falls outside are dropped silently (same index-redirect that drops
    # padding), so size the domain to the column's full range — 2^16
    # covers ports; don't point this at a 32-bit column.
    domain: int = 1 << 16
    value_cols: tuple[str, ...] = ("bytes", "packets")  # plane 0 ranks
    batch_size: int = 8192
    # Serving-side sampling correction (see HeavyHitterConfig.scale_col):
    # each per-row value is multiplied by max(<scale_col>, 1) in uint32
    # with saturation at 2^32-1 — exact whenever value*rate < 2^32
    # (bytes < 1500 covers rates to ~2.8M; a saturated row clamps, the
    # same contract device_columns applies to oversized raw counters).
    scale_col: str | None = "sampling_rate"


# Largest sub-batch whose scatter partial stays int32-exact when every
# row lands on one cell with a saturated 16-bit plane: 2^15 * 0xFFFF =
# 0x7FFF8000 < 2^31. Bigger caller batches are split into static
# sub-chunks inside the jit — a power of two so the common TPU-friendly
# batch sizes divide evenly (no ragged trailing scatter).
_DENSE_SUB_MAX = 32768


def dense_input_cols(config: DenseTopConfig) -> list[str]:
    """Columns the update step reads: key + values + the scale column."""
    out = [config.key_col, *config.value_cols]
    if config.scale_col:
        out.append(config.scale_col)
    return out


@partial(jax.jit, static_argnames=("config",), donate_argnames=("totals",))
def dense_update(totals, cols, valid, *, config: DenseTopConfig):
    """totals: [domain, P+1, 2] int32 — (lo, hi) 16-bit planes per value
    column plus the count plane, lo normalized to [0, 2^16).

    Exact for ANY batch size: the scatter runs over <= 2^15-row
    sub-chunks (static unrolled slices), and the fold normalizes the
    partial's lo plane BEFORE adding the carried-in totals lo — two-stage
    carry — so neither addition can leave int32."""
    key_full = cols[config.key_col].astype(jnp.int32)
    # invalid rows -> index `domain`, out of range HIGH, dropped by the
    # "drop" mode (a negative index would wrap before the check)
    key_full = jnp.where(valid, key_full, config.domain)
    lanes = [cols[name].astype(jnp.uint32) for name in config.value_cols]
    if config.scale_col:
        rate = jnp.maximum(cols[config.scale_col].astype(jnp.uint32),
                           jnp.uint32(1))
        # saturating u32 multiply: u32*u32 wraps in XLA, so detect
        # overflow with a per-row division bound and clamp — exact
        # whenever value*rate < 2^32
        def _scale(v):
            lim = jnp.uint32(0xFFFFFFFF) // jnp.maximum(v, jnp.uint32(1))
            return jnp.where(rate > lim, jnp.uint32(0xFFFFFFFF), v * rate)
        lanes = [_scale(v) for v in lanes]
    lanes.append(jnp.ones(key_full.shape[0], jnp.uint32))  # count
    lo = jnp.stack([(v & jnp.uint32(0xFFFF)).astype(jnp.int32)
                    for v in lanes], axis=1)
    hi = jnp.stack([(v >> jnp.uint32(16)).astype(jnp.int32)
                    for v in lanes], axis=1)
    planes_full = jnp.stack([lo, hi], axis=2)  # [N, P+1, 2]
    planes_full = jnp.where(valid[:, None, None], planes_full, 0)
    n = key_full.shape[0]
    for start in range(0, n, _DENSE_SUB_MAX):
        key = key_full[start:start + _DENSE_SUB_MAX]
        planes = planes_full[start:start + _DENSE_SUB_MAX]
        partial_ = jnp.zeros_like(totals).at[key].add(planes, mode="drop")
        # two-stage carry: normalize the partial's lo plane first (it can
        # be up to 2^15 * 0xFFFF), then add the carried-in lo (< 2^16) —
        # both sums fit int32 with room to spare
        p_lo = partial_[:, :, 0] & jnp.int32(0xFFFF)
        p_carry = partial_[:, :, 0] >> jnp.int32(16)
        lo_sum = totals[:, :, 0] + p_lo
        new_lo = lo_sum & jnp.int32(0xFFFF)
        carry = lo_sum >> jnp.int32(16)
        new_hi = totals[:, :, 1] + partial_[:, :, 1] + p_carry + carry
        totals = jnp.stack([new_lo, new_hi], axis=2)
    return totals


@partial(jax.jit, static_argnames=("config", "k"))
def dense_top(totals, *, config: DenseTopConfig, k: int):
    """Rank by plane 0; returns (keys [k], planes [k, P+1, 2], valid [k]).

    Validity comes from the COUNT plane, not the ranking value: a key
    observed only through zero-byte flows (count > 0, bytes == 0) is a
    real row and must not be silently excluded from the top-K output. The
    ranking carries a count-presence tie-break bit so such keys also
    outrank never-seen cells (at magnitudes where the bit exceeds float32
    granularity the tie-break is moot — byte totals dominate)."""
    seen = (totals[:, -1, 0] + totals[:, -1, 1]) > 0  # count planes >= 0
    rank = (totals[:, 0, 1].astype(jnp.float32) * 65536.0
            + totals[:, 0, 0].astype(jnp.float32)) * 2.0 \
        + seen.astype(jnp.float32)
    _, idx = jax.lax.top_k(rank, k)
    return idx, totals[idx], seen[idx]


def _planes_to_uint64(planes: np.ndarray) -> np.ndarray:
    """[..., 2] int32 (lo, hi) -> exact uint64 totals."""
    p = planes.astype(np.uint64)
    return p[..., 0] + (p[..., 1] << np.uint64(16))


def _top_from_totals(totals, config: DenseTopConfig,
                     k: int | None) -> dict[str, np.ndarray]:
    """Materialize top-k rows from one captured totals array — pure
    function so lazy extraction stays valid after the model moves on."""
    k = min(k or 100, config.domain)
    idx, planes, valid = dense_top(totals, config=config, k=k)
    rows = _planes_to_uint64(np.asarray(planes))  # exact values
    out: dict[str, np.ndarray] = {config.key_col: np.asarray(idx)}
    for j, name in enumerate(config.value_cols):
        out[name] = rows[:, j]
    out["count"] = rows[:, -1]
    out["valid"] = np.asarray(valid)
    return out


@functools.lru_cache(maxsize=None)
def dense_fold_program(name: str, n: int):
    """The jitted fold of ``n`` totals arrays into one: the sum the
    four-chip close runs over its replicas
    (``parallel.sharded.ShardedDenseTopK``), over the sub-windows of a
    sliding window's ring (see ``heavy_hitter.hh_fold_program``)."""

    @named_program(name)
    def fold(states):
        with jax.named_scope("slide_fold_planes"):
            return fold_planes(jnp.stack(states))

    return jax.jit(fold)


class DenseTopKModel:
    """Host wrapper with the HeavyHitterModel surface (update/top/reset),
    so WindowedHeavyHitter can drive it interchangeably."""

    snapshot_kind = "windowed_dense"  # worker checkpoint dispatch tag

    def __init__(self, config: DenseTopConfig = DenseTopConfig()):
        self.config = config
        planes = len(config.value_cols) + 1
        self.totals = jnp.zeros((config.domain, planes, 2), jnp.int32)

    def update(self, batch: FlowBatch) -> None:
        bs = self.config.batch_size
        for start in range(0, len(batch), bs):
            padded, mask = batch.slice(start, start + bs).pad_to(bs)
            cols = padded.device_columns(dense_input_cols(self.config))
            cols = {k: jnp.asarray(v) for k, v in cols.items()}
            self.totals = dense_update(
                self.totals, cols, jnp.asarray(mask), config=self.config
            )

    def _merged_totals(self):
        return self.totals  # sharded subclass reduces over the device axis

    def top(self, k: int | None = None) -> dict[str, np.ndarray]:
        return _top_from_totals(self._merged_totals(), self.config, k)

    def top_lazy(self, k: int | None = None):
        """Zero-arg closure producing top(k) from the totals captured now
        (immutable array; reset/update replace it) — lets the ingest
        flusher run the extraction off the update path."""
        totals, config = self._merged_totals(), self.config
        return lambda: _top_from_totals(totals, config, k)

    def reset(self) -> None:
        self.totals = jnp.zeros_like(self.totals)

    # ---- a sliding window's ring (engine.windowed.SubWindowRing) ------

    def window_state(self):
        return self.totals

    def load_window_state(self, totals) -> None:
        self.totals = totals

    def empty_state(self):
        return jnp.zeros_like(self.totals)

    def fold_program(self, name: str, n: int):
        return dense_fold_program(name, n)

    def top_from(self, totals, k: int | None = None):
        return _top_from_totals(totals, self.config, k)

    @staticmethod
    def state_arrays(totals) -> dict:
        return {"totals": totals}

    @staticmethod
    def state_from_arrays(arrays: dict):
        return jnp.asarray(arrays["totals"])
