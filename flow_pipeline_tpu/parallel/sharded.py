"""Sharded sketch pipelines: shard_map update + collective merges.

Per-chip sketch state is stacked on a leading device axis ([n_dev, ...],
sharded on axis 0), batches are row-sharded, and the hot update loop runs
with ZERO cross-chip communication — collectives happen only at window
close:

    cms / rates / histograms : psum over ICI (exact: monoid merge)
    top-K candidate tables   : all_gather + static fold of topk_merge

This is the design SURVEY.md §5 calls for: "shard the stream across chips,
per-chip count-min/space-saving sketches, psum-style merge across ICI —
sketches are commutative monoids, so merge == allreduce".

What a device trace and the flight recorder see of it (names are a
contract, docs/OBSERVABILITY.md): every sharded program is jitted from a
function named for its family and model (``mesh_hh_update_top_talkers``,
``mesh_dense_merge_top_src_ports``, ``mesh_ddos_close``,
``mesh_wagg_update``), so an ``XLA Modules`` event of any chip says whose
it is; the host side records ``mesh_shard`` round each global step's
pad, column build and host->device placement (with the rows each chip
got), ``mesh_update`` round a placement and the dispatches that read it
(a part of a poll for every model under ``pipeline.ShardedPipeline``,
one model's update on the per-model path here), ``mesh_merge`` round
each close collective and ``mesh_drain`` round the flows_5m drain.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import ddos as ddos_mod
from ..models import dense_top as dense_mod
from ..models import heavy_hitter as hh
from ..models.window_agg import (
    WindowAggConfig,
    WindowAggregator,
    _cached_update,
    _cached_update_exact,
    group_cols,
)
from ..obs.trace import TRACER
from ..ops.fold import fold_planes, fold_tables
from ..ops.fold import named_program as _program
from ..schema.batch import FlowBatch
from .mesh import DATA_AXIS, make_mesh, shard_batch_columns


def place_global_step(mesh: Mesh, batch: FlowBatch, start: int, rows: int,
                      col_names, model: str):
    """Pad ``batch[start:start + rows]`` to ``rows`` (one global step),
    build the int32 lanes of ``col_names`` and place each row-sharded
    over ``mesh``, for ``model``'s program to read: a ``mesh_shard``
    span. ``models`` is how many programs share the placement.
    ``chip_rows`` is the valid rows each chip gets, from the host's mask
    (``pad_to`` pads at the end, so a part-full step fills the leading
    chips and leaves the rest idle). Returns (cols, valid) on the
    device."""
    with TRACER.span("mesh_shard", model=model, models=1) as span:
        padded, mask = batch.slice(start, start + rows).pad_to(rows)
        cols = padded.device_columns(col_names)
        chip_rows = mask.reshape(mesh.devices.size, -1).sum(axis=1)
        span["rows"] = int(chip_rows.sum())
        span["chip_rows"] = chip_rows.tolist()
        span["bytes"] = mask.nbytes + sum(v.nbytes for v in cols.values())
        return shard_batch_columns(mesh, cols, mask)


def _sharded_steps(model, batch: FlowBatch) -> None:
    """One model's update of ``batch`` under a mesh, on its own (a
    ``mesh_update`` span): each global step's ``model.input_cols`` are
    placed for this model alone (``place_global_step``) and handed to
    ``model.update_device_columns``, which dispatches its sharded
    program. ``parallel.pipeline.ShardedPipeline`` cuts a poll once for
    every model of a worker and dispatches through the same two calls;
    this is the per-model path it is held to."""
    gb = model.global_batch
    with TRACER.span("mesh_update", model=model.name, steps=0) as update:
        for start in range(0, len(batch), gb):
            cols, valid = place_global_step(
                model.mesh, batch, start, gb, model.input_cols, model.name)
            model.update_device_columns(cols, valid)
            update["steps"] += 1


def _tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# Heavy hitter, sharded
# ---------------------------------------------------------------------------


def stack_state(state: hh.HHState, n_dev: int) -> hh.HHState:
    """Replicate a fresh single-chip state onto a leading device axis."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_dev,) + x.shape), state
    )


def sharded_hh_update(mesh: Mesh, config: hh.HeavyHitterConfig,
                      name: str = "hh"):
    """Build the jitted SPMD update: (stacked_state, global cols, valid) ->
    stacked_state. No collectives — pure per-chip work."""

    @_program(f"mesh_hh_update_{name}")
    def per_chip(state, cols, valid):
        state = jax.tree.map(lambda x: x[0], state)  # strip device axis
        new = hh.hh_update.__wrapped__(state, cols, valid, config=config)
        return jax.tree.map(lambda x: x[None], new)

    state_spec = hh.HHState(
        cms=P(DATA_AXIS), table_keys=P(DATA_AXIS), table_vals=P(DATA_AXIS)
    )
    fn = shard_map(
        per_chip,
        mesh=mesh,
        in_specs=(state_spec, P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=state_spec,
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(0,))


def sharded_hh_merge(mesh: Mesh, config: hh.HeavyHitterConfig,
                     name: str = "hh"):
    """Build the jitted window-close merge: stacked per-chip states ->
    one replicated merged state. psum for the CMS, all_gather + fold for
    the candidate table."""
    @_program(f"mesh_hh_merge_{name}")
    def per_chip(state):
        cms = lax.psum(state.cms[0], DATA_AXIS)
        tk = lax.all_gather(state.table_keys[0], DATA_AXIS)  # [n_dev, C, W]
        tv = lax.all_gather(state.table_vals[0], DATA_AXIS)
        # the fold the sliding window's ring runs over its sub-windows
        # (ops/fold.py); the plane sum is the psum above
        mk, mv = fold_tables(tk, tv)
        return hh.HHState(cms=cms, table_keys=mk, table_vals=mv)

    state_spec = hh.HHState(
        cms=P(DATA_AXIS), table_keys=P(DATA_AXIS), table_vals=P(DATA_AXIS)
    )
    out_spec = hh.HHState(cms=P(), table_keys=P(), table_vals=P())
    fn = shard_map(
        per_chip, mesh=mesh, in_specs=(state_spec,), out_specs=out_spec,
        check_vma=False,
    )
    return jax.jit(fn)


class ShardedHeavyHitter:
    """Multi-chip heavy-hitter model.

    Same surface as models.HeavyHitterModel, but update() consumes a global
    batch sharded over the mesh and top() runs the ICI merge first.
    """

    snapshot_kind = "windowed_hh"  # worker checkpoint dispatch tag

    def __init__(self, config: hh.HeavyHitterConfig, mesh: Mesh | None = None,
                 name: str = "hh"):
        self.config = config
        self.name = name  # in the programs' names and the spans' args
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_dev = self.mesh.devices.size
        self.input_cols = tuple(hh.input_cols(config))
        self._update = sharded_hh_update(self.mesh, config, name)
        self._merge = sharded_hh_merge(self.mesh, config, name)
        self.state = stack_state(hh.hh_init(config), self.n_dev)
        # stacked state starts replicated; reshard onto the device axis
        sharding = NamedSharding(self.mesh, P(DATA_AXIS))
        self.state = jax.tree.map(
            lambda x: jax.device_put(x, sharding), self.state
        )

    @property
    def global_batch(self) -> int:
        return self.config.batch_size * self.n_dev

    def update(self, batch: FlowBatch) -> None:
        _sharded_steps(self, batch)

    def update_device_columns(self, cols, valid) -> None:
        """Update from already-placed global arrays of exactly global_batch
        rows: the seam every sharded model has. The multi-host feed uses
        it (each process supplies only its local devices' shards,
        parallel.multihost.LocalShardFeeder), and so does a worker's
        ShardedPipeline, with the mask of the rows that are this
        model's to fold."""
        self.state = self._update(self.state, cols, valid)

    def merged_state(self) -> hh.HHState:
        # every reader goes on to read the merged state on the host, so
        # waiting here costs nothing and the span holds the collective
        # (and whatever the chips still had queued before it)
        with TRACER.span("mesh_merge", model=self.name,
                         bytes=_tree_bytes(self.state)):
            return jax.block_until_ready(self._merge(self.state))

    def local_state(self) -> dict[str, np.ndarray]:
        """This process's device shards of the stacked state, as numpy —
        the multi-host checkpoint unit (np.asarray on the full sharded
        state would fail: no process addresses every shard)."""
        from ..utils.shards import local_device_blocks

        return {f: local_device_blocks(getattr(self.state, f))
                for f in hh.HHState._fields}

    def load_local_state(self, local: dict[str, np.ndarray]) -> None:
        """Rebuild the global sharded state from per-process local shards
        (each process passes what ITS local_state() returned)."""
        sharding = NamedSharding(self.mesh, P(DATA_AXIS))
        self.state = hh.HHState(**{
            f: jax.make_array_from_process_local_data(
                sharding, np.asarray(local[f]))
            for f in hh.HHState._fields
        })

    def top(self, k: int | None = None) -> dict[str, np.ndarray]:
        merged = self.merged_state()
        single = hh.HeavyHitterModel.__new__(hh.HeavyHitterModel)
        single.config = self.config
        single.state = merged
        return hh.HeavyHitterModel.top(single, k)

    def reset(self) -> None:
        sharding = NamedSharding(self.mesh, P(DATA_AXIS))
        self.state = jax.tree.map(
            lambda x: jax.device_put(x, sharding),
            stack_state(hh.hh_init(self.config), self.n_dev),
        )

    # ---- a window held for its late rows (models/held.py): the stacked
    # replicas are set aside whole and merged at the deferred close

    def window_state(self) -> hh.HHState:
        return self.state

    def load_window_state(self, state: hh.HHState) -> None:
        self.state = state

    state_arrays = staticmethod(hh.HeavyHitterModel.state_arrays)
    state_from_arrays = staticmethod(hh.HeavyHitterModel.state_from_arrays)


# ---------------------------------------------------------------------------
# Exact window aggregation, sharded
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _sharded_window_update(mesh, window_seconds, key_cols, value_cols):
    """Jitted per-chip window-agg step (hash-grouped fast path), cached
    on (mesh, program fields) so fresh aggregators (supervisor restarts,
    benches) reuse the compiled executable instead of re-tracing per
    instance. Returns stacked per-chip (keys, sums, counts, n, collided);
    the drain re-runs a chunk through the exact variant below when any
    chip's collision flag fires."""
    base = _cached_update(window_seconds, key_cols, value_cols)

    @_program("mesh_wagg_update")
    def per_chip(cols, valid):
        keys, sums, counts, n, collided = base.__wrapped__(cols, valid)
        # Globalize the collision flag (any-chip OR via pmax): every host
        # must observe the SAME verdict, because the exact fallback is a
        # global shard_map launch that all processes of a multi-controller
        # mesh have to enter together — a host acting on only its local
        # chips' flags would launch it alone and deadlock.
        collided = jax.lax.pmax(collided.astype(jnp.int32), DATA_AXIS) > 0
        return keys[None], sums[None], counts[None], n[None], collided[None]

    return jax.jit(
        shard_map(
            per_chip,
            mesh=mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                       P(DATA_AXIS), P(DATA_AXIS)),
            check_vma=False,
        )
    )


@functools.lru_cache(maxsize=None)
def _sharded_window_update_exact(mesh, window_seconds, key_cols, value_cols):
    """Lexicographic per-chip window-agg step — the collision fallback."""
    base = _cached_update_exact(window_seconds, key_cols, value_cols)

    @_program("mesh_wagg_update_exact")
    def per_chip(cols, valid):
        keys, sums, counts, n = base.__wrapped__(cols, valid)
        return keys[None], sums[None], counts[None], n[None]

    return jax.jit(
        shard_map(
            per_chip,
            mesh=mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                       P(DATA_AXIS)),
            check_vma=False,
        )
    )


class ShardedWindowAggregator(WindowAggregator):
    """Exact windowed aggregation over a mesh.

    The device step runs per-chip sort_groupby under shard_map and returns
    stacked per-chip partials; the host merge (which already combines
    arbitrary partial aggregates into per-window dicts) treats the extra
    device axis as more partial rows. Exactness is unaffected — partial-sum
    merge is associative, the same property SummingMergeTree leans on.
    """

    def __init__(self, config: WindowAggConfig = WindowAggConfig(),
                 mesh: Mesh | None = None, name: str = "wagg"):
        super().__init__(config)
        self.name = name
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_dev = self.mesh.devices.size
        self.input_cols = ("time_received", *group_cols(config),
                           *config.value_cols)
        self._sharded = _sharded_window_update(
            self.mesh, config.window_seconds, group_cols(config),
            config.value_cols,
        )
        self._sharded_exact = _sharded_window_update_exact(
            self.mesh, config.window_seconds, group_cols(config),
            config.value_cols,
        )

    @property
    def global_batch(self) -> int:
        return self.config.batch_size * self.n_dev

    def update(self, batch: FlowBatch) -> None:
        if len(batch) == 0:
            return
        # stacked partials stay on device until a flush drains them
        _sharded_steps(self, batch)
        wm = int(batch.columns["time_received"].max())
        if wm > self.watermark:
            self.watermark = wm

    def _fold_partials(self, pending: list) -> None:
        """The drain under a mesh. The per-model path queues partials
        with no slot bound, so the worker's per-batch probe folds every
        one at once (``left`` 0) and waits for the program it has just
        dispatched; the wagg_* spans nest inside."""
        if not pending:
            return
        with TRACER.span("mesh_drain", partials=len(pending),
                         left=len(self._pending_partials)):
            super()._fold_partials(pending)

    def update_device_columns(self, cols, valid,
                              watermark: Optional[int] = None) -> None:
        """Update from already-placed global arrays of exactly global_batch
        rows (multi-host feed path; see ShardedHeavyHitter). The caller
        supplies the batch watermark — the host only sees its own rows, so
        max(time_received) must come from the feed layer."""
        self.add_partial(self._sharded(cols, valid),
                         fallback=lambda: self._sharded_exact(cols, valid))
        if watermark is not None and watermark > self.watermark:
            self.watermark = watermark


# ---------------------------------------------------------------------------
# DDoS detection, sharded
# ---------------------------------------------------------------------------


class ShardedDDoSDetector(ddos_mod.DDoSDetector):
    """Multi-chip DDoS detector.

    Per-chip scatter into rate/witness shards on the hot path; sub-window
    close merges over ICI: psum for the rates (a monoid), and an
    all_gather + argmax-by-wmax pick of the witness addresses (the chip
    that saw the heaviest per-dst contribution supplies the address —
    elementwise maxing would splice words of different addresses). The EW
    baseline and the quantile histogram then fold once on the merged rates,
    identically on every chip, so mean/var/seen/hist stay replicated with
    no further collectives.
    """

    def __init__(self, config: ddos_mod.DDoSConfig = ddos_mod.DDoSConfig(),
                 mesh: Mesh | None = None, name: str = "ddos",
                 lateness: int = 0):
        super().__init__(config, lateness)
        self.name = name
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_dev = self.mesh.devices.size
        self.input_cols = tuple(ddos_mod.ddos_input_cols(config))
        spec_obj = self.spec
        cfg = config

        @_program("mesh_ddos_update")
        def acc_per_chip(state, cols, valid):
            state = jax.tree.map(lambda x: x[0], state)
            new = ddos_mod.ddos_accumulate.__wrapped__(
                state, cols, valid, config=cfg
            )
            return jax.tree.map(lambda x: x[None], new)

        state_spec = ddos_mod.DDoSState(
            *([P(DATA_AXIS)] * len(ddos_mod.DDoSState._fields))
        )
        self._acc = jax.jit(
            shard_map(
                acc_per_chip, mesh=self.mesh,
                in_specs=(state_spec, P(DATA_AXIS), P(DATA_AXIS)),
                out_specs=state_spec, check_vma=False,
            ),
            donate_argnums=(0,),
        )

        @_program("mesh_ddos_close")
        def close_per_chip(state):
            s = jax.tree.map(lambda x: x[0], state)
            rates = lax.psum(s.rates, DATA_AXIS)
            # hist is NOT psum'd: after each close every chip adds the same
            # merged rates into its replica, so the replicas stay identical —
            # summing them would multiply historical mass by n_dev per window
            # (geometric blow-up of the quantile gate).
            # witness merge: per bucket, take the address from the chip that
            # saw the heaviest per-dst sum (elementwise pmax would splice
            # words of different addresses together)
            wmax_all = lax.all_gather(s.wmax, DATA_AXIS)  # [n_dev, M]
            addrs_all = lax.all_gather(s.addrs, DATA_AXIS)  # [n_dev, M, 4]
            winner = jnp.argmax(wmax_all, axis=0)  # [M]
            addrs = jnp.take_along_axis(
                addrs_all, winner[None, :, None], axis=0
            )[0]
            wmax = jnp.max(wmax_all, axis=0)
            merged = s._replace(rates=rates, addrs=addrs, wmax=wmax)
            new, z, r = ddos_mod.ddos_close_window.__wrapped__(
                merged, config=cfg, spec=spec_obj
            )
            return jax.tree.map(lambda x: x[None], new), z[None], r[None]

        self._close = jax.jit(
            shard_map(
                close_per_chip, mesh=self.mesh, in_specs=(state_spec,),
                out_specs=(state_spec, P(DATA_AXIS), P(DATA_AXIS)),
                check_vma=False,
            )
        )
        self.state = self._fresh_state()

    def _fresh_state(self) -> ddos_mod.DDoSState:
        # the single-chip init state re-stacked onto the device axis
        sharding = NamedSharding(self.mesh, P(DATA_AXIS))
        return jax.tree.map(
            lambda x: jax.device_put(
                jnp.broadcast_to(x[None], (self.n_dev,) + x.shape), sharding
            ),
            super()._fresh_state(),
        )

    @property
    def global_batch(self) -> int:
        return self.config.batch_size * self.n_dev

    def _accumulate(self, batch: FlowBatch) -> None:
        _sharded_steps(self, batch)

    def update_device_columns(self, cols, valid) -> None:
        """Accumulate already-placed global arrays into the open
        sub-window (see ShardedHeavyHitter); the caller has advanced the
        sub-window lifecycle and ``valid`` masks the rows of this one."""
        self.state = self._acc(self.state, cols, valid)

    def _close_state(self) -> list[dict]:
        s = self.state
        with TRACER.span("mesh_merge", model=self.name,
                         bytes=s.rates.nbytes + s.wmax.nbytes
                         + s.addrs.nbytes):
            self.state, z_stack, rates_stack = self._close(s)
            # every chip computed the same merged scores; read chip 0's
            # replicas
            z, rates = np.asarray(z_stack)[0], np.asarray(rates_stack)[0]
        return self._emit_alerts(z, rates, self.state.hist[0],
                                 self.state.addrs[0])


# ---------------------------------------------------------------------------
# Dense exact top-K (small key domains), sharded
# ---------------------------------------------------------------------------


class ShardedDenseTopK(dense_mod.DenseTopKModel):
    """Multi-chip dense accumulator — per-chip (lo, hi) plane totals are a
    sum monoid (carry re-normalization happens inside dense_top's exact
    uint64 recombination), so the hot path needs no collectives and the
    window close is one cross-chip reduce. top()/reset()/checkpointing
    are inherited; only placement and the merge differ."""

    def __init__(self, config: dense_mod.DenseTopConfig,
                 mesh: Mesh | None = None, name: str = "dense"):
        super().__init__(config)
        self.name = name
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_dev = self.mesh.devices.size
        self.input_cols = tuple(dense_mod.dense_input_cols(config))
        cfg = config

        @_program(f"mesh_dense_update_{name}")
        def per_chip(totals, cols, valid):
            new = dense_mod.dense_update.__wrapped__(
                totals[0], cols, valid, config=cfg
            )
            return new[None]

        self._update = jax.jit(
            shard_map(
                per_chip, mesh=self.mesh,
                in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
                out_specs=P(DATA_AXIS), check_vma=False,
            ),
            donate_argnums=(0,),
        )

        # per-chip planes sum exactly in int32: each chip's lo is
        # normalized < 2^16, so n_dev * 2^16 is far from overflow, and
        # the hi planes stay within the same 2^47 budget documented in
        # models.dense_top (now shared across chips)
        @_program(f"mesh_dense_merge_{name}")
        def merge(totals):
            return fold_planes(totals)

        self._merge = jax.jit(merge)
        sharding = NamedSharding(self.mesh, P(DATA_AXIS))
        self.totals = jax.device_put(
            jnp.zeros((self.n_dev,) + self.totals.shape, jnp.int32),
            sharding,
        )

    @property
    def global_batch(self) -> int:
        return self.config.batch_size * self.n_dev

    def update(self, batch: FlowBatch) -> None:
        _sharded_steps(self, batch)

    def update_device_columns(self, cols, valid) -> None:
        """Update from already-placed global arrays (see
        ShardedHeavyHitter)."""
        self.totals = self._update(self.totals, cols, valid)

    def _merged_totals(self):
        with TRACER.span("mesh_merge", model=self.name,
                         bytes=self.totals.nbytes):
            return jax.block_until_ready(self._merge(self.totals))

    def reset(self) -> None:
        sharding = NamedSharding(self.mesh, P(DATA_AXIS))
        self.totals = jax.device_put(jnp.zeros_like(self.totals), sharding)
