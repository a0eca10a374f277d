"""Multi-host scale-out over DCN.

Single-host meshes span a chip pod slice over ICI; beyond one host, JAX's
distributed runtime extends the same mesh over DCN — the framework's
equivalent of the reference scaling Kafka consumers across machines
(SURVEY.md §2: "jax collectives over ICI ..., DCN for multi-host").

Nothing in the kernels or models changes: the sharded pipelines in
parallel.sharded already address devices through a Mesh, and psum /
all_gather lower to cross-host collectives automatically. What multi-host
adds is process bootstrap + per-process data feeding, wrapped here:

    init_distributed(coordinator, num_processes, process_id)
    mesh = make_mesh()                       # now spans all hosts' devices
    feeder = LocalShardFeeder(mesh)          # per-host batch placement
    model = ShardedHeavyHitter(config, mesh)
    model.state = ...                        # as usual

Each host consumes its own bus partitions (the Kafka consumer-group
assignment IS the data-parallel split) and places its rows on its local
devices with make_array_from_process_local_data.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import DATA_AXIS


def init_distributed(coordinator_address: str, num_processes: int,
                     process_id: int, local_device_ids=None) -> None:
    """jax.distributed bootstrap (idempotent). coordinator_address is
    host:port of process 0; every process calls this before building meshes
    AND before any other jax call (backend init must not have happened yet —
    which is also why the guard below must not touch devices/process_count)."""
    if num_processes <= 1:
        return  # single-process: nothing to do
    from jax._src import distributed as _dist

    if getattr(_dist.global_state, "client", None) is not None:
        return  # already initialized
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )


def reassign_lost_partitions(lost: dict[int, int], survivors: list[int],
                             n_batches: int) -> dict[int, list[tuple[int, int]]]:
    """Deterministic reassignment of permanently lost hosts' partitions.

    ``lost`` maps each orphaned partition to its COMMITTED offset (the
    batch index its late owner had made durable — 0 if it never
    snapshotted); ``survivors`` is the ordered surviving-process list.
    Returns {survivor: [(partition, batch_index), ...]} round-robining
    the orphaned (partition, batch) slices over survivors from each
    partition's committed offset — the consumer-group rebalance rule,
    expressed as a pure function so every survivor computes the SAME map
    with no coordination. At-least-once follows from using committed
    offsets: anything the dead host processed but did not make durable
    is replayed; anything under its committed offsets is covered by its
    durable state and NOT replayed (no duplication).

    Exercised end-to-end (4 jax.distributed processes, one killed
    permanently, survivors re-consume to oracle-exact output) in
    tests/test_multihost.py."""
    out: dict[int, list[tuple[int, int]]] = {s: [] for s in survivors}
    i = 0
    for part in sorted(lost):
        for b in range(lost[part], n_batches):
            out[survivors[i % len(survivors)]].append((part, b))
            i += 1
    return out


class MultihostPipeline:
    """The full worker loop over a multi-host mesh.

    Scale-out follows the reference's consumer-group model (ref:
    inserter/inserter.go:238-256 — each consumer owns partitions and
    writes independently): every process consumes its own partition
    subset (its contiguous row-block of each global batch), places local
    shards with LocalShardFeeder, and the sharded models run SPMD over
    the whole mesh with zero cross-host data movement on the hot path.
    Collectives (psum / all_gather over DCN) happen only at window close.

    Emission contract:
    - flows_5m rows are HOST-PARTIAL — each process emits the partial
      aggregates of the rows it ingested, and merging sinks combine them
      by key exactly like SummingMergeTree merges partial rows.
    - top-K rows come from the replicated cross-process merged sketch;
      they are identical on every process, so only process 0 should
      write them.

    Checkpoint/restore is per-process: each host snapshots its window
    store and ITS device shards of the sketch state (local_state), and a
    restarted world rebuilds the global arrays from each host's shards.
    Tested end-to-end (2 real jax.distributed processes, kill-and-resume
    mid-window, oracle-exact totals) in tests/test_multihost.py.
    """

    def __init__(self, mesh: Mesh, wagg_config, hh_configs: dict,
                 k: int = 100):
        from .sharded import ShardedHeavyHitter, ShardedWindowAggregator

        self.mesh = mesh
        self.feeder = LocalShardFeeder(mesh)
        self.wagg = ShardedWindowAggregator(wagg_config, mesh)
        self.hh = {name: ShardedHeavyHitter(cfg, mesh)
                   for name, cfg in hh_configs.items()}
        self.k = k
        self.batches_done = 0

    def update(self, local_cols: dict, local_valid: np.ndarray,
               watermark: int) -> None:
        """One global batch step; each process passes ITS rows (1/Pth of
        the global batch, padded to global_batch/process_count) plus the
        GLOBAL batch watermark (no single host sees every row)."""
        cols, valid = self.feeder.feed_columns(local_cols, local_valid)
        self.wagg.update_device_columns(cols, valid, watermark)
        for m in self.hh.values():
            m.update_device_columns(cols, valid)
        self.batches_done += 1

    def flush(self, force: bool = False) -> dict:
        """Rows to emit: {'flows_5m': host-partial rows} always, plus one
        replicated top-K rows dict per sketch model when force-closing.
        Every process MUST call this at the same step — the sketch merge
        is a collective."""
        out = {"flows_5m": self.wagg.flush(force)}
        if force:
            for name, m in self.hh.items():
                out[name] = m.top(self.k)
                m.reset()
        return out

    def snapshot(self, path: str) -> None:
        from ..engine.checkpoint import save_checkpoint
        from ..engine.worker import save_wagg_state

        save_checkpoint(path, {
            "batches_done": self.batches_done,
            # drains first: the snapshot covers everything ingested
            "wagg": save_wagg_state(self.wagg),
            "hh": {name: m.local_state() for name, m in self.hh.items()},
        })

    def restore(self, path: str) -> Optional[int]:
        """Rehydrate this process's share; returns the number of batches
        the snapshot covers (the resume offset), or None if absent."""
        from ..engine.checkpoint import checkpoint_exists, load_checkpoint
        from ..engine.worker import restore_wagg_state

        if not checkpoint_exists(path):
            return None
        snap = load_checkpoint(path)
        self.batches_done = snap["batches_done"]
        restore_wagg_state(self.wagg, snap["wagg"], self.wagg.name)
        for name, local in snap["hh"].items():
            self.hh[name].load_local_state(local)
        return self.batches_done


class LocalShardFeeder:
    """Builds global device arrays from per-process local rows.

    On host h with L local devices out of G global, feed() takes the rows
    this host consumed (local_rows == global_rows / (G/L) after padding)
    and returns a global jax.Array row-sharded over the mesh without any
    cross-host data movement — each host supplies exactly its devices'
    shards.
    """

    def __init__(self, mesh: Mesh, axis: str = DATA_AXIS):
        self.mesh = mesh
        self.axis = axis
        self.sharding = NamedSharding(mesh, P(axis))

    def feed_columns(self, cols: dict, valid: np.ndarray):
        if jax.process_count() == 1:
            out = {
                k: jax.device_put(v, self.sharding) for k, v in cols.items()
            }
            return out, jax.device_put(valid, self.sharding)
        out = {
            k: jax.make_array_from_process_local_data(self.sharding, v)
            for k, v in cols.items()
        }
        return out, jax.make_array_from_process_local_data(
            self.sharding, valid
        )
