"""A worker's sharded model set under a mesh: a poll is cut once.

The per-model loop (``engine/worker.py``: ``model.update(batch)`` for
each model) makes every windowed family and the detector find the poll's
slots for itself (an ``np.unique`` of every row's slot) and copy the rows
of each slot into a batch of its own, all 27 columns, even when the poll
lies in one slot: six times a poll, on the one host thread, while every
chip waits. ``ShardedPipeline`` takes the worker's ``fused`` slot for a
model set made of the sharded kinds and, for each polled batch:

1. cuts it once at (window slot, detector sub-window) boundaries
   (``WindowLifecycle._split_groups``, the cut ``FusedPipeline`` uses:
   a scalar fast path that touches no row when the poll holds one pair),
   gathers the groups into runs (``engine.lifecycle._runs``, shared with
   ``FusedPipeline`` since PR 40) and advances the windowed families
   and the detector in lockstep;
2. pads, builds and places each model's columns a global step of
   ``n_dev x batch_size`` rows (``sharded.place_global_step``), once a
   poll whatever the cut;
3. dispatches each model's own program through
   ``update_device_columns``, ``flows_5m`` first so that the drain's
   wait stays short.

A poll that straddles a boundary is not compacted into copies: rows stay
where they were placed and each family group gets the mask of its rows
(``slot == s`` for the windowed families, ``sub == s`` for the
detector, all valid rows for ``flows_5m``, which groups by timeslot
itself). So a poll that crosses a sub-window runs only the detector's
program twice, and the windowed families run twice only at a slot roll.
``FusedPipeline`` runs by the same rule on one chip: there a slot run
is one fused step that carries the detector's newest sub-window, and
the other sub-windows run the detector's own program alone.

A placement is still a model's own (``mesh_shard`` [models] 1). One
placement of the union of the columns for all seven programs was built
and measured (PERF.md 6, PR 27): the loop then ran at the chips' pace,
1.17M flows/s on four v5e chips, more than the benchmark's four-chip
cell then held on its bus, so every run of it aborted. The cell's file
has provisioned 2,240,000 flows/s of window since PR 29; the step is
ROADMAP A3, gated by B-mech 8b; ``_Placed.step`` is where it goes.

The device programs, their names and their order are the per-model
path's; tests/test_mesh_pipeline.py holds this pipeline to that path,
which stays for the model sets this one does not know.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..engine.lifecycle import WindowLifecycle, _count, _runs
from ..engine.windowed import WindowedHeavyHitter
from ..obs.trace import TRACER
from ..schema.batch import FlowBatch
from .mesh import DATA_AXIS
from .sharded import (
    ShardedDDoSDetector,
    ShardedDenseTopK,
    ShardedHeavyHitter,
    ShardedWindowAggregator,
    place_global_step,
)


class _Placed:
    """One polled batch on the mesh: each model's columns of each global
    step, placed at their first use and kept until the batch is done (a
    later part of a straddling poll reads the same placement under
    another mask)."""

    def __init__(self, mesh, global_batch: int, batch: FlowBatch):
        self._mesh, self._gb, self._batch = mesh, global_batch, batch
        self._steps: dict = {}

    def __len__(self) -> int:
        return -(-len(self._batch) // self._gb)

    def step(self, g: int, name: str, model):
        """(cols, valid) of ``model``'s columns of global step ``g``."""
        if (g, name) not in self._steps:
            self._steps[g, name] = place_global_step(
                self._mesh, self._batch, g * self._gb, self._gb,
                model.input_cols, name)
        return self._steps[g, name]

    def rows(self, g: int, rows: np.ndarray):
        """``rows`` (a mask over the whole batch) cut to step ``g`` and
        placed as its valid mask; None where it selects nothing there."""
        part = rows[g * self._gb:(g + 1) * self._gb]
        if not part.any():
            return None
        mask = np.zeros(self._gb, dtype=bool)
        mask[:len(part)] = part
        return jax.device_put(mask, NamedSharding(self._mesh, P(DATA_AXIS)))


class ShardedPipeline(WindowLifecycle):
    """Drives a worker's sharded models through one cut a polled
    batch."""

    # -window.lateness: a held unit is a second set of stacked replicas,
    # merged over the chips at the deferred close
    honours_lateness = True
    # StreamWorker hands this to save_checkpoint: the stacked replicas'
    # archive is built in memory and written whole, as before the
    # arrays of a checkpoint were streamed. Not for the program's sake
    # (streamed, a 53 MB checkpoint here takes 102 ms and not 246) but
    # for the yardstick's: estate-mesh4-catchup then drains 2.22-2.29M
    # flows/s where its traffic file provisions 2.26M, and a run that
    # drains its backlog aborts. ROADMAP B-bench 0 re-provisions the
    # file; the PR after it deletes this line and ``whole``.
    checkpoint_whole = True

    @staticmethod
    def supported(models: dict[str, Any]) -> bool:
        """True iff every model is a sharded kind this pipeline knows,
        all on one mesh with one per-chip batch size, and the windowed
        families and the detector agree on their cadence."""
        meshes, batch_sizes, windows, subs = set(), set(), set(), []
        for m in models.values():
            if type(m) is ShardedWindowAggregator:
                inner = m
            elif type(m) is WindowedHeavyHitter and type(m.model) in (
                    ShardedHeavyHitter, ShardedDenseTopK):
                inner = m.model
                windows.add((m.window_seconds, m.lateness))
            elif type(m) is ShardedDDoSDetector:
                inner = m
                subs.append(m.config.sub_window_seconds)
            else:
                return False
            meshes.add(inner.mesh)
            batch_sizes.add(inner.config.batch_size)
        return (len(meshes) == 1 and len(batch_sizes) == 1
                and len(windows) <= 1 and len(subs) <= 1)

    def __init__(self, models: dict[str, Any]):
        if not self.supported(models):
            raise ValueError(
                "model set not of the sharded kinds (see supported())")
        # (name, the model whose update_device_columns is dispatched),
        # in the worker's model order
        self._waggs: list = []
        self._families: list = []
        self._ddos: list = []
        self._whh: list[WindowedHeavyHitter] = []
        for name, m in models.items():
            if type(m) is ShardedWindowAggregator:
                self._waggs.append((name, m))
            elif type(m) is ShardedDDoSDetector:
                self._ddos.append((name, m))
            else:
                self._families.append((name, m.model))
                self._whh.append(m)
        self._window_seconds = (self._whh[0].window_seconds
                                if self._whh else None)
        self._sub_seconds = (self._ddos[0][1].config.sub_window_seconds
                             if self._ddos else None)
        inner = [m for _, m in self._waggs + self._families + self._ddos]
        self.mesh = inner[0].mesh
        self.global_batch = inner[0].global_batch

    def update(self, batch: FlowBatch) -> None:
        if len(batch) == 0:
            return
        with TRACER.span("mesh_split", parts=0, copied_rows=0) as span:
            groups, wm = self._split_groups(batch)
            slot_runs, sub_runs = _runs(groups, 0), _runs(groups, 1)
            span["parts"] = len(groups)
        placed = _Placed(self.mesh, self.global_batch, batch)
        for i in range(len(groups)):
            # what this part runs: (models, rows, watermark)
            run = []
            hh_unit = dd_unit = None
            if i == 0:
                run.append((self._waggs, None, wm))
            if i in slot_runs:
                slot, rows = slot_runs[i]
                hh_unit = self._advance_hh(slot, _count(rows, batch))
                if hh_unit:
                    run.append((self._families, rows, None))
            if i in sub_runs:
                sub, rows = sub_runs[i]
                dd_unit = self._advance_ddos(sub, _count(rows, batch))
                if dd_unit:
                    run.append((self._ddos, rows, None))
            # a family's programs run on its open replicas, or on the
            # held ones its rows belong to
            with self._units(hh_unit, dd_unit):
                self._dispatch(placed, [r for r in run if r[0]])
        self._advance_watermark(wm)

    def _dispatch(self, placed: _Placed, run: list) -> None:
        """One part's programs over the batch's placements: a
        ``mesh_update`` span, with the ``mesh_shard`` of every placement
        this part is the first to read nested inside."""
        if not run:
            return
        served = [name for models, _, _ in run for name, _ in models]
        steps = set()  # the global steps this part had rows in
        with TRACER.span("mesh_update", model=served, steps=0) as update:
            for models, rows, wm in run:
                args = () if wm is None else (wm,)
                for g in range(len(placed)):
                    part = None if rows is None else placed.rows(g, rows)
                    if rows is not None and part is None:
                        continue
                    for name, m in models:
                        cols, valid = placed.step(g, name, m)
                        m.update_device_columns(
                            cols, valid if part is None else part, *args)
                    steps.add(g)
            update["steps"] = len(steps)
