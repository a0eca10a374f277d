"""The host lifecycle a pipeline shares with the models it drives.

A polled batch is cut where the windowed families' slot or the DDoS
detector's sub-window changes, and each homogeneous group advances the
wrapped models' own lifecycle (``models/held.py``: first slot adopts, a
newer slot rolls, which closes the open unit or under
``-window.lateness`` holds it; rows of the held unit go to its state,
older ones are counted as late) before the device work for it is
dispatched, and the batch's watermark then closes what it has passed.

A family is cut at its own unit only, as on the per-model path: the
groups of a cut are gathered into *runs* (``_runs``), the neighbours
that share a slot for the windowed families and those that share a
sub-window for the detector, and a run is a mask over rows that stay
where they are. So a poll that crosses only a sub-window is one run of
the tables and two of the detector. ``engine.fused.FusedPipeline`` (one
fused step a slot run, the detector's own program for the run's other
sub-windows) and ``parallel.pipeline.ShardedPipeline`` (a sharded
program a model and run) differ in what a run *runs*, not in how a batch
is cut: both take the cut, the runs and the transitions from here, and
tests/test_fused.py and tests/test_mesh_pipeline.py hold each to the
per-model path.
"""

from __future__ import annotations

import contextlib

import numpy as np

from ..models.held import HELD
from ..schema.batch import FlowBatch


def _runs(groups: list, key: int) -> dict:
    """The maximal runs of consecutive ``groups`` that share their
    ``key`` (0: slot, 1: sub-window), as {index of the run's first
    group: (value, rows)}; ``rows`` is the union of the run's row masks,
    None for the whole batch. Slot and sub-window are both monotone in a
    row's time, so in (slot, sub) order equal values are neighbours."""
    runs: list = []
    for i, group in enumerate(groups):
        if runs and runs[-1][1] == group[key]:
            runs[-1][2] = runs[-1][2] | group[2]
        else:
            runs.append([i, group[key], group[2]])
    if len(runs) == 1:
        runs[0][2] = None
    return {first: (value, rows) for first, value, rows in runs}


def _count(rows, batch: FlowBatch) -> int:
    """Rows of ``batch`` under the mask ``rows`` (None: all of them)."""
    return len(batch) if rows is None else int(rows.sum())


class WindowLifecycle:
    """Mixin. The pipeline provides ``_whh`` (WindowedHeavyHitter
    wrappers, all of one ``_window_seconds``: the grain their slot
    rolls at, which under ``-window.slide`` is the slide) and ``_ddos``
    ((name, detector) pairs, all of one ``_sub_seconds``)."""

    # What a pipeline can do, read by engine/dataplane.py::choose (the
    # worker asks no class by name): rows that come after their unit
    # rolled are folded into it, held open for -window.lateness;
    # prepare(batch) / apply(prepared) besides update(batch), so a
    # group thread can run ahead of the step; invertible hh families
    # (-hh.sketch) are folded; the sampled shadow audit (-obs.audit)
    # is fed; the spread detectors' planes live on the device and its
    # step updates them (False: in host numpy, folded between steps).
    honours_lateness = False
    has_prepare_split = False
    serves_invertible = False
    feeds_audit = False
    spread_in_step = False

    _whh: list
    _ddos: list
    _window_seconds: int | None
    _sub_seconds: int | None

    def _split_groups(self, batch: FlowBatch):
        """Cut a batch at (window slot, DDoS sub-window) boundaries.
        Returns (groups, wm): groups = [(slot, sub, rows)] in (slot,
        sub) order, ``rows`` a boolean mask over the batch's rows, or
        None where the one group is the whole batch; wm the batch
        watermark. Pure host work that copies no column."""
        n = len(batch)
        t = batch.columns["time_received"].astype(np.int64)
        slots = ((t // self._window_seconds) * self._window_seconds
                 if self._whh else np.zeros(n, np.int64))
        subs = ((t // self._sub_seconds) * self._sub_seconds
                if self._ddos else np.zeros(n, np.int64))
        # One (slot, sub) pair per batch is the overwhelmingly common case
        # (sub-windows are tens of seconds, batches are milliseconds of
        # traffic) — detect it with scalar min/max passes (~0.1 ms).
        # Boundary batches rank each axis on its own (two 1-D uniques:
        # ~5 ms for 131,072 rows where a row-tuple np.unique(axis=0)
        # void-sorts the batch in ~70 ms) and pair the RANKS, which are
        # below n, so the pairing orders correctly for ANY int64 pair —
        # scalar-encoding the values themselves can wrap on corrupt
        # extreme timestamps and would process real rows under an
        # adopted garbage slot.
        if slots.min() == slots.max() and subs.min() == subs.max():
            return [(int(slots[0]), int(subs[0]), None)], int(t.max())
        uniq_slots, slot_rank = np.unique(slots, return_inverse=True)
        uniq_subs, sub_rank = np.unique(subs, return_inverse=True)
        pair = slot_rank.reshape(-1) * len(uniq_subs) + sub_rank.reshape(-1)
        groups = [
            (int(uniq_slots[p // len(uniq_subs)]),
             int(uniq_subs[p % len(uniq_subs)]), pair == p)
            for p in np.unique(pair)
        ]
        return groups, int(t.max())

    def _advance_hh(self, slot: int, n_rows: int) -> str | None:
        """Lockstep WindowedHeavyHitter lifecycle (the transitions of its
        own update(): ``HeldUnits.admit``). Returns where the group's
        rows go: OPEN, HELD, or None when the group is late."""
        unit = None
        for w in self._whh:
            unit = w.admit(slot, n_rows)
        return unit

    def _advance_ddos(self, sub: int, n_rows: int) -> str | None:
        """Lockstep DDoSDetector sub-window lifecycle (a close scores the
        OLD sub-window before current_sub advances, as in its update())."""
        unit = None
        for _, d in self._ddos:
            unit = d.admit(sub, n_rows)
        return unit

    @contextlib.contextmanager
    def _units(self, hh_unit: str | None, dd_unit: str | None):
        """Inside, a family whose group goes to its held unit has that
        unit's state in the open one's place, so what the pipeline runs
        reads and writes it there; a late group is one more dispatch of
        the same programs."""
        swapped = ([*self._whh] if hh_unit == HELD else []) + (
            [d for _, d in self._ddos] if dd_unit == HELD else [])
        for m in swapped:
            m.swap_held()
        try:
            yield
        finally:
            for m in swapped:
                m.swap_held()

    def _advance_watermark(self, wm: int) -> None:
        """The batch whose newest row is ``wm`` is folded: every held
        unit the watermark has passed by its lateness closes, in the
        batch that brought it there (as ``flows_5m``'s windows do)."""
        for w in self._whh:
            w.advance_watermark(wm)
        for _, d in self._ddos:
            d.advance_watermark(wm)
