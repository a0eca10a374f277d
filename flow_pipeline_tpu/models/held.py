"""Late rows within ``-window.lateness`` for a family whose closed state
cannot reopen: the closing unit is *held* beside the open one.

A ranked table's window and the detector's sub-window are *units* of
event time (their start, in seconds). A unit used to close when the
first row of a newer one came, and every later row of it was dropped
(``late_flows_dropped``). With lateness L > 0 that roll *holds* the
unit instead: its state stays on the device beside a fresh open one,
rows of it that still arrive fold into it, and it closes once the
watermark (the newest ``time_received`` folded, over all partitions:
what ``flows_5m`` closes by) reaches its end + L, at the end of the
batch that brings the watermark there. At most two units are alive, the
open one and the held one before it: a roll that finds a unit still
held closes it first (a jump in event time, or L at or above the unit's
length), so closes stay in order; a row older than the held unit is
dropped and counted as before. L = 0 is the behaviour of before, bit
for bit. ``models/oracle.py::late_unit_sums`` states the same semantics
in plain numpy and tier-1 holds every path to it.

``HeldUnits`` is the lifecycle; ``engine.windowed.WindowedHeavyHitter``
and ``models.ddos.DDoSDetector`` say what a unit's state is. The held
state has the shapes of the open one, so a pipeline folds a late group
by running its compiled step with the two exchanged (``swap_held``): no
new program.
"""

from __future__ import annotations

import time

from ..obs.trace import TRACER

OPEN, HELD = "open", "held"  # the unit a group's rows go to (None: dropped)


class HeldUnits:
    """Mixin. The family provides ``name``, ``_unit`` (property: the
    open unit, None before the first row), ``_unit_seconds``,
    ``_adopt(unit)``, ``_close_open()`` (close the open unit now),
    ``_window_state()`` / ``_load_window_state(state)`` /
    ``_reset_window()`` (the open unit's state) and
    ``_close_held_state(unit, open_state)`` (close ``unit`` from the
    state now loaded, return the state to go on with as the open
    one)."""

    lateness = 0  # a family built without _init_held holds nothing

    def _init_held(self, lateness: int) -> None:
        if lateness < 0:
            raise ValueError(f"lateness must be >= 0 s, got {lateness}")
        self.lateness = int(lateness)
        self.held_unit = None
        self._held_state = None
        self._held_at = 0.0   # perf_counter at the roll that held it
        self.held_rows = 0    # rows folded into the unit now held
        self.watermark = None
        # rows of a unit that had closed (or was never opened): dropped
        self.late_flows_dropped = 0
        # rows that came after their unit rolled and went into it held
        self.late_flows_folded = 0

    def admit(self, unit: int, n_rows: int) -> str | None:
        """The lifecycle transition for ``n_rows`` rows of ``unit``:
        the first unit is adopted, a newer one rolls, and the answer is
        where the rows go: OPEN, HELD, or None (late: counted here,
        folded nowhere)."""
        cur = self._unit
        if cur is None:
            self._adopt(unit)
        elif unit > cur:
            self.roll(unit)
        elif unit == self.held_unit:
            self.late_flows_folded += n_rows
            self.held_rows += n_rows
            return HELD
        elif unit < cur:
            self.late_flows_dropped += n_rows
            return None
        return OPEN

    def _can_hold(self) -> bool:
        return self.lateness > 0

    def roll(self, unit: int) -> None:
        """Rows of a newer ``unit`` have come: the open one closes, or
        under a lateness is held until the watermark passes it."""
        if self.held_unit is not None:
            self.close_held()
        if not self._can_hold():
            self._close_open()
        else:
            self.held_unit = self._unit
            self._held_state = self._window_state()
            self._reset_window()
            self._held_at, self.held_rows = time.perf_counter(), 0
        self._adopt(unit)

    def advance_watermark(self, wm: int) -> None:
        """A batch whose newest row is ``wm`` has been folded."""
        if self.watermark is None or wm > self.watermark:
            self.watermark = wm
        if self.held_unit is not None and self.watermark >= (
                self.held_unit + self._unit_seconds + self.lateness):
            self.close_held()

    def swap_held(self) -> None:
        """Exchange the open unit's state with the held one's: between
        two calls every update, read and extraction of the family works
        on the held unit."""
        state = self._window_state()
        self._load_window_state(self._held_state)
        self._held_state = state

    def close_held(self) -> None:
        unit = self.held_unit
        with TRACER.span("held_close", model=self.name, unit=unit,
                         held_ms=(time.perf_counter()
                                  - self._held_at) * 1e3,
                         late_rows=self.held_rows):
            self.swap_held()
            open_state, self._held_state = self._held_state, None
            self.held_unit = None
            self._load_window_state(
                self._close_held_state(unit, open_state))

    # ---- checkpoint (engine/worker.py) ------------------------------------

    def held_checkpoint(self, arrays) -> dict | None:
        """The held unit as a checkpoint carries it (``arrays``: the
        family's state -> a dict of arrays), None where none is held."""
        if self.held_unit is None:
            return None
        return {"unit": self.held_unit, "rows": self.held_rows,
                "state": arrays(self._held_state)}

    def restore_held(self, held: dict | None, from_arrays) -> None:
        if not held:
            self.held_unit, self._held_state = None, None
            return
        # under -window.lateness 0 it closes with the next batch
        self.held_unit = int(held["unit"])
        self.held_rows = int(held["rows"])
        self._held_state = from_arrays(held["state"])
        self._held_at = time.perf_counter()
