"""Window-driving wrapper for the ranked (top-K) models.

HeavyHitterModel aggregates an unbounded stream; this wrapper gives it a
window lifecycle on event time. Tumbling (the default) is the exact
aggregator's: at watermark close it extracts the window's top-K rows and
resets the sketch, the streaming equivalent of flows_5m's per-timeslot
grouping, for key spaces too large to aggregate exactly (the north-star
5-tuple configs, BASELINE.json). Sliding (``slide_seconds``,
``-window.slide``) answers what upstream's dashboards ask, "the top
talkers of the last ``window_seconds``, now": the sketch covers one
sub-window of ``slide_seconds``, the last K - 1 closed sub-windows'
states stay on the device in a ring (``SubWindowRing``), and every
``slide_seconds`` of event time the fold of the K is extracted
(``ops/fold.py``: the monoid the four-chip close runs over its replicas)
and only the oldest is dropped.
"""

from __future__ import annotations

from collections import deque

import jax
import numpy as np

from ..models.heavy_hitter import HeavyHitterConfig, HeavyHitterModel
from ..models.held import HELD, HeldUnits
from ..models.oracle import SECONDS_PER_SLOT
from ..obs import get_logger
from ..obs.trace import TRACER
from ..schema.batch import FlowBatch
from .checkpoint import Member

log = get_logger("windowed")


class LazyWindowTop:
    """Deferred top-K extraction for one closed window.

    Closing a sketch window costs a device sync (top-K ranking + CMS
    estimates pulled to host) that the HOT PATH does not need — only the
    sink does. The close captures the window's state (immutable jax
    arrays; reset() replaces rather than mutates, and the update step's
    buffer donation only ever consumes the NEW state), and resolve()
    materializes the rows wherever the flusher runs it.
    """

    __slots__ = ("_thunk", "timeslot")

    def __init__(self, thunk, timeslot: int):
        self._thunk = thunk
        self.timeslot = timeslot

    def resolve(self) -> dict:
        top = self._thunk()
        top["timeslot"] = np.full(
            len(top["valid"]), self.timeslot, dtype=np.uint64)
        return top


class SubWindowRing:
    """The closed sub-windows of one sliding table: a host-side deque of
    device states, not an axis in the step. At a slide the open
    sub-window's state (immutable jax arrays, as ``LazyWindowTop`` relies
    on) moves in here and a fresh one takes its place, so the update
    step, its shapes and its donation are a tumbling window's. The fold
    is one jitted program a model (``slide_fold_<name>``: K slots, the
    unused ones filled with the init state), and the fold of the closed
    states alone is kept from one slide to the next, so that a publish
    merges two states (``slide_view_<name>``) and does not read K."""

    def __init__(self, model, name: str, k: int):
        self.model, self.name, self.k = model, name, k
        # (sub-window start, its state or None where it saw no flow, the
        # state's checkpoint member or None), oldest first
        self.closed: deque = deque(maxlen=k - 1)
        self.last_sub: int | None = None  # the newest sub-window closed
        self._fold = model.fold_program(f"slide_fold_{name}", k)
        self._merge = model.fold_program(f"slide_view_{name}", 2)
        self._empty = model.empty_state()
        self.state_bytes = sum(
            x.nbytes for x in jax.tree.leaves(self._empty))
        self._closed_fold = None    # fold of the live closed states
        self._view = (None, None)   # (open state, its fold with them)

    def live(self) -> list:
        return [s for _sub, s, _m in self.closed if s is not None]

    def _run_fold(self, states: list):
        with TRACER.span("slide_fold", model=self.name, states=len(states),
                         bytes=(self.k + 1) * self.state_bytes):
            return self._fold(
                tuple(states) + (self._empty,) * (self.k - len(states)))

    def fold(self, open_state):
        """The window that ends with the open sub-window: the closed
        states and ``open_state`` (None: it saw no flow) as one."""
        states = self.live()
        if open_state is not None:
            states.append(open_state)
        return self._run_fold(states)

    def view(self, open_state):
        """The same window for a reader between slides (a publish, a
        query): the kept fold of the closed states merged with the open
        one, once for each open state."""
        live = self.live()
        if not live:
            return open_state
        if self._view[0] is not open_state:
            if self._closed_fold is None:
                self._closed_fold = (live[0] if len(live) == 1
                                     else self._run_fold(live))
            self._view = (open_state,
                          self._merge((self._closed_fold, open_state)))
        return self._view[1]

    def rotate(self, sub: int, state) -> None:
        """Sub-window ``sub`` has closed with ``state`` (None: no flow);
        the oldest leaves once K - 1 are held."""
        full = 0 < self.closed.maxlen == len(self.closed)
        with TRACER.span(
                "ring_rotate", model=self.name, sub=sub,
                dropped_sub=self.closed[0][0] if full else None) as span:
            member = None if state is None else Member(
                f"{self.name}.{sub}", sub, self.model.state_arrays(state))
            self.closed.append((sub, state, member))
            self.last_sub = sub
            self._closed_fold, self._view = None, (None, None)
            # what the ring holds on the device beside the open state
            span["ring_bytes"] = (len(self.live()) + 1) * self.state_bytes

    # ---- checkpoint (engine/worker.py's family hooks) ---------------------

    def checkpoint_state(self) -> dict:
        """The ring as a checkpoint names it: each closed state is a
        member written once (``engine.checkpoint.Member``), so a
        checkpoint carries the open state alone."""
        return {"last_sub": self.last_sub,
                "subs": [sub for sub, _s, _m in self.closed],
                "members": [m for _sub, _s, m in self.closed]}

    def restore(self, ring: dict) -> None:
        self.closed.clear()
        for sub, arrays in zip(ring["subs"], ring["members"]):
            sub = int(sub)
            self.closed.append(
                (sub, None, None) if arrays is None else
                (sub, self.model.state_from_arrays(arrays),
                 Member(f"{self.name}.{sub}", sub, written=True)))
        self.last_sub = ring["last_sub"]
        self._closed_fold, self._view = None, (None, None)


class WindowedHeavyHitter(HeldUnits):
    """Windowed top-K: update(batch) per batch; flush() yields rows for
    closed windows. Tumbling: one reset sketch per window. Sliding
    (``slide_seconds`` > 0, a divisor of ``window_seconds``): one reset
    sketch per sub-window and, at every slide end e, the rows of the
    window [e - window_seconds, e) under ``timeslot`` e -
    window_seconds, from the fold of the ring with the closing state; a
    window that reaches back before the first flow is the fold of what
    there is, and one that holds no flow emits nothing. With
    ``slide_seconds`` = ``window_seconds`` (K = 1) the rows are a
    tumbling window's bit for bit. ``lateness`` (``-window.lateness``)
    holds a tumbling window that rolled open for its late rows
    (``models/held.py``); 0 closes it at the roll, as ever."""

    def __init__(self, config: HeavyHitterConfig = HeavyHitterConfig(),
                 window_seconds: int = SECONDS_PER_SLOT, k: int = 100,
                 model_cls=HeavyHitterModel, slide_seconds: int = 0,
                 slide_name: str = "hh", lateness: int = 0, **model_kw):
        self.config = config
        self.window_seconds = window_seconds
        self.k = k
        self.model = model_cls(config, **model_kw)
        # the table's name, for the close's span (a sharded backing
        # model is given its own as ``name``)
        self.name = model_kw.get("name", slide_name)
        # the grain ``current_slot`` rolls at: the window, or the slide
        self.slot_seconds = slide_seconds or window_seconds
        self.ring: SubWindowRing | None = None
        if slide_seconds:
            if slide_seconds < 0 or window_seconds % slide_seconds:
                raise ValueError(
                    f"slide_seconds must divide window_seconds "
                    f"{window_seconds}, got {slide_seconds}")
            if not hasattr(self.model, "fold_program") \
                    or hasattr(self.model, "mesh"):
                raise ValueError(
                    f"{model_cls.__name__} has no single-chip state for "
                    f"a sliding window's ring to hold")
            self.ring = SubWindowRing(self.model, slide_name,
                                      window_seconds // slide_seconds)
        self.current_slot: int | None = None
        # flowmesh capture seam (mesh/member.py): when set, a window
        # close hands (slot, backing model) to the hook INSTEAD of
        # extracting rows locally — per-shard state is merged
        # network-wide at the coordinator and extracted ONCE from the
        # merged sketch. None (the default) keeps the single-worker
        # behavior byte-identical.
        self.capture = None
        # sketchwatch seam (obs/audit.py): when set, a window close
        # first hands (closing slot, backing model) to the audit so the
        # sampled exact shadow cohort is sealed against EXACTLY the
        # state being closed — before capture/extraction/reset. Fires
        # on every close path (slot roll, forced flush, mesh resync).
        self.audit_hook = None
        # Ingest-runtime knob (engine.worker sets it in pipelined mode):
        # close windows as LazyWindowTop handles so extraction runs on
        # the background flusher instead of the update path. Only honored
        # when the backing model can capture its state (top_lazy).
        self.lazy_extract = False
        self._pending: list = []  # dicts, or LazyWindowTop when lazy
        # A closed sketch cannot reopen: a tumbling window's was reset at
        # its close, and a sliding window's closed sub-windows are in
        # the ring, folded and (under a checkpoint) written. So rows
        # older than the current slot (the window, or under a slide the
        # sub-window) and, under a lateness, than the held one before
        # it are DROPPED and counted in late_flows_dropped — unlike the
        # exact aggregator, which emits late partials. (The ring is
        # where a late row's sub-window still lives: it does not hold
        # one open yet, ROADMAP B-mech 1.)
        why = self._cannot_hold()
        if lateness and why:
            log.warning(
                "-window.lateness %d: table %s still drops the rows that "
                "arrive after their window rolled, and counts them in "
                "late_flows_dropped (%s)", lateness, self.name, why)
            lateness = 0
        self._init_held(lateness)

    def _cannot_hold(self) -> str | None:
        """Why this table cannot hold a rolled window open, in words."""
        if self.ring is not None:
            return ("under -window.slide a closed sub-window is in the "
                    "ring, folded and written")
        if not hasattr(self.model, "load_window_state"):
            return (f"{type(self.model).__name__} has no window state to "
                    f"set aside")
        if getattr(self.config, "hh_sketch", "table") == "invertible":
            return "hh_sketch=invertible keeps its planes in the host engine"
        return None

    # ---- models/held.py's hooks -------------------------------------------

    @property
    def _unit(self) -> int | None:
        return self.current_slot

    @property
    def _unit_seconds(self) -> int:
        return self.slot_seconds

    def _can_hold(self) -> bool:
        # a flowmesh member ships a window's state at the roll
        return self.lateness > 0 and self.capture is None

    def _adopt(self, slot: int) -> None:
        self.open(slot)

    def _close_open(self) -> None:
        self._close(self.current_slot)

    def _window_state(self):
        return self.model.window_state()

    def _load_window_state(self, state) -> None:
        self.model.load_window_state(state)

    def _reset_window(self) -> None:
        self.model.reset()

    def _close_held_state(self, slot: int, open_state):
        self._close(slot, reset=False)
        return open_state

    @property
    def window_start(self) -> int | None:
        """``timeslot`` of the window a reader sees now."""
        if self.current_slot is None:
            return None
        return self.current_slot + self.slot_seconds - self.window_seconds

    def view_state(self):
        """The state a reader sees now (a publish, a query): the open
        window's, which under a slide is the ring folded with the open
        sub-window."""
        state = self.model.window_state()
        return state if self.ring is None else self.ring.view(state)

    def top(self, k: int | None = None) -> dict:
        """Top-k rows of the window a reader sees now."""
        if self.ring is None:
            return self.model.top(k)
        return self.model.top_from(self.view_state(), k)

    def update(self, batch: FlowBatch) -> None:
        if len(batch) == 0:
            return
        # split rows by slot so each sketch covers exactly one (sub-)window
        times = batch.columns["time_received"].astype(np.int64)
        slots = times // self.slot_seconds * self.slot_seconds
        for slot in np.unique(slots):
            idx = np.flatnonzero(slots == slot)
            part = FlowBatch(
                {k: v[idx] for k, v in batch.columns.items()}, batch.partition
            )
            # late rows for a closed (reset) window: dropped, never
            # misattributed to the current window's timeslot
            unit = self.admit(int(slot), len(part))
            if unit == HELD:
                self.swap_held()
                self.model.update(part)
                self.swap_held()
            elif unit is not None:
                self.model.update(part)
        self.advance_watermark(int(times.max()))

    def open(self, slot: int) -> None:
        """Adopt ``slot`` as the open one (the first rows, or the first
        after a forced flush). Under a slide the sub-windows between the
        last one closed and ``slot`` saw no flow; their slide ends are
        emitted from what the ring still holds."""
        if self.ring is not None and self.ring.last_sub is not None:
            self._slide_over(self.ring.last_sub + self.slot_seconds, slot)
        self.current_slot = slot

    def _close(self, slot: int, reset: bool = True) -> None:
        """Close window ``slot`` from the state the model holds."""
        if self.audit_hook is not None:
            self.audit_hook(slot, self.model)
        if self.capture is not None:
            # mesh member: ship the window's raw sketch state; no local
            # row extraction (the coordinator extracts from the merge)
            self.capture(slot, self.model)
        elif self.ring is not None:
            state = self.model.window_state()
            self._emit_slide(slot, state)
            self.ring.rotate(slot, state)
        else:
            self._emit_window(slot)
        if reset:
            self.model.reset()

    def _emit_window(self, slot: int) -> None:
        """Queue the rows of the tumbling window ``slot``: one extraction
        (or, under lazy_extract, the handle that defers it to the
        flusher), ``slide_close``'s twin."""
        with TRACER.span("window_close", model=self.name,
                         slot=slot) as span:
            if self.lazy_extract and hasattr(self.model, "top_lazy"):
                self._pending.append(LazyWindowTop(
                    self.model.top_lazy(self.k), slot))
                return
            top = self.model.top(self.k)
            top["timeslot"] = np.full(
                len(top["valid"]), slot, dtype=np.uint64)
            span["rows"] = int(top["valid"].sum())
            # the largest sum the table emits: past 2^24 a float32 plane
            # no longer holds every integer (ops/cms.py)
            ranked = top.get("bytes")
            if ranked is not None and span["rows"]:
                span["bytes_max"] = float(ranked[top["valid"]].max())
            self._pending.append(top)

    def _emit_slide(self, sub: int, open_state) -> None:
        """Queue the rows of the window that ends with sub-window
        ``sub``: one fold, one extraction, through the pending list a
        tumbling close fills."""
        end = sub + self.slot_seconds
        timeslot = end - self.window_seconds
        with TRACER.span("slide_close", window_end=end) as span:
            span["states"] = len(self.ring.live()) + (
                open_state is not None)
            folded = self.ring.fold(open_state)
            model, k = self.model, self.k
            if self.lazy_extract:
                self._pending.append(LazyWindowTop(
                    lambda: model.top_from(folded, k), timeslot))
                return
            top = model.top_from(folded, k)
            top["timeslot"] = np.full(
                len(top["valid"]), timeslot, dtype=np.uint64)
            span["rows"] = int(top["valid"].sum())
            self._pending.append(top)

    def _slide_over(self, sub: int, upto: int) -> None:
        """Sub-windows [sub, upto) saw no flow: each still ends a window
        while the ring holds one that did."""
        while sub < upto and self.ring.live():
            self._emit_slide(sub, None)
            self.ring.rotate(sub, None)
            sub += self.slot_seconds

    def flush(self, force: bool = False) -> list:
        """Rows for closed windows (and the open one too, when force) —
        dicts, or unresolved LazyWindowTop handles under lazy_extract."""
        if force:
            if self.held_unit is not None:
                self.close_held()
            if self.current_slot is not None:
                self._close(self.current_slot)
                self.current_slot = None
        out, self._pending = self._pending, []
        return out
