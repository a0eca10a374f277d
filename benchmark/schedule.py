"""The phase lock: which flow indices a run warms up on and measures.

Everything here is arithmetic on flow indices, a function of the traffic
file, the configuration's stream and ``--seconds`` alone — never of the
seed or of anything a run observes. A window close is tied to the
stream's position (the first flow of a new slot closes the old one), so
fixing the indices fixes the closes.

A traffic file names its ``mode``; the module ``modes/<mode>.py`` (found
by that name under the manifest's ``paths``) makes the ``Plan`` and drives
the run. What the modes share is here: the plan's fields, and the choice
of the stream's phase that puts a close at a wanted flow index. ``stream``
is a ``manifest.Stream`` throughout: the configuration's ``stream`` object
with its kind beside it, whose spec says where slots roll.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Plan:
    mode: str
    seconds: float
    first_close_flow: int    # the warm-up's close
    phase_s: int             # event seconds the slot after it starts at
    window_start_flow: int
    window_flows: int        # flows offered in the window; 0: time decides
    backlog_flows: int       # flows put on the bus before anything is timed
    total_flows: int         # all flows made from the seed (chunk multiple)
    rate: float              # offered flows/s; 0: as fast as they are taken
    closes_expected: tuple   # close indices known to lie inside the window

    def due_offset(self, flow: int) -> float:
        """Seconds after T0 at which ``flow`` is due (``rate`` > 0)."""
        return (flow - self.backlog_flows) / self.rate


def ceil_to(n: int, chunk: int) -> int:
    return -(-n // chunk) * chunk


def phase_for(stream: dict, first_close_flow: int, close_flow: int):
    """(phase_s, the close's flow index): the stream's phase that rolls a
    slot at the first whole event second at or after ``close_flow``."""
    rate, slot = int(stream["event_rate"]), int(stream["slot_seconds"])
    secs = -(-(close_flow - first_close_flow) // rate)
    if secs <= 0:
        raise ValueError("the close must lie past the warm-up's close")
    return (-secs) % slot, first_close_flow + secs * rate


def closes_between(stream, first_close_flow: int, phase_s: int,
                   lo: int, hi: int) -> tuple:
    return tuple(stream.spec(0, first_close_flow, phase_s)
                 .close_flows(lo, hi))


def spec_for(seed: int, stream, plan: Plan):
    return stream.spec(seed, plan.first_close_flow, plan.phase_s)
