"""A traffic mode added as a file only: the backlog mode with its first
close ``close_late_chunks`` further into the window."""

import dataclasses

from benchmark import schedule
from benchmark.modes import backlog

control, window_flows = backlog.control, backlog.window_flows


def plan(traffic: dict, stream: dict, seconds: float) -> schedule.Plan:
    later = int(traffic["close_late_chunks"]) * int(stream["chunk_flows"])
    base = backlog.plan(dict(traffic, first_close_into_flows=int(
        traffic["first_close_into_flows"]) + later), stream, seconds)
    return dataclasses.replace(base, mode=traffic["mode"])


def describe(run) -> dict:
    return {"close_late_chunks": run.cell.traffic["close_late_chunks"]}
