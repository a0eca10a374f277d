"""Traffic mode ``backlog``: the bus always holds more than the worker
can take. The window opens when the fetch position reaches
``window_start_chunks`` and closes at the first fetch ``--seconds`` or
more later. The first close inside it comes ``first_close_into_flows``
after it opens (to the next whole event second) and the next a slot
later, at the same flow indices in every run."""

from __future__ import annotations

import time

from benchmark import drive, schedule


def plan(traffic: dict, stream: dict, seconds: float) -> schedule.Plan:
    chunk = int(stream["chunk_flows"])
    k = int(traffic["first_close_chunks"]) * chunk
    i0 = int(traffic["window_start_chunks"]) * chunk
    if i0 <= k:
        raise ValueError("window_start_chunks must lie past "
                         "first_close_chunks")
    phase, _close = schedule.phase_for(
        stream, k, i0 + int(traffic["first_close_into_flows"]))
    total = schedule.ceil_to(
        i0 + int(float(traffic["provision_flows_per_s"]) * seconds)
        + int(traffic["provision_tail_chunks"]) * chunk, chunk)
    return schedule.Plan("backlog", seconds, k, phase, i0, 0, total, total,
                         0.0, ())


def control(run, chunks) -> None:
    plan, c = run.plan, run.spec.chunk_flows
    run_in = int(run.cell.traffic["run_in_chunks"]) * c
    drive.generate(run, chunks, plan.window_start_flow - run_in)
    drive.wait(run, lambda: run.sut.worker is not None
               and run.sut.worker.flows_seen
               >= plan.window_start_flow - run_in,
               "the warm-up flows to be folded")
    drive.produce(run, plan.window_start_flow - run_in, plan.total_flows)
    # the window opens at the fetch that reaches window_start_flow
    scan = drive.FetchScan(run)

    def opened():
        for t1, first, n in scan.new():
            if first >= plan.window_start_flow:
                run.t_a, run.pos_a = t1, first + n
                return True
        return False

    drive.wait(run, opened, "the fetch position to reach the window", 0.002)
    run.t_first_flow = run.t_a
    drive.log(f"window open at flow {run.pos_a}")
    times = drive.tracing(run, run.t_a)

    def closed():
        drive.drive_profiler(run, times, time.monotonic())
        for t1, first, n in scan.new():
            if t1 >= run.t_a + plan.seconds:
                run.t_b, run.pos_b = t1, first + n
                return True
            if first + n >= plan.total_flows:
                raise drive.Abort(
                    f"the backlog ran dry {t1 - run.t_a:.2f} s into the "
                    f"window, at {(first + n - run.pos_a) / (t1 - run.t_a):.0f}"
                    f" flows/s: raise provision_flows_per_s in the traffic "
                    f"file")
        return False

    drive.wait(run, closed, "the window to close", 0.002)
    if run.pos_b >= plan.total_flows - c:
        raise drive.Abort(
            f"the backlog ran dry as the window closed, at "
            f"{(run.pos_b - run.pos_a) / (run.t_b - run.t_a):.0f} flows/s: "
            f"raise provision_flows_per_s in the traffic file")
    run.rate_edges = [(run.t_a, run.pos_a), (run.t_b, run.pos_b)]
    drive.wait(run, lambda: run.sut.worker.flows_seen >= run.pos_b,
               "the flows taken in the window to be folded")


def window_flows(run) -> tuple:
    """The flows the window attempted: those taken between its edges."""
    return run.pos_a, run.pos_b


def describe(run) -> dict:
    return {}
