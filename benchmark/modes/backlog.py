"""Traffic mode ``backlog``: the bus always holds more than the worker
can take. The window opens when the fetch position (the count of flows
fetched over all partitions) reaches ``window_start_chunks`` and closes
at the first fetch ``--seconds`` or more later. The first close inside
it comes ``first_close_into_flows`` after it opens (to the next whole
event second) and the next a slot later, at the same flow indices in
every run."""

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
    drive.log("warm-up folded")
    drive.produce(run, plan.window_start_flow - run_in, plan.total_flows)
    drive.freeze_heap()  # the bus's log is one list of every frame now
    drive.log("backlog on the bus")
    # the window opens at the fetch that reaches window_start_flow
    scan = drive.FetchScan(run)

    def opened():
        for t1, _p, _first, n, pos in scan.new():
            if pos - n >= plan.window_start_flow:
                run.t_a, run.pos_a = t1, pos
                return True
        return False

    drive.wait(run, opened, "the fetch position to reach the window", 0.002)
    run.t_first_flow = run.t_a
    drive.log(f"window open at flow {run.pos_a}")
    times = drive.tracing(run, run.t_a)

    def closed():
        drive.drive_profiler(run, times, time.monotonic())
        for t1, _p, _first, _n, pos in scan.new():
            if t1 >= run.t_a + plan.seconds:
                run.t_b, run.pos_b = t1, pos
                return True
            if pos >= plan.total_flows:
                raise drive.Abort(
                    f"the backlog ran dry {t1 - run.t_a:.2f} s into the "
                    f"window, at {(pos - run.pos_a) / (t1 - run.t_a):.0f}"
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
    drive.wait(run, lambda: not run.deal.beyond(drive.folded(run), 0,
                                                run.pos_b),
               "the flows taken in the window to be folded")


def window_flows(run) -> tuple:
    """The flows the window attempted: those taken between its edges (on
    several partitions: as many, the positions between the two counts,
    every one of them folded before the stream ends)."""
    return run.pos_a, run.pos_b


def describe(run) -> dict:
    """Where the run stood against its ceiling and its layout: flows
    left on the bus at the close, and each checkpoint of the window as
    [seconds into the window, seconds it took], so that a window whose
    end fell inside one shows."""
    return {"backlog_left_flows": run.plan.total_flows - run.pos_b,
            "checkpoints_s": [[round(s[1] - run.t_a, 3),
                               round(s[2] - s[1], 3)]
                              for s in run.in_window("snapshot_and_commit")]}
