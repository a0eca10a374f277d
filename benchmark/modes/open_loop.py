"""Traffic mode ``open_loop``: flows ``[0, backlog)`` are on the bus at
once (warm-up: they hold the first close and a checkpoint), then flow
``i`` is due at ``T0 + (i - backlog) / rate``, offered every ``tick_s``
whether or not the worker keeps up (a flow's due time is its position's:
one partition only, ``run.py`` refuses more at plan time). The window is
the flows
``[window_start, window_start + rate * seconds)``, i.e. the wall interval
in which they are due; its first close is due ``first_close_into_s``
after it opens (the first of the file's candidates that keeps every close
``edge_guard_s`` clear of both edges)."""

from __future__ import annotations

import time

from benchmark import drive, schedule


def plan(traffic: dict, stream: dict, seconds: float) -> schedule.Plan:
    chunk = int(stream["chunk_flows"])
    k = int(traffic["first_close_chunks"]) * chunk
    rate = float(traffic["rate_flows_per_s"])
    n = int(round(rate * seconds))
    settle = int(round(rate * float(traffic["settle_s"])))
    guard = int(round(rate * float(traffic["edge_guard_s"])))
    # the warm-up's close, the backlog that holds it, the settle, then
    # the window
    i0 = k + 2 * chunk + settle
    for into_s in traffic["first_close_into_s"]:
        phase, _close = schedule.phase_for(
            stream, k, i0 + int(round(rate * float(into_s))))
        if schedule.closes_between(stream, k, phase, i0 - settle, i0):
            continue  # a slot would roll in the settle
        closes = schedule.closes_between(stream, k, phase, i0, i0 + n)
        if closes and all(c - i0 >= guard and i0 + n - c >= guard
                          for c in closes):
            return schedule.Plan(
                "open_loop", seconds, k, phase, i0, n, i0 - settle,
                schedule.ceil_to(i0 + n, chunk), rate, closes)
    raise ValueError(
        f"no first_close_into_s of {traffic['first_close_into_s']} keeps "
        f"every window close {traffic['edge_guard_s']} s clear of the edges "
        f"of a {seconds} s window at {rate} flows/s")


def control(run, chunks) -> None:
    plan, tr = run.plan, run.cell.traffic
    drive.generate(run, chunks, plan.backlog_flows)
    drive.wait(run, lambda: run.sut.worker is not None
               and run.sut.worker.flows_seen >= plan.backlog_flows,
               "the warm-up backlog to be folded")
    drive.freeze_heap()  # the bus's log grows by every flow offered
    tick = float(tr["tick_s"])
    t0 = run.t0_schedule = time.monotonic() + 0.25
    run.t_a = t0 + plan.due_offset(plan.window_start_flow)
    run.t_b = run.t_a + plan.seconds
    run.t_first_flow = run.t_a
    last = plan.window_start_flow + plan.window_flows
    times = drive.tracing(run, run.t_a)
    sent, j = plan.backlog_flows, 0
    drive.log(f"open loop at {plan.rate:.0f} flows/s; window opens in "
              f"{run.t_a - time.monotonic():.2f} s at flow "
              f"{plan.window_start_flow}")
    while sent < last:
        j += 1
        sched = t0 + j * tick
        now = time.monotonic()
        if now < sched:
            time.sleep(sched - now)
        now = time.monotonic()
        if now - drive.T_PROCESS > drive.RUN_LIMIT_S or run.error is not None:
            raise drive.Abort("open loop cut short")
        upto = min(last, plan.backlog_flows + int((now - t0) * plan.rate))
        if upto > sent:
            drive.produce(run, sent, upto)
            run.ticks.append((sched, now, upto - sent))
            sent = upto
        drive.drive_profiler(run, times, now)
    # the stream has ended: give the worker a bounded time to take what
    # was offered; what it has not committed by then counts as failed
    deadline = time.monotonic() + float(tr["drain_timeout_s"])
    drive.wait(run, lambda: run.sut.worker.flows_seen >= last
               or time.monotonic() > deadline,
               "the offered flows to be folded")
    # the rate is read between the first fetch at or after each edge of
    # the window, as in a backlog cell: right after a fetch the bus holds
    # only what the worker has not kept up with
    f = [(t1, pos) for t1, _p, _first, _n, pos in run.fetches()]
    run.rate_edges = [next((at for at in f if at[0] >= edge), f[-1])
                      for edge in (run.t_a, run.t_b)]
    run.pos_a, run.pos_b = run.rate_edges[0][1], run.rate_edges[1][1]


def window_flows(run) -> tuple:
    """The flows the window attempted: those offered in it."""
    lo = run.plan.window_start_flow
    return lo, lo + run.plan.window_flows


def describe(run) -> dict:
    plan = run.plan
    lag = [plan.backlog_flows + (t1 - run.t0_schedule) * plan.rate
           - pos for t1, _p, _first, _n, pos in run.fetches()
           if run.t_a <= t1 <= run.t_b]
    return {"backlog_max_flows": max(lag, default=0.0),
            "backlog_last_flows": lag[-1] if lag else 0.0,
            "reader": run.reader_stats}
