"""The phase lock: the same flow indices, and the same window closes,
whatever the seed; the flows themselves are the seed's own."""

import json
import os

import numpy as np
import pytest

from benchmark import schedule
from benchmark.flowgen import KeyTable, chunk_draws
from benchmark.modes import backlog, open_loop

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


STREAM = _load("configs", "default-estate.json")["stream"]


@pytest.mark.parametrize("seconds", [10, 25, 51])
def test_live_closes_sit_inside_the_window_at_fixed_flows(seconds):
    traffic = _load("traffic", "live-knee80.json")
    plan = open_loop.plan(traffic, STREAM, seconds)
    lo, n = plan.window_start_flow, plan.window_flows
    assert n == round(traffic["rate_flows_per_s"] * seconds)
    guard = traffic["edge_guard_s"] * traffic["rate_flows_per_s"] - 1
    specs = [schedule.spec_for(seed, STREAM, plan)
             for seed in (1, 2**31 + 5)]
    closes = [s.close_flows(lo, lo + n) for s in specs]
    assert closes[0] == closes[1] == list(plan.closes_expected)
    assert len(closes[0]) == 1  # one close a run, as a live stream has
    for c in closes[0]:
        assert lo + guard <= c <= lo + n - guard
    # the warm-up holds one close before the window, then a settle with none
    assert specs[0].close_flows(0, plan.backlog_flows) == [
        plan.first_close_flow]
    assert plan.backlog_flows < lo
    assert not specs[0].close_flows(plan.backlog_flows, lo)


def test_the_51_s_live_window_closes_20_s_in():
    traffic = _load("traffic", "live-knee80.json")
    plan = open_loop.plan(traffic, STREAM, 51)
    into = (plan.closes_expected[0] - plan.window_start_flow) \
        / traffic["rate_flows_per_s"]
    assert 20.0 <= into < 20.0 + STREAM["event_rate"] \
        / traffic["rate_flows_per_s"]


def test_a_close_is_where_event_time_says():
    plan = backlog.plan(_load("traffic", "backlog-drain.json"), STREAM, 51)
    spec = schedule.spec_for(3, STREAM, plan)
    k = plan.first_close_flow
    second = k + (spec.slot_seconds - spec.phase_s) * spec.event_rate
    ts = spec.event_ts(np.array([k - 1, k, second - 1, second,
                                 second + spec.slot_flows - 1,
                                 second + spec.slot_flows]))
    slots = ts // spec.slot_seconds
    assert slots[0] + 1 == slots[1] == slots[2] == slots[3] - 1
    assert slots[3] == slots[4] == slots[5] - 1
    assert spec.close_flows(0, second + spec.slot_flows + 1) == [
        k, second, second + spec.slot_flows]
    assert spec.close_flows(k + 1, second + 1) == [second]
    assert spec.close_flows(second + 1, second + 5) == []


def test_backlog_window_starts_at_a_fixed_chunk_and_is_provisioned():
    traffic = _load("traffic", "backlog-drain.json")
    a = backlog.plan(traffic, STREAM, 51)
    assert a.window_start_flow == traffic["window_start_chunks"] * 32768
    assert a.window_start_flow > a.first_close_flow
    assert a.total_flows % 32768 == 0
    assert a.total_flows >= a.window_start_flow + 51 * 600_000
    # one close of a whole window inside a 51 s drain at 300-520k flows/s,
    # seconds clear of both edges, none in the run-in
    spec = schedule.spec_for(1, STREAM, a)
    lo = a.window_start_flow
    for rate in (300_000, 456_000, 520_000):
        (close,) = spec.close_flows(lo, lo + 51 * rate)
        assert 5 * rate <= close - lo == 8_059_392 <= 46 * rate
    assert spec.close_flows(a.first_close_flow + 1, lo) == []


def test_an_edge_close_is_refused():
    traffic = dict(_load("traffic", "live-knee80.json"),
                   first_close_into_s=[0.5], edge_guard_s=1.0)
    with pytest.raises(ValueError):
        open_loop.plan(traffic, STREAM, 51)


def test_the_flows_are_the_seeds_own_and_repeat():
    stream = dict(STREAM, n_keys=5000)
    plan = backlog.plan(_load("traffic", "backlog-drain.json"), stream, 1)
    s1 = schedule.spec_for(2**31 + 7, stream, plan)
    s2 = schedule.spec_for(8, stream, plan)
    t1, t2 = KeyTable(s1), KeyTable(s2)
    # another seed: another key universe and other draws ...
    assert not np.array_equal(t1.src_host, t2.src_host)
    assert not np.array_equal(t1.dst_as, t2.dst_as)
    a, b = chunk_draws(s1, t1, 3), chunk_draws(s2, t2, 3)
    assert not any(np.array_equal(x, y) for x, y in zip(a, b))
    # ... the same seed: the same flows, whichever process makes the chunk
    again = chunk_draws(s1, KeyTable(s1), 3)
    assert all(np.array_equal(x, y) for x, y in zip(a, again))
    # ... and the same closes
    assert s1.close_flows(0, 10**8) == s2.close_flows(0, 10**8)


def test_chunks_cut_blocks_anywhere():
    """A chunk's draws do not depend on how chunks and blocks align."""
    stream = dict(STREAM, n_keys=500, chunk_flows=2048, block_flows=1000)
    plan = backlog.plan(_load("traffic", "backlog-drain.json"), stream, 1)
    spec = schedule.spec_for(5, stream, plan)
    table = KeyTable(spec)
    whole = [np.concatenate([chunk_draws(spec, table, c)[i]
                             for c in range(4)]) for i in range(3)]
    wide = schedule.spec_for(5, dict(stream, chunk_flows=4096), plan)
    for i in range(3):
        assert np.array_equal(
            whole[i], np.concatenate([chunk_draws(wide, table, c)[i]
                                      for c in range(2)]))
