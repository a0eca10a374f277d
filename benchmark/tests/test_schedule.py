"""The phase lock: the same flow indices, and the same window closes,
whatever the seed; the flows themselves are the seed's own."""

import json
import os

import numpy as np
import pytest

from benchmark import manifest, schedule
from benchmark.flowgen import KeyTable, chunk_draws
from benchmark.modes import backlog, open_loop

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


STREAM = manifest.load_stream(
    ROOT, ["benchmark"], _load("configs", "default-estate.json")["stream"])


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


# ---- the catch-up cells' ceilings and layouts (PR 29) -----------------------
#
# What a backlog traffic file keeps at the rate its cell runs at and at the
# rates the queue's next changes are reckoned, or were measured, to sustain
# there (PERF.md 4 has where each number comes from). No run reads these: a
# PR that adds a backlog file brings its own beside its own test.

PROVISION_STEP = 64_000
# no close in the window's first 9 % at the rate the cell runs at, none
# within 9 % of the window either side of its end: three closes 1.92e7
# flows apart leave 10.6 % a side at best in the mesh cell's 4.87e7 flows
CLOSE_MARGIN = 0.09
BACKLOGS = {
    # holds: (flows/s, the factor of it that provision_flows_per_s covers)
    "backlog-drain": dict(
        config="default-estate", runs_at=556_000, closes=2,
        holds=[(938_000, 1.5),      # the device step alone, above A1's ~890k
               (1_070_000, 1.3)],   # A1 with A2's first third
        closes_hold_at=[890_000, 1_070_000]),
    "backlog-drain-mesh4": dict(
        config="estate-mesh4", runs_at=955_000, closes=3,
        holds=[(1_170_000, 1.5)],   # one placement a batch, measured (PR 27)
        closes_hold_at=[1_170_000]),
}


def _backlog(name):
    lay = BACKLOGS[name]
    return (_load("traffic", name + ".json"), manifest.load_stream(
        ROOT, ["benchmark"],
        _load("configs", lay["config"] + ".json")["stream"]), lay)


def _close_shares(traffic, stream, rate, seconds=51):
    """Where the closes fall when the worker takes ``rate`` flows/s: each
    as a share of a window of ``seconds x rate`` flows, up to one and a
    half windows."""
    p = backlog.plan(traffic, stream, seconds)
    lo, flows = p.window_start_flow, seconds * rate
    return [(c - lo) / flows for c in schedule.closes_between(
        stream, p.first_close_flow, p.phase_s, lo, lo + int(1.5 * flows))]


def _edge_closes(traffic, stream, rate):
    return [s for s in _close_shares(traffic, stream, rate)
            if s < CLOSE_MARGIN or abs(s - 1.0) < CLOSE_MARGIN]


@pytest.mark.parametrize("name", BACKLOGS)
def test_a_backlog_holds_what_its_cell_is_next_to_sustain(name):
    traffic, _stream, lay = _backlog(name)
    assert traffic["provision_flows_per_s"] % PROVISION_STEP == 0
    for rate, factor in lay["holds"]:
        assert traffic["provision_flows_per_s"] >= factor * rate, rate


@pytest.mark.parametrize("name", BACKLOGS)
def test_the_closes_keep_clear_of_the_windows_edges(name):
    traffic, stream, lay = _backlog(name)
    assert _edge_closes(traffic, stream, lay["runs_at"]) == []
    inside = [s for s in _close_shares(traffic, stream, lay["runs_at"])
              if s < 1.0]
    assert len(inside) == lay["closes"]


@pytest.mark.parametrize("name,rate", [
    (name, rate) for name, lay in BACKLOGS.items()
    for rate in [lay["runs_at"], *lay["closes_hold_at"]]])
def test_the_count_of_closes_holds_across_a_cells_spread(name, rate):
    """3 % either way, at the rate the cell runs at and at each rate the
    queue's next changes are reckoned to bring."""
    traffic, stream, _lay = _backlog(name)
    counts = {sum(1 for s in _close_shares(traffic, stream, rate * f)
                  if s < 1.0) for f in (0.97, 1.0, 1.03)}
    assert len(counts) == 1 and counts.pop() >= 1, (rate, counts)


@pytest.mark.parametrize("name", BACKLOGS)
@pytest.mark.parametrize("into_flows", [64_000, 27_000_000])
def test_a_close_at_an_edge_is_found(name, into_flows):
    traffic, stream, lay = _backlog(name)
    moved = dict(traffic, first_close_into_flows=into_flows)
    assert _edge_closes(moved, stream, lay["runs_at"])


def test_backlog_window_starts_at_a_fixed_chunk_and_is_provisioned():
    traffic = _load("traffic", "backlog-drain.json")
    a = backlog.plan(traffic, STREAM, 51)
    assert a.window_start_flow == traffic["window_start_chunks"] * 32768
    assert a.window_start_flow > a.first_close_flow
    assert a.total_flows % 32768 == 0
    assert a.total_flows >= a.window_start_flow + 51 * 1_400_000
    # at the rate the tree runs at, two closes in every run, 11 % and 79 %
    # into the window; at what the queue's next changes are reckoned to
    # sustain, three; none in the run-in
    spec = schedule.spec_for(1, STREAM, a)
    lo = a.window_start_flow
    assert spec.close_flows(lo, lo + 51 * 556_000) == [3_521_536,
                                                       22_721_536]
    for rate in (890_000, 1_070_000):
        assert spec.close_flows(lo, lo + 51 * rate) == [
            3_521_536, 22_721_536, 41_921_536]
    assert spec.close_flows(a.first_close_flow + 1, lo) == []


def test_an_edge_close_is_refused():
    traffic = dict(_load("traffic", "live-knee80.json"),
                   first_close_into_s=[0.5], edge_guard_s=1.0)
    with pytest.raises(ValueError):
        open_loop.plan(traffic, STREAM, 51)


def test_the_flows_are_the_seeds_own_and_repeat():
    stream = STREAM.with_params(n_keys=5000)
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
    stream = STREAM.with_params(n_keys=500, chunk_flows=2048,
                                block_flows=1000)
    plan = backlog.plan(_load("traffic", "backlog-drain.json"), stream, 1)
    spec = schedule.spec_for(5, stream, plan)
    table = KeyTable(spec)
    whole = [np.concatenate([chunk_draws(spec, table, c)[i]
                             for c in range(4)]) for i in range(3)]
    wide = schedule.spec_for(5, stream.with_params(chunk_flows=4096), plan)
    for i in range(3):
        assert np.array_equal(
            whole[i], np.concatenate([chunk_draws(wide, table, c)[i]
                                      for c in range(2)]))
