"""The exact-quantile arithmetic on a hand-made publish log."""

import pytest

from benchmark import staleness


def due(i):  # 10 flows/s from t = 100
    return 100.0 + i / 10.0


# (t_seen, version, flows_seen): v1 holds flows 0..9 (newest due 100.9)
LOG = [(101.0, 1, 10), (103.0, 2, 30), (104.0, 3, 40)]


def test_pieces_are_the_sawtooth():
    ps = staleness.pieces(LOG, due, 101.0, 105.0)
    # v1: 101.0 - 100.9 = 0.1 rising for 2 s; v2: 103 - 102.9 for 1 s;
    # v3: 104 - 103.9 for 1 s
    assert [(round(s, 9), d) for s, d in ps] == [
        (0.1, 2.0), (0.1, 1.0), (0.1, 1.0)]


def test_quantiles_are_exact():
    ps = staleness.pieces(LOG, due, 101.0, 105.0)
    # time at staleness <= s: 3 pieces rise together up to 1.1, then one
    # alone up to 2.1: F(s) = 3 (s - 0.1) for s <= 1.1, 3 + (s - 1.1) after
    assert staleness.quantile(ps, 0.5) == pytest.approx(0.1 + 2.0 / 3.0)
    assert staleness.quantile(ps, 0.75) == pytest.approx(1.1)
    assert staleness.quantile(ps, 0.95) == pytest.approx(1.1 + 0.8)
    assert staleness.quantile(ps, 1.0) == pytest.approx(2.1)
    assert staleness.mean(ps) == pytest.approx(
        (1.1 * 2 + 0.6 * 1 + 0.6 * 1) / 4.0)


def test_window_cuts_pieces_and_needs_a_visible_snapshot():
    ps = staleness.pieces(LOG, due, 102.0, 103.5)
    assert [(round(s, 9), round(d, 9)) for s, d in ps] == [
        (1.1, 1.0), (0.1, 0.5)]
    with pytest.raises(ValueError):
        staleness.pieces(LOG, due, 100.5, 103.0)


def test_a_stall_moves_the_tail_not_the_median():
    steady = [(float(t), t, 10 * t) for t in range(1, 61)]
    stalled = [e for e in steady if not 30 < e[0] < 36]  # one 6 s stall
    d = lambda i: i / 10.0  # noqa: E731
    a = staleness.pieces(steady, d, 1.0, 60.0)
    b = staleness.pieces(stalled, d, 1.0, 60.0)
    assert staleness.quantile(a, 0.5) == pytest.approx(0.6)
    assert staleness.quantile(b, 0.5) < 0.7
    assert staleness.quantile(b, 0.95) > 2.0 > staleness.quantile(a, 0.95)
