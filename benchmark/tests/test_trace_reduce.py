"""The trace reduction on a small recorded trace: 0.45 s of the traced
run of estate-catchup on a TPU v5 lite (PR 23), device plane and the
benchmark's own host spans, names cut to the part before " = "."""

import json
import os

import pytest

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
SPANS = {"process", "flush_closed", "snapshot_and_commit", "publish",
         "decode", "bus_fetch", "sink_write"}


@pytest.fixture(scope="module")
def planes():
    with open(os.path.join(HERE, "fixtures", "trace_piece.json")) as f:
        return json.load(f)


def test_busy_steps_ops_and_gaps(planes):
    r = trace_reduce.reduce_planes(planes, SPANS, "jit_step", 0.45, "tpu")
    assert r.chips == 1 and r.window_s == 0.45
    assert 0.2 < r.busy_s < 0.3            # eight steps of ~33.7 ms
    assert len(r.step_ms) == 8
    assert all(33.0 < ms < 34.5 for ms in r.step_ms)
    ops = r.breakdown["device_ops"]
    assert len(ops) == 10 and ops[0][0].startswith("%while")
    assert ops == sorted(ops, key=lambda o: -o[1])
    gaps = r.breakdown["idle_gaps"]
    assert len(gaps) <= 10 and gaps == sorted(gaps, key=lambda g: -g[1])
    # the longest gaps are the dispatch thread inside flush_closed (the
    # flows_5m drain and fold), not an unowned wait
    assert gaps[0][0] == "flush_closed" and 0.03 < gaps[0][1] < 0.05


def test_busy_is_a_union_not_a_sum(planes):
    ops = planes["/device:TPU:0"]["XLA Ops"]
    doubled = {"/device:TPU:0": {"XLA Ops": ops + ops, "XLA Modules": []}}
    a = trace_reduce.reduce_planes(planes, SPANS, "jit_step", 0.45, "tpu")
    b = trace_reduce.reduce_planes(doubled, SPANS, "jit_step", 0.45, "tpu")
    assert b.busy_s == pytest.approx(a.busy_s)


def test_no_device_plane_is_the_cpu_stand_in_or_an_error():
    host = {"/host:CPU": {
        "tf_XLAPjRtCpuClient/1": [["dot.1", 0.0, 5e6], ["dot.2", 1e7, 5e6]],
        "python3": [["process", 0.0, 2e7], ["snapshot_and_commit", 5e6,
                                            5e6]]}}
    r = trace_reduce.reduce_planes(host, SPANS, "jit_step", 0.02, "cpu")
    assert r.busy_s == pytest.approx(0.01) and r.step_ms == []
    assert r.breakdown["idle_gaps"] == [["snapshot_and_commit", 0.005]]
    # the stand-in is the CPU dry run's alone: on a TPU a trace without a
    # device plane holds no device number, and saying so is an error
    with pytest.raises(ValueError, match="no /device:TPU plane"):
        trace_reduce.reduce_planes(host, SPANS, "jit_step", 0.02, "tpu")
