"""The two readers of the lagged flows_5m drain (PR 25):
``batch_period_ms_p50`` and ``drain_lagged_share``. On fabricated spans,
on a program whose ``wagg_wait`` does not say what it left (the parent),
and in the CPU dry run of both tiny cells.

The tiny manifest may not be edited, so the dry runs use a manifest made
here: the tiny one plus these two entries of BENCHMARK.json, listed for
the tiny cells. (Entries of ``per_layer`` are looked up by name: every
later PR appends its own.)
"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import program_spans
from benchmark.layer_metrics import batch_period_ms_p50, drain_lagged_share

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
METRICS = ["batch_period_ms_p50", "drain_lagged_share"]
LAYER = "flows_5m drain + host fold"


def _entries() -> list:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    return [m for m in per_layer if m["name"] in METRICS]


def test_the_manifest_lists_both_under_the_drains_layer():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    assert [m["name"] for m in _entries()] == METRICS
    assert [m["better"] for m in _entries()] == ["lower", "higher"]
    for m in _entries():
        assert m["layer"] == LAYER
        assert m["moves"] == "sustained_flows_per_s"
        assert m["source"] == "program_span"
        # a later cell appends itself to a metric's list
        assert m["workloads"][:2] == ["estate-catchup", "estate-live"]
    assert LAYER in {m["layer"] for m in per_layer
                     if m["name"] not in METRICS}


def _run(spans, t_a=0.5, t_b=10.0):
    return types.SimpleNamespace(
        _program_spans=program_spans.Window(spans, t_a, t_b))


def test_batch_period_is_the_median_start_to_start_on_the_worker_thread():
    spans = [("apply", 0.1, 0.2, "w", 0, {}),      # before the window
             ("apply", 1.000, 1.010, "w", 1, {}),
             ("apply", 1.034, 1.040, "w", 2, {}),
             ("apply", 1.070, 1.080, "w", 3, {}),
             ("apply", 1.075, 1.076, "other", 3, {}),
             ("apply", 2.300, 2.310, "w", 4, {}),  # after a checkpoint
             ("apply", 2.335, 2.340, "w", 5, {})]
    assert batch_period_ms_p50.read(_run(spans)) == pytest.approx(35.5)
    assert batch_period_ms_p50.read(_run(spans[:2])) is None
    assert batch_period_ms_p50.read(_run([])) is None


def test_lagged_share_counts_drains_that_left_a_partial():
    wait = lambda t, **args: ("wagg_wait", t, t + 0.02, "w", 0, args)
    spans = [wait(0.1, folded=1, left=1),          # before the window
             wait(1.0, folded=1, left=1), wait(1.1, folded=2, left=1),
             wait(1.2, folded=2, left=0), wait(1.3, folded=1, left=1)]
    assert drain_lagged_share.read(_run(spans)) == pytest.approx(75.0)


def test_a_program_whose_wagg_wait_says_nothing_reads_none():
    spans = [("wagg_wait", 1.0, 1.03, "w", 0, {}),
             ("wagg_wait", 1.1, 1.13, "w", 0, {})]
    assert drain_lagged_share.read(_run(spans)) is None
    assert drain_lagged_share.read(_run([])) is None
    none = types.SimpleNamespace(_program_spans=None)  # spans not trusted
    assert drain_lagged_share.read(none) is None
    assert batch_period_ms_p50.read(none) is None


@pytest.fixture(scope="module")
def dry_runs(tmp_path_factory):
    """The result line of ``--trace 1`` for both tiny cells."""
    with open(os.path.join(HERE, "fixtures", "BENCHMARK.tiny.json")) as f:
        manifest = json.load(f)
    manifest["per_layer"] += [
        dict(m, workloads=["tiny-catchup", "tiny-live"])
        for m in _entries()]
    path = tmp_path_factory.mktemp("manifest") / "BENCHMARK.drain.json"
    path.write_text(json.dumps(manifest))
    out = {}
    for cell in ("tiny-catchup", "tiny-live"):
        p = subprocess.run(
            [sys.executable, "-m", "benchmark.run", "--workload", cell,
             "--seed", str(2**31 + 25), "--seconds", "4", "--trace", "1",
             "--manifest", str(path)],
            cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        out[cell] = json.loads(p.stdout.splitlines()[-1])
    return out


@pytest.mark.parametrize("cell", ["tiny-catchup", "tiny-live"])
def test_dry_run_reports_both(dry_runs, cell):
    line = dry_runs[cell]
    assert line["correct"] is True
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["batch_period_ms_p50"] > 0
    if cell == "tiny-catchup":
        # full batches: every probe lags but a close's; a close's and a
        # checkpoint's drain are the other kind, and at this size they are
        # most of the drains: ~70 batches, ~25 closes and ~35 checkpoints
        # in the window, whatever the machine (all three count flows)
        assert 20 < m["drain_lagged_share"] <= 100
    else:  # part-full batches while the loop keeps up: drained at once
        assert 0 <= m["drain_lagged_share"] < 100
