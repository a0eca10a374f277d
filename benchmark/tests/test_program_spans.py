"""The readers of the program's own spans and kernel scopes (PR 24): the
CPU dry run of both tiny cells reports every new metric, and the helpers
refuse what they cannot trust.

The tiny manifest may not be edited, so the dry runs use a manifest made
here: the tiny one plus the per-layer entries that BENCHMARK.json gained
with these readers, listed for the tiny cells.
"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import kernel_scopes, manifest, program_spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SPAN_METRICS = [
    "poll_wait_ms_p50", "feed_depth_mean", "lane_build_ms_p50",
    "spread_fold_ms_p50", "h2d_ms_p50", "step_dispatch_ms_p50",
    "batch_fill_share", "device_steps_per_batch", "drain_wait_ms_p50",
    "drain_copy_ms_p50", "fold_host_ms_p50", "checkpoint_state_ms_p50",
    "checkpoint_d2h_ms_p50", "checkpoint_serialize_ms_p50",
    "checkpoint_write_ms_p50", "checkpoint_commit_ms_p50",
    "checkpoint_raw_mb_p50", "loop_unowned_share"]
TRACE_METRICS = [
    "step_chain_sort_ms", "step_dst_sort_ms", "step_table_merge_ms",
    "step_dense_scatter_ms", "step_ddos_ms", "step_wagg_groupby_ms",
    "step_unscoped_share", "idle_unowned_share"]


def _new_entries() -> list:
    """PR 24's entries of BENCHMARK.json, looked up by name: later PRs
    append theirs."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    return [entries[name] for name in (
        *SPAN_METRICS[:-1], *TRACE_METRICS[:-1], "loop_unowned_share",
        "idle_unowned_share")]


def test_the_manifest_lists_every_one_of_these_metrics():
    assert len(_new_entries()) == len(SPAN_METRICS) + len(TRACE_METRICS)
    for m in _new_entries():
        assert m["moves"] == "sustained_flows_per_s"
        # a later cell appends itself to a metric's list
        assert m["workloads"][:2] == ["estate-catchup", "estate-live"]


@pytest.fixture(scope="module")
def dry_runs(tmp_path_factory):
    """The result line of ``--trace 1`` for both tiny cells."""
    with open(os.path.join(HERE, "fixtures", "BENCHMARK.tiny.json")) as f:
        manifest = json.load(f)
    manifest["per_layer"] += [
        dict(m, workloads=["tiny-catchup", "tiny-live"])
        for m in _new_entries()]
    path = tmp_path_factory.mktemp("manifest") / "BENCHMARK.spans.json"
    path.write_text(json.dumps(manifest))
    out = {}
    for cell in ("tiny-catchup", "tiny-live"):
        p = subprocess.run(
            [sys.executable, "-m", "benchmark.run", "--workload", cell,
             "--seed", str(2**31 + 24), "--seconds", "4", "--trace", "1",
             "--manifest", str(path)],
            cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        out[cell] = json.loads(p.stdout.splitlines()[-1])
    return out


@pytest.mark.parametrize("cell", ["tiny-catchup", "tiny-live"])
@pytest.mark.parametrize("metric", SPAN_METRICS + TRACE_METRICS)
def test_dry_run_reports_the_metric(dry_runs, cell, metric):
    line = dry_runs[cell]
    assert line["correct"] is True
    assert metric in line["metrics"], sorted(line["metrics"])
    value = line["metrics"][metric]["value"]
    assert isinstance(value, float) and value >= 0.0


@pytest.mark.parametrize("cell", ["tiny-catchup", "tiny-live"])
def test_dry_run_counts_and_shares_are_sane(dry_runs, cell):
    m = {k: v["value"] for k, v in dry_runs[cell]["metrics"].items()}
    assert m["device_steps_per_batch"] >= 1
    assert 0 < m["batch_fill_share"] <= 100
    assert 0 <= m["feed_depth_mean"] <= 2  # -feed.prefetch 2
    assert m["spread_fold_ms_p50"] == 0.0  # no spread family configured
    assert m["checkpoint_raw_mb_p50"] > 0
    for share in ("loop_unowned_share", "step_unscoped_share",
                  "idle_unowned_share"):
        assert 0 <= m[share] <= 100
    # the parts of a checkpoint are parts of the checkpoint
    parts = sum(m[f"checkpoint_{p}_ms_p50"] for p in
                ("state", "d2h", "serialize", "write", "commit"))
    assert 0.5 * m["checkpoint_ms_p50"] <= parts \
        <= 1.2 * m["checkpoint_ms_p50"]
    scopes = sum(m[k] for k in TRACE_METRICS[:6])
    assert scopes > 0


# ---- program_spans ------------------------------------------------------------


def _ring(recorded: int, capacity: int = 8):
    from flow_pipeline_tpu.obs.trace import TraceRecorder

    tracer = TraceRecorder(capacity=capacity, mode="ring")
    for i in range(recorded):
        tracer.record("apply", 1000.0 + i, 1000.5 + i, chunk=i, rows=i)
    return tracer


def test_a_ring_that_lost_the_windows_start_reads_none(capsys):
    # the window opened at wall 1000.25 (monotonic 0.25, offset 1000)
    whole = program_spans.held_spans(_ring(8), 0.25, 1000.0, 1000.0)
    assert [s[4] for s in whole] == list(range(8))
    assert whole[0][1:3] == (0.0, 0.5)  # on the monotonic clock
    assert program_spans.held_spans(_ring(9), 0.25, 1000.0, 1000.0) is None
    assert "overwritten the start" in capsys.readouterr().err
    # a window that opened after everything lost had ended is whole
    assert program_spans.held_spans(_ring(9), 1.5, 1000.0, 1000.0)


def test_a_stepped_wall_clock_reads_none(capsys):
    assert program_spans.held_spans(_ring(4), 0.25, 1000.0, 1000.0005)
    assert program_spans.held_spans(_ring(4), 0.25, 1000.0, 1000.002) \
        is None
    assert "wall clock moved" in capsys.readouterr().err


def test_a_tracer_without_the_ring_check_reads_none(capsys):
    class Parent:  # the tracer of a commit from before these spans
        def snapshot(self):
            return [("apply", 1000.0, 1000.5, "t", 1, None)]

    assert program_spans.held_spans(Parent(), 0.25, 1000.0, 1000.0) is None
    assert "cannot say" in capsys.readouterr().err


def test_window_reductions():
    spans = [
        ("apply", 1.0, 2.0, "w", 1, {"rows": 10}),
        ("step_dispatch", 1.1, 1.2, "w", 1, {"rows": 6, "padded": 8}),
        ("step_dispatch", 1.3, 1.4, "w", 1, {"rows": 4, "padded": 8}),
        ("step_dispatch", 1.5, 1.6, "other", 1, {"rows": 1, "padded": 8}),
        ("apply", 3.0, 4.0, "w", 2, {"rows": 8}),
        ("step_dispatch", 3.5, 3.6, "w", 2, {"rows": 8, "padded": 8}),
        ("apply", 9.0, 9.5, "w", 3, {"rows": 8}),  # after the window
    ]
    w = program_spans.Window(spans, 0.5, 5.0)
    assert w.ms("apply") == [1000.0, 1000.0]
    assert w.args("step_dispatch", "rows") == [6, 4, 1, 8]
    assert w.worker_thread() == "w"
    assert program_spans.per_parent(w, "apply", "step_dispatch") == [2, 1]
    assert program_spans.covered_s(
        [(1.0, 2.0), (1.5, 2.5), (4.0, 9.0)], 0.5, 5.0) == 2.5


def test_late_rows_are_the_held_share_of_both_of_the_detectors_spans():
    """A slot run's step carries the tables' rows and the detector's rows
    of its newest sub-window (``dd_rows``); each older sub-window rides a
    ``detector_dispatch`` of its own. The share is held over applied rows
    a kind of family, and the larger of the two."""
    read = manifest._load_reader(os.path.join(
        ROOT, "benchmark", "layer_metrics", "late_rows_folded_share.py")).read

    def step(t, rows, hh_unit, dd_unit, dd_rows):
        return ("step_dispatch", t, t + 0.01, "w", 1, {
            "rows": rows, "padded": 128, "hh_unit": hh_unit,
            "dd_unit": dd_unit, "dd_rows": dd_rows})

    def alone(t, rows, dd_unit):
        return ("detector_dispatch", t, t + 0.01, "w", 1, {
            "rows": rows, "padded": 128, "dd_unit": dd_unit})

    spans = [
        step(0.1, 100, "held", "held", 100),   # before the window
        step(1.0, 100, "open", "open", 100),
        alone(1.1, 30, "held"), step(1.2, 100, "open", "open", 70),
        alone(1.3, 10, "dropped"), step(1.4, 100, "open", "held", 90),
        step(1.5, 100, "held", "dropped", 0),
    ]
    run = types.SimpleNamespace(
        _program_spans=program_spans.Window(spans, 0.5, 5.0))
    # tables: 100 of 400 held; detector: 30 + 90 held of 100 + 30 + 70 + 90
    assert read(run) == pytest.approx(100.0 * 120 / 290)
    run._program_spans = program_spans.Window(spans[-1:], 0.5, 5.0)
    assert read(run) == pytest.approx(100.0)  # the detector applied none
    run._program_spans = program_spans.Window(
        [("apply", 1.0, 2.0, "w", 1, {"rows": 10})], 0.5, 5.0)
    assert read(run) is None
    run._program_spans = None
    assert read(run) is None


# ---- kernel_scopes --------------------------------------------------------------

HLO = """
HloModule jit_step

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %add.9 = f32[8]{0} add(%p, %p), metadata={op_name="jit(step)/hh_chain_sort/add"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/hh_chain_sort/add" stack_frame_id=3}
  %while.2 = (s32[], f32[8]{0}) while(%tuple.1), condition=%c, body=%b, metadata={op_name="jit(step)/hh_table_merge_1/jit(searchsorted)/while"}
  %reduce-window.3 = f32[8]{0} reduce-window(%fusion.1, %const.1), window={size=8}
  %copy.4 = f32[8]{0} copy(%reduce-window.3)
  %copy.5 = f32[8]{0} copy(%a), metadata={op_name="states[0][0].cms"}
  ROOT %fusion.6 = f32[8]{0} fusion(%copy.4), kind=kLoop, calls=%fc, metadata={op_name="jit(step)/wagg_groupby/jit(update)/scatter"}
}
"""


@pytest.mark.parametrize("instruction,scope", [
    ("fusion.1", "hh_chain_sort"),      # a fused op: its root's op_name
    ("while.2", "hh_table_merge"),      # families summed under one name
    ("reduce-window.3", "hh_chain_sort"),  # no metadata: first operand's
    ("copy.4", "hh_chain_sort"),        # ... through a chain of them
    ("copy.5", None),                   # metadata, no scope: a state copy
    ("fusion.6", "wagg_groupby"),
    ("a", None),
])
def test_scope_map(instruction, scope):
    assert kernel_scopes.scope_map(HLO)[instruction] == scope


def test_self_times_and_steps_by_scope():
    events = [("%while.2 = (s32[]) while(...)", 0, 100),
              ("%fusion.1 = f32[8] fusion(...)", 10, 30),  # in the while
              ("%copy.5 = f32[8] copy(...)", 50, 20),      # in the while
              ("%fusion.6 = f32[8] fusion(...)", 100, 40)]
    own = dict(kernel_scopes.self_times(events))
    assert own["%while.2 = (s32[]) while(...)"] == 50
    rows = kernel_scopes.step_scope_ms(
        [kernel_scopes.self_times(events)], kernel_scopes.scope_map(HLO))
    assert rows == [{"hh_chain_sort": 30 / 1e6, "hh_table_merge": 50 / 1e6,
                     kernel_scopes.UNSCOPED: 20 / 1e6,
                     "wagg_groupby": 40 / 1e6}]
    assert sum(rows[0].values()) == pytest.approx(140 / 1e6)


def test_overlap_of_gaps_and_spans():
    gaps = [(0, 10), (20, 30), (40, 50)]
    cover = [[5, 25], [45, 60]]
    assert kernel_scopes._overlap(gaps, cover) == 5 + 5 + 5
