"""The benchmark's seam: `BENCHMARK.json`'s command run as the driver runs
it, at the tiny manifest's size on the CPU.

`benchmark/` decides every PR on the chip, and it reaches the program
through `cli.processor_main -listen.feed`, the in-process bus, the
result line's `window.dataplane` and the tracer's spans. A change that
breaks one of them should fail here, not come back from the chip as a
refused PR. Nothing under `benchmark/` is imported: each cell is run
once as a subprocess and its last line of output is what is judged. All
of it is one file, so that xdist's `--dist loadfile` gives it one worker.

A CPU dry run gives counts and correctness, never a rate: no value is
compared with anything here but for being a finite number.
"""

import json
import math
import os
import subprocess
import sys
from typing import NamedTuple

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = "benchmark/tests/fixtures/BENCHMARK.tiny.json"
# a manifest of its own beside it: estate-as64k-catchup's twin (ISSUE 31)
TINY_AS = "benchmark/tests/fixtures/BENCHMARK.tiny-as.json"
# and estate-sliding-catchup's (ISSUE 33)
TINY_SLIDING = "benchmark/tests/fixtures/BENCHMARK.tiny-sliding.json"
# and estate-2part-catchup's (ISSUE 39)
TINY_2PART = "benchmark/tests/fixtures/BENCHMARK.tiny-2part.json"
# and hh-backbone-catchup's (ISSUE 42)
TINY_BACKBONE = "benchmark/tests/fixtures/BENCHMARK.tiny-backbone.json"
# and estate-spread-catchup's (ISSUE 47)
TINY_SPREAD = "benchmark/tests/fixtures/BENCHMARK.tiny-spread.json"


class Cell(NamedTuple):
    ledger: str      # the ledger's cell of the same traffic kind
    dataplane: str   # what the worker picks for it
    seed: int
    devices: int     # virtual CPU devices; 0: the backend's one
    manifest: str = TINY
    control: str = ""  # controls compared beside the program's result
    seconds: int = 3   # of measured window


# 2^31+26 loses one flow of the mesh cell at the tiny capacity (PERF.md
# §6, PR 27)
CELLS = {
    "tiny-catchup": Cell("estate-catchup", "FusedPipeline", 2**31 + 11, 0),
    "tiny-live": Cell("estate-live", "FusedPipeline", 2**31 + 11, 0),
    "tiny-mesh4-catchup": Cell("estate-mesh4-catchup", "ShardedPipeline",
                               2**31 + 27, 4),
}
TRACED_CELL = "tiny-catchup"
# 256 ASes a side at the tiny size: run once, traced, for what the exact
# path counts
AS_CELL = "tiny-as-catchup"
# -window.slide 30 at the tiny size: run once, traced, with both controls
SLIDING_CELL = "tiny-sliding-catchup"
SLIDING_KIND = "ranked_bytes_sliding"
# two partitions, flows out of order, -window.lateness: run once, traced,
# with both controls
TWOPART_CELL = "tiny-2part-catchup"
# the host-pair family, three sampling rates and both address families:
# run once, traced, with both controls
BACKBONE_CELL = "tiny-backbone-catchup"
BACKBONE = "hh-backbone-catchup"
BACKBONE_KIND = "ranked_bytes_sampled"
# the spread detectors inside the fused step, on a stream with spreaders:
# run once, traced, with both controls
SPREAD_CELL = "tiny-spread-catchup"
SPREAD = "estate-spread-catchup"
SPREAD_KIND = "ranked_spread"
# the live and the four-chip twin, traced too (ISSUE 35: the spans of the
# serve path and of a publish of four stacked replicas)
LIVE_CELL, MESH_CELL = "tiny-live", "tiny-mesh4-catchup"
TRACED = {TRACED_CELL: CELLS[TRACED_CELL],
          LIVE_CELL: CELLS[LIVE_CELL],
          MESH_CELL: CELLS[MESH_CELL],
          AS_CELL: Cell("estate-as64k-catchup", "FusedPipeline", 2**31 + 11,
                        0, TINY_AS),
          SLIDING_CELL: Cell("estate-sliding-catchup", "FusedPipeline",
                             2**31 + 11, 0, TINY_SLIDING,
                             f"bf16,bf16:{SLIDING_KIND}", seconds=5),
          TWOPART_CELL: Cell("estate-2part-catchup", "FusedPipeline",
                             2**31 + 11, 0, TINY_2PART,
                             "bf16,bf16:ranked_bytes"),
          BACKBONE_CELL: Cell(BACKBONE, "FusedPipeline", 2**31 + 11, 0,
                              TINY_BACKBONE,
                              f"bf16,bf16:{BACKBONE_KIND}"),
          SPREAD_CELL: Cell(SPREAD, "FusedPipeline", 2**31 + 11, 0,
                            TINY_SPREAD, f"bf16,bf16:{SPREAD_KIND}")}
# they read the `XLA Modules` line of a /device:TPU plane: a CPU trace has
# none, and a CPU number never goes under a device metric's name
TPU_PLANE_ONLY = ("step_device_ms_p50", "fused_step_roofline",
                  "slide_fold_device_ms_per_slide", "slide_fold_roofline",
                  "step_device_ms_p50.2part", "fused_step_roofline.2part",
                  "hh_step_roofline", "spread_update_roofline")


# ISSUE 35's readers of the program's spans inside the layers that only
# the hooks timed: `BENCHMARK.json` lists each for the ledger's cells
INSIDE_METRICS = (
    "fetch_ms_p50", "feed_decode_us_per_kflow",
    "prefetch_queue_wait_ms_p50.live", "flow_age_at_apply_ms_p50.live",
    "split_parts_ms_p50", "close_extract_ms_per_close",
    "flush_rows_ms_per_close", "sink_records_ms_per_close",
    "sink_execute_ms_per_close", "sink_ledger_ms_per_close",
    "publish_loop_ms_per_min", "publish_view_ms_p50", "publish_view_mb_p50",
    "publish_swap_ms_p50", "publish_late_ms_p50.live",
    "publish_period_s_p50.live", "flow_age_at_publish_ms_p50.live")
# ISSUE 37's counter: the live bound's share of a family's group slots,
# from two args of `ckpt_state`; the fused step's, so the one-chip cells'
LIVE_SHARE = "cms_live_share"
ALL_LEDGER_CELLS = ["estate-catchup", "estate-live", "estate-mesh4-catchup",
                    "estate-as64k-catchup", "estate-sliding-catchup"]
ONE_CHIP = [c for c in ALL_LEDGER_CELLS if c != "estate-mesh4-catchup"]


def _manifest(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def _listed(entries, cell):
    return [e["name"] for e in entries
            if cell in e.get("workloads", [cell])]


def _twin(spec, cell) -> dict:
    """A twin's manifest: its fixture, and after it each per-layer metric
    that ``BENCHMARK.json`` has listed for the ledger's cell since the
    fixture was written (a PR appends to the manifest and edits no file
    the benchmark has), here listed for the twin."""
    man = _manifest(spec.manifest)
    if spec.manifest == TINY:
        # several cells' and no one's twin: it stands as it was written,
        # but for ISSUE 35's and 37's metrics, listed here for each cell
        # whose ledger cell lists them
        man["per_layer"] += [
            {**e, "workloads": [c for c, s in CELLS.items()
                                if s.ledger in e["workloads"]]}
            for e in _manifest("BENCHMARK.json")["per_layer"]
            if e["name"] in (*INSIDE_METRICS, LIVE_SHARE)]
        return man
    have = set(_listed(man["per_layer"], cell))
    man["per_layer"] += [
        {**e, "workloads": [cell]}
        for e in _manifest("BENCHMARK.json")["per_layer"]
        if spec.ledger in e.get("workloads", []) and e["name"] not in have]
    return man


@pytest.fixture(scope="module")
def dry_run(tmp_path_factory):
    """``dry_run(cell, trace)`` -> (exit code, stdout lines, stderr's
    end); each (cell, trace) runs once a module."""
    command = _manifest("BENCHMARK.json")["command"]
    if command[0] == "python3":
        command = [sys.executable, *command[1:]]
    done = {}

    def run(cell, trace=0):
        if (cell, trace) not in done:
            spec = CELLS.get(cell) or TRACED[cell]
            manifest = str(tmp_path_factory.mktemp(cell) / "manifest.json")
            with open(manifest, "w") as f:
                json.dump(_twin(spec, cell), f)
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            env.pop("XLA_FLAGS", None)  # conftest's eight devices
            if spec.devices:
                env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                                    f"{spec.devices}")
            p = subprocess.run(
                [*command, "--manifest", manifest, "--workload", cell,
                 "--seed", str(spec.seed), "--seconds", str(spec.seconds),
                 "--trace", str(trace),
                 *(["--control", spec.control] if spec.control else [])],
                cwd=ROOT, env=env, capture_output=True, text=True,
                timeout=300)
            lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
            done[cell, trace] = (p.returncode, lines, p.stderr[-2000:])
        return done[cell, trace]

    return run


def _result(dry_run, cell, trace=0):
    rc, lines, err = dry_run(cell, trace)
    assert rc == 0 and lines, err
    line = json.loads(lines[-1])
    assert isinstance(line, dict)
    return line


@pytest.mark.parametrize("cell", CELLS)
def test_dry_run_ends_in_one_correct_result_line(dry_run, cell):
    line = _result(dry_run, cell)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, [c for c in line["checks"]
                                     if not c["ok"]]
    assert line["failed"] == 0 < line["attempted"]
    assert line["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", CELLS)
def test_the_worker_picks_the_dataplane_the_ledgers_cell_runs(dry_run,
                                                              cell):
    # chosen by StreamWorker from the configuration's processor flags,
    # not by this test: what tier-1's own default worker is not
    assert (_result(dry_run, cell)["window"]["dataplane"]
            == CELLS[cell].dataplane)


@pytest.mark.parametrize("cell", CELLS)
def test_end_to_end_metrics_are_the_ones_the_ledger_judges(dry_run, cell):
    metrics = _result(dry_run, cell)["metrics"]
    judged = _listed(_manifest("BENCHMARK.json")["end_to_end"],
                     CELLS[cell].ledger)
    assert sorted(metrics) == sorted(judged)
    for name, m in metrics.items():
        assert isinstance(m["value"], (int, float)), (name, m)
        assert math.isfinite(m["value"]), (name, m)


@pytest.mark.parametrize("cell,metric", [
    (cell, metric) for cell, spec in TRACED.items()
    for metric in _listed(_twin(spec, cell)["per_layer"], cell)])
def test_traced_dry_run_reads_every_layer_metric(dry_run, cell, metric):
    """A metric its reader cannot read is left out of the line, and the
    ledger then holds a ``null`` for the PR."""
    line = _result(dry_run, cell, trace=1)
    assert line["correct"] is True
    if metric in TPU_PLANE_ONLY:
        assert metric not in line["metrics"]
    else:
        assert math.isfinite(line["metrics"][metric]["value"])


@pytest.mark.parametrize("cell", [AS_CELL, SLIDING_CELL, TWOPART_CELL,
                                  BACKBONE_CELL, SPREAD_CELL])
def test_a_twin_is_the_ledgers_cell_at_the_tiny_size(cell):
    """Every per-layer metric the ledger's cell reports, and no other:
    the fixture's own in the ledger's order, then those added since."""
    ledger = _listed(_manifest("BENCHMARK.json")["per_layer"],
                     TRACED[cell].ledger)
    fixture = _listed(_manifest(TRACED[cell].manifest)["per_layer"], cell)
    assert fixture == ledger[:len(fixture)]
    assert _listed(_twin(TRACED[cell], cell)["per_layer"], cell) == ledger


def test_the_as_twin_folds_a_large_store_into_a_checkpoint_of_few_members(
        dry_run):
    line = _result(dry_run, AS_CELL, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["window"]["dataplane"] == TRACED[AS_CELL].dataplane
    value = {name: m["value"] for name, m in line["metrics"].items()}
    # 16 ASes a side make 256 groups at most; 256 a side fill a batch
    assert value["fold_groups_per_batch"] > 256
    assert value["close_rows_flows5m"] > 256
    assert value["store_groups_p50"] > 10 * value["checkpoint_members_p50"]


def test_only_the_as_cell_reports_what_its_fold_inserted(dry_run):
    """ISSUE 34's counter: listed for estate-as64k-catchup alone, read
    there from the ``wagg_fold`` span, and small against the drain's rows
    (the steady state adds into rows that are there)."""
    (entry,) = [e for e in _manifest("BENCHMARK.json")["per_layer"]
                if e["name"] == "fold_inserted_per_batch"]
    assert entry["workloads"] == [TRACED[AS_CELL].ledger]
    value = {name: m["value"]
             for name, m in _result(dry_run, AS_CELL, trace=1)[
                 "metrics"].items()}
    assert 0 <= value["fold_inserted_per_batch"] < value[
        "fold_groups_per_batch"]
    for cell in (TRACED_CELL, SLIDING_CELL):
        assert "fold_inserted_per_batch" not in _result(
            dry_run, cell, trace=1)["metrics"]


def _values(dry_run, cell) -> dict:
    return {name: m["value"] for name, m in
            _result(dry_run, cell, trace=1)["metrics"].items()}


@pytest.mark.parametrize("metric", INSIDE_METRICS)
def test_an_inside_metric_lists_the_cells_that_have_its_span(metric):
    (entry,) = [e for e in _manifest("BENCHMARK.json")["per_layer"]
                if e["name"] == metric]
    assert entry["source"] == "program_span" and entry["better"] == "lower"
    if metric.endswith(".live"):
        assert entry["workloads"] == ["estate-live"]
        assert entry["moves"] == "query_staleness_p50_s"
        return
    assert entry["moves"] == "sustained_flows_per_s"
    # each list ends with the cells ISSUEs 42 and 47 added: they have
    # every span
    if metric == "split_parts_ms_p50":  # the fused pipeline's cut
        assert entry["workloads"] == [*ONE_CHIP, TRACED[TWOPART_CELL].ledger,
                                      BACKBONE, SPREAD]
    elif metric == "close_extract_ms_per_close":  # a tumbling close
        assert sorted(entry["workloads"]) == sorted(
            [*(c for c in ALL_LEDGER_CELLS if c != "estate-sliding-catchup"),
             BACKBONE, SPREAD])
    else:
        assert entry["workloads"] == [*ALL_LEDGER_CELLS, BACKBONE, SPREAD]


# the two-partition twin lists its ledger cell's metrics and no other:
# of ISSUE 35's and 37's it has the cut's alone
TILED = [c for c in TRACED if c != TWOPART_CELL]


@pytest.mark.parametrize("cell", TILED)
def test_the_inside_tiles_sum_under_their_outside_span(dry_run, cell):
    """Counts and order, not rates: a tile is never longer than what it
    tiles, and the hook round ``_write_rows`` holds every sink's part."""
    v = _values(dry_run, cell)
    listed = _listed(_twin(TRACED[cell], cell)["per_layer"], cell)
    assert set(INSIDE_METRICS) & set(listed) <= set(v)
    assert v["publish_view_ms_p50"] + v["publish_swap_ms_p50"] > 0
    assert v["publish_view_mb_p50"] > 0
    minutes = _result(dry_run, cell, trace=1)["window"]["seconds"] / 60
    assert v["publish_loop_ms_per_min"] * minutes >= v["publish_view_ms_p50"]
    # every closing chunk wrote flows_5m through sqlite and the ledger
    assert v["sink_records_ms_per_close"] > 0
    assert v["sink_execute_ms_per_close"] > 0
    assert v["sink_ledger_ms_per_close"] > 0
    assert v["fetch_ms_p50"] > 0 and v["feed_decode_us_per_kflow"] > 0
    if cell == SLIDING_CELL:
        assert "close_extract_ms_per_close" not in v
        assert v["slide_close_ms_p50"] > 0
    else:
        assert v["close_extract_ms_per_close"] > 0
    assert ("split_parts_ms_p50" in v) == (cell != MESH_CELL)


def test_the_live_share_is_listed_for_the_fused_steps_cells():
    (entry,) = [e for e in _manifest("BENCHMARK.json")["per_layer"]
                if e["name"] == LIVE_SHARE]
    assert entry == {
        "name": LIVE_SHARE, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "fused device step",
        "moves": "sustained_flows_per_s",
        "workloads": [*ONE_CHIP, BACKBONE, SPREAD]}


@pytest.mark.parametrize("cell", TILED)
def test_a_traced_line_says_what_share_of_the_slots_is_live(dry_run, cell):
    """Read from the checkpoints of the window wherever the fused step
    runs; a per-layer metric, so an untraced line has none, and the
    sharded programs hand out no bound."""
    v = _values(dry_run, cell)
    if cell == MESH_CELL:
        assert LIVE_SHARE not in v
    else:
        # some group is real, and a batch's groups leave slots over
        assert 0 < v[LIVE_SHARE] < 100
    if cell in CELLS:
        assert LIVE_SHARE not in _result(dry_run, cell)["metrics"]


def test_the_live_twin_carries_a_flows_age_to_the_snapshot(dry_run):
    v = _values(dry_run, LIVE_CELL)
    # bus -> pick-up -> the swap of the snapshot that first holds it
    assert 0 <= v["prefetch_queue_wait_ms_p50.live"] \
        <= v["flow_age_at_apply_ms_p50.live"] \
        < v["flow_age_at_publish_ms_p50.live"]
    assert v["publish_late_ms_p50.live"] >= 0
    # the tiny configuration's refresh, and a loop that asks once a batch
    assert v["publish_period_s_p50.live"] > 0
    assert v["publish_view_ms_p50"] + v["publish_swap_ms_p50"] \
        <= v["publish_ms_p50"] * 1.05 + 1.0
    for cell in (TRACED_CELL, MESH_CELL, AS_CELL, SLIDING_CELL,
                 TWOPART_CELL, BACKBONE_CELL):
        assert not [m for m in _values(dry_run, cell) if m in INSIDE_METRICS
                    and m.endswith(".live")]


def test_a_mesh_publish_captures_the_stacked_planes(dry_run):
    """Under a mesh a publish freezes the four chips' stacked count-min
    planes: four times what one chip's captures."""
    assert _values(dry_run, MESH_CELL)["publish_view_mb_p50"] \
        > 3 * _values(dry_run, TRACED_CELL)["publish_view_mb_p50"]


def test_the_sliding_twin_slides_on_the_fused_path(dry_run):
    """`-window.slide 30` through cli.processor_main: the fused dataplane,
    several slides inside the window, every one of them with rows, each
    closed sub-window written once and the checkpoint a window's size."""
    line = _result(dry_run, SLIDING_CELL, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["window"]["dataplane"] == TRACED[SLIDING_CELL].dataplane
    checks = {c["name"]: c for c in line["checks"]}
    assert checks["slide_windows_missing"]["value"] == 0
    assert checks["topk_bytes_max_rel_err"]["ok"]
    value = {name: m["value"] for name, m in line["metrics"].items()}
    assert value["slides_in_window"] >= 5 > value["window_closes_in_window"]
    assert value["slide_rows_per_close"] > 100
    assert value["compiles_in_window"] == 0
    # ten sub-window states on the device, one in a checkpoint (8.7
    # checkpoints' worth once the ring is full: the twin's 5 s hold the
    # slides that fill it under six workers too, where 3 s held the
    # median at a ring half full, 4.4 in PR 42's whole run)
    assert value["ring_mb_on_device"] > 5 * value[
        "checkpoint_raw_mb_p50.sliding"]
    assert 0 < value["checkpoint_member_mb_p50"] < value[
        "checkpoint_raw_mb_p50.sliding"]


@pytest.mark.parametrize("control", TRACED[SLIDING_CELL].control.split(","))
def test_the_sliding_twins_controls_come_out_not_correct(dry_run, control):
    """The reference one precision step down, in the program's place,
    fails the sliding tables' limit at the tiny size too; lowered for
    the sliding kind alone, it fails nothing else."""
    line = _result(dry_run, SLIDING_CELL, trace=1)
    found = next(c for c in line["controls"] if c["control"] == control)
    assert found["correct"] is False
    failed = {c["name"] for c in found["checks"] if not c["ok"]}
    assert "topk_bytes_max_rel_err" in failed
    assert "slide_windows_missing" not in failed
    if ":" in control:
        assert failed == {"topk_bytes_max_rel_err"}


def test_the_2part_twin_folds_its_late_rows_on_the_fused_path(dry_run):
    """Two partitions dealt round-robin, a tenth of the flows 1-3 s
    behind, `-window.lateness 9` through cli.processor_main: the harness
    counts thousands of flows that arrive after their slot rolled, no
    family drops one, every table is the reference's over exactly the
    flows consumed on both partitions, and the spans say the held units
    did the work."""
    line = _result(dry_run, TWOPART_CELL, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    window = line["window"]
    assert window["dataplane"] == TRACED[TWOPART_CELL].dataplane
    assert window["late_expected"] > 0
    assert set(window["late_by_model"].values()) == {0}
    assert len(window["folded"]) == 2 and min(window["folded"]) > 0
    assert window["folded"] == window["committed"]
    checks = {c["name"]: c["value"] for c in line["checks"]}
    assert checks["topk_bytes_max_rel_err"] < 1e-5
    assert checks["commits_ahead_of_flush"] == 0
    value = {name: m["value"] for name, m in line["metrics"].items()}
    assert value["compiles_in_window"] == 0
    assert value["late_rows_dropped"] == 0
    assert 0 < value["late_rows_folded_share"] < 50
    assert value["device_steps_per_batch.2part"] > 1
    assert value["batch_fill_share.2part"] < 100
    assert value["held_close_delay_ms_p50"] > 0
    assert 0 <= value["held_units_at_checkpoint_p50"] <= 6
    assert 0 <= value["partition_skew_s_p50"] <= 9


@pytest.mark.parametrize("control", TRACED[TWOPART_CELL].control.split(","))
def test_the_2part_twins_controls_come_out_not_correct(dry_run, control):
    line = _result(dry_run, TWOPART_CELL, trace=1)
    found = next(c for c in line["controls"] if c["control"] == control)
    assert found["correct"] is False
    failed = {c["name"] for c in found["checks"] if not c["ok"]}
    assert "topk_bytes_max_rel_err" in failed
    if ":" in control:
        assert failed == {"topk_bytes_max_rel_err"}


def test_the_backbone_twin_ranks_sampled_dual_stack_keys_on_the_fused_path(
        dry_run):
    """`-model.pairs` through cli.processor_main on a stream of both
    address families whose ranks carry one of three sampling rates: the
    four ranked tables (`top_pairs` among them) and `flows_5m`'s scaled
    columns are the reference's over exactly the flows consumed, and the
    new spans' readers say what the stream is."""
    line = _result(dry_run, BACKBONE_CELL, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["window"]["dataplane"] == TRACED[BACKBONE_CELL].dataplane
    checks = {c["name"]: c["value"] for c in line["checks"]}
    assert {"flows5m_mismatched_groups", "flows5m_scaled_mismatches",
            "unaccounted_flows", "topk_bytes_max_rel_err",
            "commit_offset_gap", "commits_ahead_of_flush",
            "query_mismatches"} == set(checks)
    assert checks["topk_bytes_max_rel_err"] < 1e-5
    assert all(v == 0 for n, v in checks.items()
               if n != "topk_bytes_max_rel_err")
    value = {name: m["value"] for name, m in line["metrics"].items()}
    assert value["compiles_in_window"] == 0
    assert 50 < value["v4_rows_share"] < 70     # v4_share 0.6 of the ranks
    assert value["fold_rates_per_batch"] == 3   # every drain mixes them
    assert value["ranked_sum_log2_max"] > 24    # past float32's integers
    assert value["table_admissions_per_batch"] >= 0
    assert 0 < value["step_pairs_merge_ms"] < value["step_table_merge_ms"]
    assert "hh_step_roofline" not in value and "step_ddos_ms" not in value


@pytest.mark.parametrize("control", TRACED[BACKBONE_CELL].control.split(","))
def test_the_backbone_twins_controls_come_out_not_correct(dry_run, control):
    line = _result(dry_run, BACKBONE_CELL, trace=1)
    found = next(c for c in line["controls"] if c["control"] == control)
    assert found["correct"] is False
    failed = {c["name"] for c in found["checks"] if not c["ok"]}
    assert "topk_bytes_max_rel_err" in failed
    if ":" in control:
        assert failed == {"topk_bytes_max_rel_err"}
    else:
        assert {"flows5m_mismatched_groups",
                "flows5m_scaled_mismatches"} <= failed


def test_the_spread_twin_runs_both_detectors_inside_the_fused_step(dry_run):
    """`-spread.enabled` through cli.processor_main on a stream with
    spreaders: the detectors' rows (typed tables of their own in the
    sink) are the reference's distinct counts within the limit over
    exactly the flows consumed, every large source among them; no host
    fold ran between dispatches; the new scopes, span and counter have
    their readers."""
    line = _result(dry_run, SPREAD_CELL, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["window"]["dataplane"] == TRACED[SPREAD_CELL].dataplane
    checks = {c["name"]: c for c in line["checks"]}
    assert {"flows5m_mismatched_groups", "flows5m_scaled_mismatches",
            "unaccounted_flows", "topk_bytes_max_rel_err",
            "spread_max_rel_err", "spread_heavy_rms_rel_err",
            "spread_missing_keys", "commit_offset_gap", "commits_ahead_of_flush",
            "query_mismatches"} == set(checks)
    assert 0 < checks["spread_max_rel_err"]["value"] \
        < checks["spread_max_rel_err"]["limit"]
    assert 0 < checks["spread_heavy_rms_rel_err"]["value"] \
        < checks["spread_heavy_rms_rel_err"]["limit"]
    assert all(c["value"] == 0 for n, c in checks.items()
               if n not in ("spread_max_rel_err", "spread_heavy_rms_rel_err",
                            "topk_bytes_max_rel_err"))
    assert {"superspreaders", "portscan"} <= set(
        line["window"]["late_by_model"])
    value = {name: m["value"] for name, m in line["metrics"].items()}
    assert value["compiles_in_window"] == 0
    assert value["spread_fold_ms_p50"] == 0     # nothing folds on the host
    assert value["step_spread_regs_ms"] > 0 and \
        value["step_spread_table_ms"] > 0
    assert value["spread_decode_ms_per_close"] > 0
    # both detectors' planes, a byte a register: 2 x (2 x 1024 x 64)
    assert value["spread_plane_mb_p50"] == pytest.approx(0.262144)
    assert value["checkpoint_raw_mb_p50"] > value["spread_plane_mb_p50"]
    assert "spread_update_roofline" not in value  # a share of the chip's


@pytest.mark.parametrize("control", TRACED[SPREAD_CELL].control.split(","))
def test_the_spread_twins_controls_come_out_not_correct(dry_run, control):
    """A source's flow count in the place of its distinct count."""
    line = _result(dry_run, SPREAD_CELL, trace=1)
    found = next(c for c in line["controls"] if c["control"] == control)
    assert found["correct"] is False
    failed = {c["name"]: c["value"] for c in found["checks"] if not c["ok"]}
    assert failed["spread_max_rel_err"] > 3.0
    assert failed["spread_heavy_rms_rel_err"] > 3.0
    if ":" in control:
        assert {"spread_max_rel_err", "spread_heavy_rms_rel_err"} <= set(
            failed) <= {"spread_max_rel_err", "spread_heavy_rms_rel_err",
                        "spread_missing_keys"}
    else:
        assert {"flows5m_mismatched_groups",
                "topk_bytes_max_rel_err"} <= set(failed)


def test_the_spread_readers_are_listed_for_their_cell_alone():
    new = {e["name"]: e for e in _manifest("BENCHMARK.json")["per_layer"]
           if e["name"] in ("step_spread_regs_ms", "step_spread_table_ms",
                            "spread_update_roofline",
                            "spread_decode_ms_per_close",
                            "spread_plane_mb_p50")}
    assert len(new) == 5
    for e in new.values():
        assert e["workloads"] == [SPREAD]
        assert e["moves"] == "sustained_flows_per_s"
    assert new["spread_update_roofline"]["unit"] == "%"
    assert {new[n]["source"] for n in ("step_spread_regs_ms",
                                       "step_spread_table_ms",
                                       "spread_update_roofline")} == {
        "device_trace"}


def test_the_detectors_own_runs_are_counted_where_polls_cross_sub_windows(
        dry_run):
    """PR 40's mechanism has its reader (ISSUE 42): listed for the
    two-partition cell alone, where two polls in five hold rows of two
    detector sub-windows."""
    (entry,) = [e for e in _manifest("BENCHMARK.json")["per_layer"]
                if e["name"] == "detector_dispatch_per_batch"]
    assert entry["workloads"] == [TRACED[TWOPART_CELL].ledger]
    assert entry["source"] == "program_counter"
    assert _values(dry_run, TWOPART_CELL)["detector_dispatch_per_batch"] > 0
    assert "detector_dispatch_per_batch" not in _values(dry_run,
                                                        BACKBONE_CELL)
