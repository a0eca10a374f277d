"""The dry run of the exact command on the CPU at tiny size, the control,
the refusals, and a run whose timed path is broken underneath."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = "benchmark/tests/fixtures/BENCHMARK.tiny.json"


def _run(workload, *extra, env_extra=None, cwd=ROOT, seconds="3"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    p = subprocess.run(
        [sys.executable if command[0] == "python3" else command[0],
         *command[1:], "--workload", workload, "--seed", str(2**31 + 11),
         "--seconds", seconds, "--manifest", TINY, *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, (json.loads(lines[-1]) if lines else None)


def _broken(line):
    return [c["name"] for c in line["checks"] if not c["ok"]]


def test_catchup_dry_run_and_control():
    p, line = _run("tiny-catchup", "--trace", "0", "--control",
                   "bf16,bf16:ranked_bytes")
    assert p.returncode == 0, p.stderr[-2000:]
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"sustained_flows_per_s", "setup_s"}
    assert line["window"]["closes_at"], "the window holds closes"
    limits = {c["name"]: c["limit"] for c in line["checks"]}
    assert limits["topk_bytes_max_rel_err"] == 1e-5  # from readings, not 1 %
    # the lower-precision control, under the same comparison, is not
    # correct: with every table in bf16 the exact table fails ...
    everything, sketches = line["controls"]
    assert everything["correct"] is False
    assert "flows5m_mismatched_groups" in _broken(everything)
    # ... and with the sketch path alone in bf16 (the exact table sound)
    # the ranked tables' bytes fail their own limit
    assert sketches["control"] == "bf16:ranked_bytes"
    assert sketches["correct"] is False
    assert _broken(sketches) == ["topk_bytes_max_rel_err"]


def test_a_mode_and_a_table_kind_added_as_files_run():
    """tiny-totals-catchup: its traffic's mode and one of its table kinds
    exist only under the fixtures' directory."""
    p, line = _run("tiny-totals-catchup", "--trace", "0")
    assert p.returncode == 0, p.stderr[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert line["window"]["mode"] == "backlog_close_late.fixture"
    assert line["window"]["close_late_chunks"] == 1
    by_name = {c["name"]: c for c in line["checks"]}
    assert by_name["slot_totals_mismatched"] == {
        "name": "slot_totals_mismatched", "value": 0, "limit": 0, "ok": True}


def test_live_traced_dry_run_reports_the_layer_metrics():
    p, line = _run("tiny-live", "--trace", "1", seconds="4")
    assert p.returncode == 0, p.stderr[-2000:]
    assert line["correct"] is True
    m = line["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    assert m["window_closes_in_window"]["value"] == 1
    assert m["checkpoints_per_min"]["value"] > 0
    assert "generator_late_ms_p99" in m and "staleness_p95_s.live" in m
    assert "sustained_flows_per_s" not in m  # end to end: --trace 0 only
    assert line["device"]["busy_s"] > 0 < line["device"]["window_s"]
    assert len(line["breakdown"]["idle_gaps"]) <= 10


def test_no_tpu_and_no_cpu_request_is_refused():
    p, line = _run("tiny-catchup", "--trace", "0",
                   env_extra={"JAX_PLATFORMS": ""})
    assert p.returncode != 0 and line is None


def test_alone_in_a_directory_is_refused(tmp_path):
    import shutil

    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p, line = _run("tiny-catchup", "--trace", "0", cwd=str(tmp_path))
    assert p.returncode != 0 and line is None


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    """Skips the harness's look for a chip (the CPU is asked for by name)
    and drives the rest of a run with the step broken underneath."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.chdir(ROOT)
    from flow_pipeline_tpu.engine.fused import FusedPipeline

    from benchmark import run as bench

    real = FusedPipeline._run_chunks
    calls = {"n": 0}

    def broken(self, part, do_hh, do_dd):
        calls["n"] += 1
        if fault == "half_batch":
            part = part.slice(0, len(part) // 2)  # leaves out half the rows
        elif calls["n"] % 2:
            return  # a step that returns its state unchanged
        return real(self, part, do_hh, do_dd)

    monkeypatch.setattr(FusedPipeline, "_run_chunks", broken)
    args = bench.argparse.Namespace(
        workload="tiny-catchup", seed=5, seconds=1.0, trace=0,
        manifest=TINY, control="", keep=False)
    result = bench.execute(args)
    assert result["correct"] is False
    assert "flows5m_mismatched_groups" in _broken(result)
