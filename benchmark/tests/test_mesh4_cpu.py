"""The four-chip cell (PR 26): ``estate-mesh4-catchup`` in the real
manifest, its readers on fabricated spans and programs, and the CPU dry
run of ``tiny-mesh4-catchup`` on four virtual devices, with the ``bf16``
control and with every new reader listed for it.

The tiny manifest may not be edited, so the traced dry run uses a
manifest made here: the tiny one plus this PR's entries of
BENCHMARK.json, listed for the tiny mesh cell.
"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import manifest, mesh_roofline, mesh_trace, program_spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "estate-mesh4-catchup"
TINY = os.path.join(HERE, "fixtures", "BENCHMARK.tiny.json")
# the checkpoint's two are every cell's readers, which list this cell too
SHARED_READERS = ["checkpoint_serialize_ms_p50", "checkpoint_raw_mb_p50"]
SPAN_READERS = ["mesh_shard_ms_per_batch", "mesh_dispatch_ms_per_batch",
                "mesh_drain_ms_p50", "chip_rows_min_share", *SHARED_READERS]
TRACE_READERS = ["mesh_hh_update_ms", "mesh_dense_update_ms",
                 "mesh_ddos_update_ms", "mesh_wagg_update_ms",
                 "mesh_rest_device_ms", "mesh_merge_device_ms_per_close",
                 "chip_busy_min_share"]
ROOFLINES = ["mesh_update_roofline", "mesh_merge_roofline"]
OLD_READERS = ["mesh_close_ms_p50", "device_ms_per_batch.mesh4"]
NEW = OLD_READERS + SPAN_READERS + TRACE_READERS + ROOFLINES


def _manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _reader(name):
    return manifest._load_reader(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


# ---- the real manifest ----------------------------------------------------


def test_the_cell_loads_with_four_chips_and_the_new_readers():
    cell = manifest.load_cell(ROOT, os.path.join(ROOT, "BENCHMARK.json"),
                              CELL)
    assert cell.chips == 4 and cell.config["chips"] == 4
    assert cell.config_name == "estate-mesh4"
    assert cell.traffic["mode"] == "backlog"
    assert [m["name"] for m, _r in cell.end_to_end] == [
        "sustained_flows_per_s", "setup_s"]
    names = [m["name"] for m, _r in cell.per_layer]
    assert set(NEW) <= set(names)
    # what the one-chip step's readers read is not listed for it
    assert "step_device_ms_p50" not in names
    assert "fused_step_roofline" not in names


def test_the_entries_are_there_and_name_only_this_cell():
    """Looked up by name: later PRs append entries of their own."""
    man = _manifest()
    (config,) = [c for c in man["configs"] if c["name"] == "estate-mesh4"]
    assert config["reduced"] == ["scale", "bus_partitions", "chips"]
    (cell,) = [w for w in man["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config="estate-mesh4",
                        traffic="backlog-drain-mesh4", chips=4)
    assert [w["chips"] for w in man["workloads"]].count(4) == 1
    entries = {m["name"]: m for m in man["per_layer"]}
    own = [name for name in NEW if name not in SHARED_READERS]
    for name in NEW:
        assert CELL in entries[name]["workloads"]
        assert entries[name]["moves"] == "sustained_flows_per_s"
    for name in own:
        assert entries[name]["workloads"] == [CELL]
    # no entry from before the cell names it, but the shared readers
    first = min(i for i, m in enumerate(man["per_layer"])
                if m["name"] in own)
    assert all(CELL not in m["workloads"]
               for m in man["per_layer"][:first]
               if "workloads" in m and m["name"] not in SHARED_READERS)
    for name in ROOFLINES:
        assert entries[name]["unit"] == "%"
        assert entries[name]["source"] == "device_trace"


def test_the_deployment_differs_from_the_one_chip_one_by_the_mesh_alone():
    with open(os.path.join(ROOT, "benchmark/configs/default-estate.json")) \
            as f:
        one = json.load(f)
    with open(os.path.join(ROOT, "benchmark/configs/estate-mesh4.json")) \
            as f:
        four = json.load(f)
    for key in ("stream", "guarantees", "checks", "close_table", "assumed",
                "bus_partitions", "topic"):
        assert four[key] == one[key], key
    assert four["processor_flags"] == one["processor_flags"] + [
        "-processor.mesh", "4"]
    assert four["chips"] == 4 and four["deployment"]["chips"] == 8
    assert set(four["reduced"]) == {"scale", "bus_partitions", "chips"}
    assert len(four["source"]) <= 200 and "BASELINE.json" in four["source"]
    assert four["spans"] == [["flow_pipeline_tpu.parallel.sharded",
                              "ShardedHeavyHitter", "merged_state",
                              "mesh_merge"]]


def test_the_traffic_is_laid_out_in_global_batches():
    with open(os.path.join(
            ROOT, "benchmark/traffic/backlog-drain-mesh4.json")) as f:
        t = json.load(f)
    assert (t["first_close_chunks"], t["run_in_chunks"]) == (8, 12)
    # whole global batches, so that every poll of a run is full and the
    # checkpoints (-flush.count batches apart) fall at fixed flows
    assert t["window_start_chunks"] % 4 == 0 \
        and t["window_start_chunks"] > t["run_in_chunks"]
    assert t["first_close_into_flows"] % 64000 == 0
    # the ceiling and where the closes fall: test_schedule.py, for every
    # backlog file. No checkpoint of the batch count inside the warm-up:
    # it would cost every run's set-up the seconds it takes
    assert t["window_start_chunks"] // 4 < 50
    assert t["trace"] == {"start_s": 2.0, "seconds": 14.0}
    assert t["generator_processes"] == 8 and t["provision_tail_chunks"] == 32


# ---- the span readers on fabricated spans ----------------------------------


def _span_run(spans, t_a=0.5, t_b=10.0):
    return types.SimpleNamespace(
        _program_spans=program_spans.Window(spans, t_a, t_b))


def _batch(t, shard_ms, update_ms, chip_rows):
    """One apply span at ``t`` holding two models' mesh spans."""
    out = [("apply", t, t + 0.1, "w", 1, {})]
    at = t + 0.001
    for model in ("a", "b"):
        out.append(("mesh_update", at, at + update_ms / 1e3, "w", None,
                    {"model": model, "steps": 1}))
        out.append(("mesh_shard", at, at + shard_ms / 1e3, "w", None,
                    {"model": model, "rows": sum(chip_rows),
                     "chip_rows": list(chip_rows), "bytes": 1}))
        at += update_ms / 1e3 + 0.001
    return out


def test_shard_and_dispatch_are_summed_by_batch():
    spans = (_batch(0.1, 9.0, 9.5, (8, 8, 8, 8))        # before the window
             + _batch(1.0, 2.0, 3.0, (8, 8, 8, 8))
             + _batch(2.0, 4.0, 4.5, (8, 8, 4, 0))
             + _batch(3.0, 2.0, 3.5, (8, 8, 8, 8))
             + [("apply", 4.0, 4.1, "w", 1, {}),        # an empty batch
                ("mesh_shard", 3.05, 3.06, "other", None,
                 {"chip_rows": [0, 0, 0, 9]})])         # another thread
    run = _span_run(spans)
    assert _reader("mesh_shard_ms_per_batch").read(run) \
        == pytest.approx(4.0)                           # 4, 8, 4
    assert _reader("mesh_dispatch_ms_per_batch").read(run) \
        == pytest.approx(2.0)                           # 2, 1, 3
    # rows by chip over the window: 48, 48, 40, 41 (the other thread's
    # span is a step like any other), mean 44.25
    assert _reader("chip_rows_min_share").read(run) \
        == pytest.approx(100.0 * 40 / 44.25)


def test_drain_and_checkpoint_read_their_spans():
    spans = [("mesh_drain", 1.0, 1.030, "w", None, {"partials": 1,
                                                      "left": 0}),
             ("mesh_drain", 2.0, 2.050, "w", None, {}),
             ("mesh_drain", 3.0, 3.040, "w", None, {}),
             ("ckpt_serialize", 4.0, 8.0, "w", None,
              {"raw_bytes": 53e6, "npz_bytes": 1e6})]
    run = _span_run(spans)
    assert _reader("mesh_drain_ms_p50").read(run) == pytest.approx(40.0)
    assert _reader("checkpoint_serialize_ms_p50").read(run) \
        == pytest.approx(4000.0)
    assert _reader("checkpoint_raw_mb_p50").read(run) \
        == pytest.approx(53.0)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_a_program_without_the_spans_reads_none(name):
    """The parent: an apply span and nothing of the mesh inside it."""
    parent = _span_run([("apply", 1.0, 1.1, "w", 1, {})])
    assert _reader(name).read(parent) is None
    untrusted = types.SimpleNamespace(_program_spans=None)
    assert _reader(name).read(untrusted) is None


# ---- the trace readers on fabricated programs -------------------------------


def _program_run(found, batches=2, kind="TPU v5 lite"):
    with open(os.path.join(ROOT, "benchmark/configs/estate-mesh4.json")) \
            as f:
        config = json.load(f)
    spans = types.SimpleNamespace(
        named=lambda name, a, b: [None] * batches)
    return types.SimpleNamespace(
        _mesh_programs=found, trace=object(), trace_span=(0.0, 1.0),
        spans=spans, device={"kind": kind, "platform": "tpu"},
        cell=types.SimpleNamespace(config=config))


def _chip(scale=1.0):
    ms = lambda v: v * scale * 1e6
    return [("mesh_hh_update_top_talkers", 0, ms(10)),
            ("mesh_hh_update_top_src_ips", 0, ms(6)),
            ("mesh_hh_update_top_talkers", 0, ms(12)),
            ("mesh_hh_update_top_src_ips", 0, ms(8)),
            ("mesh_dense_update_top_src_ports", 0, ms(2)),
            ("mesh_ddos_update", 0, ms(1)), ("mesh_ddos_close", 0, ms(1)),
            ("mesh_wagg_update", 0, ms(3)), ("mesh_wagg_update", 0, ms(5)),
            ("mesh_hh_merge_top_talkers", 0, ms(0.5)),
            ("mesh_dense_merge_top_src_ports", 0, ms(0.25)),
            ("dense_top", 0, ms(0.25))]


def test_device_time_by_family_rest_and_close():
    run = _program_run({"/device:TPU:0": _chip(), "/device:TPU:1": _chip()})
    read = lambda name: _reader(name).read(run)
    assert read("mesh_hh_update_ms") == pytest.approx(18.0)   # 36 / 2
    assert read("mesh_dense_update_ms") == pytest.approx(1.0)
    assert read("mesh_ddos_update_ms") == pytest.approx(1.0)  # + the close
    assert read("mesh_wagg_update_ms") == pytest.approx(4.0)
    assert read("mesh_rest_device_ms") == pytest.approx(0.5)
    assert read("mesh_merge_device_ms_per_close") == pytest.approx(0.75)


def test_rooflines_are_bytes_over_peak_over_one_execution_of_each():
    run = _program_run({"/device:TPU:0": _chip()})
    step_ms = 11 + 7 + 2 + 1 + 4  # medians; the sub-window close left out
    bytes_ = sum(mesh_roofline.mesh_update_bytes(run.cell.config).values())
    assert _reader("mesh_update_roofline").read(run) == pytest.approx(
        100.0 * bytes_ / 819e9 / (step_ms / 1e3))
    least, bound = mesh_roofline.merge_least_seconds(run.cell.config,
                                                     "TPU v5 lite")
    assert bound == "ici_bytes"
    assert _reader("mesh_merge_roofline").read(run) == pytest.approx(
        100.0 * least / 0.75e-3)
    with pytest.raises(KeyError, match="no peak"):
        _reader("mesh_merge_roofline").read(_program_run(
            {"/device:TPU:0": _chip()}, kind="TPU v9"))


def test_the_byte_counts_follow_the_flags():
    with open(os.path.join(ROOT, "benchmark/configs/estate-mesh4.json")) \
            as f:
        config = json.load(f)
    update = mesh_roofline.mesh_update_bytes(config)
    assert set(update) == {"hh", "dense", "ddos", "wagg"}
    assert all(v > 0 for v in update.values())
    # every model reads its own lanes: more than the fused step moves
    from benchmark import roofline

    assert sum(update.values()) > roofline.fused_step_bytes(config)
    merge = mesh_roofline.mesh_merge_bytes(config)
    cms = 4 * 3 * 65536 * 4
    assert merge["ici"] > 3 * (2 * 3 * cms // 4)  # three psums at least
    fewer = dict(config, processor_flags=config["processor_flags"]
                 + ["-model.ips=false", "-model.ports=false"])
    assert mesh_roofline.mesh_update_bytes(fewer)["dense"] == 0
    assert mesh_roofline.mesh_merge_bytes(fewer)["ici"] < merge["ici"] / 2


@pytest.mark.parametrize("name", TRACE_READERS + ROOFLINES)
def test_a_trace_without_the_names_reads_none(name):
    """The parent: every sharded program is ``per_chip``."""
    run = _program_run(None)
    run.trace = None
    assert _reader(name).read(run) is None


def test_program_names_come_from_the_module_events():
    assert mesh_trace.program_name(
        "jit_mesh_hh_update_top_talkers(1036164776253159876)") \
        == "mesh_hh_update_top_talkers"
    assert mesh_trace.program_name("jit_per_chip") == "per_chip"
    assert mesh_trace.is_update("mesh_ddos_update")
    assert not mesh_trace.is_update("mesh_ddos_close")
    assert mesh_trace.is_merge("mesh_dense_merge_top_dst_ports")
    assert not mesh_trace.is_merge("mesh_hh_update_top_talkers")


# ---- the CPU dry run on four virtual devices --------------------------------


def _bench(manifest_path, *extra):
    # seed 2^31+26 loses one flow of a slot's 20th key at the tiny
    # capacity (which flows a full table drops depends on the chip a row
    # sits on; PERF.md 6, PR 27); 2^31+27 reads 0
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "tiny-mesh4-catchup", "--seed", str(2**31 + 27), "--seconds", "3",
         "--manifest", str(manifest_path), *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.splitlines()[-1])


def test_the_tiny_mesh_cell_is_correct_and_the_bf16_control_is_not():
    line = _bench(TINY, "--trace", "0", "--control", "bf16")
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"] == dict(line["device"], platform="cpu", count=4)
    assert line["window"]["dataplane"] == "ShardedPipeline"  # since PR 27
    assert line["window"]["closes_at"]
    (control,) = line["controls"]
    assert control["correct"] is False


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """``--trace 1`` with this PR's entries listed for the tiny cell."""
    with open(TINY) as f:
        tiny = json.load(f)
    have = {m["name"] for m in tiny["per_layer"]}
    tiny["per_layer"] += [
        dict(m, workloads=["tiny-mesh4-catchup"])
        for m in _manifest()["per_layer"]
        if m["name"] in NEW and m["name"] not in have
        and m["name"] not in ROOFLINES]  # no peak for a CPU
    path = tmp_path_factory.mktemp("manifest") / "BENCHMARK.mesh4.json"
    path.write_text(json.dumps(tiny))
    return _bench(path, "--trace", "1")


@pytest.mark.parametrize("name", OLD_READERS + SPAN_READERS + TRACE_READERS)
def test_the_traced_dry_run_reports(traced, name):
    assert traced["correct"] is True
    value = traced["metrics"][name]["value"]
    assert value > 0
    if name.endswith("_share"):
        assert value <= 100.0
    if name == "checkpoint_raw_mb_p50":
        # four stacked replicas: the two 65536x3x2 int32 port planes
        # alone are 3.1 MB a chip, the three 4x3x4096 sketches 0.6 MB
        assert 4 * 3.7 < value < 4 * 5.0


def test_the_families_and_the_rest_sum_to_the_programs_time(traced):
    m = {k: v["value"] for k, v in traced["metrics"].items()}
    parts = sum(m[f"mesh_{f}_update_ms"] for f in
                ("hh", "dense", "ddos", "wagg")) + m["mesh_rest_device_ms"]
    assert parts > 0
    # the stand-in's executions overlap across its threads, so only the
    # order of magnitude can be held against the chips' busy time
    assert parts < 20 * m["device_ms_per_batch.mesh4"]
