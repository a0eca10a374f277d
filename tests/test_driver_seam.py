"""Driver-seam guards: the round-4 artifact died in staging code no test
executed (`bench.py` staged [keys, values] by hand while the model read
config.scale_col too — KeyError at the first update). These tests run the
REAL driver entry points and the REAL bench staging paths at tiny shapes,
so a config-schema change that breaks the seam fails the suite instead of
the official artifact.

Methodology: bench's workload sizes are module-level constants precisely
so this file can shrink them (monkeypatch) and execute the genuine
functions end to end — replicating the staging logic here would guard
nothing.
"""

from __future__ import annotations

import json

import jax
import pytest

import __graft_entry__ as graft
import bench


def test_entry_compiles_and_runs():
    """The driver's single-chip compile check, verbatim."""
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    # state pytree comes back with the same structure
    assert type(out) is type(args[0])


def test_dryrun_multichip_small_mesh():
    """The driver's multi-chip dry run on a small virtual mesh (conftest
    forces the 8-device CPU platform)."""
    graft.dryrun_multichip(min(4, len(jax.devices())))


@pytest.fixture
def tiny_bench(monkeypatch):
    """Shrink every bench workload; tests run on the forced-CPU backend
    (conftest), so the platform is known without selecting it."""
    monkeypatch.setattr(bench, "_PLATFORM", "cpu")
    monkeypatch.setattr(bench, "HH_BATCH", 512)
    monkeypatch.setattr(bench, "HH_STAGED", 2)
    monkeypatch.setattr(bench, "HH_STEPS", 2)
    monkeypatch.setattr(bench, "E2E_FLOWS", 16384)
    monkeypatch.setattr(bench, "SWEEP_BATCHES_CPU", (512,))
    monkeypatch.setattr(bench, "SWEEP_STEPS", 2)
    monkeypatch.setattr(bench, "HH_SKETCH_PAIRS", 1)
    monkeypatch.setattr(bench, "SHARDED_PER_CHIP", 256)
    monkeypatch.setattr(bench, "SHARDED_STEPS", 2)
    return bench


def _last_json(capsys) -> dict:
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    return json.loads(lines[-1])


def test_bench_main_staging(tiny_bench, capsys):
    """`python bench.py` — the artifact the driver records every round
    (flagship step + the e2e legs it carries), naming its platform."""
    bench.main()
    out = _last_json(capsys)
    assert out["value"] > 0
    assert out["platform"] == "cpu"
    assert "(cpu, 1 device)" in out["metric"]
    assert out["e2e_flows_per_sec"] > 0


def test_bench_e2e_staging(tiny_bench, capsys):
    """`python bench.py e2e` — full pipeline with the default model set."""
    bench._run_e2e  # the shared path main() also records
    stats = bench._run_e2e(tiny_bench.E2E_FLOWS, samples=1)
    assert stats["value"] > 0


def test_bench_hostsketch_staging(tiny_bench, capsys):
    """`python bench.py hostsketch` — the r8 sketch-backend A/B artifact
    (BENCH_r08.json's producer) at tiny shapes."""
    bench.bench_hostsketch()
    out = _last_json(capsys)
    assert out["metric"].startswith("e2e sketch-backend A/B")
    assert out["host_flows_per_sec"] > 0
    assert out["device_flows_per_sec"] > 0
    assert "device_apply_share_device_pct" in out
    assert "host_note" in out


def test_bench_sweep_staging(tiny_bench, capsys):
    bench.bench_sweep()
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    outs = [json.loads(l) for l in lines]
    best = next(o for o in outs if o["metric"] == "hh sweep best")
    assert best["value"] > 0
    # the r16 sketch-family paired A/B rides the same artifact
    ab = outs[-1]
    if "error" not in ab:
        assert "admission_share_invertible_pct" in ab
        assert ab["invertible_flows_per_sec"] > 0
        assert "inv" in ab["host_fused_phases_invertible"]


@pytest.mark.slow  # ~9s of paired e2e legs; gated by `make fused-parity`
def test_bench_fused_staging(tiny_bench, monkeypatch, capsys):
    """`python bench.py fused` — the r10/r19 A/B artifact (BENCH_r19's
    producer) at tiny shapes: paired staged/fused legs, the flowspeed
    baseline-vs-threaded+C-lanes legs, the thread-scaling curve and the
    in-process lane-build sub-A/Bs all execute for real; only the
    subprocess SIMD A/B is stubbed (a novec compile + fresh interpreter
    spawns — its plumbing is exercised by the real bench run)."""
    monkeypatch.setattr(bench, "FUSED_PAIRS", 1)
    monkeypatch.setattr(bench, "FUSED_THREAD_POINTS", (2,))
    monkeypatch.setattr(bench, "_simd_ab",
                        lambda pairs=3: {"simd_ab_stubbed": True})
    real_lanes = bench._lane_build_native_ab
    monkeypatch.setattr(bench, "_lane_build_native_ab",
                        lambda: real_lanes(pairs=2, reps=2))
    real_r16 = bench._lane_build_ab
    monkeypatch.setattr(bench, "_lane_build_ab",
                        lambda: real_r16(pairs=2, reps=2))
    bench.bench_fused()
    out = _last_json(capsys)
    assert out["metric"].startswith("e2e fused-dataplane A/B")
    assert out["fused_flows_per_sec"] > 0
    assert out["staged_flows_per_sec"] > 0
    assert len(out["fused_pairs"]) == 1
    assert out["flowspeed_baseline_flows_per_sec"] > 0
    assert set(out["thread_scaling_flows_per_sec"]) == {"2"}
    assert out["lane_build_native_speedup"] > 0
    # the r19 attribution slot: the flowspeed leg built lanes in C
    assert "lanes" in out["host_group_phases_flowspeed"]
    assert out["host_group_phases_baseline"].get("lanes", 0.0) == 0.0
    assert "nproc" in out


def test_bench_kernels_staging(tiny_bench, capsys):
    """`python bench.py kernels` — the SIMD A/B's per-leg timing body
    (runs in subprocesses with FLOWDECODE_LIB in production)."""
    from flow_pipeline_tpu import native as native_lib

    if not native_lib.lanes_available():
        pytest.skip("libflowdecode lacks the r19 kernels")
    bench.bench_kernels()
    out = _last_json(capsys)
    assert out["metric"] == "r19 fused-kernel microbench"
    for key in ("inv_ns_per_row", "cms_ns_per_row", "lanes_ns_per_row"):
        assert out[key] > 0


def test_bench_sharded_staging(tiny_bench, capsys):
    n = min(4, len(jax.devices()))
    bench.bench_sharded(n)
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    outs = [json.loads(l) for l in lines]
    assert any("sharded heavy-hitter" in o["metric"] and o["value"] > 0
               for o in outs)
    assert any("sharded exact-agg" in o["metric"] and o["value"] > 0
               for o in outs)
