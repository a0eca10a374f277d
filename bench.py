"""Throughput benchmark: flows/sec through the flagship heavy-hitter
aggregation step. Requires a TPU unless the CPU is asked for explicitly
(JAX_PLATFORMS=cpu) — utils.platform.select_platform, the CLI's rule —
and every record names the platform it ran on.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "flows/sec", "vs_baseline": N}

vs_baseline is against the reference's headline number — its production
pipeline ingests ">100k flows per second" (ref: README.md:91-92; the
docker-compose demo caps at "a few thousands rows per second",
ref: README.md:86-88). The north-star target is 1M flows/sec (BASELINE.json).

Methodology: pre-stage G generated batches on device (host generation and
transfer excluded — the metric is the aggregation tier, the part that
replaces ClickHouse's rollup), warm up the jit, then time a steady-state
update loop round-robining over the staged batches, including one window
close + top-K merge at the end, and block on the result.

Modes (default ``hh`` is what the driver records):

    python bench.py              # flagship heavy-hitter step, one JSON line
    python bench.py decode       # native host decode throughput
    python bench.py cms          # XLA scatter vs Pallas CMS updates (x4)
    python bench.py e2e          # full in-process pipeline flows/sec
    python bench.py hostsketch   # sketch.backend=device|host e2e A/B
    python bench.py fused        # ingest.fused=off|on host-backend A/B
    python bench.py flowtrace    # -obs.trace=off|ring overhead A/B +
                                 # host_fused in-kernel phase breakdown
    python bench.py audit        # -obs.audit=off|sample overhead A/B +
                                 # sketchwatch error-vs-fill sweep
    python bench.py sharded [n]  # n-device mesh rate + merge cost
    python bench.py mesh         # flowmesh 1/2/4-worker scaling curve
    python bench.py serve        # flowserve: concurrent query load
                                 # during full-rate ingest + paired
                                 # serve-on/off ingest A/B
    python bench.py sweep        # batch x width x impl tuning sweep
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

_PLATFORM = None
_NATIVE = False  # whether the C++ bulk codec was active for e2e/decode

# Load average above which a sample window is considered contended on this
# box: the timed loop is single-threaded, so anything past "one busy core +
# scheduler noise" means another process is stealing the core mid-window.
_BUSY_LOAD = 1.5


class _JsonLineTee:
    """Collects the mode functions' one-JSON-object-per-line streaming
    output while forwarding every completed line to stderr as live
    progress. ``__main__`` then renders ONE valid JSON document to the
    real stdout — multi-record modes (cms, sweep, fused...) used to
    leave ``BENCH_*.json`` artifacts as JSON-lines that ``json.load``
    rejects (the r19 fix; ``load_bench`` still reads the old shape)."""

    def __init__(self, progress):
        self.lines: list[str] = []
        self._progress = progress
        self._buf = ""

    def write(self, s: str) -> int:
        self._buf += s
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            if line.strip():
                self.lines.append(line)
                print(line, file=self._progress)
        return len(s)

    def flush(self) -> None:
        self._progress.flush()

    def finish(self) -> list:
        """Remaining partial line, then every line parsed. A non-JSON
        stdout line would already have corrupted redirected artifacts;
        now it is forwarded to stderr and kept OUT of the document."""
        if self._buf.strip():
            self.lines.append(self._buf)
            print(self._buf, file=self._progress)
        self._buf = ""
        records = []
        for line in self.lines:
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                print(f"bench: non-JSON stdout line dropped from "
                      f"artifact: {line!r}", file=self._progress)
        return records


def _render_document(records: list) -> str:
    """One valid JSON document: a bare object for single-record modes
    (the unchanged r08+ artifact shape), a one-record-per-line array
    for multi-record modes (grep- and diff-friendly, json.load-able)."""
    if len(records) == 1:
        return json.dumps(records[0])
    return "[\n" + ",\n".join(json.dumps(r) for r in records) + "\n]"


def load_bench(path: str) -> list:
    """Read a ``BENCH_*.json`` artifact as a list of records: a single
    valid JSON document (object -> [object], array -> the list — the
    r19 writer's shapes) OR the pre-r19 JSON-lines layout."""
    with open(path) as f:
        text = f.read()
    if not text.strip():
        return []
    try:
        doc = json.loads(text)
        return doc if isinstance(doc, list) else [doc]
    except json.JSONDecodeError:
        return [json.loads(line) for line in text.splitlines()
                if line.strip()]

# Workload sizes, module-level so the driver-seam guard test
# (tests/test_driver_seam.py) can run every REAL staging path at tiny
# shapes — the round-4 artifact died in staging code no test executed.
HH_BATCH = 32768
HH_STAGED = 8
HH_STEPS = 48
E2E_FLOWS = 400_000
# bench_fused's r19 legs: paired-A/B pair count and the -ingest.threads
# scaling points (module-level so the driver-seam guard test can run
# the REAL staging paths at tiny shapes)
FUSED_PAIRS = 3
FUSED_THREAD_POINTS = (1, 2, 4, 8)
SWEEP_BATCHES_CPU = (16384,)
SWEEP_STEPS = 24
SHARDED_PER_CHIP = 16384
SHARDED_STEPS = 24


def _host_conditions() -> dict:
    """Snapshot of the things that make a one-shot number untrustworthy."""
    try:
        load1 = os.getloadavg()[0]
    except OSError:  # pragma: no cover - non-POSIX
        load1 = -1.0
    return {"nproc": os.cpu_count() or 1, "load1": round(load1, 2)}


def _timed_samples(step, *, samples: int = 5) -> dict:
    """Run ``step() -> flows_processed`` repeatedly and fold the rates.

    A single perf_counter window is hostage to whatever else the box is
    doing (the round-2 driver artifact under-reported by ~45% because of a
    concurrent process); the median of >=5 windows plus the recorded
    spread makes the artifact self-diagnosing. Host load is snapshotted
    before AND after: a busy box is annotated, never silently reported.
    """
    before = _host_conditions()
    step()  # one untimed pass: first-touch allocations, cache warm-up
    rates = []
    for _ in range(samples):
        t0 = time.perf_counter()
        res = step()
        dt = time.perf_counter() - t0
        # a step may pre-time itself (excluding setup like bus production)
        flows, dt = res if isinstance(res, tuple) else (res, dt)
        rates.append(flows / dt)
    after = _host_conditions()
    med = statistics.median(rates)
    spread = (max(rates) - min(rates)) / med if med else 0.0
    out = {
        "value": round(med, 1),
        "samples": len(rates),
        "min": round(min(rates), 1),
        "max": round(max(rates), 1),
        "spread_pct": round(spread * 100, 1),
        "nproc": before["nproc"],
        "load1_before": before["load1"],
        "load1_after": after["load1"],
    }
    if max(before["load1"], after["load1"]) > _BUSY_LOAD:
        out["contended"] = True  # treat `value` with suspicion; rerun idle
    return out


# Nominal per-chip peaks (dense bf16 FLOP/s, HBM bytes/s) keyed by
# device_kind substring — public spec-sheet numbers used only to turn a
# measured rate into a utilization estimate. The workload is f32
# sort/scatter-heavy, so MFU vs the bf16 MXU peak is an upper-bound
# denominator; the HBM row is usually the binding roofline here.
_CHIP_PEAKS = {
    "v5 lite": (197e12, 819e9),   # v5e
    "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v4": (275e12, 1228e9),
    "v6": (918e12, 1640e9),       # Trillium
}


def _roofline_fields(lowerable, steps_per_sec: float, *args, **kwargs) -> dict:
    """XLA cost-analysis roofline for one compiled step (VERDICT r2 #1).

    Lowers ``lowerable`` for the given args, reads the compiler's
    flops / bytes-accessed estimates, and converts the measured rate into
    achieved TFLOP/s + GB/s. On a TPU the fields additionally carry
    MFU / HBM-utilization percentages against the chip's nominal peaks —
    a device_kind missing from _CHIP_PEAKS is an error, not a skip; on an
    explicit CPU run only the absolute per-step costs land in the
    artifact (they size the program the chip will run)."""
    import jax

    ca = lowerable.lower(*args, **kwargs).compile().cost_analysis()
    flops = float(ca.get("flops", 0.0))
    bytes_acc = float(ca.get("bytes accessed", 0.0))
    out = {
        "flops_per_step": round(flops),
        "bytes_per_step": round(bytes_acc),
        "achieved_tflops": round(flops * steps_per_sec / 1e12, 4),
        "achieved_membw_gbps": round(bytes_acc * steps_per_sec / 1e9, 2),
    }
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return out
    kind = dev.device_kind.lower()
    peaks = next((v for sub, v in _CHIP_PEAKS.items() if sub in kind), None)
    if peaks is None:
        raise RuntimeError(
            f"no _CHIP_PEAKS entry for device_kind {dev.device_kind!r}")
    peak_f, peak_b = peaks
    out["mfu_pct"] = round(100 * flops * steps_per_sec / peak_f, 3)
    out["hbm_util_pct"] = round(100 * bytes_acc * steps_per_sec / peak_b, 1)
    out["peak_ref"] = f"{kind} nominal bf16 {peak_f/1e12:.0f}TF " \
                      f"/ {peak_b/1e9:.0f}GB/s"
    return out


def _ensure_native() -> bool:
    """Build the native decode library if it is missing (fresh boxes).

    The e2e/decode artifacts are meaningless without the C++ bulk codec
    — round 3 started on a box where it simply had not been built and
    the first e2e measurement came out 5x low. A failed build or an
    unloadable library fails the run."""
    from flow_pipeline_tpu import native

    if native.available():
        return True
    import subprocess

    subprocess.run(
        ["make", "-C", os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "native")],
        check=True, capture_output=True, timeout=120,
    )
    native.reload()
    if not native.available():
        raise RuntimeError("libflowdecode.so built but did not load")
    return True


def _select_platform() -> str:
    """The CLI's platform rule (utils.platform.select_platform: explicit
    CPU request or a TPU, else exit; places the compile cache), memoized."""
    global _PLATFORM
    if not _PLATFORM:
        from flow_pipeline_tpu.utils.platform import select_platform

        _PLATFORM = select_platform()
    return _PLATFORM


def main() -> None:
    platform = _PLATFORM or _select_platform()
    import jax
    import jax.numpy as jnp

    from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
    from flow_pipeline_tpu.models import heavy_hitter as hh

    BATCH, STAGED, STEPS = HH_BATCH, HH_STAGED, HH_STEPS

    config = hh.HeavyHitterConfig(
        key_cols=("src_addr", "dst_addr"),
        batch_size=BATCH,
        width=1 << 16,
        capacity=1024,
    )
    gen = FlowGenerator(ZipfProfile(n_keys=100_000, alpha=1.1), seed=0)
    staged = []
    for _ in range(STAGED):
        b = gen.batch(BATCH)
        cols = b.device_columns(hh.input_cols(config))
        cols = {k: jax.device_put(jnp.asarray(v)) for k, v in cols.items()}
        staged.append(cols)
    valid = jax.device_put(jnp.ones(BATCH, bool))

    state = hh.hh_init(config)
    # warmup / compile
    state = hh.hh_update(state, staged[0], valid, config=config)
    jax.block_until_ready(state)

    def step() -> int:
        nonlocal state
        for i in range(STEPS):
            state = hh.hh_update(state, staged[i % STAGED], valid,
                                 config=config)
        jax.block_until_ready(state)
        return BATCH * STEPS

    stats = _timed_samples(step)
    baseline = 100_000.0  # reference production ">100k flows/s"
    result = {
        "metric": f"heavy-hitter sketch aggregation throughput "
                  f"({platform}, 1 device)",
        "unit": "flows/sec",
        **stats,
        "vs_baseline": round(stats["value"] / baseline, 3),
        "platform": platform,
    }
    result.update(_roofline_fields(
        hh.hh_update, stats["value"] / BATCH,
        state, staged[0], valid, config=config,
    ))
    # The honest north-star number is the END-TO-END rate (BASELINE.json's
    # metric is flows/sec INGESTED, not the bare kernel step) — carry it
    # in the official artifact next to the flagship step (VERDICT r3 #1).
    global _NATIVE
    _NATIVE = _ensure_native()
    e2e = _run_e2e(E2E_FLOWS, samples=3)
    result["e2e_flows_per_sec"] = e2e["value"]
    result["e2e_stages"] = e2e["stages"]
    result["e2e_native_decode"] = _NATIVE
    result["vs_baseline_e2e"] = round(e2e["value"] / baseline, 3)
    result["e2e_ingest_mode"] = e2e["ingest_mode"]
    result["e2e_host_group_share_pct"] = e2e["host_group_share_pct"]
    result["e2e_flushing_share_pct"] = e2e["flushing_share_pct"]
    # A/B: the pre-r6 single-threaded dataplane on the same stream
    serial = _run_e2e(E2E_FLOWS, samples=2, ingest_mode="serial")
    result["e2e_serial_flows_per_sec"] = serial["value"]
    result["e2e_pipelined_speedup"] = round(
        e2e["value"] / serial["value"], 3) if serial["value"] else 0.0
    print(json.dumps(result))


def bench_decode() -> None:
    """Native host decode throughput (the feed path)."""
    from flow_pipeline_tpu import native
    from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile

    _ensure_native()
    batch = FlowGenerator(ZipfProfile(), seed=1).batch(65536)
    data = native.encode_stream(batch)
    native.decode_stream(data)  # warm
    t0 = time.perf_counter()
    reps = 20
    for _ in range(reps):
        native.decode_stream(data)
    dt = time.perf_counter() - t0
    print(json.dumps({
        "metric": "native protobuf->columnar decode",
        "value": round(65536 * reps / dt, 1),
        "unit": "flows/sec",
        "vs_baseline": round(65536 * reps / dt / 100_000.0, 3),
    }))


def bench_cms() -> None:
    """CMS update shootout: XLA scatter vs Pallas dense-tile kernels, for
    both the linear and conservative updates (all four share one bucket
    scheme/state — ops.cms / ops.cms_pallas). The flagship config is
    conservative, so the row to watch is cu_*."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from flow_pipeline_tpu.ops.cms import (
        cms_add,
        cms_add_conservative,
        cms_init,
    )
    from flow_pipeline_tpu.ops.cms_pallas import (
        cms_add_conservative_pallas,
        cms_add_pallas,
    )

    rng = np.random.default_rng(0)
    n, planes, depth, width = 8192, 3, 4, 1 << 16
    keys = jnp.asarray(rng.integers(0, 2**31, size=(n, 8), dtype=np.int64)
                       .astype(np.int32))
    vals = jnp.asarray(rng.integers(1, 1500, size=(n, planes))
                       .astype(np.float32))
    valid = jnp.ones(n, bool)
    # the Pallas legs run COMPILED or not at all: an explicit CPU run
    # times the XLA twins only (interpret mode is a test tool, and its
    # time says nothing about the kernel)
    on_tpu = jax.devices()[0].platform == "tpu"
    variants = {
        "lin_xla": jax.jit(cms_add),
        "cu_xla": jax.jit(cms_add_conservative),
    }
    if on_tpu:
        variants["lin_pallas"] = cms_add_pallas
        variants["cu_pallas"] = cms_add_conservative_pallas
    results = {}
    for name, fn in variants.items():
        reps = 20
        s = fn(cms_init(planes, depth, width), keys, vals, valid)
        jax.block_until_ready(s)
        t0 = time.perf_counter()
        for _ in range(reps):
            s = fn(s, keys, vals, valid)
        jax.block_until_ready(s)
        us = (time.perf_counter() - t0) / reps * 1e6
        results[f"{name}_us"] = round(us, 1)
        results[f"{name}_mflows_s"] = round(n / us, 2)
    if on_tpu:
        cu = {k: v for k, v in results.items()
              if k.startswith("cu_") and k.endswith("_us")}
        results["cu_winner"] = min(cu, key=cu.get).removesuffix("_us")
    results["pallas_compiled"] = on_tpu
    results["platform"] = _PLATFORM
    print(json.dumps({"metric": "cms update step", "unit": "us/batch",
                      "batch": n, **results}))


def _stage_sums() -> dict:
    """Current per-stage wall-time totals (us) from the metrics registry —
    the flow_summary_*_time_us family every pipeline stage feeds."""
    from flow_pipeline_tpu.obs import REGISTRY

    out = {}
    for name, metric in list(REGISTRY._metrics.items()):
        if name.startswith("flow_summary_") and name.endswith("_time_us") \
                and hasattr(metric, "_sum"):
            out[name[len("flow_summary_"):-len("_time_us")]] = metric._sum
    return out


def _phase_sums(counter: str) -> dict:
    """Current in-kernel phase totals (ns) for one stage counter — the
    flowtrace counters the native kernels publish from their stats
    out-structs."""
    from flow_pipeline_tpu import native
    from flow_pipeline_tpu.obs import REGISTRY

    ctr = REGISTRY._metrics.get(counter)
    if ctr is None:
        return {}
    return {ph: ctr.value(phase=ph) for ph in native.FF_STAT_PHASES}


def _fused_phase_sums() -> dict:
    return _phase_sums("host_fused_phase_ns_total")


def _group_phase_sums() -> dict:
    """host_group's kernel attribution: the ff_group_sum wagg fold
    (radix/refine/fold) plus — r19 — the `lanes` phase from
    ff_build_lanes / ff_build_planes, the number that shows the C lane
    building actually carrying the prepare half."""
    return _phase_sums("host_group_phase_ns_total")


def _sketch_phase_sums() -> dict:
    """host_sketch's kernel attribution — r21 adds the `spread` phase
    from hs_spread_update (the flowspread register scatter-max), which
    publishes here even on fused legs because spread families keep the
    staged pair-grouping path (hostsketch/pipeline.py _fold_spread)."""
    return _phase_sums("host_sketch_phase_ns_total")


def _phase_breakdown(before: dict, after: dict,
                     stage_total_us: float) -> dict:
    """host_fused phase shares (pct of the host_fused STAGE total, so
    they sum to 100 with `other` = Python-side overhead the kernels
    don't see: lane extraction, state import, ctypes marshalling)."""
    if not after or stage_total_us <= 0:
        return {}
    out = {}
    covered = 0.0
    for ph, v in after.items():
        us = (v - before.get(ph, 0.0)) / 1e3
        share = 100 * us / stage_total_us
        covered += share
        out[ph] = round(share, 1)
    out["other"] = round(max(0.0, 100 - covered), 1)
    return out


def _run_e2e(n_flows: int, samples: int = 5,
             ingest_mode: str = "pipelined",
             sketch_backend: str = "device",
             ingest_fused: str = "off",
             obs_audit: str = "off",
             hh_sketch: str = "table",
             ingest_threads: int = 0,
             native_lanes: bool = True,
             spread: str = "off",
             zipf_spread: float = 0.0) -> dict:
    """Shared e2e measurement: stats + per-stage budget (VERDICT r3 #1).

    The budget diffs the stage summaries across the timed samples and
    reports each stage's us/kflow and share of wall time. consume_*
    stages run on the prefetch feed thread, host_group on the ingest
    group thread, flushing on the background flusher (pipelined mode) —
    all overlapped with the worker — so shares are a breakdown, not a
    disjoint partition. ingest_mode="serial" is the pre-r6
    single-threaded path, the A/B baseline the artifact records;
    sketch_backend="host" swaps the jitted CMS/top-K apply for the
    native hostsketch engine (the r8 A/B — device_apply share is the
    number that leg exists to shrink); ingest_fused="on" additionally
    collapses grouping + cascade + sketch into the single-pass native
    dataplane (the r10 A/B — host_group + host_sketch shares are what
    it exists to shrink). The default here is "off" so pre-r10 modes
    (e2e, hostsketch) keep measuring the staged legs they always did —
    bench_fused passes both settings explicitly."""
    from flow_pipeline_tpu.cli import (
        _batch_frames, _build_models, _make_generator, _processor_flags,
        _common_flags, _gen_flags,
    )
    from flow_pipeline_tpu.engine import StreamWorker, WorkerConfig
    from flow_pipeline_tpu.transport import Consumer, InProcessBus
    from flow_pipeline_tpu.utils.flags import FlagSet

    fs = _processor_flags(_gen_flags(_common_flags(FlagSet("bench"))))
    argv = ["-produce.profile", "zipf", "-hh.sketch", hh_sketch]
    if zipf_spread:
        # spreader/scanner legs in the stream — BOTH legs of a spread
        # A/B get the same fraction so the delta is the family's cost,
        # not the stream's shape
        argv += ["-zipf.spread", str(zipf_spread)]
    if spread == "on":
        argv += ["-spread.enabled"]
    vals = fs.parse(argv)

    def run_stream(n):
        bus = InProcessBus()
        bus.create_topic("flows", 2)
        gen = _make_generator(vals)
        produced = 0
        while produced < n:
            bus.produce_many("flows", _batch_frames(gen.batch(16384)))
            produced += 16384
        # native_lanes=False pins the pipeline onto the numpy lane
        # builders (the r16/r18-shaped baseline leg): the choice is
        # resolved ONCE at pipeline construction, so masking the
        # capability probe during construction is a clean, reversible
        # A/B knob — exactly the fallback a pre-r19 .so would take
        from flow_pipeline_tpu import native as native_lib
        real_lanes_available = native_lib.lanes_available
        if not native_lanes:
            native_lib.lanes_available = lambda: False
        try:
            worker = StreamWorker(
                Consumer(bus, fixedlen=True),
                _build_models(vals),  # identical configs -> shared jit caches
                [],  # sink writes are benched via the insert paths
                # native grouping ON in BOTH legs (the CLI default), so the
                # serial-vs-pipelined delta isolates the dataplane overlap
                # instead of conflating it with the C kernel
                WorkerConfig(poll_max=vals["processor.batch"],
                             snapshot_every=0,
                             ingest_mode=ingest_mode,
                             sketch_backend=sketch_backend,
                             ingest_native_group=True,
                             ingest_fused=ingest_fused,
                             obs_audit=obs_audit,
                             ingest_threads=ingest_threads),
            )
        finally:
            native_lib.lanes_available = real_lanes_available
        t0 = time.perf_counter()
        worker.run(stop_when_idle=True)  # incl. finalize: closes + flushes
        return produced, time.perf_counter() - t0

    # _timed_samples' untimed first pass covers the FULL lifecycle (updates,
    # window closes, top-K extraction, final flush) so one-time XLA
    # compilation — over 10s of work across the default model set — stays
    # out of the timed samples.
    before = None
    phases_before = {}
    gphases_before = {}
    sphases_before = {}

    def step():
        nonlocal before, phases_before, gphases_before, sphases_before
        if before is None:  # first call = the untimed warm pass
            before = ()
        elif before == ():  # arm the stage diff after warm-up
            before = _stage_sums()
            phases_before = _fused_phase_sums()
            gphases_before = _group_phase_sums()
            sphases_before = _sketch_phase_sums()
        return run_stream(n_flows)

    stats = _timed_samples(step, samples=samples)
    after = _stage_sums()
    total_flows = n_flows * samples
    wall_us = total_flows / stats["value"] * 1e6 if stats["value"] else 0.0
    stages = {}
    stage_us = {}
    for name, v in sorted(after.items()):
        d = v - (before.get(name, 0.0) if isinstance(before, dict) else 0.0)
        if d <= 0:
            continue
        stage_us[name] = d
        stages[name] = {
            "us_per_kflow": round(d / total_flows * 1000, 1),
            "share_pct": round(100 * d / wall_us, 1) if wall_us else 0.0,
        }
    stats["stages"] = stages
    # the flowtrace in-kernel breakdown of the host_fused stage (fused
    # legs only — empty otherwise): per-phase shares of the stage total,
    # restoring the attribution the single-pass kernel erased
    stats["host_fused_phases"] = _phase_breakdown(
        phases_before, _fused_phase_sums(),
        stage_us.get("host_fused", 0.0))
    # host_group's kernel attribution (the wagg fold + the r19 `lanes`
    # slot): on a native-lanes leg the lanes share IS the C lane
    # building's slice of the prepare half; on the numpy-fallback
    # baseline it reads 0 and the same work hides in `other`
    stats["host_group_phases"] = _phase_breakdown(
        gphases_before, _group_phase_sums(),
        stage_us.get("host_group", 0.0))
    # the two shares the ingest runtime exists to shrink, promoted to
    # first-class artifact fields (acceptance: host_group <30, flush <20)
    stats["ingest_mode"] = ingest_mode
    stats["ingest_native_group"] = True  # both A/B legs (see run_stream)
    stats["sketch_backend"] = sketch_backend
    stats["ingest_fused"] = ingest_fused
    stats["hh_sketch"] = hh_sketch
    stats["ingest_threads"] = ingest_threads
    stats["native_lanes"] = native_lanes
    stats["host_group_share_pct"] = stages.get(
        "host_group", {}).get("share_pct", 0.0)
    stats["flushing_share_pct"] = stages.get(
        "flushing", {}).get("share_pct", 0.0)
    # the share the hostsketch backend exists to shrink (r8 acceptance:
    # host leg cuts it >=2x vs the device leg on the same box)
    stats["device_apply_share_pct"] = stages.get(
        "device_apply", {}).get("share_pct", 0.0)
    # the r10 fused-dataplane seam: host_sketch is the staged engine,
    # host_fused the single-pass group+cascade+sketch kernel
    stats["host_sketch_share_pct"] = stages.get(
        "host_sketch", {}).get("share_pct", 0.0)
    stats["host_fused_share_pct"] = stages.get(
        "host_fused", {}).get("share_pct", 0.0)
    # the r21 flowspread seam: host_spread is the staged register fold
    # stage (prep + scatter-max + candidate-table merge + audit fold);
    # spread_kernel_share_pct is the hs_spread_update slice alone, from
    # the kernel's own stats out-struct — the gap between the two is
    # Python-side pair grouping + marshalling
    stats["spread"] = spread
    stats["zipf_spread"] = zipf_spread
    stats["host_spread_share_pct"] = stages.get(
        "host_spread", {}).get("share_pct", 0.0)
    spread_ns = (_sketch_phase_sums().get("spread", 0.0)
                 - sphases_before.get("spread", 0.0))
    stats["spread_kernel_share_pct"] = (
        round(100 * spread_ns / 1e3 / wall_us, 2) if wall_us else 0.0)
    # benchmarks must never quietly measure a fallback: record the
    # loaded library's capability surface in the artifact and name any
    # missing feature up front (a stale .so shows up here before its
    # numbers can masquerade as the native path's)
    from flow_pipeline_tpu import native as native_lib

    stats["native_capabilities"] = native_lib.capabilities()
    # only features this leg actually drives; stderr keeps redirected
    # artifacts (bench.py ... > BENCH.json) parseable
    used = {"decode", "group"}
    if sketch_backend == "host":
        used.add("sketch")
    if ingest_fused == "on":
        used.add("fused")
    if hh_sketch == "invertible" and sketch_backend == "host":
        used.add("invsketch")
    if spread == "on":
        used.add("spread")
    missing = sorted(used & set(native_lib.missing_features()))
    if missing:
        print(f"WARNING: native library cannot serve {missing} — "
              "this leg measures fallback paths (run `make native`)",
              file=sys.stderr)
    return stats


def bench_hostsketch() -> None:
    """Same-box sketch-backend A/B (the BENCH_r08 artifact): the full
    e2e pipeline with the jitted sketch apply vs the native hostsketch
    engine, per-stage shares included. Same stream, same process, legs
    interleaved only by the jit warm-up order — never compare the
    absolute rates across boxes or rounds (r06 host-variance caveat);
    the A/B ratio and the device_apply share delta are the portable
    numbers."""
    global _NATIVE
    _NATIVE = _ensure_native()
    from flow_pipeline_tpu import native as native_lib

    device = _run_e2e(E2E_FLOWS, samples=3, sketch_backend="device")
    host = _run_e2e(E2E_FLOWS, samples=3, sketch_backend="host")
    print(json.dumps({
        "metric": "e2e sketch-backend A/B (device_apply offload)",
        "unit": "flows/sec",
        "value": host["value"],
        "device_flows_per_sec": device["value"],
        "host_flows_per_sec": host["value"],
        "host_speedup": round(host["value"] / device["value"], 3)
        if device["value"] else 0.0,
        "device_apply_share_device_pct": device["device_apply_share_pct"],
        "device_apply_share_host_pct": host["device_apply_share_pct"],
        "device_apply_share_cut": round(
            device["device_apply_share_pct"]
            / host["device_apply_share_pct"], 2)
        if host["device_apply_share_pct"] else 0.0,
        "host_sketch_share_pct": host["stages"].get(
            "host_sketch", {}).get("share_pct", 0.0),
        "stages_device": device["stages"],
        "stages_host": host["stages"],
        "spread_pct_device": device["spread_pct"],
        "spread_pct_host": host["spread_pct"],
        "native_decode": _NATIVE,
        "native_sketch": native_lib.sketch_available(),
        "platform": _PLATFORM,
        "host_note": (
            "bench boxes differ 3-4x between rounds and swing within "
            "hours (r06 caveat); a 2-core throttled box cannot sustain "
            "the 1M flows/s target — the portable numbers are the "
            "same-box host_speedup and the device_apply share cut"),
        **_host_conditions(),
    }))


def _lane_build_ab(pairs: int = 6, reps: int = 30) -> dict:
    """Paired A/B of the r16 lane-build change (ROADMAP 4a): the old
    per-lane concat (_key_lanes_np) vs the preallocated direct-fill
    buffer (_key_lanes_into) over a real decoded chunk's 5-tuple
    columns — the extraction that IS the fused prepare half. Alternating
    order inside each pair, median of per-pair ratios."""
    import numpy as np

    from flow_pipeline_tpu.engine.hostfused import (_key_lanes_into,
                                                    _key_lanes_np)
    from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile

    cols = FlowGenerator(ZipfProfile(n_keys=100_000, alpha=1.1),
                         seed=0).batch(32768).columns
    key_cols = ("src_addr", "dst_addr", "src_port", "dst_port", "proto")
    ref = _key_lanes_np(cols, key_cols)
    new = _key_lanes_into(cols, key_cols)
    assert np.array_equal(np.ascontiguousarray(ref), new)

    def time_fn(fn):
        t0 = time.perf_counter()
        for _ in range(reps):
            fn(cols, key_cols)
        return (time.perf_counter() - t0) / reps * 1e6

    concat_us, fill_us, ratios = [], [], []
    for i in range(pairs):
        if i % 2 == 0:
            c, f = time_fn(_key_lanes_np), time_fn(_key_lanes_into)
        else:
            f, c = time_fn(_key_lanes_into), time_fn(_key_lanes_np)
        concat_us.append(c)
        fill_us.append(f)
        if f:
            ratios.append(c / f)
    return {
        "lane_build_concat_us": round(statistics.median(concat_us), 1),
        "lane_build_prealloc_us": round(statistics.median(fill_us), 1),
        "lane_build_speedup": round(statistics.median(ratios), 3)
        if ratios else 0.0,
        "lane_build_pairs": [round(r, 3) for r in ratios],
    }


def _lane_build_native_ab(pairs: int = 6, reps: int = 20) -> dict:
    """r19 lane-build sub-A/B: the numpy twins (the r16 preallocated
    fill + _value_planes_np — still the fallback path) vs the native
    ff_build_lanes / ff_build_planes off the SAME decoded chunk's
    columns, single-threaded so the delta isolates the per-lane
    saturation copies + buffer fill the C pass deletes (the threaded
    story is the e2e legs'). Equality asserted before any timing —
    a sub-A/B of two different answers measures nothing."""
    import numpy as np

    from flow_pipeline_tpu import native as native_lib
    from flow_pipeline_tpu.engine.hostfused import (_key_lanes_into,
                                                    _value_planes_np)
    from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile

    if not native_lib.lanes_available():
        return {"lane_build_native_error": "library lacks ff_build_lanes"}
    cols = FlowGenerator(ZipfProfile(n_keys=100_000, alpha=1.1),
                         seed=0).batch(32768).columns
    key_cols = ("src_addr", "dst_addr", "src_port", "dst_port", "proto")
    value_cols = ("bytes", "packets")

    def np_build():
        lanes = _key_lanes_into(cols, key_cols)
        vals = np.ascontiguousarray(
            _value_planes_np(cols, value_cols, "sampling_rate"),
            dtype=np.float32)
        return lanes, vals

    def c_build():
        lanes = native_lib.build_lanes([cols[c] for c in key_cols])
        vals = native_lib.build_planes_f32(
            [cols[c] for c in value_cols], scale=cols["sampling_rate"])
        return lanes, vals

    for a, b in zip(np_build(), c_build()):
        assert np.array_equal(a, b), "native lane builders not bit-exact"

    def time_fn(fn):
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        return (time.perf_counter() - t0) / reps * 1e6

    # one pairing harness (_paired_e2e_ab): with µs-per-build legs the
    # per-pair b/a ratio is np_us/c_us — the native speedup
    c_runs, np_runs, ratios = _paired_e2e_ab(
        lambda: {"value": time_fn(c_build)},
        lambda: {"value": time_fn(np_build)}, pairs=pairs)
    np_us = [r["value"] for r in np_runs]
    c_us = [r["value"] for r in c_runs]
    return {
        "lane_build_numpy_us": round(statistics.median(np_us), 1),
        "lane_build_native_us": round(statistics.median(c_us), 1),
        "lane_build_native_speedup": round(statistics.median(ratios), 3)
        if ratios else 0.0,
        "lane_build_native_pairs": [round(r, 3) for r in ratios],
    }


def bench_kernels() -> None:
    """Kernel-level microbench of the r19-restructured inner loops —
    the invertible keysum fold (row-major mul-accumulate), the plain
    CMS scatter (hoisted addends) and the lane builders — at
    threads=1, ns per row. Honors FLOWDECODE_LIB, so the SIMD A/B can
    run the identical timing against the ``make -C native novec``
    twin (-fno-tree-vectorize) in a fresh process; a loaded .so cannot
    be swapped in-process."""
    import numpy as np

    from flow_pipeline_tpu import native as native_lib

    if not native_lib.lanes_available():
        print(json.dumps({"error": "library lacks the r19 kernels",
                          "hint": "make native"}))
        return
    rng = np.random.default_rng(5)
    n, kw, planes, depth, width = 32768, 4, 3, 4, 1 << 16
    keys = rng.integers(0, 1 << 20, size=(n, kw), dtype=np.uint32)
    vals = rng.integers(0, 1500, size=(n, planes)).astype(np.float32)
    big = rng.integers(0, 1 << 36, size=n, dtype=np.uint64)
    addr = rng.integers(0, 1 << 32, size=(n, 4),
                        dtype=np.uint64).astype(np.uint32)

    # state allocated ONCE and kept warm across reps: a fresh buffer
    # per rep would charge first-touch page faults to the kernel and
    # wash out the loop-level delta the SIMD A/B exists to measure
    inv_cms = np.zeros((planes, depth, width), np.uint64)
    inv_ks = np.zeros((depth, width, kw), np.uint64)
    inv_kc = np.zeros((depth, width), np.uint64)
    cms_state = np.zeros((planes, depth, width), np.uint64)

    def t_inv():
        t0 = time.perf_counter()
        native_lib.hs_inv_update(inv_cms, inv_ks, inv_kc, keys, vals,
                                 None, 1)
        return time.perf_counter() - t0

    def t_cms():
        t0 = time.perf_counter()
        native_lib.hs_cms_update(cms_state, keys, vals, None, False, 1)
        return time.perf_counter() - t0

    def t_lanes():
        t0 = time.perf_counter()
        native_lib.build_lanes([big, addr, keys[:, 0]])
        native_lib.build_planes_f32([big, keys[:, 1]],
                                    scale=keys[:, 2])
        return time.perf_counter() - t0

    out = {}
    for name, fn in (("inv", t_inv), ("cms", t_cms), ("lanes", t_lanes)):
        fn()  # warm: first-touch pages, branch predictors
        out[f"{name}_ns_per_row"] = round(
            statistics.median(fn() for _ in range(9)) / n * 1e9, 2)
    print(json.dumps({
        "metric": "r19 fused-kernel microbench",
        "unit": "ns/row", "rows": n,
        "lib": os.path.basename(
            os.environ.get("FLOWDECODE_LIB", "libflowdecode.so")),
        **out,
        **_host_conditions(),
    }))


def _simd_ab(pairs: int = 3) -> dict:
    """The r19 SIMD A/B: the SAME kernel sources compiled with and
    without autovectorization (``make -C native novec``), each timed by
    the ``kernels`` subcommand in a fresh subprocess, alternating order
    inside each pair. This is the "restructure first, intrinsics only
    if the A/B demands it" evidence: a novec/vec ratio ~1.0 would mean
    the compiler never vectorized the restructured loop and intrinsics
    are back on the table."""
    import subprocess

    root = os.path.dirname(os.path.abspath(__file__))
    try:
        subprocess.run(
            ["make", "-C", os.path.join(root, "native"), "novec"],
            check=True, capture_output=True, timeout=600)
    except (OSError, subprocess.SubprocessError) as e:
        return {"simd_ab_error": f"novec build failed: {e}"}

    def leg(lib: str) -> dict:
        env = dict(os.environ)
        env["FLOWDECODE_LIB"] = os.path.join(
            root, "flow_pipeline_tpu", "native", lib)
        env.setdefault("JAX_PLATFORMS", "cpu")
        out = subprocess.run(
            [sys.executable, os.path.join(root, "bench.py"), "kernels"],
            env=env, capture_output=True, text=True, timeout=600,
            check=True)
        return json.loads(out.stdout)

    vec_runs, novec_runs = [], []
    try:
        for i in range(pairs):
            if i % 2 == 0:
                v = leg("libflowdecode.so")
                nv = leg("libflowdecode_novec.so")
            else:
                nv = leg("libflowdecode_novec.so")
                v = leg("libflowdecode.so")
            vec_runs.append(v)
            novec_runs.append(nv)
    except (OSError, subprocess.SubprocessError, ValueError) as e:
        # same degradation contract as the novec-build guard above: a
        # failing subprocess leg (strict FLOWDECODE_LIB load failure,
        # OOM kill, garbled stdout) must not lose the whole fused
        # artifact after the expensive e2e legs already ran
        return {"simd_ab_error": f"kernels leg failed: {e}"}
    out = {}
    for key in ("inv_ns_per_row", "cms_ns_per_row", "lanes_ns_per_row"):
        kernel = key.split("_")[0]
        # a kernels leg on a stale .so reports {"error": ...} with no
        # timing keys — degrade that kernel's record to 0.0 instead of
        # losing the whole fused artifact to a KeyError after the
        # expensive e2e legs already ran
        vec = [v[key] for v in vec_runs if v.get(key)]
        novec = [nv[key] for nv in novec_runs if nv.get(key)]
        ratios = [nv[key] / v[key]
                  for v, nv in zip(vec_runs, novec_runs)
                  if v.get(key) and nv.get(key)]
        out[f"simd_{kernel}_vec_ns_per_row"] = round(
            statistics.median(vec), 2) if vec else 0.0
        out[f"simd_{kernel}_novec_ns_per_row"] = round(
            statistics.median(novec), 2) if novec else 0.0
        out[f"simd_{kernel}_novec_over_vec"] = round(
            statistics.median(ratios), 3) if ratios else 0.0
    return out


def _degraded_np_ab(pairs: int = 3, n_chunks: int = 40) -> dict:
    """Degraded no-native sub-A/B (ROADMAP 3c): the numpy twin of the
    host sketch engine's grouped update step, r19-shaped (one murmur
    pass per consumer — the admission query rehashed every chunk — and
    stack+reduce min queries) vs r20 (ONE murmur pass reused across
    the CMS update and the admission query, prefilter subsetting the
    precomputed bucket columns, running-min query). Unique-key group
    tables at the flagship 5-tuple config — the shape the pipeline
    actually feeds the engine. Both legs are bit-exact twins; the A/B
    is purely the cost of graceful degradation."""
    import numpy as np

    from flow_pipeline_tpu.hostsketch import engine as hs_engine
    from flow_pipeline_tpu.hostsketch.state import host_hh_init
    from flow_pipeline_tpu.models.heavy_hitter import HeavyHitterConfig
    from flow_pipeline_tpu.ops.hostgroup import hash_u64

    cfg = HeavyHitterConfig(
        key_cols=("src_addr", "dst_addr", "src_port", "dst_port",
                  "proto"),
        batch_size=4096, width=1 << 13, capacity=512)
    rng = np.random.default_rng(0)
    kw = host_hh_init(cfg).table_keys.shape[1]
    b = 4096
    chunks = []
    for _ in range(n_chunks):
        uniq = np.zeros((b, kw), np.uint32)
        uniq[:, :5] = rng.integers(0, 2**32, size=(b, 5),
                                   dtype=np.int64).astype(np.uint32)
        chunks.append((uniq, rng.random((b, 3)).astype(np.float32) * 1e4))

    def r19_update(st, uniq, sums):
        depth, width = st.cms.shape[1], st.cms.shape[2]
        buckets = hs_engine._np_buckets(uniq, depth, width)
        add = hs_engine._addend_u64(sums)
        est0 = np.stack([st.cms[:, d, buckets[d]]
                         for d in range(depth)]).min(axis=0).T
        target = est0 + add
        for pi in range(st.cms.shape[0]):
            for d in range(depth):
                np.maximum.at(st.cms[pi, d], buckets[d], target[:, pi])
        th = (hash_u64(np.ascontiguousarray(st.table_keys))
              >> np.uint64(32)).astype(np.uint32)
        gh = (hash_u64(uniq) >> np.uint64(32)).astype(np.uint32)
        ts = np.sort(th)
        pos = np.clip(np.searchsorted(ts, gh), 0, cfg.capacity - 1)
        metric = sums[:, 0].copy()
        metric[ts[pos] == gh] = np.float32(np.inf)
        sel = np.argsort(-metric, kind="stable")[:2 * cfg.capacity]
        uniq, sums = uniq[sel], sums[sel]
        b2 = hs_engine._np_buckets(uniq, depth, width)  # the rehash
        est = np.stack([st.cms[:, d, b2[d]]
                        for d in range(depth)]).min(axis=0).T \
            .astype(np.float32)
        st.table_keys, st.table_vals = hs_engine.np_topk_merge(
            st.table_keys, st.table_vals, uniq, sums, est)

    def leg_old():
        st = host_hh_init(cfg)
        t0 = time.perf_counter()
        for uniq, sums in chunks:
            r19_update(st, uniq, sums)
        dt = time.perf_counter() - t0
        return {"value": n_chunks * b / dt}

    def leg_new():
        eng = hs_engine.HostSketchEngine([cfg], use_native="numpy")
        eng.reset(0)
        t0 = time.perf_counter()
        for uniq, sums in chunks:
            eng.update(0, uniq, sums, b)
        dt = time.perf_counter() - t0
        return {"value": n_chunks * b / dt}

    old_runs, new_runs, ratios = _paired_e2e_ab(leg_old, leg_new,
                                                pairs=pairs)
    return {
        "degraded_np_r19_groups_per_sec": _med(old_runs, "value"),
        "degraded_np_r20_groups_per_sec": _med(new_runs, "value"),
        "degraded_np_speedup": round(statistics.median(ratios), 3)
        if ratios else 0.0,
        "degraded_np_pairs": [round(r, 3) for r in ratios],
    }


def _paired_e2e_ab(leg_a, leg_b, pairs: int = 3):
    """Paired alternating-order e2e A/B (the r11 methodology, promoted
    to the shared harness): legs run in adjacent pairs so slow host
    drift cancels within a pair, pair ORDER alternates so the
    warm-second bias cancels across pairs, and the headline statistic
    is the MEDIAN of per-pair b/a speedups. Returns (a_runs, b_runs,
    ratios)."""
    a_runs, b_runs, ratios = [], [], []
    for i in range(pairs):
        if i % 2 == 0:
            a, b = leg_a(), leg_b()
        else:
            b, a = leg_b(), leg_a()
        a_runs.append(a)
        b_runs.append(b)
        if a["value"]:
            ratios.append(b["value"] / a["value"])
    return a_runs, b_runs, ratios


def _med(runs, key):
    return round(statistics.median(r[key] for r in runs), 1)


def _runs_spread_pct(runs, key: str = "value") -> float:
    """(max-min)/median across a leg's per-run rates, in percent."""
    vals = [r[key] for r in runs]
    med = statistics.median(vals)
    if not med:
        return 0.0
    return round((max(vals) - min(vals)) / med * 100, 1)


def bench_fused() -> None:
    """Same-box fused-dataplane A/B (BENCH_r10, extended r19): the full
    e2e pipeline on the host sketch backend, paired alternating-order
    legs throughout (r11 methodology — single-leg spreads on a noisy
    2-core box cannot resolve the effects being claimed):

    (1) staged group->cascade->sketch vs the single-pass native
        dataplane (-ingest.fused) — the r10 claim, re-measured;
    (2) flowspeed (r19): the fused pass with threads=1 + the numpy
        lane builders (the r16/r18-shaped baseline) vs threaded + C
        lane building — THE r19 acceptance leg, with per-phase shares
        from both legs so the win is attributed to lanes/inv/cms, not
        inferred;
    (3) a thread-scaling leg at -ingest.threads {1,2,4,8} (nproc in the
        artifact: past the core count the curve SHOULD flatten);
    (4) sub-A/Bs: numpy vs native lane building (in-process, paired)
        and vectorized vs -fno-tree-vectorize kernel builds (fresh
        subprocesses via FLOWDECODE_LIB) — the "restructure first,
        intrinsics only if the A/B demands it" evidence.

    The portable numbers are same-box speedups and share deltas —
    never absolute rates across boxes or rounds (r06 caveat)."""
    global _NATIVE
    _NATIVE = _ensure_native()
    from flow_pipeline_tpu import native as native_lib

    if not native_lib.fused_available():
        print(json.dumps({"error": "libflowdecode lacks the fused "
                          "dataplane", "hint": "make native"}))
        return

    # (1) staged vs fused, paired
    staged_runs, fused_runs, ratios = _paired_e2e_ab(
        lambda: _run_e2e(E2E_FLOWS, samples=1, sketch_backend="host",
                         ingest_fused="off"),
        lambda: _run_e2e(E2E_FLOWS, samples=1, sketch_backend="host",
                         ingest_fused="on"),
        pairs=FUSED_PAIRS)
    staged, fused = staged_runs[-1], fused_runs[-1]
    group_shares = {
        "host_group_share_staged_pct": _med(staged_runs,
                                            "host_group_share_pct"),
        "host_group_share_fused_pct": _med(fused_runs,
                                           "host_group_share_pct"),
        "host_sketch_share_staged_pct": _med(staged_runs,
                                             "host_sketch_share_pct"),
        "host_sketch_share_fused_pct": _med(fused_runs,
                                            "host_sketch_share_pct"),
        "host_fused_share_pct": _med(fused_runs, "host_fused_share_pct"),
    }

    # (2) flowspeed: r16/r18-shaped baseline (fused, single-threaded,
    # numpy lane builders) vs the r19 dataplane (threaded + C lanes)
    base_runs, speed_runs, speed_ratios = _paired_e2e_ab(
        lambda: _run_e2e(E2E_FLOWS, samples=1, sketch_backend="host",
                         ingest_fused="on", ingest_threads=1,
                         native_lanes=False),
        lambda: _run_e2e(E2E_FLOWS, samples=1, sketch_backend="host",
                         ingest_fused="on"),
        pairs=FUSED_PAIRS)
    flowspeed = {
        "flowspeed_baseline_flows_per_sec": _med(base_runs, "value"),
        "flowspeed_flows_per_sec": _med(speed_runs, "value"),
        "flowspeed_speedup": round(statistics.median(speed_ratios), 3)
        if speed_ratios else 0.0,
        "flowspeed_pairs": [round(r, 3) for r in speed_ratios],
        # the acceptance share: host_fused's slice of e2e, before/after
        "host_fused_share_baseline_pct": _med(base_runs,
                                              "host_fused_share_pct"),
        "host_fused_share_flowspeed_pct": _med(speed_runs,
                                               "host_fused_share_pct"),
        # per-phase attribution for BOTH legs: the win must land in
        # lanes (C builders) / inv (keysum restructure) / cms (hoisted
        # addends) / radix (threaded groupby), not smear into noise
        "host_fused_phases_baseline": base_runs[-1]["host_fused_phases"],
        "host_fused_phases_flowspeed": speed_runs[-1]["host_fused_phases"],
        "host_group_share_baseline_pct": _med(base_runs,
                                              "host_group_share_pct"),
        "host_group_share_flowspeed_pct": _med(speed_runs,
                                               "host_group_share_pct"),
        # host_group attribution: the flowspeed leg's `lanes` share is
        # the C lane building carrying the prepare half; the baseline
        # leg's reads 0 (numpy builds are invisible to the kernels)
        "host_group_phases_baseline": base_runs[-1]["host_group_phases"],
        "host_group_phases_flowspeed": speed_runs[-1]["host_group_phases"],
        "flowspeed_note": (
            "on a 2-core box the engine's auto thread count resolves "
            "to 1 (memory-bound kernels thrash a small shared cache — "
            "the thread_scaling curve records exactly that), so the "
            "paired flowspeed delta isolates the C lane building; the "
            "threaded-kernel win needs >=4 cores (ROADMAP 4c), and the "
            "SIMD story is the simd_* novec sub-A/B: the restructures' "
            "gain is fewer passes/branches, not vector units"),
    }

    # (3) thread scaling (single sample per point: the curve SHAPE on
    # this box is the signal; nproc rides the artifact)
    thread_curve = {}
    for t in FUSED_THREAD_POINTS:
        run = _run_e2e(E2E_FLOWS, samples=1, sketch_backend="host",
                       ingest_fused="on", ingest_threads=t)
        thread_curve[str(t)] = run["value"]

    print(json.dumps({
        "metric": "e2e fused-dataplane A/B (single-pass group+sketch)",
        "unit": "flows/sec",
        "value": _med(fused_runs, "value"),
        "staged_flows_per_sec": _med(staged_runs, "value"),
        "fused_flows_per_sec": _med(fused_runs, "value"),
        "fused_speedup": round(statistics.median(ratios), 3)
        if ratios else 0.0,
        "fused_pairs": [round(r, 3) for r in ratios],
        **group_shares,
        # the r10 acceptance number: everything the staged path spent
        # between decode and the jitted rest-step, vs the fused pass
        "staged_group_plus_sketch_pct": round(
            staged["host_group_share_pct"]
            + staged["host_sketch_share_pct"], 1),
        "fused_group_plus_sketch_pct": round(
            fused["host_group_share_pct"]
            + fused["host_fused_share_pct"]
            + fused["host_sketch_share_pct"], 1),
        # flowtrace in-kernel attribution: what the host_fused stage
        # spends on radix/refine/regroup/fold/cms/prefilter/topk/lanes
        # (pct of the stage total; `other` = Python-side residue)
        "host_fused_phase_breakdown": fused["host_fused_phases"],
        **flowspeed,
        "thread_scaling_flows_per_sec": thread_curve,
        # r16 lane-build A/B (ROADMAP 4a): concat vs preallocated fill
        **_lane_build_ab(),
        # r19 lane-build sub-A/B: numpy twins vs ff_build_lanes/planes
        **_lane_build_native_ab(),
        # r20 degraded-mode sub-A/B (ROADMAP 3c): the numpy engine's
        # grouped update, r19-shaped vs hash-reuse fast path
        **_degraded_np_ab(),
        # r19 SIMD sub-A/B: vectorized vs -fno-tree-vectorize builds
        **_simd_ab(),
        "stages_staged": staged["stages"],
        "stages_fused": fused["stages"],
        # was-the-box-calm self-diagnostic (r06 discipline): the paired
        # legs run samples=1 each, so the in-run spread is vacuous —
        # spread ACROSS the leg's runs is the honest number here
        "spread_pct_staged": _runs_spread_pct(staged_runs),
        "spread_pct_fused": _runs_spread_pct(fused_runs),
        "native_decode": _NATIVE,
        "native_capabilities": native_lib.capabilities(),
        "platform": _PLATFORM,
        "host_note": (
            "bench boxes differ 3-4x between rounds and swing within "
            "hours (r06 caveat); judge by the same-box paired speedups "
            "and the share deltas, never cross-round absolutes"),
        **_host_conditions(),
    }))


def bench_flowtrace() -> None:
    """Same-box flowtrace overhead A/B (the r11 acceptance leg): the
    full e2e pipeline with the span recorder OFF vs the production
    `-obs.trace=ring` flight recorder, on the fastest available
    dataplane (host sketch backend; the fused pass when the library
    exports it). The acceptance bar is ring overhead <2% — tracing that
    taxes the hot path does not stay always-on for long. The artifact
    also carries the host_fused phase breakdown (fused legs) and a
    span-count sanity figure from the ring."""
    global _NATIVE
    _NATIVE = _ensure_native()
    from flow_pipeline_tpu import native as native_lib
    from flow_pipeline_tpu.obs.trace import TRACER

    fused_mode = "on" if native_lib.fused_available() else "off"
    # (1) Deterministic recorder cost: ns per recorded span, measured
    # directly. The pipeline records ~10 spans per 32k-flow chunk, so
    # this bounds the mechanical overhead independent of box noise.
    TRACER.configure("ring")
    reps = 200_000
    t0 = time.perf_counter()
    for i in range(reps):
        TRACER.record("bench", 0.0, 1.0, chunk=i)
    ns_per_span = (time.perf_counter() - t0) / reps * 1e9
    # ~10 spans/chunk at the default 32768-row chunk
    bound_pct = round(100 * 10 * ns_per_span
                      / (32768 / 500_000 * 1e9), 4)  # vs ~500k flows/s
    # (2) Same-box e2e A/B, PAIRED with alternating order: the r06
    # host-variance caveat bites hardest here (single-leg spreads of
    # 10-30% cannot resolve a 2% effect), so off/ring legs run in
    # adjacent pairs — slow drift cancels within a pair — and the pair
    # ORDER alternates, cancelling the warm-second bias a fixed order
    # bakes in. The statistic is the median of per-pair ratios.
    pairs = 6
    off_rates, ring_rates, ratios = [], [], []
    phases = {}
    spans = 0

    def leg(mode):
        TRACER.configure(mode)
        return _run_e2e(E2E_FLOWS, samples=1, sketch_backend="host",
                        ingest_fused=fused_mode)

    for i in range(pairs):
        if i % 2 == 0:
            off, ring = leg("off"), leg("ring")
        else:
            ring, off = leg("ring"), leg("off")
        off_rates.append(off["value"])
        ring_rates.append(ring["value"])
        if off["value"]:
            ratios.append(1 - ring["value"] / off["value"])
        phases = ring["host_fused_phases"] or phases
        spans = max(spans, len(TRACER.snapshot()))
    overhead = 100 * statistics.median(ratios) if ratios else 0.0
    print(json.dumps({
        "metric": "e2e flowtrace overhead A/B (-obs.trace=off vs ring)",
        "unit": "flows/sec",
        "value": round(statistics.median(ring_rates), 1),
        "off_flows_per_sec": round(statistics.median(off_rates), 1),
        "ring_flows_per_sec": round(statistics.median(ring_rates), 1),
        "trace_overhead_pct": round(overhead, 2),
        "trace_overhead_pairs_pct": [round(100 * r, 2) for r in ratios],
        "overhead_budget_pct": 2.0,
        "within_budget": overhead < 2.0,
        "ns_per_span": round(ns_per_span, 1),
        "recorder_cost_bound_pct": bound_pct,
        "ring_spans_recorded": spans,
        "host_fused_phase_breakdown": phases,
        "ingest_fused": fused_mode,
        "native_capabilities": native_lib.capabilities(),
        "platform": _PLATFORM,
        "host_note": (
            "single legs on this class of box spread 10-30% (r06 "
            "caveat), so the overhead statistic is the median of PAIRED "
            "off/ring ratios (drift cancels within a pair) and can dip "
            "negative; ns_per_span x ~10 spans/chunk is the "
            "box-independent mechanical bound"),
        **_host_conditions(),
    }))
    TRACER.configure(os.environ.get("FLOWTPU_TRACE", "ring"))


AUDIT_PAIRS = 4
AUDIT_SWEEP_WIDTHS = (1 << 16, 1 << 10, 1 << 7)
AUDIT_SWEEP_KEYS = 4096
AUDIT_SWEEP_CHUNKS = 8


def _audit_fill_sweep() -> list[dict]:
    """Error-vs-fill curve: the SAME zipf key stream through one hh
    family at shrinking CMS widths, audited in full mode. As fill
    grows the count-min epsilon bound loosens and the sampled-cohort
    relative error must grow with it; at the widest point (fill ~
    keys/width << 1, conservative update) the audit must report the
    exact regime — error 0. This is the live analogue of HashPipe's
    accuracy curves (1611.04825) and the standing acceptance instrument
    for new sketch families."""
    import numpy as np

    from flow_pipeline_tpu.hostsketch.engine import HostSketchEngine
    from flow_pipeline_tpu.models.heavy_hitter import HeavyHitterConfig
    from flow_pipeline_tpu.obs.audit import SketchAudit

    rng = np.random.default_rng(7)
    # zipf-ish key universe with two uint32 lanes, integer byte counts
    zipf = rng.zipf(1.2, size=AUDIT_SWEEP_KEYS * AUDIT_SWEEP_CHUNKS)
    key_ids = (zipf % AUDIT_SWEEP_KEYS).astype(np.uint32)
    lanes_all = np.stack([key_ids * np.uint32(2654435761),
                          key_ids ^ np.uint32(0x9E3779B9)], axis=1)
    vals_all = rng.integers(40, 1500, size=len(key_ids)).astype(
        np.float32)
    points = []
    for width in AUDIT_SWEEP_WIDTHS:
        cfg = HeavyHitterConfig(key_cols=("src_as", "dst_as"),
                                batch_size=AUDIT_SWEEP_KEYS,
                                width=width, capacity=256)
        engine = HostSketchEngine([cfg], use_native="numpy")
        engine.reset(0)
        audit = SketchAudit({"sweep": (cfg, 64)}, mode="full")
        for c in range(AUDIT_SWEEP_CHUNKS):
            sl = slice(c * AUDIT_SWEEP_KEYS, (c + 1) * AUDIT_SWEEP_KEYS)
            lanes, vals = lanes_all[sl], vals_all[sl]
            # group the chunk exactly like the prepare half would
            order = np.lexsort(lanes.T[::-1])
            sk = lanes[order]
            bound = np.ones(len(sk), bool)
            bound[1:] = (sk[1:] != sk[:-1]).any(axis=1)
            starts = np.flatnonzero(bound)
            uniq = np.ascontiguousarray(sk[starts])
            vsum = np.add.reduceat(vals[order].astype(np.float64),
                                   starts).astype(np.float32)
            cnt = np.diff(np.append(starts, len(sk))).astype(np.float32)
            sums = np.stack([vsum, vsum, cnt], axis=1)  # bytes/packets/n
            engine.update(0, uniq, sums, len(uniq))
            audit.observe_grouped("sweep", uniq, sums, len(uniq))
        part = audit.take_partial("sweep")
        from flow_pipeline_tpu.obs.audit import audit_report

        report = audit_report(part["keys"], part["vals"],
                              engine.states[0], cfg, 64, scale=1)
        report.pop("_cms_ratios", None)
        report.pop("_table_ratios", None)
        points.append({
            "width": width,
            "fill_ratio": report["fill_ratio"][-1],
            "cms_err_p50": report["cms_err"]["p50"],
            "cms_err_p99": report["cms_err"]["p99"],
            "sampled_keys": report["sampled_keys"],
            "recall_at_k": report["recall_at_k"],
        })
    return points


def bench_audit() -> None:
    """sketchwatch acceptance artifact (BENCH_r15): (1) paired
    audit-off vs audit-sample e2e A/B on the fastest dataplane —
    alternating leg order, the r11 methodology; budget <2% like
    flowtrace, because an accuracy watch that taxes the hot path does
    not stay always-on; (2) the error-vs-fill sweep — sampled-cohort
    CMS relative error must GROW with fill and report 0 in the exact
    regime, matching the analytic epsilon-bound direction."""
    global _NATIVE
    _NATIVE = _ensure_native()
    from flow_pipeline_tpu import native as native_lib

    fused_mode = "on" if native_lib.fused_available() else "off"
    off_rates, on_rates, ratios, shares = [], [], [], []

    def leg(mode):
        return _run_e2e(E2E_FLOWS, samples=1, sketch_backend="host",
                        ingest_fused=fused_mode, obs_audit=mode)

    for i in range(AUDIT_PAIRS):
        if i % 2 == 0:
            off, on = leg("off"), leg("sample")
        else:
            on, off = leg("sample"), leg("off")
        off_rates.append(off["value"])
        on_rates.append(on["value"])
        # the budget statistic: the audit is timed as its own pipeline
        # stage, so its share of wall is measured WITHIN each audited
        # leg — robust to the cross-leg frequency drift that dominates
        # 2-core bench boxes (the r06/r12 caveat; observed >40% swings
        # BETWEEN legs against a ~1% effect)
        shares.append(on["stages"].get("sketch_audit",
                                       {}).get("share_pct", 0.0))
        if off["value"]:
            ratios.append(1 - on["value"] / off["value"])
    overhead = 100 * statistics.median(ratios) if ratios else 0.0
    share = statistics.median(shares) if shares else 0.0
    # the close evaluation is a once-per-window lump (CMS freeze + fill
    # scan + report): reported as total wall over the leg — this stream
    # packs ONE 300s window per hh family into ~a second of bench wall,
    # so charging it as a share would overstate production cost ~300x
    audit_close_ms = round(
        on["stages"].get("sketch_audit_close", {}).get("us_per_kflow",
                                                       0.0)
        * E2E_FLOWS / 1000 / 1000, 2)
    sweep = _audit_fill_sweep()
    errs = [p["cms_err_p99"] for p in sweep]
    fills = [p["fill_ratio"] for p in sweep]
    print(json.dumps({
        "metric": "e2e sketchwatch audit overhead A/B "
                  "(-obs.audit=off vs sample) + error-vs-fill sweep",
        "unit": "flows/sec",
        "value": round(statistics.median(on_rates), 1),
        "off_flows_per_sec": round(statistics.median(off_rates), 1),
        "sample_flows_per_sec": round(statistics.median(on_rates), 1),
        "audit_share_pct": round(share, 2),
        "audit_share_pairs_pct": [round(s, 2) for s in shares],
        "audit_close_ms_per_leg": audit_close_ms,
        "audit_overhead_pct": round(overhead, 2),
        "audit_overhead_pairs_pct": [round(100 * r, 2) for r in ratios],
        "overhead_budget_pct": 2.0,
        "within_budget": share < 2.0,
        "error_vs_fill": sweep,
        # the two acceptance directions: error grows as fill grows
        # (widths shrink left to right), and the widest point is the
        # exact regime (error 0)
        "error_monotone_with_fill": errs == sorted(errs)
        and fills == sorted(fills),
        "exact_regime_error_zero": errs[0] == 0.0,
        "ingest_fused": fused_mode,
        "native_capabilities": native_lib.capabilities(),
        "platform": _PLATFORM,
        "host_note": (
            "audit_share_pct is the budget statistic: the CONTINUOUS "
            "per-chunk observation cost, timed as its own stage INSIDE "
            "each audited leg — immune to the cross-leg frequency "
            "drift this 2-core box class shows (legs observed swinging "
            ">40% both directions against a ~1% effect; r06/r12 "
            "caveat). audit_close_ms_per_leg is the once-per-WINDOW "
            "close evaluation (one 300s window per hh family packed "
            "into ~a second of bench wall here — in production it "
            "amortizes over the window). The paired A/B is recorded "
            "for completeness; the sweep's error direction is "
            "box-independent"),
        **_host_conditions(),
    }))


SPREAD_PAIRS = 4
# the always-on budget for the FOLD half (the host_spread stage):
# looser than sketchwatch's 2% because the family does real per-flow
# work (two register scatter-maxes per flow vs an observation), but it
# must stay a minor line item next to host_group. The prepare half
# (pair grouping) rides host_group on the group thread and is recorded
# as the cross-leg host_group delta, not budgeted: it overlaps with the
# worker on any multi-core box.
SPREAD_BUDGET_PCT = 8.0


def bench_spread() -> None:
    """flowspread acceptance artifact (BENCH_r21): paired spread-off vs
    spread-on e2e A/B on the fastest dataplane — alternating leg order,
    the r11 methodology. BOTH legs consume the same zipf stream with
    spreader/scanner legs mixed in (-zipf.spread=0.25; harmonic fan-out,
    even ranks superspread dst addrs, odd ranks scan dst ports), so the
    delta is the distinct-count family's cost, not the stream's shape.
    The budget statistic is host_spread's share of wall WITHIN each
    spread-on leg (the stage covers pair grouping + the register
    scatter-max + candidate-table merge), which is robust to the
    cross-leg frequency drift that dominates 2-core bench boxes (the
    r06/r12 caveat); spread_kernel_share_pct narrows that to the
    hs_spread_update kernel alone, from its stats out-struct."""
    global _NATIVE
    _NATIVE = _ensure_native()
    from flow_pipeline_tpu import native as native_lib

    fused_mode = "on" if native_lib.fused_available() else "off"
    off_rates, on_rates, ratios = [], [], []
    shares, kernel_shares, group_deltas = [], [], []

    def leg(mode):
        return _run_e2e(E2E_FLOWS, samples=1, sketch_backend="host",
                        ingest_fused=fused_mode, spread=mode,
                        zipf_spread=0.25)

    for i in range(SPREAD_PAIRS):
        if i % 2 == 0:
            off, on = leg("off"), leg("on")
        else:
            on, off = leg("on"), leg("off")
        off_rates.append(off["value"])
        on_rates.append(on["value"])
        shares.append(on["host_spread_share_pct"])
        kernel_shares.append(on["spread_kernel_share_pct"])
        # the prepare half: pair grouping rides the host_group stage on
        # the group thread, so its cost is the cross-leg host_group
        # share delta (overlapped with the worker on multi-core boxes)
        group_deltas.append(on["host_group_share_pct"]
                            - off["host_group_share_pct"])
        if off["value"]:
            ratios.append(1 - on["value"] / off["value"])
    overhead = 100 * statistics.median(ratios) if ratios else 0.0
    share = statistics.median(shares) if shares else 0.0
    print(json.dumps({
        "metric": "e2e flowspread overhead A/B "
                  "(-spread.enabled off vs on, same spreader stream)",
        "unit": "flows/sec",
        "value": round(statistics.median(on_rates), 1),
        "off_flows_per_sec": round(statistics.median(off_rates), 1),
        "on_flows_per_sec": round(statistics.median(on_rates), 1),
        "spread_share_pct": round(share, 2),
        "spread_share_pairs_pct": [round(s, 2) for s in shares],
        "spread_kernel_share_pct": round(
            statistics.median(kernel_shares), 2),
        "spread_prep_group_delta_pct": round(
            statistics.median(group_deltas), 2),
        "spread_overhead_pct": round(overhead, 2),
        "spread_overhead_pairs_pct": [round(100 * r, 2) for r in ratios],
        "fold_budget_pct": SPREAD_BUDGET_PCT,
        "within_budget": share < SPREAD_BUDGET_PCT,
        "zipf_spread_fraction": 0.25,
        "spread_families": 2,
        "ingest_fused": fused_mode,
        "native_capabilities": native_lib.capabilities(),
        "platform": _PLATFORM,
        "host_note": (
            "spread_share_pct is the budget statistic: host_spread's "
            "wall share (the fold half: register scatter-max + "
            "candidate-table merge + audit fold) timed as its own stage "
            "INSIDE each spread-on leg — immune to the cross-leg "
            "frequency drift this box class shows (r06/r12 caveat). "
            "Two families (superspreader + scan) fold per chunk; "
            "spread_kernel_share_pct is the native hs_spread_update "
            "slice alone. The prepare half (unique (key,element) pair "
            "grouping) rides host_group on the group thread — "
            "spread_prep_group_delta_pct — and overlaps with the "
            "worker wherever there is a second core; on a 1-core box "
            "NOTHING overlaps, so the paired e2e overhead is an upper "
            "bound that charges prep at full serial price. Both legs "
            "consume an identical spreader-spiked stream, so the delta "
            "isolates the family, not the traffic shape."),
        **_host_conditions(),
    }))


def bench_e2e() -> None:
    """Full in-process pipeline flows/sec: bus fetch + wire decode +
    columnarization + ALL models + sink flushes, with a per-stage budget.
    The north star is a pipeline rate, so this is measured as flows/sec
    like the kernel bench — produce time is excluded (production happens
    upstream of the processor in the reference architecture too)."""
    global _NATIVE
    _NATIVE = _ensure_native()  # the Python fallback decoder is ~10x slower

    stats = _run_e2e(E2E_FLOWS, samples=5)
    serial = _run_e2e(E2E_FLOWS, samples=2, ingest_mode="serial")
    print(json.dumps({
        "metric": "e2e pipeline throughput (decode + all models + flush)",
        "unit": "flows/sec",
        **stats,
        "vs_baseline": round(stats["value"] / 100_000.0, 3),
        "serial_flows_per_sec": serial["value"],
        "pipelined_speedup": round(stats["value"] / serial["value"], 3)
        if serial["value"] else 0.0,
        "native_decode": _NATIVE,
        "platform": _PLATFORM,
    }))


MESH_FLOWS = 60_000
MESH_PARTITIONS = 8
MESH_WORKERS = (1, 2, 4)


def bench_mesh() -> None:
    """flowmesh partition-count scaling curve: the SAME key-hash-sharded
    stream through an in-process mesh of 1, 2 and 4 workers (ROADMAP
    item 3's acceptance artifact). Same-box, same-stream legs: the
    speedup column is the honest statistic; absolute flows/s swings with
    the box (see BASELINE host_note history). On boxes with fewer cores
    than workers the curve flattens — the artifact records nproc so a
    flat curve on a 2-core box reads as the box, not the mesh."""
    global _NATIVE
    _NATIVE = _ensure_native()
    from flow_pipeline_tpu.cli import (_build_models, _common_flags,
                                       _gen_flags, _make_generator,
                                       _processor_flags)
    from flow_pipeline_tpu.engine import WorkerConfig
    from flow_pipeline_tpu.mesh import InProcessMesh, produce_sharded
    from flow_pipeline_tpu.transport import InProcessBus
    from flow_pipeline_tpu.utils.flags import FlagSet

    fs = _processor_flags(_gen_flags(_common_flags(FlagSet("bench"))))
    vals = fs.parse(["-produce.profile", "zipf"])

    def make_bus():
        bus = InProcessBus()
        bus.create_topic("flows", MESH_PARTITIONS)
        gen = _make_generator(vals)
        done = 0
        while done < MESH_FLOWS:
            n = min(16384, MESH_FLOWS - done)
            done += produce_sharded(bus, "flows", gen.batch(n),
                                    MESH_PARTITIONS)
        return bus

    def one_mesh_run(n_workers):
        bus = make_bus()  # untimed: production is upstream
        mesh = InProcessMesh(
            bus, "flows", n_workers,
            model_factory=lambda: _build_models(vals),
            config=WorkerConfig(poll_max=vals["processor.batch"],
                                snapshot_every=0,
                                ingest_native_group=True),
            sinks=[])
        elapsed = mesh.run()
        return MESH_FLOWS, elapsed

    def leg(n_workers):
        return _timed_samples(lambda: one_mesh_run(n_workers), samples=3)

    legs = {}
    for n in MESH_WORKERS:
        legs[n] = leg(n)
    base = legs[MESH_WORKERS[0]]["value"] or 1.0
    # meshscope trace-overhead A/B (r13 acceptance): the full 4-worker
    # mesh with the span recorder off vs the production ring, in
    # ADJACENT PAIRS with alternating order (the r11 methodology: slow
    # drift cancels within a pair, alternation cancels the warm-second
    # bias; single legs on throttled boxes spread 10-30%). Budget: the
    # same <2% as single-process flowtrace — mesh protocol spans ride
    # the same ring.
    from flow_pipeline_tpu.obs.trace import TRACER

    n_ab = max(MESH_WORKERS)
    pairs = 4
    ratios, off_rates, ring_rates = [], [], []

    def trace_leg(mode):
        TRACER.configure(mode)
        flows, elapsed = one_mesh_run(n_ab)
        return flows / max(elapsed, 1e-9)

    for i in range(pairs):
        if i % 2 == 0:
            off, ring = trace_leg("off"), trace_leg("ring")
        else:
            ring, off = trace_leg("ring"), trace_leg("off")
        off_rates.append(off)
        ring_rates.append(ring)
        if off:
            ratios.append(1 - ring / off)
    TRACER.configure(os.environ.get("FLOWTPU_TRACE", "ring"))
    overhead = 100 * statistics.median(ratios) if ratios else 0.0
    from flow_pipeline_tpu import native as native_lib

    print(json.dumps({
        "metric": "mesh partition-count scaling "
                  "(key-hash sharded, window-close merge)",
        "unit": "flows/sec",
        "partitions": MESH_PARTITIONS,
        "flows_per_leg": MESH_FLOWS,
        "legs": [{
            "workers": n,
            **legs[n],
            "speedup_vs_1": round(legs[n]["value"] / base, 3),
        } for n in MESH_WORKERS],
        "value": legs[max(MESH_WORKERS)]["value"],
        "mesh_trace_overhead_pct": round(overhead, 2),
        "mesh_trace_overhead_pairs_pct": [round(100 * r, 2)
                                          for r in ratios],
        "mesh_trace_off_flows_per_sec": round(
            statistics.median(off_rates), 1) if off_rates else None,
        "mesh_trace_ring_flows_per_sec": round(
            statistics.median(ring_rates), 1) if ring_rates else None,
        "overhead_budget_pct": 2.0,
        "within_budget": overhead < 2.0,
        "native_capabilities": native_lib.capabilities(),
        "native_decode": _NATIVE,
        "platform": _PLATFORM,
        "host_note": (
            "paired alternating-order off/ring legs (r11 methodology) "
            "— single mesh legs on throttled boxes spread 10-30%, so "
            "the median per-pair ratio is the honest overhead and can "
            "dip negative"),
    }))


CHAOS_FLOWS = 60_000
CHAOS_PARTITIONS = 8
CHAOS_WORKERS = 2
CHAOS_PAIRS = 4
# armed-but-(effectively-)never-firing: every seam consults its RNG on
# every call — the WORST-case cost of the fault machinery. The true
# faults-off path is one attribute read per seam and strictly cheaper.
CHAOS_ARMED_PLAN = ("sink.write:p=1e-12;mesh.submit:p=1e-12;"
                    "mesh.sync:p=1e-12@seed=1")
CHAOS_FAULT_PLAN = "mesh.submit:p=0.05;mesh.sync:p=0.03@seed=7"


def bench_chaos() -> None:
    """flowchaos acceptance artifact (r17): (1) the seam-overhead
    paired A/B — the in-process mesh (whose members cross the
    mesh.submit/mesh.sync seams every submission, with a
    ResilientSink-wrapped member sink crossing sink.write) run with the
    fault layer DISARMED vs ARMED at p~0, in adjacent alternating-order
    pairs (r11 methodology); budget <2% median. (2) the seeded-fault
    leg: the same mesh under the CHAOS_FAULT_PLAN with the coordinator
    write-ahead journal on — records injected-fault and retry counts,
    journal record volume, and the wall time a fresh coordinator takes
    to RECOVER from that journal."""
    global _NATIVE
    _NATIVE = _ensure_native()
    import shutil
    import tempfile

    from flow_pipeline_tpu.cli import (_build_models, _common_flags,
                                       _gen_flags, _make_generator,
                                       _processor_flags)
    from flow_pipeline_tpu.engine import WorkerConfig
    from flow_pipeline_tpu.mesh import (InProcessMesh, MeshCoordinator,
                                        produce_sharded,
                                        spec_from_models)
    from flow_pipeline_tpu.mesh.journal import replay_journal
    from flow_pipeline_tpu.obs import REGISTRY
    from flow_pipeline_tpu.sink import MemorySink, ResilientSink
    from flow_pipeline_tpu.transport import InProcessBus
    from flow_pipeline_tpu.utils.faults import FAULTS
    from flow_pipeline_tpu.utils.flags import FlagSet

    fs = _processor_flags(_gen_flags(_common_flags(FlagSet("bench"))))
    # modeled rate 150/s spreads the stream over ~2 windows and the
    # smaller batch multiplies submissions — the seams (submit/sync/
    # sink.write) are crossed often enough that the A/B measures them
    # and the seeded leg injects a meaningful fault count
    vals = fs.parse(["-produce.profile", "zipf", "-produce.rate", "150",
                     "-processor.batch", "4096"])

    def make_bus():
        bus = InProcessBus()
        bus.create_topic("flows", CHAOS_PARTITIONS)
        gen = _make_generator(vals)
        done = 0
        while done < CHAOS_FLOWS:
            n = min(16384, CHAOS_FLOWS - done)
            done += produce_sharded(bus, "flows", gen.batch(n),
                                    CHAOS_PARTITIONS)
        return bus

    def mesh_leg(journal=None, member_sink=False):
        bus = make_bus()  # untimed: production is upstream
        sinks = [ResilientSink(MemorySink(), retries=2)] \
            if member_sink else []
        mesh = InProcessMesh(
            bus, "flows", CHAOS_WORKERS,
            model_factory=lambda: _build_models(vals),
            config=WorkerConfig(poll_max=vals["processor.batch"],
                                snapshot_every=0),
            sinks=[], member_sinks=sinks, submit_every=4,
            journal=journal)
        elapsed = mesh.run()
        return CHAOS_FLOWS / max(elapsed, 1e-9)

    # ---- (1) paired alternating seam-overhead A/B -------------------------
    mesh_leg(member_sink=True)  # untimed warmup: jit compilation must
    # not land inside pair 0's first leg
    ratios, off_rates, armed_rates = [], [], []

    def leg(armed):
        FAULTS.configure(CHAOS_ARMED_PLAN if armed else None)
        try:
            return mesh_leg(member_sink=True)
        finally:
            FAULTS.configure(None)

    for i in range(CHAOS_PAIRS):
        if i % 2 == 0:
            off, armed = leg(False), leg(True)
        else:
            armed, off = leg(True), leg(False)
        off_rates.append(off)
        armed_rates.append(armed)
        if off:
            ratios.append(1 - armed / off)
    overhead = 100 * statistics.median(ratios) if ratios else 0.0

    # ---- (2) seeded-fault leg + journal recovery wall time ----------------
    retries = REGISTRY.counter("mesh_member_retries_total")
    injected = REGISTRY.counter("faults_injected_total")

    def counter_total(c):
        with c._lock:
            return sum(c._values.values())

    retries_before = counter_total(retries)
    injected_before = counter_total(injected)
    jdir = tempfile.mkdtemp(prefix="flowtpu-chaos-journal-")
    try:
        FAULTS.configure(CHAOS_FAULT_PLAN)
        try:
            fault_rate = mesh_leg(journal=jdir)
            fault_snapshot = FAULTS.snapshot()
        finally:
            FAULTS.configure(None)
        journal_path = os.path.join(jdir, "coordinator.journal")
        n_records = sum(1 for _ in replay_journal(journal_path))
        journal_bytes = os.path.getsize(journal_path)
        specs = spec_from_models(_build_models(vals))
        t0 = time.perf_counter()
        recovered = MeshCoordinator(specs, CHAOS_PARTITIONS,
                                    journal=jdir)
        recovery_s = time.perf_counter() - t0
        recovered.close()
    finally:
        shutil.rmtree(jdir, ignore_errors=True)

    print(json.dumps({
        "metric": "flowchaos seam overhead (paired A/B) + seeded-fault "
                  "recovery",
        "unit": "flows/sec",
        "flows_per_leg": CHAOS_FLOWS,
        "workers": CHAOS_WORKERS,
        "value": round(statistics.median(off_rates), 1)
        if off_rates else None,
        "seam_overhead_pct": round(overhead, 2),
        "seam_overhead_pairs_pct": [round(100 * r, 2) for r in ratios],
        "faults_off_flows_per_sec": round(statistics.median(off_rates), 1)
        if off_rates else None,
        "faults_armed_p0_flows_per_sec": round(
            statistics.median(armed_rates), 1) if armed_rates else None,
        "overhead_budget_pct": 2.0,
        "within_budget": overhead < 2.0,
        "armed_plan": CHAOS_ARMED_PLAN,
        "fault_plan": CHAOS_FAULT_PLAN,
        "faulted_flows_per_sec": round(fault_rate, 1),
        "faults_injected": fault_snapshot,
        "mesh_member_retries": counter_total(retries) - retries_before,
        "faults_injected_total": counter_total(injected)
        - injected_before,
        "journal_records": n_records,
        "journal_bytes": journal_bytes,
        "journal_recovery_seconds": round(recovery_s, 4),
        "native_decode": _NATIVE,
        "platform": _PLATFORM,
        "host_note": (
            "paired alternating-order disarmed/armed legs (r11 "
            "methodology); the armed leg consults every seam's RNG per "
            "call at p~0 — the worst case; the true faults-off path is "
            "one attribute read per seam. Median per-pair ratio is the "
            "honest overhead and can dip negative on throttled boxes."),
    }))


GUARD_FLOWS = 300_000
GUARD_PAIRS = 3
GUARD_PARTITIONS = 2
GUARD_OVERLOAD_SECONDS = 6.0
GUARD_OVERLOAD_MAX_FLOWS = 2_000_000  # backlog cap: the in-process bus
# shares this process's RSS, so the 2x leg bounds its own offered total
# the overload leg's chaos plan: a coin-flipped poll stall (the
# slow-dependency shape) + a sink-write stall at window close — both
# counted on faults_delayed_total, neither ever failing a call
GUARD_OVERLOAD_FAULTS = "bus.poll:p=0.2:delay=0.01;sink.write:delay=0.02@seed=11"


def bench_guard() -> None:
    """flowguard acceptance artifact (r20): (1) the armed-but-idle
    paired A/B — the full host-backend e2e worker with the guard
    DISARMED (-guard.lag=0, the exact default: every guard seam is one
    attribute read) vs ARMED with a budget the stream never approaches
    (the worst case that still stays at level 0: a per-batch lag
    observe + the optional-work flag writes), adjacent alternating-
    order pairs (r11 methodology); budget <2% median. (2) the overload
    leg: a paced producer offers 2x the measured disarmed capacity for
    a fixed wall interval under injected poll/sink delay faults while
    the armed worker rides the degradation ladder — records the level
    reached, the shed fraction, peak RSS, max observed watermark lag,
    and the exact accounting identity produced == admitted + shed."""
    global _NATIVE
    _NATIVE = _ensure_native()
    import resource
    import threading as _threading

    from flow_pipeline_tpu.cli import (_build_models, _common_flags,
                                       _gen_flags, _make_generator,
                                       _processor_flags, _worker_config)
    from flow_pipeline_tpu.engine import StreamWorker
    from flow_pipeline_tpu.guard import GuardConfig
    from flow_pipeline_tpu.mesh import produce_sharded
    from flow_pipeline_tpu.sink import MemorySink, ResilientSink
    from flow_pipeline_tpu.transport import Consumer, InProcessBus
    from flow_pipeline_tpu.utils.faults import FAULTS
    from flow_pipeline_tpu.utils.flags import FlagSet

    def vals_for(*extra):
        fs = _processor_flags(_gen_flags(_common_flags(FlagSet("bench"))))
        # flows5m + talkers keep the leg wall time in budget while still
        # exercising the grouped host dataplane the admission wrapper
        # fronts (the guard seams are per-batch, not per-model)
        return fs.parse(["-produce.profile", "zipf",
                         "-zipf.keys", "20000",
                         "-model.ports=false", "-model.ddos=false",
                         "-model.ips=false",
                         "-processor.batch", "4096",
                         "-sketch.backend", "host", *extra])

    def fill_bus(vals, n_flows):
        bus = InProcessBus()
        bus.create_topic("flows", GUARD_PARTITIONS)
        gen = _make_generator(vals)
        done = 0
        while done < n_flows:
            n = min(16384, n_flows - done)
            done += produce_sharded(bus, "flows", gen.batch(n),
                                    GUARD_PARTITIONS)
        return bus

    def worker_for(vals, bus, sinks=()):
        return StreamWorker(Consumer(bus, "flows", fixedlen=True),
                            _build_models(vals), list(sinks),
                            _worker_config(vals))

    def leg(guard_lag):
        vals = vals_for("-guard.lag", str(guard_lag))
        bus = fill_bus(vals, GUARD_FLOWS)
        w = worker_for(vals, bus)
        t0 = time.perf_counter()
        w.run(stop_when_idle=True)
        elapsed = time.perf_counter() - t0
        assert w.flows_seen == GUARD_FLOWS  # level 0 throughout: no shed
        return {"value": GUARD_FLOWS / max(elapsed, 1e-9)}

    leg(0.0)  # untimed warmup: jit compilation must not land in pair 0
    off_runs, armed_runs, ratios = _paired_e2e_ab(
        # armed budget 1e6 s: the ladder never engages, so the leg
        # measures exactly the armed-but-level-0 observe cost
        lambda: leg(0.0), lambda: leg(1e6), pairs=GUARD_PAIRS)
    overhead = (100 * (1 - statistics.median(ratios))) if ratios else 0.0
    capacity = statistics.median(r["value"] for r in off_runs)

    # ---- (2) the 2x-overload leg -------------------------------------------
    vals = vals_for("-guard.lag", "0.5")
    bus = InProcessBus()
    bus.create_topic("flows", GUARD_PARTITIONS)
    sink = ResilientSink(MemorySink(), retries=2)
    w = worker_for(vals, bus, [sink])
    # bench-cadence ladder: the default 5 s dwell is production tuning
    # (one transition per dwell); a 6 s leg needs the ladder able to
    # actually climb while the soak runs
    w.guard.config = GuardConfig(lag_budget=0.5, max_level=6,
                                 hysteresis=0.5, dwell=0.3)
    gen = _make_generator(vals)
    offered_rate = 2.0 * capacity
    produced = 0
    max_lag = 0.0
    done = _threading.Event()

    def producer():
        nonlocal produced, max_lag
        t_start = time.perf_counter()
        while True:
            t = time.perf_counter() - t_start
            if t >= GUARD_OVERLOAD_SECONDS:
                break
            target = min(int(min(t + 0.05, GUARD_OVERLOAD_SECONDS)
                             * offered_rate), GUARD_OVERLOAD_MAX_FLOWS)
            while produced < target:
                n = min(16384, target - produced)
                produced += produce_sharded(bus, "flows", gen.batch(n),
                                            GUARD_PARTITIONS)
            max_lag = max(max_lag, w.guard.m_lag.value())
            time.sleep(0.05)
        done.set()

    FAULTS.configure(GUARD_OVERLOAD_FAULTS)
    rss_before_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    prod_thread = _threading.Thread(target=producer, daemon=True)
    t0 = time.perf_counter()
    prod_thread.start()
    try:
        # run_once-driven loop instead of run(stop_when_idle=True): a
        # transient idle poll while the paced producer sleeps must not
        # end the leg early — only idle AFTER production finishes does
        while True:
            if w.run_once():
                continue
            if done.is_set():
                break
            time.sleep(0.002)
        w.finalize()
    finally:
        # snapshot BEFORE configure(None): clearing the plan drops the
        # per-site roll/delay counters the artifact records
        delay_snapshot = FAULTS.snapshot()
        FAULTS.configure(None)
        if w.executor is not None:
            w.executor.stop()
        if w.flusher is not None:
            w.flusher.stop()
    elapsed = time.perf_counter() - t0
    prod_thread.join()
    rss_after_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    meta = w.guard.meta()
    shed = meta["shed_total"]

    print(json.dumps({
        "metric": "flowguard armed-idle overhead (paired A/B) + 2x "
                  "overload leg",
        "unit": "flows/sec",
        "flows_per_leg": GUARD_FLOWS,
        "value": round(capacity, 1),
        "guard_overhead_pct": round(overhead, 2),
        "guard_overhead_pairs_pct": [round(100 * (1 - r), 2)
                                     for r in ratios],
        "disarmed_flows_per_sec": round(capacity, 1),
        "armed_idle_flows_per_sec": round(
            statistics.median(r["value"] for r in armed_runs), 1)
        if armed_runs else None,
        "overhead_budget_pct": 2.0,
        "within_budget": overhead < 2.0,
        "overload_offered_flows_per_sec": round(offered_rate, 1),
        "overload_seconds": GUARD_OVERLOAD_SECONDS,
        "overload_fault_plan": GUARD_OVERLOAD_FAULTS,
        "overload_produced": produced,
        "overload_admitted": w.flows_seen,
        "overload_shed": shed,
        "overload_accounting_exact": produced == w.flows_seen + shed,
        "overload_shed_fraction": round(shed / produced, 4)
        if produced else 0.0,
        "overload_max_level": meta["max_level_seen"],
        "overload_final_level": meta["level"],
        "overload_max_observed_lag_s": round(max_lag, 3),
        "overload_elapsed_s": round(elapsed, 2),
        "overload_faults_delayed": delay_snapshot,
        "peak_rss_before_mb": round(rss_before_kb / 1024, 1),
        "peak_rss_after_mb": round(rss_after_kb / 1024, 1),
        "native_decode": _NATIVE,
        "platform": _PLATFORM,
        "host_note": (
            "paired alternating-order disarmed/armed-idle legs (r11 "
            "methodology; median per-pair ratio, can dip negative on "
            "throttled boxes). The overload leg paces a producer at 2x "
            "the measured disarmed capacity under injected poll/sink "
            "delay faults with a bench-cadence ladder (dwell 0.3 s vs "
            "the production 5 s); level-0 bit-exactness and the soak "
            "gates live in `make guard-parity`, this artifact carries "
            "the throughput/accounting shape."),
    }))


SERVE_FLOWS = 800_000
SERVE_PROCS = 2      # reader subprocesses (honest concurrency: no GIL
SERVE_THREADS = 4    # sharing with the server) x connections each
SERVE_PAIRS = 4
GATEWAY_PAIRS = 2    # direct-vs-gateway alternating A/B pairs (r18)
TRICKLE_PUBLISHES = 4   # production-cadence delta-efficiency samples
TRICKLE_FLOWS = 4096    # stream between trickle publishes (~4s modeled)


def bench_serve() -> None:
    """flowserve acceptance artifact (ROADMAP item 5): a closed-loop
    8-connection query load (2 reader subprocesses x 4 keep-alive
    connections — separate interpreters, so the measurement does not
    throttle itself on the server's GIL) hammers /query/* WHILE the
    worker ingests at full rate, and a paired serve-on / serve-off
    ingest A/B (alternating leg order, the r11 methodology) measures
    what serving costs the dataplane. The queries/sec value is the
    sustained concurrent read rate DURING ingest — cache hits dominate
    between publishes, which is the design (thousands of readers share
    one extraction per snapshot)."""
    import threading

    global _NATIVE
    _NATIVE = _ensure_native()
    from flow_pipeline_tpu.cli import (_batch_frames, _build_models,
                                       _common_flags, _gen_flags,
                                       _make_generator, _processor_flags)
    from flow_pipeline_tpu.engine import StreamWorker, WorkerConfig
    from flow_pipeline_tpu.serve import ServeServer, attach_worker
    from flow_pipeline_tpu.serve.loadgen import (run_load_procs,
                                                 sample_ages, wait_ready)
    from flow_pipeline_tpu.transport import Consumer, InProcessBus
    from flow_pipeline_tpu.utils.flags import FlagSet

    fs = _processor_flags(_gen_flags(_common_flags(FlagSet("bench"))))
    # modeled rate 1000/s: the 800k-flow stream spans ~800s of event
    # time, so windows CLOSE mid-leg — publishes exercise the
    # window-close trigger and /query/range serves real closed rows
    vals = fs.parse(["-produce.profile", "zipf",
                     "-produce.rate", "1000"])

    def make_bus():
        bus = InProcessBus()
        bus.create_topic("flows", 2)
        gen = _make_generator(vals)
        produced = 0
        while produced < SERVE_FLOWS:
            bus.produce_many("flows", _batch_frames(gen.batch(16384)))
            produced += 16384
        return bus

    def run_leg(mode: str, load_s: float = 0.0):
        """One full ingest leg. ``mode``: "off" = bare worker (the A/B
        baseline); "pub" = flowserve wired (publisher in the batch
        loop, snapshots publishing, server up) but NO readers — what
        the serving MACHINERY costs the dataplane; "load" = "pub" plus
        the reader processes for ``load_s`` inside the ingest window;
        "gwload" = "load" with the readers pointed at a flowgate
        REPLICA mirroring the serve surface over HTTP (delta-fed; the
        load stats gain the feed's bytes-per-publish ledger).
        Returns (ingest flows/s, load stats | None, max age | None,
        server | None — still running, for the idle-ceiling leg)."""
        worker = StreamWorker(
            Consumer(make_bus(), fixedlen=True), _build_models(vals), [],
            WorkerConfig(poll_max=vals["processor.batch"],
                         snapshot_every=0, ingest_native_group=True))
        server = None
        load = ages = None
        if mode != "off":
            # the SHIPPED refresh default: the A/B measures what a
            # production deployment pays (window closes + 2s cadence)
            pub = attach_worker(worker, refresh=2.0)
            server = ServeServer(pub.store, port=0).start()
        gw = gws = None
        if mode == "gwload":
            from flow_pipeline_tpu.gateway import SnapshotGateway
            from flow_pipeline_tpu.serve import ServeServer as _SS

            gw = SnapshotGateway([f"127.0.0.1:{server.port}"],
                                 poll=0.05)
            gws = _SS(gw.store, port=0).start()
            gw.serve_on(gws).start()
        dt = {}

        def ingest():
            t0 = time.perf_counter()
            worker.run(stop_when_idle=True)
            dt["s"] = time.perf_counter() - t0

        t = threading.Thread(target=ingest, daemon=True)
        t.start()
        if mode in ("load", "gwload"):
            read_port = gws.port if mode == "gwload" else server.port
            assert wait_ready("127.0.0.1", read_port, timeout=60)
            done = threading.Event()
            sampler, ages = sample_ages("127.0.0.1", read_port, done)
            load = run_load_procs("127.0.0.1", read_port,
                                  procs=SERVE_PROCS,
                                  threads=SERVE_THREADS,
                                  duration=load_s)
            done.set()
            sampler.join(timeout=10)
        t.join()
        if mode == "gwload":
            # the upstream feed's shipping-cost ledger IS the honest
            # delta-efficiency evidence (encoded sizes per observed
            # publish, both codings)
            feed = server._feed
            load["feed_stats"] = feed.stats() if feed else None
            gw.stop()
            gws.stop()
        return (SERVE_FLOWS / dt["s"] if dt.get("s") else 0.0, load,
                max(ages) if ages else None, server)

    warm_rate, _, _, _ = run_leg("off")  # warm: XLA compile excluded
    # load window sized to sit INSIDE the warm ingest wall (the qps
    # value must be "during full-rate ingest", not "mostly idle")
    load_s = min(10.0, max(1.0, 0.8 * SERVE_FLOWS / max(warm_rate, 1.0)))
    # A/B 1 — the budgeted claim: serving MACHINERY (publisher hook,
    # snapshot extraction + pointer swaps, server thread) vs bare
    # worker, paired with alternating order (r11 methodology)
    pub_rates, off_rates, pub_ratios = [], [], []
    for i in range(SERVE_PAIRS):
        if i % 2 == 0:
            on, _, _, srv = run_leg("pub")
            off, _, _, _ = run_leg("off")
        else:
            off, _, _, _ = run_leg("off")
            on, _, _, srv = run_leg("pub")
        srv.stop()
        pub_rates.append(on)
        off_rates.append(off)
        if off:
            pub_ratios.append(1 - on / off)
    # A/B 2 — reader CONTENTION: the same ingest with 2 reader
    # processes saturating the serving surface. On a box with spare
    # cores this converges to A/B 1; on a 2-core box the readers and
    # the dataplane share cores BY CONSTRUCTION and the delta is the
    # box, not the architecture (the BENCH_r12 flat-curve precedent).
    from flow_pipeline_tpu.obs import REGISTRY

    loads, load_rates, max_ages = [], [], []
    idle_server = None
    # hits are diffed across exactly the load legs: the counter is
    # process-global and the idle-ceiling leg below would otherwise
    # inflate the ratio past 1.0
    hits0 = REGISTRY.counter("serve_cache_hits_total").value()
    for _ in range(2):
        on, load, age, srv = run_leg("load", load_s)
        if idle_server is not None:
            idle_server.stop()
        idle_server = srv  # the last leg's server feeds the idle leg
        load_rates.append(on)
        loads.append(load)
        if age is not None:
            max_ages.append(age)
    hits = REGISTRY.counter("serve_cache_hits_total").value() - hits0
    # idle-ceiling leg: the same readers against the (quiesced) server
    # — what the serving path alone sustains on this box
    idle = run_load_procs("127.0.0.1", idle_server.port,
                          procs=SERVE_PROCS, threads=SERVE_THREADS,
                          duration=2.0)
    idle_server.stop()
    # flowgate leg (r18): the same reader fleet through a delta-fed
    # gateway REPLICA, paired alternating-order against the direct
    # path (r11 methodology — same box, adjacent legs, the RATIO is
    # the claim; absolutes are box-bound like everything here). The
    # gateway mirrors over real HTTP /sub/snapshot polls, so the leg
    # also produces the honest delta-vs-full bytes-per-publish ledger.
    from flow_pipeline_tpu.obs import REGISTRY as _REG

    syncs0 = {k: _REG.counter("gateway_syncs_total").value(kind=k)
              for k in ("full", "delta", "none")}
    gw_loads, gw_direct_loads, feed_ledgers = [], [], []
    for i in range(GATEWAY_PAIRS):
        order = ("gwload", "load") if i % 2 == 0 else ("load", "gwload")
        for m in order:
            _, load, _, srv = run_leg(m, load_s)
            srv.stop()
            if m == "gwload":
                gw_loads.append(load)
                if load.get("feed_stats"):
                    feed_ledgers.append(load["feed_stats"])
            else:
                gw_direct_loads.append(load)
    sync_kinds = {k: _REG.counter("gateway_syncs_total").value(kind=k)
                  - syncs0[k] for k in syncs0}

    # delta efficiency at PRODUCTION cadence: the saturated legs above
    # compress ~400s of event time into one refresh interval, dirtying
    # every CMS tile — the honest worst case (delta ~= full + tile
    # overhead). The append-mostly regime the codec targets is a
    # publish per FEW SECONDS of traffic; this leg measures it with a
    # real worker: full 800k warmup, then TRICKLE_FLOWS of additional
    # stream per publish (at -produce.rate 1000 that is ~4s of modeled
    # open-window traffic between versions).
    def delta_trickle_ledger():
        from flow_pipeline_tpu.gateway import SnapshotFeed

        bus = InProcessBus()
        bus.create_topic("flows", 2)
        gen = _make_generator(vals)
        produced = 0
        while produced < SERVE_FLOWS:
            bus.produce_many("flows", _batch_frames(gen.batch(16384)))
            produced += 16384
        worker = StreamWorker(
            Consumer(bus, fixedlen=True), _build_models(vals), [],
            WorkerConfig(poll_max=vals["processor.batch"],
                         snapshot_every=0, ingest_native_group=True))
        pub = attach_worker(worker, refresh=0.0)
        while worker.run_once():
            pass
        with worker.lock:
            pub.publish(worker)
        feed = SnapshotFeed(pub.store)
        feed.frame_since(0)  # observe the warmed-up full
        for _ in range(TRICKLE_PUBLISHES):
            bus.produce_many("flows",
                             _batch_frames(gen.batch(TRICKLE_FLOWS)))
            while worker.run_once():
                pass
            with worker.lock:
                pub.publish(worker)
            feed.frame_since(0)  # observe -> the ledger records the delta
        return feed.stats()

    trickle = delta_trickle_ledger()
    gw_qps = statistics.median(x["qps"] for x in gw_loads)
    gw_direct_qps = statistics.median(x["qps"]
                                      for x in gw_direct_loads)
    gw_codes: dict[str, int] = {}
    for x in gw_loads:
        for c, n in x["codes"].items():
            gw_codes[c] = gw_codes.get(c, 0) + n
    fed = {
        "publishes": sum(f["publishes"] for f in feed_ledgers),
        "deltas": sum(f["deltas"] for f in feed_ledgers),
        "full_bytes": sum(f["full_bytes"] for f in feed_ledgers),
        "delta_bytes": sum(f["delta_bytes"] for f in feed_ledgers),
    } if feed_ledgers else {}
    gateway_section = {
        "replica_qps": round(gw_qps, 1),
        "replica_p50_ms": round(statistics.median(
            x["p50_ms"] for x in gw_loads), 3),
        "replica_p99_ms": round(statistics.median(
            x["p99_ms"] for x in gw_loads), 3),
        "direct_qps": round(gw_direct_qps, 1),
        "direct_p50_ms": round(statistics.median(
            x["p50_ms"] for x in gw_direct_loads), 3),
        "direct_p99_ms": round(statistics.median(
            x["p99_ms"] for x in gw_direct_loads), 3),
        "qps_ratio_gateway_vs_direct": round(
            gw_qps / gw_direct_qps, 3) if gw_direct_qps else None,
        "pairs": GATEWAY_PAIRS,
        "poll_s": 0.05,
        "codes": gw_codes,
        "zero_5xx": not any(c.startswith("5") for c in gw_codes),
        "transport_errors": sum(x["errors"] for x in gw_loads),
        "sync_kinds": sync_kinds,
        "bytes_per_publish_full": round(
            fed["full_bytes"] / fed["publishes"], 1)
        if fed.get("publishes") else None,
        "bytes_per_publish_delta": round(
            fed["delta_bytes"] / fed["deltas"], 1)
        if fed.get("deltas") else None,
        "delta_to_full_bytes_ratio": round(
            (fed["delta_bytes"] / fed["deltas"])
            / (fed["full_bytes"] / fed["publishes"]), 4)
        if fed.get("deltas") and fed.get("publishes") else None,
        "trickle": {
            "flows_per_publish": TRICKLE_FLOWS,
            "publishes": trickle.get("deltas", 0),
            "bytes_per_publish_full": trickle.get(
                "full_bytes_per_publish"),
            "bytes_per_publish_delta": trickle.get(
                "delta_bytes_per_publish"),
            "delta_to_full_bytes_ratio": round(
                trickle["delta_bytes_per_publish"]
                / trickle["full_bytes_per_publish"], 4)
            if trickle.get("delta_bytes_per_publish")
            and trickle.get("full_bytes_per_publish") else None,
        },
        "note": (
            "paired alternating-order direct-vs-gateway legs on the "
            "SAME box: readers, dataplane AND the mirror thread share "
            "nproc cores, so the ratio (not either absolute) is the "
            "honest statistic. bytes_per_publish_* come from the "
            "upstream feed's encoded-frame ledger: the load legs "
            "compress ~400s of event time into one refresh interval "
            "(every CMS tile dirty — delta ~= full, the recorded "
            "worst case); `trickle` is the append-mostly regime the "
            "codec targets — a publish per few seconds of modeled "
            "open-window traffic"),
    }
    qps = statistics.median(x["qps"] for x in loads)
    codes: dict[str, int] = {}
    for x in loads + [idle]:
        for c, n in x["codes"].items():
            codes[c] = codes.get(c, 0) + n
    n5xx = sum(n for c, n in codes.items() if c.startswith("5"))
    pub_overhead = 100 * statistics.median(pub_ratios) \
        if pub_ratios else 0.0
    off_med = statistics.median(off_rates) if off_rates else 0.0
    contention = 100 * (1 - statistics.median(load_rates) / off_med) \
        if off_med else 0.0
    from flow_pipeline_tpu import native as native_lib

    reqs = sum(x["requests"] for x in loads)
    print(json.dumps({
        "metric": "flowserve concurrent query serving during "
                  "full-rate ingest",
        "unit": "queries/sec",
        "value": round(qps, 1),
        "qps_target": 1000.0,
        "qps_target_met": qps >= 1000.0,
        "idle_qps": idle["qps"],
        "idle_p50_ms": idle["p50_ms"],
        "query_p50_ms": round(statistics.median(
            x["p50_ms"] for x in loads), 3),
        "query_p99_ms": round(statistics.median(
            x["p99_ms"] for x in loads), 3),
        "reader_procs": SERVE_PROCS,
        "reader_connections": SERVE_PROCS * SERVE_THREADS,
        "requests_total": reqs,
        "codes": codes,
        "zero_5xx": n5xx == 0,
        "transport_errors": sum(x["errors"] for x in loads),
        "cache_hit_ratio": round(hits / reqs, 3) if reqs else 0.0,
        "snapshot_max_age_s": round(max(max_ages), 3) if max_ages
        else None,
        "flows_per_leg": SERVE_FLOWS,
        "ingest_off_flows_per_sec": round(off_med, 1),
        "ingest_serving_flows_per_sec": round(
            statistics.median(pub_rates), 1),
        "ingest_under_load_flows_per_sec": round(
            statistics.median(load_rates), 1),
        "serve_overhead_pct": round(pub_overhead, 2),
        "serve_overhead_pairs_pct": [round(100 * r, 2)
                                     for r in pub_ratios],
        # the same overhead off the leg-rate MEDIANS (noise-robust on
        # boxes where individual pairs spread wider than the effect)
        "serve_overhead_medians_pct": round(
            100 * (1 - statistics.median(pub_rates) / off_med)
            if off_med else 0.0, 2),
        "overhead_budget_pct": 2.0,
        "within_budget": pub_overhead < 2.0,
        "reader_contention_pct": round(contention, 2),
        "gateway": gateway_section,
        "native_capabilities": native_lib.capabilities(),
        "native_decode": _NATIVE,
        "platform": _PLATFORM,
        "nproc": os.cpu_count(),
        "load_window_s": round(load_s, 2),
        "host_note": (
            "serve_overhead_pct is the budgeted A/B (publisher + "
            "snapshot publishing + server, NO readers; paired "
            "alternating-order legs, r11 methodology — single legs on "
            "throttled boxes spread 10-30% and the median per-pair "
            "ratio can dip negative). reader_contention_pct and the "
            "qps value add 2 reader processes x 4 keep-alive "
            "connections INSIDE the ingest window: on this nproc-core "
            "box readers and dataplane share cores by construction, "
            "so both are box-bound (the BENCH_r12 flat-curve "
            "precedent) — re-measure the 1k-qps target on a box with "
            "spare cores for the readers; idle_qps is the serving "
            "path's own ceiling here"),
    }))


HISTORY_FLOWS = 200_000      # warmup stream before the archived publishes
HISTORY_PUBLISHES = 12       # archived trickle publishes (v2..v13)
HISTORY_TRICKLE_FLOWS = 4096  # ~4s of modeled traffic between publishes
HISTORY_KEYFRAME_EVERY = 4   # short cadence so the reconstruct sweep
# covers depths 0..4 inside 13 versions (prod default is 64)
HISTORY_PAIRS = 3            # archive-on vs archive-off A/B pairs
HISTORY_RECON_REPS = 3       # cold reconstructs per archived version


def bench_history() -> None:
    """flowhistory acceptance artifact (ROADMAP item 6): what archiving
    the delta chain COSTS and what time travel PAYS. Three claims: (1)
    write amplification — archive bytes per publish, keyframe vs delta
    coding split, at the append-mostly trickle cadence the codec
    targets; (2) reconstruct latency vs chain depth — a cold reader
    (nearest keyframe + delta replay, no state cache) per archived
    version; (3) the archiver's dataplane-side cost — paired
    alternating-order archive-on/off trickle legs (r11 methodology),
    budget <2%. Replay BYTE-parity is a test gate (`make
    history-parity`), not a benchmark statistic."""
    import shutil
    import tempfile

    global _NATIVE
    _NATIVE = _ensure_native()
    from flow_pipeline_tpu.cli import (_batch_frames, _build_models,
                                       _common_flags, _gen_flags,
                                       _make_generator, _processor_flags)
    from flow_pipeline_tpu.engine import StreamWorker, WorkerConfig
    from flow_pipeline_tpu.gateway import SnapshotGateway
    from flow_pipeline_tpu.history import (ArchiveReader, ArchiveWriter,
                                           register_history_metrics)
    from flow_pipeline_tpu.obs import REGISTRY
    from flow_pipeline_tpu.serve import attach_worker
    from flow_pipeline_tpu.transport import Consumer, InProcessBus
    from flow_pipeline_tpu.utils.flags import FlagSet

    register_history_metrics()
    fs = _processor_flags(_gen_flags(_common_flags(FlagSet("bench"))))
    vals = fs.parse(["-produce.profile", "zipf",
                     "-produce.rate", "1000"])

    def run_leg(archive_dir):
        """One warm-ingest + trickle-publish leg. ``archive_dir`` set =
        a gateway with an embedded ArchiveWriter mirrors every publish
        (record + group commit + fsync per sync); None = the identical
        gateway sync WITHOUT the archiver (the A/B baseline). Returns
        (trickle flows/s, per-sync wall ms list)."""
        bus = InProcessBus()
        bus.create_topic("flows", 2)
        gen = _make_generator(vals)
        produced = 0
        while produced < HISTORY_FLOWS:
            bus.produce_many("flows", _batch_frames(gen.batch(16384)))
            produced += 16384
        worker = StreamWorker(
            Consumer(bus, fixedlen=True), _build_models(vals), [],
            WorkerConfig(poll_max=vals["processor.batch"],
                         snapshot_every=0, ingest_native_group=True))
        pub = attach_worker(worker, refresh=0.0)
        while worker.run_once():
            pass
        with worker.lock:
            pub.publish(worker)
        writer = None
        if archive_dir is not None:
            writer = ArchiveWriter(archive_dir,
                                   keyframe_every=HISTORY_KEYFRAME_EVERY)
        gw = SnapshotGateway([pub.store], poll=60, archive=writer)
        gw.sync_once()  # v1: the anchoring keyframe (outside the window)
        sync_ms = []
        t0 = time.perf_counter()
        for _ in range(HISTORY_PUBLISHES):
            bus.produce_many(
                "flows", _batch_frames(gen.batch(HISTORY_TRICKLE_FLOWS)))
            while worker.run_once():
                pass
            with worker.lock:
                pub.publish(worker)
            s0 = time.perf_counter()
            gw.sync_once()
            sync_ms.append(1000 * (time.perf_counter() - s0))
        dt = time.perf_counter() - t0
        if writer is not None:
            writer.close()
        rate = HISTORY_PUBLISHES * HISTORY_TRICKLE_FLOWS / dt if dt \
            else 0.0
        return rate, sync_ms

    # ledger leg first (also the warm leg — XLA compile excluded from
    # the A/B): counters are diffed across exactly this leg so the
    # coding split is per-publish-attributable
    recs0 = {k: REGISTRY.counter("history_records_total").value(kind=k)
             for k in ("key", "delta")}
    bytes0 = {k: REGISTRY.counter(
        "history_record_bytes_total").value(kind=k)
        for k in ("key", "delta")}
    archive_dir = tempfile.mkdtemp(prefix="bench_history_")
    try:
        _, ledger_sync_ms = run_leg(archive_dir)
        recs = {k: REGISTRY.counter(
            "history_records_total").value(kind=k) - recs0[k]
            for k in recs0}
        rec_bytes = {k: REGISTRY.counter(
            "history_record_bytes_total").value(kind=k) - bytes0[k]
            for k in bytes0}
        seg_files = sorted(f for f in os.listdir(archive_dir)
                           if f.endswith(".fharc"))
        archive_bytes = sum(
            os.path.getsize(os.path.join(archive_dir, f))
            for f in seg_files)
        # seg-{version}.fharc — a segment STARTS at its keyframe, so
        # depth(v) = v - newest segment start <= v
        seg_starts = sorted(int(f[4:-6]) for f in seg_files)

        # reconstruct sweep: a COLD reader per measurement (fresh scan,
        # empty state cache) — the latency claimed is the worst case,
        # not an LRU hit
        reader = ArchiveReader(archive_dir)
        versions = reader.versions()
        by_depth: dict[int, list] = {}
        for v in versions:
            depth = v - max(s for s in seg_starts if s <= v)
            for _ in range(HISTORY_RECON_REPS):
                cold = ArchiveReader(archive_dir)
                r0 = time.perf_counter()
                cold.reconstruct(v)
                by_depth.setdefault(depth, []).append(
                    1000 * (time.perf_counter() - r0))
        recon_ms = {str(d): round(statistics.median(ts), 3)
                    for d, ts in sorted(by_depth.items())}
    finally:
        shutil.rmtree(archive_dir, ignore_errors=True)

    # A/B: the archiver's cost to the gateway's publish-sync loop,
    # paired alternating order (r11 methodology). Each pair gets a
    # FRESH archive dir — retention must not skew later legs.
    on_rates, off_rates, ratios = [], [], []
    on_sync, off_sync = [], []
    for i in range(HISTORY_PAIRS):
        d = tempfile.mkdtemp(prefix="bench_history_ab_")
        try:
            if i % 2 == 0:
                on, s_on = run_leg(d)
                off, s_off = run_leg(None)
            else:
                off, s_off = run_leg(None)
                on, s_on = run_leg(d)
        finally:
            shutil.rmtree(d, ignore_errors=True)
        on_rates.append(on)
        off_rates.append(off)
        on_sync.extend(s_on)
        off_sync.extend(s_off)
        if off:
            ratios.append(1 - on / off)
    overhead = 100 * statistics.median(ratios) if ratios else 0.0
    n_recs = recs["key"] + recs["delta"]
    sync_on_med = statistics.median(on_sync) if on_sync else 0.0
    sync_off_med = statistics.median(off_sync) if off_sync else 0.0
    archiver_ms = sync_on_med - sync_off_med
    # the budgeted claim: the archiver's per-publish wall against the
    # SHIPPED 2s refresh cadence — the trickle loop compresses that
    # cadence ~20x, so its raw on/off pct is the worst case, not the
    # production cost
    shipped_refresh_s = 2.0
    overhead_shipped = 100 * archiver_ms / (1000 * shipped_refresh_s)

    print(json.dumps({
        "metric": "flowhistory archive write cost and time-travel "
                  "reconstruct latency",
        "unit": "pct of a gateway publish interval (shipped 2s "
                "refresh) spent archiving",
        "value": round(overhead_shipped, 2),
        "overhead_budget_pct": 2.0,
        "within_budget": overhead_shipped < 2.0,
        "overhead_compressed_loop_pct": round(overhead, 2),
        "overhead_pairs_pct": [round(100 * r, 2) for r in ratios],
        "pairs": HISTORY_PAIRS,
        "publishes": n_recs,
        "keyframes": recs["key"],
        "deltas": recs["delta"],
        "keyframe_every": HISTORY_KEYFRAME_EVERY,
        "bytes_per_keyframe": round(
            rec_bytes["key"] / recs["key"], 1) if recs["key"] else None,
        "bytes_per_delta": round(
            rec_bytes["delta"] / recs["delta"], 1)
        if recs["delta"] else None,
        "delta_to_keyframe_bytes_ratio": round(
            (rec_bytes["delta"] / recs["delta"])
            / (rec_bytes["key"] / recs["key"]), 4)
        if recs["delta"] and recs["key"] else None,
        "archive_bytes_total": archive_bytes,
        "segments": len(seg_files),
        "sync_ms_archived_p50": round(sync_on_med, 3),
        "sync_ms_plain_p50": round(sync_off_med, 3),
        "archiver_ms_per_publish": round(archiver_ms, 3),
        "shipped_refresh_s": shipped_refresh_s,
        "ledger_sync_ms_p50": round(
            statistics.median(ledger_sync_ms), 3)
        if ledger_sync_ms else None,
        "reconstruct_ms_by_depth": recon_ms,
        "reconstruct_versions": len(versions),
        "reconstruct_reps_per_version": HISTORY_RECON_REPS,
        "flows_warmup": HISTORY_FLOWS,
        "trickle_flows_per_publish": HISTORY_TRICKLE_FLOWS,
        "replay_parity_gate": "make history-parity "
                              "(tests/test_history.py — byte-identical "
                              "replay, damage honesty)",
        "native_decode": _NATIVE,
        "platform": _PLATFORM,
        "nproc": os.cpu_count(),
        "host_note": (
            "trickle legs compress ~4s of modeled event time per "
            "publish into wall-clock milliseconds, so "
            "overhead_compressed_loop_pct measures the fsync'd group "
            "commit against an ARTIFICIALLY dense publish cadence — "
            "the recorded worst case. The budgeted claim is the "
            "paired per-publish archiver wall (sync_ms_archived - "
            "sync_ms_plain, r11 alternating-order pairs) against the "
            "shipped 2s refresh interval the gateway actually "
            "publishes at. reconstruct_ms_by_depth is COLD (fresh "
            "reader per call): depth 0 = keyframe hit, depth d = "
            "keyframe + d delta applies with the unchanged gateway "
            "codec"),
    }))


HH_SKETCH_PAIRS = 4


def _sweep_hh_sketch_ab() -> dict:
    """Paired alternating-order -hh.sketch=table|invertible e2e legs on
    the fused host dataplane (the r11 methodology: drift cancels within
    a pair, alternation cancels the warm-second bias), recording the
    host_fused in-kernel phase breakdown PER LEG — so the admission-
    path deletion is MEASURED, not asserted: the invertible leg's
    topk/cms/prefilter phases must read ~0 (its whole sketch fold is
    the `inv` phase), while the table leg carries the ~56% admission
    share BENCH_r11 attributed."""
    from flow_pipeline_tpu import native as native_lib

    if not (native_lib.fused_available() and native_lib.inv_available()):
        return {"error": "libflowdecode lacks the fused/invertible "
                         "kernels", "hint": "make native"}
    table_rates, inv_rates, ratios = [], [], []
    table_phases, inv_phases = {}, {}

    def leg(mode):
        return _run_e2e(E2E_FLOWS, samples=1, sketch_backend="host",
                        ingest_fused="on", hh_sketch=mode)

    for i in range(HH_SKETCH_PAIRS):
        if i % 2 == 0:
            tab, inv = leg("table"), leg("invertible")
        else:
            inv, tab = leg("invertible"), leg("table")
        table_rates.append(tab["value"])
        inv_rates.append(inv["value"])
        if tab["value"]:
            ratios.append(inv["value"] / tab["value"])
        table_phases = tab["host_fused_phases"] or table_phases
        inv_phases = inv["host_fused_phases"] or inv_phases

    def admission_share(phases):
        return round(sum(phases.get(ph, 0.0)
                         for ph in ("topk", "cms", "prefilter")), 1)

    speedup = statistics.median(ratios) if ratios else 0.0
    return {
        "metric": "hh sweep -hh.sketch=table|invertible paired A/B "
                  "(admission-path deletion, fused host dataplane)",
        "unit": "flows/sec",
        "value": round(statistics.median(inv_rates), 1),
        "table_flows_per_sec": round(statistics.median(table_rates), 1),
        "invertible_flows_per_sec": round(
            statistics.median(inv_rates), 1),
        "invertible_speedup": round(speedup, 3),
        "invertible_speedup_pairs": [round(r, 3) for r in ratios],
        "pairs": HH_SKETCH_PAIRS,
        # the acceptance numbers: the table leg's admission phases
        # (topk + cms + prefilter, pct of host_fused) vs the invertible
        # leg's — which must sit at ~0 with the new `inv` phase
        # carrying that family's whole fold
        "host_fused_phases_table": table_phases,
        "host_fused_phases_invertible": inv_phases,
        "admission_share_table_pct": admission_share(table_phases),
        "admission_share_invertible_pct": admission_share(inv_phases),
        "inv_phase_share_pct": inv_phases.get("inv", 0.0),
        "native_capabilities": native_lib.capabilities(),
        "platform": _PLATFORM,
        "host_note": (
            "paired alternating-order legs (r11 methodology) — single "
            "legs on throttled 2-core boxes spread 10-30%, so the "
            "median per-pair ratio is the honest statistic; the phase "
            "shares are in-kernel attribution and box-independent"),
        **_host_conditions(),
    }


def bench_sweep() -> None:
    """Tuning sweep for the flagship step: batch size x CMS width x impl
    x table prefilter x admission rule. One JSON line per point plus a
    final best-config line — run this the moment real hardware is
    attached to pick hh defaults empirically. The final line is the
    r16 -hh.sketch=table|invertible paired e2e A/B (BENCH_r16's
    headline: the admission-path deletion, measured per leg).

    The (prefilter, admission) axes quantify the admission path
    (VERDICT #2): prefilter on/off isolates the table-aware candidate
    truncation, admission est/plain isolates topk_merge_est's extra
    planes (space-saving CMS-seeded entry) vs the plain batch-sum merge.
    These two legs run on CPU as well — the regression question is about
    the admission path's relative cost, which the CPU A/B answers on
    the same box with the same stream."""
    import jax
    import jax.numpy as jnp

    from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
    from flow_pipeline_tpu.models import heavy_hitter as hh

    on_tpu = jax.devices()[0].platform != "cpu"
    batches = (16384, 32768, 65536) if on_tpu else SWEEP_BATCHES_CPU
    widths = (1 << 15, 1 << 16, 1 << 17) if on_tpu else (1 << 16,)
    impls = ("xla", "pallas") if on_tpu else ("xla",)
    prefilters = (True, False)
    admissions = ("est", "plain")
    gen = FlowGenerator(ZipfProfile(n_keys=100_000, alpha=1.1), seed=0)
    best = None
    points = []
    for batch in batches:
        staged = []
        for _ in range(4):
            b = gen.batch(batch)
            cols = b.device_columns(("src_addr", "dst_addr", "bytes",
                                     "packets", "sampling_rate"))
            staged.append({k: jax.device_put(jnp.asarray(v))
                           for k, v in cols.items()})
        valid = jax.device_put(jnp.ones(batch, bool))
        for width in widths:
            for impl in impls:
                for pre in prefilters:
                    for adm in admissions:
                        config = hh.HeavyHitterConfig(
                            key_cols=("src_addr", "dst_addr"),
                            batch_size=batch,
                            width=width, capacity=1024, cms_impl=impl,
                            table_prefilter=pre, table_admission=adm,
                        )
                        state = hh.hh_init(config)
                        state = hh.hh_update(state, staged[0], valid,
                                             config=config)
                        jax.block_until_ready(state)
                        steps = SWEEP_STEPS
                        t0 = time.perf_counter()
                        for i in range(steps):
                            state = hh.hh_update(state, staged[i % 4],
                                                 valid, config=config)
                        jax.block_until_ready(state)
                        rate = batch * steps / (time.perf_counter() - t0)
                        point = {"batch": batch, "width": width,
                                 "impl": impl, "prefilter": pre,
                                 "admission": adm,
                                 "flows_per_sec": round(rate, 1)}
                        points.append(point)
                        print(json.dumps(
                            {"metric": "hh sweep point", **point}))
                        if best is None or rate > best["flows_per_sec"]:
                            best = point

    def _median_rate(**match):
        sel = [p["flows_per_sec"] for p in points
               if all(p[k] == v for k, v in match.items())]
        return statistics.median(sel) if sel else 0.0

    # The two admission-path ratios the artifact exists to record: each
    # compares matched configs differing ONLY in the axis under test.
    pre_on, pre_off = (_median_rate(prefilter=True, admission="est"),
                       _median_rate(prefilter=False, admission="est"))
    adm_est, adm_plain = (_median_rate(prefilter=True, admission="est"),
                          _median_rate(prefilter=True, admission="plain"))
    print(json.dumps({
        "metric": "hh sweep best", "unit": "flows/sec",
        "value": best["flows_per_sec"], "platform": _PLATFORM,
        **best,
        "prefilter_speedup": round(pre_on / pre_off, 3) if pre_off else 0.0,
        "est_vs_plain_admission": round(adm_est / adm_plain, 3)
        if adm_plain else 0.0,
        **_host_conditions(),
    }))
    # r16: the sketch-family paired e2e A/B (the BENCH_r16 headline)
    global _NATIVE
    _NATIVE = _ensure_native()
    print(json.dumps(_sweep_hh_sketch_ab()))


def bench_sharded(n_devices: int = 8) -> None:
    """Multi-chip flagship step over an n-device mesh: aggregate flows/sec
    across shards plus the window-close merge cost (psum + table fold over
    ICI on real hardware). On CPU the mesh is virtual host devices, which
    validates the sharding program and grounds the v5e-8 extrapolation the
    day multi-chip hardware is attached."""
    import os

    import jax

    if _PLATFORM == "cpu" and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    have = len(jax.devices())
    n_devices = min(n_devices, have)

    from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
    from flow_pipeline_tpu.models import heavy_hitter as hh
    from flow_pipeline_tpu.parallel import ShardedHeavyHitter, make_mesh

    PER_CHIP, STEPS = SHARDED_PER_CHIP, SHARDED_STEPS
    mesh = make_mesh(n_devices)
    config = hh.HeavyHitterConfig(
        key_cols=("src_addr", "dst_addr"), batch_size=PER_CHIP,
        width=1 << 16, capacity=1024,
    )
    model = ShardedHeavyHitter(config, mesh)
    gen = FlowGenerator(ZipfProfile(n_keys=100_000, alpha=1.1), seed=0)
    # pre-shard onto the mesh outside the timed loop — same methodology as
    # the single-chip bench (the metric is the aggregation tier, not the
    # host columnarize/transfer path)
    from flow_pipeline_tpu.parallel import shard_batch_columns

    staged = []
    for _ in range(4):
        b = gen.batch(model.global_batch)
        cols = b.device_columns(hh.input_cols(config))
        import numpy as np

        staged.append(shard_batch_columns(
            mesh, {k: np.asarray(v) for k, v in cols.items()},
            np.ones(model.global_batch, bool),
        ))

    model.update_device_columns(*staged[0])  # warm / compile
    jax.block_until_ready(model.state)

    def step() -> int:
        for i in range(STEPS):
            model.update_device_columns(*staged[i % len(staged)])
        jax.block_until_ready(model.state)
        return model.global_batch * STEPS

    stats = _timed_samples(step)
    rate = stats["value"]

    merged = model.merged_state()  # warm the merge path
    jax.block_until_ready(merged)
    t0 = time.perf_counter()
    for _ in range(10):
        merged = model.merged_state()
    jax.block_until_ready(merged)
    merge_us = (time.perf_counter() - t0) / 10 * 1e6

    print(json.dumps({
        "metric": f"sharded heavy-hitter throughput ({n_devices}-device mesh)",
        "unit": "flows/sec",
        **stats,
        "vs_baseline": round(rate / 100_000.0, 3),
        "per_chip_flows_sec": round(rate / n_devices, 1),
        "merge_us": round(merge_us, 1),
        "n_devices": n_devices,
        "platform": _PLATFORM,
    }))
    _bench_sharded_exact_merge(mesh, n_devices, PER_CHIP)


def _bench_sharded_exact_merge(mesh, n_devices: int, per_chip: int) -> None:
    """Exact-aggregator host-merge cost on the mesh (VERDICT r2 #6): the
    sharded window-agg defers stacked per-chip partials and folds them
    into host dicts every DRAIN_PENDING_MAX chunks — this prints the
    device step rate, the host fold cost per chunk, the fold's share of
    total step time, and the per-chunk fold cost at threshold 1 vs the
    default (is deferral buying anything?)."""
    import numpy as np

    import jax

    from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
    from flow_pipeline_tpu.models.window_agg import (
        DRAIN_PENDING_MAX,
        WindowAggConfig,
        group_cols,
    )
    from flow_pipeline_tpu.parallel import shard_batch_columns
    from flow_pipeline_tpu.parallel.sharded import ShardedWindowAggregator

    cfg = WindowAggConfig(batch_size=per_chip)
    gen = FlowGenerator(ZipfProfile(n_keys=100_000, alpha=1.1), seed=2)
    global_batch = per_chip * n_devices
    staged = []
    for _ in range(4):
        b = gen.batch(global_batch)
        cols = b.device_columns(
            ["time_received", *group_cols(cfg), *cfg.value_cols])
        staged.append(shard_batch_columns(
            mesh, {k: np.asarray(v) for k, v in cols.items()},
            np.ones(global_batch, bool)))

    def run(threshold: int, chunks: int):
        """Returns (update_s, drain_s) for `chunks` chunks at the given
        drain threshold. Partials are queued manually (bypassing
        add_partial's own auto-drain) so the threshold under test is the
        only drain policy in effect."""
        agg = ShardedWindowAggregator(cfg, mesh)
        part = agg._sharded(*staged[0])  # warm/compile
        jax.block_until_ready(part[0])
        agg._pending_partials.append((part, None, None))
        agg._drain()
        t_update = t_drain = 0.0
        for i in range(chunks):
            t0 = time.perf_counter()
            part = agg._sharded(*staged[i % len(staged)])
            jax.block_until_ready(part[0])
            t_update += time.perf_counter() - t0
            agg._pending_partials.append((part, None, None))
            if len(agg._pending_partials) >= threshold:
                t0 = time.perf_counter()
                agg._drain()
                t_drain += time.perf_counter() - t0
        t0 = time.perf_counter()
        agg._drain()
        t_drain += time.perf_counter() - t0
        return t_update, t_drain

    run(DRAIN_PENDING_MAX, 8)  # warm every path incl. the host fold
    chunks = 2 * DRAIN_PENDING_MAX
    upd, drain = run(DRAIN_PENDING_MAX, chunks)
    upd1, drain1 = run(1, chunks)
    rate = chunks * global_batch / (upd + drain)
    print(json.dumps({
        "metric": f"sharded exact-agg (flows_5m) on {n_devices}-device mesh",
        "unit": "flows/sec",
        "value": round(rate, 1),
        "host_merge_us_per_chunk": round(drain / chunks * 1e6, 1),
        "host_merge_share_pct": round(100 * drain / (upd + drain), 1),
        "drain_threshold": DRAIN_PENDING_MAX,
        "merge_us_per_chunk_at_threshold_1": round(drain1 / chunks * 1e6, 1),
        "rate_at_threshold_1": round(
            chunks * global_batch / (upd1 + drain1), 1),
        "n_devices": n_devices,
        "platform": _PLATFORM,
    }))


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "hh"
    if mode != "kernels":  # kernels is ctypes-only — the SIMD A/B spawns
        # it repeatedly, it never touches jax, and as a child of a
        # bench that holds the chip it must not ask for one
        _select_platform()  # every other mode uses jax: TPU or an
        # explicit CPU request, else exit non-zero
    # mode functions stream one JSON object per line; the tee forwards
    # each to stderr live and the real stdout gets ONE valid JSON
    # document at the end (redirected BENCH_*.json artifacts json.load)
    _real_stdout = sys.stdout
    _tee = _JsonLineTee(sys.stderr)
    sys.stdout = _tee
    _rc = 0
    try:
        if mode == "hh":
            main()
        elif mode == "decode":
            bench_decode()
        elif mode == "cms":
            bench_cms()
        elif mode == "e2e":
            bench_e2e()
        elif mode == "hostsketch":
            bench_hostsketch()
        elif mode == "fused":
            bench_fused()
        elif mode == "flowtrace":
            bench_flowtrace()
        elif mode == "audit":
            bench_audit()
        elif mode == "spread":
            bench_spread()
        elif mode == "sharded":
            bench_sharded(int(sys.argv[2]) if len(sys.argv) > 2 else 8)
        elif mode == "mesh":
            bench_mesh()
        elif mode == "serve":
            bench_serve()
        elif mode == "chaos":
            bench_chaos()
        elif mode == "guard":
            bench_guard()
        elif mode == "history":
            bench_history()
        elif mode == "sweep":
            bench_sweep()
        elif mode == "kernels":
            bench_kernels()
        else:
            print(json.dumps({"error": f"unknown mode {mode}"}))
            _rc = 2
    finally:
        sys.stdout = _real_stdout
        _records = _tee.finish()
        if _records:
            print(_render_document(_records))
    sys.exit(_rc)
