#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the processor still starts,
runs and answers correctly on a TPU.

    python3 chip_smoke.py [--seed N] [--out DIR] [--stages a,b,...]
    python3 chip_smoke.py --tiny        # CPU dry run of the same file

It drives the system's main path once, through the entry point a user
calls (``flow_pipeline_tpu.cli.pipeline_main``, i.e. ``python -m
flow_pipeline_tpu.cli pipeline``), with every processor default left
alone — batch 32768, CMS width 65536 x depth 4, table capacity 1024, all
five model families, ``-sketch.backend device``,
``-processor.hostassist auto`` — and checks what comes out against the
repo's exact oracle (``models/oracle.py``).

Stages, each a child process run one after another so the chip has one
owner at a time (the parent imports neither jax nor flow_pipeline_tpu):

    native       require the device, then build native/*.cc into
                 flow_pipeline_tpu/native/libflowdecode.so and load it (a
                 missing library would silently fall back to the
                 pure-Python codec)
    pipeline     the stream below through pipeline_main on the chip, with
                 the sqlite sink, the flowserve query surface (polled by
                 the parent over HTTP while it runs) and a checkpoint;
                 asserts FusedPipeline on TPU devices
    oracle       (CPU) flows_5m bit-exact vs the oracle, every window's
                 top-20 top_talkers within 1% of the exact byte totals
    cache        a second, short pipeline process on the same shapes: the
                 fused step must come out of the persistent compile cache
    cms_kernels  ops/cms's conservative update (padding slots out of the
                 scatter) bit for bit against the one that scatters every
                 slot, at the processor's default shapes and widths 2^16
                 and 2^18 on a Zipf, a part-full and an all-distinct
                 batch, with each one's ms a call
    spread_kernels  ops/spread's register update as the fused step runs it
                 under -spread.enabled (spread_scatter on the flat device
                 plane, rows that are not valid dropped) bit for bit
                 against the numpy twin, at
                 estate-spread's shapes (2 x 2^15 x 256 registers), for
                 both detectors, with ms a call for int32 and uint8
    mesh4        the same stream with -processor.mesh 4 (skipped, with
                 the device count it saw, on fewer than four devices)
    oracle4      (CPU) the oracle checks on mesh4's output

The stream: Zipf(1.1) over 10^6 5-tuples, 4x10^6 flows at 4,000 flows/s
of event time = 1,000 s = four flows_5m timeslots, three window rolls
mid-stream plus the final flush.

Reduced (also printed under ``reduced``):
- scale: 4,000 flows/s is upstream's documented compose-demo rate ("a
  few thousands rows per second", SURVEY.md §6); production (">100k
  flows per second", 3x10^7 flows per 5-minute window) is for the
  benchmark's cells. This system keeps little on the device by nature,
  so what has to be real is the stream.
- -bus.partitions 1 (upstream: 2): with two partitions and
  -window.lateness 0 every sketch family drops the lagging partition's
  rows at each window roll (ROADMAP B6; 0.9-2.3% top-20 under-count on
  the CPU), which would hide a chip fault of the same size.

Output: one JSON line per stage (platform, device_kind, device count,
pass/fail, set-up times — no rate is a metric here), then as the LAST
line ``{"ok": true, "device": {...}}``. Any failed stage, a missing TPU
(without --tiny), or a directory that holds nothing else of the repo
exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
STAGES = ("native", "pipeline", "oracle", "cache", "cms_kernels",
          "spread_kernels", "mesh4", "oracle4")
CPU_STAGES = ("oracle", "oracle4")  # run under JAX_PLATFORMS=cpu
SERVED_STAGES = ("pipeline", "mesh4")  # parent polls -serve.addr
DEADLINE_S = 1150  # the whole smoke, compilation included (limit: 1200)
FUSED_STEP = "jit(step)"  # engine/fused.py _cached_step, as JAX names it

FULL = dict(count=4_000_000, rate=4000.0, keys=1_000_000, extra=())
# --tiny: the same four timeslots / three rolls at 1/200 the flows, at
# small shapes so the CPU compiles in seconds; hostassist off so the CPU
# takes FusedPipeline too
TINY = dict(count=20_000, rate=20.0, keys=2000, extra=(
    "-processor.backend", "cpu", "-processor.hostassist", "off",
    "-processor.batch", "2048", "-sketch.width", "4096",
    "-sketch.capacity", "256"))

REDUCED = [
    "scale: 4,000 flows/s of event time for 1,000 s (4.0e6 flows, upstream's "
    "compose-demo rate) vs production >100k flows/s (3e7 flows per window)",
    "bus.partitions 1 (upstream 2): with 2 partitions and lateness 0 the "
    "sketch families drop the lagging partition's rows at each roll "
    "(ROADMAP B6)",
]
REDUCED_TINY = [
    "tiny: 2.0e4 flows at 20 flows/s, 2,000 keys, batch 2048, CMS width "
    "4096, capacity 256, hostassist off, CPU",
]


# ---------------------------------------------------------------------------
# parent: no jax, no flow_pipeline_tpu
# ---------------------------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url: str):
    """(status, decoded JSON or None); None status = not reachable."""
    try:
        with urllib.request.urlopen(url, timeout=5) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, None
    except (urllib.error.URLError, OSError, ValueError):
        return None, None


class ServePolls:
    """GET /query/{version,topk,range} against the running child and
    judge them: 200s with monotone versions, nothing but 503 (no
    snapshot yet) and 400 (range before the first window closes)
    besides."""

    def __init__(self, port: int):
        self.base = f"http://127.0.0.1:{port}"
        self.versions: list[int] = []
        self.flows: list[int] = []
        self.topk_ok = 0
        self.range_ok = 0
        self.bad: list[str] = []

    def poll(self) -> None:
        status, doc = _get(self.base + "/query/version")
        if status is None:
            return  # child still starting, or already gone
        if status == 200:
            self.versions.append(int(doc["version"]))
            self.flows.append(int(doc["flows_seen"]))
        elif status != 503:
            self.bad.append(f"version -> {status}")
        status, doc = _get(self.base + "/query/topk?model=top_talkers&k=5")
        if status == 200 and doc["rows"]:
            self.topk_ok += 1
        elif status not in (None, 200, 503):
            self.bad.append(f"topk -> {status}")
        status, doc = _get(self.base + "/query/range?model=flows_5m")
        if status == 200 and doc["rows"]:
            self.range_ok += 1
        elif status not in (None, 200, 400, 503):
            self.bad.append(f"range -> {status}")

    def verdict(self, min_polls: int) -> dict:
        errors = list(self.bad)
        if len(self.versions) < min_polls:
            errors.append(f"only {len(self.versions)} /query/version 200s "
                          f"(need {min_polls})")
        if self.versions != sorted(self.versions):
            errors.append(f"versions not monotone: {self.versions}")
        if self.flows != sorted(self.flows):
            errors.append(f"flows_seen not monotone: {self.flows}")
        if not self.topk_ok:
            errors.append("no /query/topk 200 with rows")
        if not self.range_ok:
            errors.append("no /query/range?model=flows_5m 200 with rows")
        return {"version_polls": len(self.versions),
                "versions": [self.versions[0], self.versions[-1]]
                if self.versions else [],
                "topk_ok": self.topk_ok, "range_ok": self.range_ok,
                "errors": errors}


def _run_stage(stage: str, args, out_dir: str, deadline: float) -> dict:
    """Run one stage child to its end; returns its record (the last
    line of its stdout). The child is killed if the parent dies or the
    smoke's deadline passes."""
    cmd = [sys.executable, os.path.abspath(__file__), "--stage", stage,
           "--seed", str(args.seed), "--out", out_dir]
    if args.tiny:
        cmd.append("--tiny")
    env = dict(os.environ)
    if args.tiny or stage in CPU_STAGES:
        env["JAX_PLATFORMS"] = "cpu"
    polls = None
    if stage in SERVED_STAGES:
        port = _free_port()
        cmd += ["--port", str(port)]
        polls = ServePolls(port)
    t0 = time.monotonic()
    # the child's stdout goes to a file, not a pipe nobody drains while
    # the parent is busy polling
    with open(os.path.join(out_dir, f"{stage}.stdout"), "w+") as out:
        proc = subprocess.Popen(cmd, stdout=out, env=env, cwd=ROOT)
        try:
            while proc.poll() is None:
                if time.monotonic() > deadline:
                    raise TimeoutError(f"stage {stage}: smoke deadline "
                                       f"({DEADLINE_S}s) passed")
                if polls is not None:
                    polls.poll()
                time.sleep(0.05 if args.tiny else 1.0)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        out.seek(0)
        lines = [ln for ln in out.read().splitlines() if ln.strip()]
    try:
        rec = json.loads(lines[-1])
    except (IndexError, ValueError):
        rec = {"stage": stage, "pass": False,
               "error": "child printed no record"}
    if proc.returncode != 0:
        rec["pass"] = False
        rec.setdefault("error", f"child exit code {proc.returncode}")
    rec["wall_s"] = round(time.monotonic() - t0, 1)
    if polls is not None and rec.get("pass") and not rec.get("skipped"):
        rec["serve"] = polls.verdict(min_polls=1 if args.tiny else 3)
        if rec["serve"]["errors"]:
            rec["pass"] = False
            rec["error"] = "; ".join(rec["serve"]["errors"])
    return rec


def parent_main(args) -> int:
    if not (os.path.isfile(os.path.join(ROOT, "flow_pipeline_tpu",
                                        "__init__.py"))
            and os.path.isfile(os.path.join(ROOT, "native", "Makefile"))):
        print("chip_smoke: flow_pipeline_tpu/ and native/ not found next "
              "to this script — run it from a checkout of the repo",
              file=sys.stderr)
        return 2
    stages = [s for s in args.stages.split(",") if s]
    unknown = [s for s in stages if s not in STAGES]
    if unknown:
        print(f"chip_smoke: unknown stages {unknown}; known: {STAGES}",
              file=sys.stderr)
        return 2
    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)
    deadline = time.monotonic() + DEADLINE_S
    device = None
    skipped = set()
    for stage in stages:
        if stage == "oracle4" and "mesh4" in skipped:
            continue  # nothing to check: mesh4 saw too few devices
        rec = _run_stage(stage, args, out_dir, deadline)
        if device is None and "device" in rec:
            device = rec.pop("device")
        rec.pop("device", None)
        if device is not None:
            # every line names the device the SMOKE runs on; the oracle
            # children compute on the CPU and say so under checked_on
            rec = {"stage": rec.pop("stage", stage),
                   "platform": device["platform"],
                   "device_kind": device["kind"],
                   "device_count": device["count"], **rec}
        if not rec.get("pass"):
            # stdout carries results only: a failure goes to stderr
            print(f"chip_smoke: stage {stage} FAILED: {json.dumps(rec)}",
                  file=sys.stderr)
            return 1
        print(json.dumps(rec), flush=True)
        if rec.get("skipped"):
            skipped.add(stage)
    if device is None:
        print("chip_smoke: no stage reported a device", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# children: one stage each
# ---------------------------------------------------------------------------


def _device_setup(tiny: bool) -> dict:
    """The CLI's platform rule (which places the compile cache) before
    any compile; returns the device as JAX reports it."""
    sys.path.insert(0, ROOT)
    from flow_pipeline_tpu.utils.platform import select_platform

    platform = select_platform("cpu" if tiny else "tpu")
    if platform != ("cpu" if tiny else "tpu"):
        # stricter than the CLI: JAX_PLATFORMS=cpu is no way around the
        # chip here — --tiny is the only mode that runs without a TPU
        raise SystemExit(
            f"chip_smoke: needs a TPU but this process is pinned to "
            f"{platform} (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r});"
            f" use --tiny for the CPU dry run")
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileLog:
    """Counts XLA compile requests, their time, and which of them the
    persistent cache served or stored (jax.monitoring events)."""

    def __init__(self):
        import jax

        self.cache_dir = jax.config.jax_compilation_cache_dir
        self.compiles: list[tuple[str, float]] = []
        self.hits: list[str] = []
        self.stored: list[str] = []
        self._last = None  # cache event inside the current compile span
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._last = "hit"
        elif event == "/jax/compilation_cache/cache_misses":
            self._last = "stored"

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event != "/jax/core/compile/backend_compile_duration":
            return
        name = str(kw.get("fun_name", "?"))
        self.compiles.append((name, secs))
        if self._last == "hit":
            self.hits.append(name)
        elif self._last == "stored":
            self.stored.append(name)
        self._last = None

    def record(self) -> dict:
        slowest = sorted(self.compiles, key=lambda c: -c[1])[:3]
        return {
            "compiles": len(self.compiles),
            "compile_s": round(sum(s for _, s in self.compiles), 1),
            "slowest_compiles": [[n, round(s, 1)] for n, s in slowest],
            "compile_cache": {"dir": self.cache_dir,
                              "hits": len(self.hits),
                              "stored": len(self.stored)},
        }


def _pipeline_argv(args, size: dict, db: str, **over) -> list[str]:
    argv = [
        "-produce.profile", "zipf", "-zipf.keys", str(size["keys"]),
        "-zipf.alpha", "1.1", "-produce.seed", str(args.seed),
        "-produce.count", str(over.get("count", size["count"])),
        "-produce.rate", str(size["rate"]), "-bus.partitions", "1",
        "-sink", f"sqlite:{db}", "-metrics.addr", "",
        *size["extra"],
    ]
    if args.port:
        argv += ["-serve.addr", f"127.0.0.1:{args.port}",
                 "-serve.refresh", "0.5"]
    return argv


def _fresh(*paths: str) -> None:
    """The sqlite sink APPENDS and the worker restores nothing here, but
    a stale db would double-count the sum checks — start clean."""
    import shutil

    for p in paths:
        if os.path.isdir(p):
            shutil.rmtree(p)
        elif os.path.exists(p):
            os.remove(p)


def _run_pipeline(argv: list[str], on_worker=None):
    """pipeline_main(argv) with the StreamWorker it builds captured, and
    the compile count noted at every sink write (window closes)."""
    from flow_pipeline_tpu import cli
    from flow_pipeline_tpu.engine import StreamWorker

    clog = CompileLog()
    seen = {}
    writes: list[tuple[str, int]] = []
    real_run, real_write = StreamWorker.run, StreamWorker._write_rows

    def run(self, *a, **kw):
        seen["worker"] = self
        if on_worker is not None:
            on_worker(self)
        return real_run(self, *a, **kw)

    def write_rows(self, table, *a, **kw):
        writes.append((table, len(clog.compiles)))
        return real_write(self, table, *a, **kw)

    StreamWorker.run, StreamWorker._write_rows = run, write_rows
    try:
        t0 = time.monotonic()
        rc = cli.pipeline_main(argv)
        wall = time.monotonic() - t0
    finally:
        StreamWorker.run, StreamWorker._write_rows = real_run, real_write
    if rc != 0:
        raise RuntimeError(f"pipeline_main returned {rc}")
    return seen["worker"], clog, writes, wall


def _array_platforms(tree) -> set:
    import jax

    return {d.platform for leaf in jax.tree.leaves(tree)
            for d in leaf.devices()}


def _check_checkpoint(path: str, flows: int) -> None:
    from flow_pipeline_tpu.engine.checkpoint import load_checkpoint

    for name in ("meta.json", "arrays.npz"):
        if not os.path.isfile(os.path.join(path, name)):
            raise AssertionError(f"checkpoint {path} lacks {name}")
    snap = load_checkpoint(path)
    if snap["flows_seen"] != flows:
        raise AssertionError(
            f"checkpoint flows_seen {snap['flows_seen']} != {flows}")


def stage_native(args) -> dict:
    device = _device_setup(args.tiny)  # no device, no smoke: fail first
    subprocess.run(["make", "-C", os.path.join(ROOT, "native")], check=True,
                   stdout=sys.stderr)
    from flow_pipeline_tpu import native

    if not native.available():
        raise AssertionError(
            "libflowdecode.so built but the loader did not take it (the "
            "pipeline would silently run the pure-Python codec)")
    return {"device": device}


def stage_pipeline(args) -> dict:
    device = _device_setup(args.tiny)
    size = TINY if args.tiny else FULL
    db, ckpt = (os.path.join(args.out, "smoke.db"),
                os.path.join(args.out, "ckpt"))
    _fresh(db, ckpt)
    argv = _pipeline_argv(args, size, db) + ["-checkpoint.path", ckpt]
    worker, clog, writes, wall = _run_pipeline(argv)

    from flow_pipeline_tpu.engine.fused import FusedPipeline

    want = "cpu" if args.tiny else "tpu"
    if type(worker.fused) is not FusedPipeline:
        raise AssertionError(
            f"worker.fused is {type(worker.fused).__name__}, not "
            f"FusedPipeline")
    if device["platform"] != want:
        raise AssertionError(f"ran on {device['platform']}, want {want}")
    hh_states = [w.model.state for _, w in worker.fused._hh]
    platforms = _array_platforms(hh_states)
    if platforms != {want}:
        raise AssertionError(f"hh state arrays live on {platforms}")
    if worker.flows_seen != size["count"]:
        raise AssertionError(f"flows_seen {worker.flows_seen}")
    _check_checkpoint(ckpt, size["count"])
    # compile requests seen by each window close (three rolls + the
    # final flush). Once every shape is warm a whole window — its chunks,
    # its drains and its close — compiles nothing: growth between the
    # second and third close is a program per chunk.
    by_close = [c for table, c in writes if table == "top_talkers"]
    if len(by_close) != 4:
        raise AssertionError(f"expected 4 window closes, saw {by_close}")
    if by_close[2] - by_close[1] > 2:
        raise AssertionError(
            f"compilations keep growing after warm-up, by close: {by_close}")
    return {"device": device, "dataplane": type(worker.fused).__name__,
            "state_platforms": sorted(platforms),
            "flows": worker.flows_seen, "batches": worker.batches_seen,
            "compiles_by_window_close": by_close,
            "stored_names": clog.stored,
            "pipeline_wall_s": round(wall, 1), **clog.record()}


def stage_cache(args) -> dict:
    """A second process on the pipeline stage's shapes: its fused step
    must be served by the persistent compile cache whenever the pipeline
    stage stored it there (JAX stores what took over a second to
    compile)."""
    device = _device_setup(args.tiny)
    size = TINY if args.tiny else FULL
    db = os.path.join(args.out, "cache.db")
    _fresh(db)
    count = 2 * (2048 if args.tiny else 32768)
    _, clog, _, wall = _run_pipeline(
        _pipeline_argv(args, size, db, count=count))
    with open(os.path.join(args.out, "pipeline.json")) as f:
        stored_before = FUSED_STEP in json.load(f)["stored_names"]
    rec = {"device": device, "flows": count,
           "step_stored_by_pipeline_stage": stored_before,
           "step_cache_hit": FUSED_STEP in clog.hits,
           "pipeline_wall_s": round(wall, 1), **clog.record()}
    if stored_before and FUSED_STEP not in clog.hits:
        raise AssertionError(
            f"the fused step was stored in {clog.cache_dir} by the "
            f"pipeline stage but this process compiled it again: {rec}")
    return rec


def stage_cms_kernels(args) -> dict:
    device = _device_setup(args.tiny)
    import numpy as np

    # the processor's defaults: N groups = batch, 5-tuple v6 key lanes
    n, lanes, planes, depth = 256 if args.tiny else 32768, 11, 3, 4
    widths = (512, 1024) if args.tiny else (1 << 16, 1 << 18)
    rounds = 2 if args.tiny else 3  # >1: estimates feed CU ceilings
    clog = CompileLog()
    live = _padding_leaves_the_scatter(
        np.random.default_rng(args.seed), n, lanes, planes, depth,
        widths=widths, n_keys=2000 if args.tiny else 1_000_000,
        rounds=rounds, reps=3 if args.tiny else 100)
    return {"device": device,
            "shape": {"groups": n, "key_lanes": lanes, "planes": planes,
                      "depth": depth, "widths": list(widths)},
            "rounds": rounds, "bit_exact": True,
            "padding_leaves_the_scatter": live, **clog.record()}


def _conservative_with_every_slot_scattered(counts, keys, values, valid,
                                            n_live):
    """ops.cms.cms_add_conservative as it ran until PR 45: the estimate
    under the live bound, then every one of the N slots in every row's
    scatter-max. The reference the update is held to, and timed beside."""
    import jax.numpy as jnp

    from flow_pipeline_tpu.ops import cms

    _, depth, width = counts.shape
    buckets = cms.cms_buckets(keys, depth, width)
    vals = jnp.where(valid[:, None], values.astype(jnp.float32), 0.0)
    target = cms.cms_query(counts, keys, n_live) + vals
    for di in range(depth):
        counts = counts.at[:, di, buckets[di]].max(target.T)
    return counts


def _group_slots(rng, n, lanes, planes, n_keys) -> dict:
    """{batch: (uniq, sums, valid)}: the N group slots that the device
    group-by (ops.segment.hash_groupby_float, the one hh_update runs)
    hands the update for three batches of N rows: ``zipf`` (Zipf 1.1 over
    ``n_keys`` keys, the catch-up cells' stream: ~30 % of the slots hold
    a group), ``part_full`` (the same with an eighth of the rows there, a
    live poll) and ``all_distinct`` (N keys, no slot is padding). The
    padding slots hold what the group-by leaves in them."""
    import jax.numpy as jnp
    import numpy as np

    from flow_pipeline_tpu.ops.segment import hash_groupby_float

    table = rng.integers(0, 2**32, size=(n_keys, lanes), dtype=np.uint32)
    weights = 1.0 / np.arange(1, n_keys + 1) ** 1.1
    ranks = rng.choice(n_keys, size=n, p=weights / weights.sum())
    values = rng.integers(1, 1500, size=(n, planes)).astype(np.float32)
    every = np.ones(n, bool)
    rows = {
        "zipf": (table[ranks], every),
        "part_full": (table[ranks], np.arange(n) < n // 8),
        "all_distinct": (rng.integers(0, 2**32, size=(n, lanes),
                                      dtype=np.uint32), every),
    }
    out = {}
    for name, (keys, valid) in rows.items():
        uniq, sums, counts = hash_groupby_float(
            jnp.asarray(keys), jnp.asarray(values), jnp.asarray(valid))
        out[name] = (uniq, sums, counts > 0)
    return out


def _padding_leaves_the_scatter(rng, n, lanes, planes, depth, *, widths,
                                n_keys, rounds, reps) -> dict:
    """PR 45: at the cells' shapes and on the three batches of
    ``_group_slots``, the conservative update with its padding slots out
    of the scatter leaves the state of the update that scatters every
    slot, bit for bit over ``rounds`` updates of one sketch (so the later
    ones raise cells that hold mass), and what a call of each takes: the
    median of three timings of ``reps`` calls with the state donated, as
    the fused step has it. A time, not a metric: the step's own is the
    benchmark's ``step_device_ms_p50``."""
    import statistics

    import jax
    import numpy as np

    from flow_pipeline_tpu.models.heavy_hitter import live_rows
    from flow_pipeline_tpu.ops import cms

    forms = {
        "every_slot": jax.jit(_conservative_with_every_slot_scattered,
                              donate_argnums=0),
        "ops_cms": jax.jit(cms.cms_add_conservative, donate_argnums=0),
    }

    def ms_a_call(fn, width, batch):
        state = fn(cms.cms_init(planes, depth, width), *batch)
        timings = []
        for _ in range(3):
            jax.block_until_ready(state)
            t0 = time.perf_counter()
            for _ in range(reps):
                state = fn(state, *batch)
            jax.block_until_ready(state)
            timings.append((time.perf_counter() - t0) * 1e3 / reps)
        return round(statistics.median(timings), 4)

    out = {}
    for name, (uniq, sums, valid) in _group_slots(
            rng, n, lanes, planes, n_keys).items():
        batch = (uniq, sums[:, :planes], valid, live_rows(valid))
        rec = out[name] = {"slots": n, "real": int(valid.sum()),
                           "live_rows": int(batch[3]), "ms_a_call": {}}
        for width in widths:
            # each form donates its state: two buffers from the start
            want = cms.cms_init(planes, depth, width)
            got = cms.cms_init(planes, depth, width)
            for r in range(rounds):
                want = forms["every_slot"](want, *batch)
                got = forms["ops_cms"](got, *batch)
                if np.asarray(want).tobytes() != np.asarray(got).tobytes():
                    raise AssertionError(
                        f"ops.cms.cms_add_conservative differs from the "
                        f"update that scatters every slot: batch {name}, "
                        f"width {width}, round {r}")
            if rec["real"] and not np.asarray(got).any():
                raise AssertionError(f"batch {name} raised no cell")
            rec["ms_a_call"][str(width)] = {
                form: ms_a_call(fn, width, batch)
                for form, fn in forms.items()}
    return out


def stage_spread_kernels(args) -> dict:
    """PR 47: the spread detectors' register update as the fused step
    runs it (ops.spread.spread_scatter on the flat device plane) against
    the numpy twin (hostsketch.engine.np_spread_update) bit for bit, at
    estate-spread's shapes, over three batches into one plane, for both
    detectors' element widths and for two row sets: every row of the
    batch (what the step hands it) and the batch's unique groups with
    their holes dropped from the scatter (what it would save to group
    first: nothing, PERF.md 6, PR 47). Beside it what a call takes for
    each register dtype
    (ops.spread.DEVICE_REG_DTYPE is the one the step uses): the median
    of three timings of ``reps`` calls with the plane donated. A time,
    not a metric: the step's own is the benchmark's
    ``step_spread_regs_ms``. Then what the candidate table's admission
    reads, ops.spread.spread_decode_device (float32, from the flat
    plane), against the host's decode of the same registers
    (hostsketch.engine.np_spread_query, float64) for the last batch's
    sources, within 1e-4, with its ms a call."""
    device = _device_setup(args.tiny)
    import statistics

    import jax
    import jax.numpy as jnp
    import numpy as np

    from flow_pipeline_tpu.hostsketch.engine import (
        np_spread_query,
        np_spread_update,
    )
    from flow_pipeline_tpu.ops import spread
    from flow_pipeline_tpu.ops.segment import hash_groupby_float

    n = 256 if args.tiny else 32768
    shape = (2, 64, 16) if args.tiny else (2, 32768, 256)
    n_keys, reps = (2000, 3) if args.tiny else (1_000_000, 100)
    rng = np.random.default_rng(args.seed)
    clog = CompileLog()
    scatter = jax.jit(spread.spread_scatter, static_argnums=1,
                      donate_argnums=0)
    decode = jax.jit(spread.spread_decode_device, static_argnums=1)
    table = rng.integers(0, 2**32, size=(n_keys, 8), dtype=np.uint32)
    weights = 1.0 / np.arange(1, n_keys + 1) ** 1.1
    out = {}
    for detector, ew in (("superspreaders", 4), ("portscan", 1)):
        want = np.zeros(shape, np.uint8)
        planes = {name: jnp.zeros(int(np.prod(shape)), spread.DEVICE_REG_DTYPE)
                  for name in ("groups", "every_row")}
        for _ in range(3):
            ranks = rng.choice(n_keys, size=n, p=weights / weights.sum())
            rows = table[ranks][:, :4 + ew]
            np_spread_update(want, rows[:, :4], rows[:, 4:])
            uniq, _, counts = hash_groupby_float(
                jnp.asarray(rows), jnp.zeros((n, 0), jnp.float32),
                jnp.ones(n, bool))
            real = counts > 0
            handed = {"groups": (uniq[:, :4], uniq[:, 4:], real),
                      "every_row": (jnp.asarray(rows[:, :4]),
                                    jnp.asarray(rows[:, 4:]),
                                    jnp.ones(n, bool))}
            for name, batch in handed.items():
                planes[name] = scatter(planes[name], shape, *batch)
        for name, plane in planes.items():
            got = np.asarray(spread.host_regs(plane, shape=shape))
            if got.dtype != np.uint8 or got.tobytes() != want.tobytes():
                raise AssertionError(
                    f"{detector}: spread_scatter over {name} differs from "
                    f"np_spread_update")
        if not want.any():
            raise AssertionError(f"{detector}: no register was raised")
        keys = uniq[:, :4]
        got = np.asarray(decode(planes["every_row"], shape, keys))
        ok = np.asarray(real)
        host = np_spread_query(want, np.asarray(keys)[ok])
        if got.dtype != np.float32 or not np.allclose(
                got[ok], host, rtol=1e-4) or not host.max() > 1:
            raise AssertionError(
                f"{detector}: spread_decode_device differs from "
                f"np_spread_query by {np.abs(got[ok] / host - 1).max()}")
        timings = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(reps):
                est = decode(planes["every_row"], shape, keys)
            jax.block_until_ready(est)
            timings.append((time.perf_counter() - t0) * 1e3 / reps)
        rec = out[detector] = {
            "rows": n, "groups": int(real.sum()),
            "registers_raised": int(np.count_nonzero(want)),
            "decode_max_rel_diff": float(np.abs(got[ok] / host - 1).max()),
            "decode_ms_a_call": round(statistics.median(timings), 4),
            "ms_a_call": {}}
        for dtype in (jnp.int32, jnp.uint8):
            for name, batch in handed.items():
                plane = scatter(jnp.zeros(int(np.prod(shape)), dtype),
                                shape, *batch)
                timings = []
                for _ in range(3):
                    jax.block_until_ready(plane)
                    t0 = time.perf_counter()
                    for _ in range(reps):
                        plane = scatter(plane, shape, *batch)
                    jax.block_until_ready(plane)
                    timings.append((time.perf_counter() - t0) * 1e3 / reps)
                rec["ms_a_call"][f"{jnp.dtype(dtype).name}.{name}"] = round(
                    statistics.median(timings), 4)
    return {"device": device, "shape": list(shape), "bit_exact": True,
            "device_dtype": jnp.dtype(spread.DEVICE_REG_DTYPE).name,
            "detectors": out, **clog.record()}


def stage_mesh4(args) -> dict:
    device = _device_setup(args.tiny)
    if device["count"] < 4:
        return {"device": device, "skipped": True,
                "reason": f"needs 4 devices, saw {device['count']}"}
    import numpy as np

    from flow_pipeline_tpu.parallel import ShardedHeavyHitter

    size = TINY if args.tiny else FULL
    db, ckpt = (os.path.join(args.out, "mesh4.db"),
                os.path.join(args.out, "ckpt4"))
    _fresh(db, ckpt)
    argv = _pipeline_argv(args, size, db) + [
        "-checkpoint.path", ckpt, "-processor.mesh", "4"]
    if args.tiny:
        # tiny windows are 6,000 flows: a per-chip batch small enough
        # that a window spans every chip of the row-sharded global batch
        argv += ["-processor.batch", "512"]
    placed = {}

    def on_worker(worker):
        # before the first batch: where does the stacked state live?
        hh = worker.models["top_talkers"].model
        placed["mesh"] = [str(d) for d in hh.mesh.devices.flat]
        placed["mesh_platforms"] = sorted(
            {d.platform for d in hh.mesh.devices.flat})
        placed["cms_devices"] = sorted(
            str(d) for d in hh.state.cms.sharding.device_set)

    # every chip must fold real rows: the largest count-plane mass each
    # chip's CMS shard held at any merge (window close or serve publish)
    busy = [0.0] * 4
    real_merge = ShardedHeavyHitter.merged_state

    def merged_state(self):
        for i, s in enumerate(self.state.cms.addressable_shards):
            busy[i] = max(busy[i], float(np.asarray(s.data)[0, -1].sum()))
        return real_merge(self)

    ShardedHeavyHitter.merged_state = merged_state
    try:
        worker, clog, writes, wall = _run_pipeline(argv, on_worker)
    finally:
        ShardedHeavyHitter.merged_state = real_merge
    want = "cpu" if args.tiny else "tpu"
    if type(worker.fused).__name__ != "ShardedPipeline":
        raise AssertionError(
            f"mesh run took {type(worker.fused).__name__}, expected "
            f"ShardedPipeline (one cut a poll, a sharded program a "
            f"model)")
    if len(set(placed["mesh"])) != 4 or placed["mesh_platforms"] != [want]:
        raise AssertionError(f"make_mesh gave {placed}")
    if len(placed["cms_devices"]) != 4:
        raise AssertionError(
            f"stacked CMS spans {placed['cms_devices']}, not 4 devices")
    if min(busy) <= 0:
        raise AssertionError(
            f"a chip never folded a real row; per-chip CMS count mass: "
            f"{busy}")
    if worker.flows_seen != size["count"]:
        raise AssertionError(f"flows_seen {worker.flows_seen}")
    _check_checkpoint(ckpt, size["count"])
    return {"device": device, "dataplane": type(worker.fused).__name__,
            "mesh": placed["mesh"], "cms_devices": placed["cms_devices"],
            "per_chip_peak_count_mass": busy,
            "flows": worker.flows_seen, "batches": worker.batches_seen,
            "global_batch": worker.models["top_talkers"].model.global_batch,
            "pipeline_wall_s": round(wall, 1), **clog.record()}


def _oracle_check(args, db: str) -> dict:
    """flows_5m bit-exact and each window's top-20 top_talkers within 1%
    (BASELINE.json's gate), against models/oracle.py on the same seeded
    stream — regenerated here exactly as pipeline_main produces it."""
    sys.path.insert(0, ROOT)
    import sqlite3

    import numpy as np

    from flow_pipeline_tpu import cli
    from flow_pipeline_tpu.models import oracle
    from flow_pipeline_tpu.schema.batch import FlowBatch
    from flow_pipeline_tpu.sink.base import _addr_str
    from flow_pipeline_tpu.utils.flags import FlagSet

    size = TINY if args.tiny else FULL
    fs = cli._processor_flags(cli._gen_flags(cli._common_flags(
        FlagSet("pipeline"))))
    vals = fs.parse(_pipeline_argv(args, size, db))
    gen = cli._make_generator(vals)
    key_cols = ["src_addr", "dst_addr", "src_port", "dst_port", "proto"]
    need = ["time_received", "src_as", "dst_as", "etype", "bytes",
            "packets", *key_cols]
    by_slot: dict[int, list] = {}
    produced = 0
    while produced < size["count"]:  # pipeline_main's produce loop
        n = min(8192, size["count"] - produced)
        b = gen.batch(n)
        produced += n
        slots = (b.columns["time_received"].astype(np.int64)
                 // oracle.SECONDS_PER_SLOT * oracle.SECONDS_PER_SLOT)
        for slot in np.unique(slots):
            idx = np.flatnonzero(slots == slot)
            by_slot.setdefault(int(slot), []).append(
                FlowBatch({k: b.columns[k][idx] for k in need}))

    con = sqlite3.connect(db)
    total = con.execute("SELECT SUM(count) FROM flows_5m").fetchone()[0]
    if total != size["count"]:
        raise AssertionError(
            f"SUM(count) FROM flows_5m = {total}, want {size['count']}")
    sink_rows = con.execute("SELECT COUNT(*) FROM flows_5m").fetchone()[0]
    got = {tuple(r[:4]): tuple(r[4:]) for r in con.execute(
        "SELECT timeslot, src_as, dst_as, etype, SUM(bytes), SUM(packets), "
        "SUM(count) FROM flows_5m GROUP BY 1, 2, 3, 4")}
    want = {}
    windows = []
    for slot in sorted(by_slot):
        batch = FlowBatch.concat(by_slot.pop(slot))
        o = oracle.flows_5m(batch)
        for i in range(len(o["timeslot"])):
            want[tuple(int(o[c][i]) for c in
                       ("timeslot", "src_as", "dst_as", "etype"))] = tuple(
                int(o[c][i]) for c in ("bytes", "packets", "count"))
        # exact per-5-tuple byte totals of this window
        ex = oracle.exact_groupby(batch, key_cols, ["bytes"],
                                  timeslot=False)
        exact = {}
        for i in np.argsort(-ex["bytes"].astype(np.int64),
                            kind="stable")[:2000]:
            exact[(_addr_str(ex["src_addr"][i]), _addr_str(ex["dst_addr"][i]),
                   int(ex["src_port"][i]), int(ex["dst_port"][i]),
                   int(ex["proto"][i]))] = int(ex["bytes"][i])
        rows = con.execute(
            "SELECT src_addr, dst_addr, src_port, dst_port, proto, bytes "
            "FROM top_talkers WHERE timeslot = ? ORDER BY bytes DESC",
            (slot,)).fetchall()
        if len(rows) < 20:
            raise AssertionError(
                f"window {slot}: {len(rows)} top_talkers rows in the sink")
        sink = {tuple(r[:5]): int(r[5]) for r in rows}
        errs = []
        for r in rows[:20]:  # the sink's top-20 vs their exact totals
            true = exact.get(tuple(r[:5]))
            if true is None:
                raise AssertionError(
                    f"window {slot}: sink top-20 key {r[:5]} is not among "
                    f"the oracle's top 2000")
            errs.append(abs(int(r[5]) - true) / true)
        for key in list(exact)[:20]:  # the oracle's top-20 are all there
            if key not in sink:
                raise AssertionError(
                    f"window {slot}: oracle top-20 key {key} missing from "
                    f"the sink's {len(rows)} rows")
            errs.append(abs(sink[key] - exact[key]) / exact[key])
        if max(errs) > 0.01:
            raise AssertionError(
                f"window {slot}: top-20 bytes error {max(errs):.5f} > 1%")
        windows.append({"timeslot": slot, "flows": len(batch),
                        "distinct_5tuples": len(ex["bytes"]),
                        "top20_max_rel_err": float(f"{max(errs):.3g}")})
    if got != want:
        bad = [k for k in want if got.get(k) != want[k]]
        raise AssertionError(
            f"flows_5m differs from the oracle in {len(bad)} of "
            f"{len(want)} groups ({len(got)} in the sink), e.g. {bad[:3]}")
    return {"checked_on": "cpu", "db": os.path.basename(db),
            "flows_5m": {"sum_count": total, "sink_rows": sink_rows,
                         "groups": len(want), "bit_exact": True},
            "windows": windows}


def stage_oracle(args) -> dict:
    return _oracle_check(args, os.path.join(args.out, "smoke.db"))


def stage_oracle4(args) -> dict:
    return _oracle_check(args, os.path.join(args.out, "mesh4.db"))


def child_main(args) -> int:
    """Run one stage; its record is the last line of stdout. Exceptions
    are not caught past: a failed stage is a traceback and a non-zero
    exit."""
    t0 = time.monotonic()
    rec = globals()[f"stage_{args.stage}"](args)
    rec = {"stage": args.stage, "pass": True,
           "reduced": REDUCED + (REDUCED_TINY if args.tiny else []),
           **rec, "stage_s": round(time.monotonic() - t0, 1)}
    with open(os.path.join(args.out, f"{args.stage}.json"), "w") as f:
        json.dump(rec, f)
    print(json.dumps(rec), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="feeds -produce.seed and the kernel inputs")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU dry run at a tiny size (the only mode that "
                         "runs without a TPU; says platform cpu)")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "chip_smoke"),
                    help="directory for the sink, checkpoint and records")
    ap.add_argument("--stages", default=",".join(STAGES),
                    help="comma-separated subset, in order")
    ap.add_argument("--stage", choices=STAGES, help=argparse.SUPPRESS)
    ap.add_argument("--port", type=int, default=0, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.stage:
        return child_main(args)
    return parent_main(args)


if __name__ == "__main__":
    sys.exit(main())
