"""One run of one cell.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. One new process a run. The last line of
standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, and with ``--trace 1``
``breakdown``); everything else goes to standard error. Without a TPU —
unless ``JAX_PLATFORMS=cpu`` asks for the CPU by name — with fewer chips
than the cell asks for, or in a directory that holds nothing of the
program, it exits non-zero and prints no result.

A run, in order: make the stream from ``--seed`` in worker processes
while the device path starts; warm up on the first flows (one window
close, one checkpoint, every shape); open the window at a fixed flow
index (``schedule.py``); measure for ``--seconds``; end the stream, let
the worker finalize; compare what it produced with the plain reference
(``reference.py``, ``check.py``); reduce spans, counters and, in a traced
run, the profiler's trace to the cell's metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time

from . import drive  # first: it reads the clock a run's set-up starts at
from .drive import Abort, log

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GROUP = "tpu-processor"  # transport.Consumer's default consumer group


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Run:
    """What a run knows; metric readers take it as their one argument."""

    def __init__(self, args, cell, plan, spec):
        from .spans import SpanLog
        from .sut import Sut

        self.args, self.cell, self.plan, self.spec = args, cell, plan, spec
        self.traced = bool(args.trace)
        self.t_process = drive.T_PROCESS
        self.spans = SpanLog()
        self.sut = Sut(self.spans, cell.config.get("topic", "flows"),
                       int(cell.config["bus_partitions"]))
        self.deal = drive.Deal(spec, self.sut.partitions, plan.total_flows)
        self.t_first_flow = None     # set-up ends here
        self.t_a = self.t_b = None   # the measured window, monotonic
        self.rate_edges = None       # [(t, position)] the rate is read between
        self.pos_a = self.pos_b = None  # flows fetched at its edges, over
        #                                 all partitions
        self.t0_schedule = None      # open loop: flow backlog_flows is due
        self.ticks: list = []        # (scheduled, actual, flows handed)
        self.compiles: list = []     # (t, name, seconds)
        self.publish_log: list = []  # reader: (t_seen, version, flows_seen)
        self.reader_stats: dict = {}
        self.draws: list = []        # per chunk (rank, bytes, packets)
        self.frames: list = []       # per chunk its frames, until sent
        self.trace = None            # trace_reduce.Reduction
        self.trace_span = None       # (t_start, t_stop) of the profiler
        self.trace_thread = None
        self.final: dict = {}
        self.error = None
        self.rundir = None
        self.device = {}

    # ---- helpers for readers ------------------------------------------------

    def in_window(self, name: str) -> list:
        return self.spans.named(name, self.t_a, self.t_b)

    def fetches(self) -> list:
        """(t_returned, partition, first_offset, n, position) of every
        fetch that took flows (``drive.FetchScan``)."""
        return drive.FetchScan(self).new()

    def due(self, flow: int) -> float:
        return self.t0_schedule + self.plan.due_offset(flow)


# ---- set-up ------------------------------------------------------------------


def ensure_native() -> None:
    so = os.path.join(ROOT, "flow_pipeline_tpu", "native",
                      "libflowdecode.so")
    if not os.path.isfile(so):
        log("building native/ (first run in this checkout)")
        subprocess.run(["make", "-C", os.path.join(ROOT, "native")],
                       check=True, stdout=sys.stderr)
    from flow_pipeline_tpu import native

    if not native.available():
        raise Abort("libflowdecode.so is built but does not load")


def require_device(cell) -> dict:
    from flow_pipeline_tpu.utils.platform import select_platform

    platform = select_platform("tpu")  # exits without a TPU or a CPU request
    import jax

    devs = jax.devices()
    if len(devs) < cell.chips:
        raise Abort(f"cell {cell.name} needs {cell.chips} chips, JAX "
                    f"reports {len(devs)} {platform} device(s)")
    # every program this cell compiles goes to the persistent cache, not
    # only those that took over a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _widen_result_pipe(pool) -> None:
    """What a chunk sends back through the pool's one result pipe (its
    cut format and its draws, ~0.33 MB; the blob goes through the ring)
    is several times the default 64 KiB of pipe, and the reader thread
    and the thread that cuts the chunks hand the interpreter lock back and
    forth for every pipeful. 1 MiB (Linux's unprivileged limit) takes a
    chunk whole; where it cannot be set, nothing but time is lost."""
    import fcntl

    try:
        fcntl.fcntl(pool._outqueue._reader.fileno(),
                    getattr(fcntl, "F_SETPIPE_SZ", 1031), 1 << 20)
    except (AttributeError, OSError) as e:
        log(f"result pipe left at its default size: {e!r}")


def _ring(procs: int, chunk_flows: int):
    """The ring the worker processes hand their blobs over through
    (``flowgen.Ring``): a slot a worker and one that is being cut, of 128
    bytes a flow (a frame of ``flowgen.chunk_columns`` is 88-95 on the
    wire in these configurations; a chunk that encodes to more than its
    slot fails its worker, and the run with it): 38 MB for
    eight workers, which a container's default ``/dev/shm`` of 64 MB
    holds. A write past what ``/dev/shm`` has free is a bus error, not an
    exception, so the run aborts here where the ring does not fit."""
    from .flowgen import Ring

    slots, slot_bytes = procs + 1, chunk_flows * 128
    need = Ring.HEADER + slots * slot_bytes
    try:
        shm = os.statvfs("/dev/shm")
    except OSError as e:
        raise Abort(f"no /dev/shm for the ring of {need} bytes the stream "
                    f"is handed over through: {e!r}") from e
    free = shm.f_bavail * shm.f_frsize
    if free < need:
        raise Abort(f"/dev/shm has {free} bytes free; the ring the stream "
                    f"is handed over through needs {need}")
    return Ring(slots, slot_bytes)


def processor_argv(run: Run, serve_port: int) -> list:
    d = run.rundir
    return [*run.cell.config["processor_flags"],
            "-kafka.topic", run.sut.topic,
            "-listen.feed", "127.0.0.1:0",
            "-serve.addr", f"127.0.0.1:{serve_port}",
            "-sink", f"sqlite:{os.path.join(d, 'sink.db')}",
            "-checkpoint.path", os.path.join(d, "ckpt"),
            "-metrics.addr", f"127.0.0.1:{_free_port()}"]


# ---- the controller thread: the traffic mode's generator and window ----------


def control(run: Run, chunks) -> None:
    try:
        run.cell.mode.control(run, chunks)
    except BaseException as e:  # noqa: BLE001 -- reported by main, which exits non-zero
        run.error = run.error or e
    finally:
        try:
            if run.trace_span and run.trace_span[1] is None:
                drive.profiler(run, False)
            if run.trace_thread is not None:
                run.trace_thread.join()  # the trace is on disk
        except Exception as e:  # noqa: BLE001 -- reported by main
            run.error = run.error or e
        run.sut.stop.set()


# ---- after the window ---------------------------------------------------------


def _get(port: int, path: str):
    import urllib.request

    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=10) as r:
        return json.loads(r.read())


def after_finalize(run: Run, port: int, worker) -> None:
    """On the main thread, after ``worker.finalize()``, the query surface
    still up."""
    f = run.final
    bus, topic = run.sut.bus, run.sut.topic
    parts = range(run.sut.partitions)
    f["flows_seen"] = int(worker.flows_seen)
    f["batches_seen"] = int(worker.batches_seen)
    # a partition: the offset the worker had folded up to (the feed thread
    # fetches ahead of it), the group's commit, the log's end
    f["folded"] = drive.folded(run)
    f["committed"] = [int(bus.committed(GROUP, topic, p)) for p in parts]
    f["bus_end"] = [int(bus.end_offset(topic, p)) for p in parts]
    if sum(f["folded"]) != f["flows_seen"]:
        raise Abort(f"the worker folded up to offsets {f['folded']} of its "
                    f"partitions and counts {f['flows_seen']} flows")
    f["late_by_model"] = {
        name: int(getattr(m, "late_flows_dropped", 0) or 0)
        for name, m in worker.models.items()}
    f["late_dropped"] = sum(f["late_by_model"].values())
    f["dataplane"] = type(worker.fused).__name__
    f["version"] = _get(port, "/query/version")
    f["queries"] = {q["name"]: _get(port, q["path"])
                    for q in run.cell.config["checks"].get("queries", [])}
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    run.device["memory_peak_bytes"] = int(max(peaks))


def counter_total(name: str) -> float:
    """Sum of every labelled sample of a counter of the program's
    registry."""
    from flow_pipeline_tpu.obs import REGISTRY

    total = 0.0
    for line in REGISTRY.render().splitlines():
        if line.startswith(name) and line[len(name):len(name) + 1] in " {":
            total += float(line.rsplit(" ", 1)[1])
    return total


def read_publish_log(run: Run, path: str) -> None:
    if not os.path.isfile(path):
        return
    with open(path) as f:
        for line in f:
            parts = line.split()
            if line.startswith("#"):
                run.reader_stats = dict(zip(parts[1::2], parts[2::2]))
            elif len(parts) == 3:
                run.publish_log.append(
                    (float(parts[0]), int(parts[1]), int(parts[2])))


def metric_values(run: Run) -> dict:
    out = {}
    for meta, reader in (run.cell.per_layer if run.traced
                         else run.cell.end_to_end):
        value = reader.read(run)
        if value is None:
            continue  # nothing to read in this cell: left out of the line
        out[meta["name"]] = {"value": float(value), "unit": meta["unit"]}
    return out


# ---- main ------------------------------------------------------------------------


def execute(args) -> dict:
    import multiprocessing

    from . import check, manifest, schedule
    from .flowgen import _init_worker, encode_chunk

    manifest_path = os.path.join(ROOT, args.manifest)
    cell = manifest.load_cell(ROOT, manifest_path, args.workload)
    plan = cell.mode.plan(cell.traffic, cell.stream, float(args.seconds))
    if plan.rate and int(cell.config["bus_partitions"]) > 1:
        # Run.due() is a flow's position's; on several partitions the
        # newest flow of a snapshot is not flow flows_seen - 1
        raise Abort(
            f"traffic mode {plan.mode} offers flows at a rate and reads "
            f"staleness against the due time of flow flows_seen - 1, which "
            f"holds on one partition only; configuration "
            f"{cell.config_name} has {cell.config['bus_partitions']}")
    spec = schedule.spec_for(args.seed, cell.stream, plan)
    run = Run(args, cell, plan, spec)
    ensure_native()
    procs = max(2, min(int(cell.traffic.get("generator_processes", 6)),
                       (os.cpu_count() or 4) - 2))
    log(f"{procs} generator processes on {os.cpu_count()} cores")
    ctx = multiprocessing.get_context("spawn")
    ring = _ring(procs, spec.chunk_flows)
    pool = chunks = reader = None
    try:
        pool = ctx.Pool(procs, initializer=_init_worker, initargs=(
            cell.stream.path,
            (args.seed, dict(cell.stream), plan.first_close_flow,
             plan.phase_s), ROOT, ring.for_workers()))
        _widen_result_pipe(pool)
        # the stream is made, and cut into frames, while JAX starts
        chunks = drive.Stream(run, pool.imap(
            encode_chunk, range(plan.total_flows // spec.chunk_flows)), ring)
        os.makedirs(os.path.join(ROOT, ".bench_run"), exist_ok=True)
        run.rundir = tempfile.mkdtemp(prefix=f"{cell.name}-",
                                      dir=os.path.join(ROOT, ".bench_run"))
        run.device = require_device(cell)
        import jax

        def on_compile(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                run.compiles.append((time.monotonic(),
                                     str(kw.get("fun_name", "?")), secs))

        jax.monitoring.register_event_duration_secs_listener(on_compile)
        port = _free_port()
        if cell.traffic.get("reader"):
            reader = subprocess.Popen(
                [sys.executable, os.path.join(ROOT, "benchmark", "reader.py"),
                 "--port", str(port), "--interval",
                 str(cell.traffic["reader"]["poll_interval_s"]),
                 "--out", os.path.join(run.rundir, "publish.log")],
                stdin=subprocess.PIPE, stdout=sys.stderr)
        run.sut.install([tuple(h) for h in cell.config.get("spans", [])])
        run.sut.after_finalize = lambda w: after_finalize(run, port, w)
        ctl = threading.Thread(target=control, args=(run, chunks),
                               name="bench-control", daemon=True)
        ctl.start()
        try:
            rc = run.sut.serve(processor_argv(run, port))
        except BaseException as e:  # noqa: BLE001 -- re-raised below as Abort
            run.error = run.error or e
            rc = 1
        finally:
            run.sut.stop.set()
            run.sut.uninstall()
        ctl.join(timeout=30)
        if reader is not None:
            reader.stdin.close()
            reader.wait(timeout=10)
            read_publish_log(run, os.path.join(run.rundir, "publish.log"))
        if run.error is not None or rc != 0:
            raise Abort(f"run failed (processor rc {rc}): {run.error!r}")
        log(f"window closed: {run.pos_b - run.pos_a} flows in "
            f"{run.t_b - run.t_a:.3f} s; checking")
        if run.traced:
            from . import trace_reduce

            run.trace = trace_reduce.reduce_dir(
                os.path.join(run.rundir, "trace"), run)
        checks = check.run_checks(run)
        run.final["late_expected"] = check.late_flows(run)
        result = build_result(run, checks)
        if args.control:
            result["controls"] = [check.run_control(run, c)
                                  for c in args.control.split(",")]
        return result
    finally:
        if chunks is not None:
            chunks.stop()  # before the pool goes and the ring under it
        if pool is not None:
            pool.terminate()
            pool.join()
        ring.close()
        if reader is not None and reader.poll() is None:
            reader.kill()
            reader.wait()
        dump = os.path.join(tempfile.gettempdir(),
                            f"flowtrace-worker-{os.getpid()}.json")
        if os.path.isfile(dump):
            os.remove(dump)  # the interrupt's flight-recorder dump
        if args.keep:
            log(f"kept {run.rundir}")
        elif run.rundir:
            shutil.rmtree(run.rundir, ignore_errors=True)


def build_result(run: Run, checks: list) -> dict:
    plan, f = run.plan, run.final
    lo, hi = run.cell.mode.window_flows(run)
    uncommitted = run.deal.beyond(f["committed"], lo, hi)
    shed = counter_total("guard_shed_total")
    dead = counter_total("sink_deadletter_total")
    failed = int(uncommitted + shed + dead + f["late_dropped"])
    device = dict(run.device)
    result = {
        "correct": all(c["ok"] for c in checks),
        "attempted": int(hi - lo),
        "failed": failed,
        "metrics": metric_values(run),
        "device": device,
        "checks": checks,
        "window": {"mode": plan.mode, "seconds": run.t_b - run.t_a,
                   "first_flow": lo, "last_flow": hi,
                   "closes_at": run.spec.close_flows(lo, hi),
                   "flows_consumed": f["flows_seen"],
                   "folded": f["folded"], "committed": f["committed"],
                   "committed_total": sum(f["committed"]),
                   "bus_end": f["bus_end"],
                   "bus_end_total": sum(f["bus_end"]),
                   "dataplane": f["dataplane"],
                   "uncommitted": uncommitted, "shed": shed,
                   "deadlettered": dead, "late_dropped": f["late_dropped"],
                   "late_by_model": f["late_by_model"],
                   "late_expected": f["late_expected"],
                   **run.cell.mode.describe(run)},
    }
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default="BENCHMARK.json",
                    help="manifest path relative to the checkout")
    ap.add_argument("--control", default="",
                    help="also compare controls with the reference, "
                         "comma-separated: <precision> or "
                         "<precision>:<table kind>, e.g. bf16:ranked_bytes; "
                         "the result stays the program's")
    ap.add_argument("--keep", action="store_true",
                    help="keep the run directory (sink, checkpoint, trace)")
    args = ap.parse_args(argv)
    if not os.path.isfile(os.path.join(ROOT, "flow_pipeline_tpu",
                                       "__init__.py")):
        print("benchmark: flow_pipeline_tpu/ not found beside benchmark/ "
              "-- run from a checkout of the repo", file=sys.stderr)
        return 2
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        result = execute(args)
    except Abort as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    # each number compared beside its limit: the last lines of standard
    # error and the last key of the result's line
    result["checks"] = result.pop("checks")
    for c in result["checks"]:
        log(f"check {c['name']}: {c['value']} (limit {c['limit']}) "
            f"{'ok' if c['ok'] else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
