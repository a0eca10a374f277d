"""flowtrace: per-chunk tracing, histogram metrics, in-kernel phase
attribution.

The contracts under test: (1) the flight recorder is a bounded,
lock-safe ring whose Chrome trace-event export is shape-stable (golden
file) and Perfetto-loadable (valid JSON, complete events, us
timestamps); (2) chunk ids minted at decode tie one chunk's spans
together ACROSS the feed/group/worker/flusher thread boundaries, live
via /debug/trace and post-mortem via the worker-error dump; (3) the
Histogram metric renders cumulative le-bucket series that aggregate
across instances, and the StageTimer's dynamically-named summary family
is capped; (4) the kernels' stats out-struct is purely observational —
bit-exact outputs with stats on vs off — and its counters are sane;
(5) recording survives concurrent scrape + mutation from many threads.
"""

from __future__ import annotations

import json
import os
import threading
import urllib.request

import numpy as np
import pytest

from flow_pipeline_tpu import native
from flow_pipeline_tpu.engine import StreamWorker, WorkerConfig
from flow_pipeline_tpu.obs import MetricsRegistry, MetricsServer, REGISTRY
from flow_pipeline_tpu.obs.trace import TRACER, TraceRecorder
from flow_pipeline_tpu.obs.tracing import MAX_STAGES, StageTimer
from flow_pipeline_tpu.transport import Consumer

from test_fused import BS, WINDOW, make_models, make_stream
from test_ingest import CollectSink, _stream_to_bus

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "flowtrace_golden.json")


@pytest.fixture
def tracer():
    """A fresh, isolated recorder (tests must not depend on — or
    pollute — the process-wide TRACER's contents)."""
    return TraceRecorder(capacity=8, mode="ring")


class TestTraceRecorder:
    def test_mode_validation(self, tracer):
        with pytest.raises(ValueError, match="off|ring|always"):
            tracer.configure("sometimes")

    def test_off_records_nothing(self, tracer):
        tracer.configure("off")
        tracer.record("x", 0.0, 1.0)
        with tracer.span("y"):
            pass
        assert tracer.snapshot() == []
        assert tracer.chrome_trace()["traceEvents"] == []

    @pytest.mark.parametrize("mode, paused, keeps", [
        ("off", False, False), ("ring", False, True), ("always", False, True),
        ("ring", True, False)])
    def test_recording_says_whether_a_span_would_be_kept(self, tracer, mode,
                                                         paused, keeps):
        tracer.configure(mode)
        tracer.paused = paused
        assert tracer.recording is keeps
        with tracer.span("y"):
            pass
        assert bool(tracer.snapshot()) is keeps

    def test_ring_bounds_and_overwrites_oldest(self, tracer):
        for i in range(20):
            tracer.record("s", float(i), float(i) + 0.5, chunk=i)
        snap = tracer.snapshot()
        assert len(snap) == 8  # capacity, not 20
        # oldest-first, and the survivors are the LAST 8 recorded
        assert [ev[4] for ev in snap] == list(range(12, 20))
        assert tracer.chrome_trace()["otherData"]["dropped_spans"] == 12

    def test_always_retains_everything(self, tracer):
        tracer.configure("always")
        for i in range(100):
            tracer.record("s", 0.0, 1.0, chunk=i)
        assert len(tracer.snapshot()) == 100

    def test_configure_resets_state(self, tracer):
        tracer.record("s", 0.0, 1.0)
        tracer.configure("ring")
        assert tracer.snapshot() == []

    def test_span_records_thread_and_args(self, tracer):
        with tracer.span("work", chunk=3, rows=10):
            pass
        (name, t0, t1, thread, chunk, args), = tracer.snapshot()
        assert name == "work" and chunk == 3
        assert t1 >= t0
        assert thread == threading.current_thread().name
        assert args == {"rows": 10}

    def test_span_records_on_exception(self, tracer):
        with pytest.raises(RuntimeError):
            with tracer.span("boom", chunk=1):
                raise RuntimeError("x")
        assert [ev[0] for ev in tracer.snapshot()] == ["boom"]

    def test_concurrent_recording_is_safe(self, tracer):
        """8 threads hammer one ring; every surviving event is intact
        (no torn tuples, no lost-slot crashes)."""
        tracer = TraceRecorder(capacity=64, mode="ring")

        def work(tid):
            for i in range(500):
                tracer.record(f"t{tid}", float(i), float(i) + 1.0,
                              chunk=tid * 1000 + i)

        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = tracer.snapshot()
        assert len(snap) == 64
        for name, t0, t1, thread, chunk, args in snap:
            assert name == f"t{chunk // 1000}"
            assert t1 == t0 + 1.0


class TestChromeExport:
    def test_golden_file_shape(self, tracer):
        """The export shape is pinned by a golden file: Perfetto and
        chrome://tracing parse this exact structure, so a field rename
        or a ts unit change must fail loudly here."""
        tracer.configure("always")
        tracer.record("decode", 100.0, 100.0015625, chunk=1, rows=512)
        tracer.record("queue_wait", 100.25, 100.5, chunk=1,
                      stage="group")
        tracer.record("apply", 100.5, 100.75, chunk=1, rows=512)
        tracer.record("flush", 101.0, 101.5, chunk=1,
                      table="flows_5m", rows=9)
        got = json.loads(json.dumps(tracer.chrome_trace()))
        for ev in got["traceEvents"]:
            ev["pid"] = 0  # process id is the one run-dependent field
            ev["tid"] = "MainThread"  # pytest's main thread name varies
        with open(GOLDEN) as f:
            want = json.load(f)
        assert got == want

    def test_events_are_complete_spans_in_us(self, tracer):
        tracer.record("s", 2.0, 2.5, chunk=9)
        ev, = tracer.chrome_trace()["traceEvents"]
        assert ev["ph"] == "X"
        assert ev["ts"] == 2.0e6 and ev["dur"] == 0.5e6
        assert ev["args"]["chunk"] == 9

    def test_dump_writes_loadable_json(self, tracer, tmp_path):
        tracer.record("s", 0.0, 1.0)
        path = tracer.dump(str(tmp_path / "trace.json"))
        with open(path) as f:
            doc = json.load(f)
        assert doc["traceEvents"]


class TestDebugTraceEndpoint:
    def test_endpoint_serves_the_flight_recorder(self):
        TRACER.configure("ring")
        with TRACER.span("endpoint_probe", chunk=42):
            pass
        server = MetricsServer(port=0).start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/debug/trace") as r:
                assert r.headers["Content-Type"] == "application/json"
                doc = json.load(r)
        finally:
            server.stop()
        probes = [e for e in doc["traceEvents"]
                  if e["name"] == "endpoint_probe"]
        assert probes and probes[0]["args"]["chunk"] == 42

    def test_metrics_endpoint_still_serves(self):
        server = MetricsServer(port=0).start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/metrics") as r:
                assert r.status == 200
        finally:
            server.stop()


def _run_traced_worker(sink=None, mode="ring", sinks=None):
    TRACER.configure(mode)
    bus = _stream_to_bus(make_stream())
    worker = StreamWorker(
        Consumer(bus, fixedlen=True),
        make_models(WINDOW, 100),
        sinks if sinks is not None else [sink or CollectSink()],
        WorkerConfig(poll_max=BS, snapshot_every=0,
                     ingest_mode="pipelined"),
    )
    worker.run(stop_when_idle=True)
    return worker


class TestChunkPropagation:
    def test_spans_cross_executor_and_flusher_threads(self):
        """The acceptance shape: one chunk's spans appear on the feed
        (decode), group (prepare), worker (queue_wait + apply) and
        flusher (flush) threads, all carrying the same chunk id."""
        try:
            _run_traced_worker()
            events = TRACER.chrome_trace()["traceEvents"]
        finally:
            TRACER.configure("off")
        by_chunk: dict = {}
        for ev in events:
            chunk = ev.get("args", {}).get("chunk")
            if chunk is not None and chunk >= 0:
                by_chunk.setdefault(chunk, []).append(ev)
        assert by_chunk, "no chunk-tagged spans recorded"
        # at least one chunk shows the full pipelined life cycle
        full = [
            c for c, evs in by_chunk.items()
            if {"decode", "prepare", "queue_wait", "apply"}
            <= {e["name"] for e in evs}
        ]
        assert full, f"no chunk with all stages: {sorted(by_chunk)[:5]}"
        evs = by_chunk[full[0]]
        tids = {e["name"]: e["tid"] for e in evs}
        # decode on the prefetch feed thread, prepare on the ingest
        # group thread, apply on the worker thread — three boundaries
        assert tids["decode"] != tids["apply"]
        assert tids["prepare"] != tids["apply"]
        assert tids["decode"] != tids["prepare"]
        # flush jobs run on the flusher thread, still chunk-tagged
        flushes = [e for e in events
                   if e["name"] == "flush"
                   and e.get("args", {}).get("chunk", -1) >= 0]
        assert flushes
        assert any(e["tid"].startswith("ingest-flush") for e in flushes)

    def test_decode_mints_monotonic_chunk_ids(self):
        bus = _stream_to_bus(make_stream())
        consumer = Consumer(bus, fixedlen=True)
        ids = []
        while True:
            b = consumer.poll(BS)
            if b is None:
                break
            ids.append(b.chunk_id)
        assert len(ids) >= 2
        assert all(i > 0 for i in ids)
        assert ids == sorted(ids) and len(set(ids)) == len(ids)

    def test_worker_error_dumps_flight_recorder(self, monkeypatch,
                                                tmp_path):
        """A crashing worker leaves the post-mortem trace behind — and
        the original exception still propagates."""
        monkeypatch.setenv("TMPDIR", str(tmp_path))
        import tempfile

        tempfile.tempdir = None  # re-read TMPDIR
        try:
            class PoisonSink:
                def write(self, table, rows):
                    raise IOError("sink down")

            from flow_pipeline_tpu.ingest import FlushError

            with pytest.raises(FlushError):
                _run_traced_worker(sink=PoisonSink())
            dumps = list(tmp_path.glob("flowtrace-worker-*.json"))
            assert len(dumps) == 1
            with open(dumps[0]) as f:
                doc = json.load(f)
            assert any(ev.get("args", {}).get("chunk", -1) >= 0
                       for ev in doc["traceEvents"])
        finally:
            tempfile.tempdir = None
            TRACER.configure("off")

    def test_trace_off_worker_parity(self):
        """Recording must be purely observational: off vs ring workers
        land identical sink rows on the same stream."""
        from test_fused import canon_rows

        a, b = CollectSink(), CollectSink()
        _run_traced_worker(sink=a, mode="off")
        _run_traced_worker(sink=b, mode="ring")
        TRACER.configure("off")
        assert set(a.rows) == set(b.rows)
        f5_a = sorted(sum([canon_rows(r) for r in a.rows["flows_5m"]], []))
        f5_b = sorted(sum([canon_rows(r) for r in b.rows["flows_5m"]], []))
        assert f5_a == f5_b


class TestWatermark:
    def test_forced_flush_of_open_window_clamps_to_now(self):
        """A forced flush (shutdown) pops the still-OPEN window, whose
        end lies in the future: the watermark must clamp to wall clock
        (never claim coverage ahead of time) and the latency histogram
        must not take negative observations."""
        import time as _time

        from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile

        TRACER.configure("off")
        gen = FlowGenerator(ZipfProfile(n_keys=50, alpha=1.2), seed=3)
        b = gen.batch(BS)
        future = int(_time.time()) + 10_000
        b.columns["time_received"] = np.full(BS, future, np.uint64)
        worker = StreamWorker(
            Consumer(_stream_to_bus([b]), fixedlen=True),
            make_models(WINDOW, 50), [CollectSink()],
            WorkerConfig(poll_max=BS, snapshot_every=0))
        worker.run(stop_when_idle=True)  # finalize force-flushes
        wm = worker.m_commit_wm.value()
        assert 0 < wm <= _time.time()
        count, total = worker.m_commit_lat.value(table="flows_5m")
        assert count >= 1 and total >= 0.0

    def test_commit_watermark_and_latency(self):
        worker = _run_traced_worker(mode="off")
        # every window in the stream is closed + flushed at finalize;
        # the watermark is the newest window END committed to sinks
        wm = worker.m_commit_wm.value()
        assert wm > 0 and wm % WINDOW == 0
        count, total = worker.m_commit_lat.value(table="flows_5m")
        assert count >= 1
        rendered = worker.m_commit_lat.render()
        assert 'le="+Inf"' in rendered
        assert "flow_sink_commit_latency_seconds_bucket" in rendered


class TestHistogram:
    def test_cumulative_buckets_and_inf(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_us", "x", buckets=(10.0, 100.0, 1000.0))
        for v in (5, 10, 50, 5000):
            h.observe(float(v))
        text = h.render()
        assert 'lat_us_bucket{le="10"} 2' in text       # 5, 10 (le is <=)
        assert 'lat_us_bucket{le="100"} 3' in text
        assert 'lat_us_bucket{le="1000"} 3' in text
        assert 'lat_us_bucket{le="+Inf"} 4' in text
        assert "lat_us_sum 5065.0" in text
        assert "lat_us_count 4" in text

    def test_aggregable_across_instances(self):
        """The reason Histogram exists next to Summary: summing bucket
        counters across two 'instances' gives the honest fleet
        distribution (quantiles of summaries cannot be summed)."""
        reg = MetricsRegistry()
        h1 = reg.histogram("a_us", "x", buckets=(10.0, 100.0))
        h2 = reg.histogram("b_us", "x", buckets=(10.0, 100.0))
        for v in (5, 50):
            h1.observe(float(v))
        for v in (50, 500):
            h2.observe(float(v))
        c1, s1 = h1.value()
        c2, s2 = h2.value()
        assert c1 + c2 == 4 and s1 + s2 == 605.0

    def test_label_cardinality_capped(self):
        reg = MetricsRegistry()
        h = reg.histogram("c_us", "x", buckets=(10.0,), max_label_sets=4)
        for i in range(50):
            h.observe(1.0, stage=f"s{i}")
        text = h.render()
        assert text.count("_count{") <= 5  # 4 real + _other
        assert 'stage="_other"' in text

    def test_type_conflict_raises(self):
        reg = MetricsRegistry()
        reg.histogram("m", "x")
        with pytest.raises(TypeError):
            reg.counter("m")


class TestStageTimerCap:
    def test_summary_family_is_capped(self):
        """Satellite: dynamically named stages must not grow the metric
        family unbounded — the tail folds into the overflow stage."""
        reg_before = set(REGISTRY._metrics)
        st = StageTimer()
        for i in range(MAX_STAGES + 50):
            st.observe(f"dyn_stage_{i}", 1.0)
        new = {n for n in REGISTRY._metrics if n not in reg_before
               and n.startswith("flow_summary_dyn_stage_")}
        assert len(new) == MAX_STAGES
        # the 50 overflowed observations all landed in the bounded
        # overflow series, not in 50 new families
        other = REGISTRY._metrics["flow_summary_other_time_us"]
        assert other._count >= 50

    def test_known_stages_unaffected_by_cap(self):
        st = StageTimer()
        st.observe("host_fused", 2.0)
        for i in range(MAX_STAGES + 10):
            st.observe(f"cap_probe_{i}", 1.0)
        st.observe("host_fused", 3.0)  # existing name: never folded
        s = REGISTRY._metrics["flow_summary_host_fused_time_us"]
        assert s._count >= 2

    def test_stage_histogram_records(self):
        st = StageTimer()
        h = REGISTRY._metrics["flow_stage_duration_us"]
        # the shared histogram may have hit ITS label cap from the
        # cap-probe stages above — count both the real and folded series
        def seen():
            return (h.value(stage="host_fused")[0]
                    + h.value(stage="_other")[0])

        before = seen()
        st.observe("host_fused", 1500.0)
        assert seen() == before + 1


class TestConcurrentScrape:
    def test_render_under_concurrent_mutation(self):
        """Satellite: 8 writer threads hammer counters/summaries/
        histograms while the HTTP endpoint is scraped — every response
        parses, no exceptions, final totals exact."""
        reg = MetricsRegistry()
        server = MetricsServer(port=0, registry=reg).start()
        c = reg.counter("scrape_total", "x")
        s = reg.summary("scrape_lat_us", "x")
        h = reg.histogram("scrape_hist_us", "x", buckets=(10.0, 100.0))
        errors = []

        def writer(tid):
            try:
                for i in range(2000):
                    c.inc(1, worker=str(tid))
                    s.observe(float(i % 100), worker=str(tid))
                    h.observe(float(i % 200), worker=str(tid))
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(8)]
        for t in threads:
            t.start()
        bodies = []
        url = f"http://127.0.0.1:{server.port}/metrics"
        try:
            # scrape, then look: on a loaded box the writers can be done
            # before this thread is first scheduled
            while True:
                alive = any(t.is_alive() for t in threads)
                with urllib.request.urlopen(url) as r:
                    bodies.append(r.read().decode())
                if not alive:
                    break
            for t in threads:
                t.join()
            with urllib.request.urlopen(url) as r:
                final = r.read().decode()
        finally:
            server.stop()
        assert not errors
        assert len(bodies) >= 1
        for body in bodies + [final]:
            for line in body.splitlines():
                assert line.startswith("#") or " " in line
        # totals exact after the dust settles: 8 threads x 2000
        total = sum(float(line.rsplit(" ", 1)[1])
                    for line in final.splitlines()
                    if line.startswith("scrape_total{"))
        assert total == 16000.0


HAVE_SKETCH = native.sketch_available()
HAVE_FUSED = native.fused_available()


@pytest.mark.skipif(not native.group_available(),
                    reason="libflowdecode.so not built")
class TestNativeStats:
    """The stats out-struct must be purely observational (bit-exact
    outputs with and without it) and its counters sane."""

    def test_hash_group_parity_and_counts(self, rng):
        lanes = rng.integers(0, 64, size=(20000, 4)).astype(np.uint32)
        p1, s1, c1 = native.hash_group(lanes)
        stats = native.new_stats()
        p2, s2, c2 = native.hash_group(lanes, stats=stats)
        np.testing.assert_array_equal(p1, p2)
        np.testing.assert_array_equal(s1, s2)
        assert c1 == c2
        assert stats[native.FF_STAT_ROWS] == 20000
        assert stats[native.FF_STAT_GROUPS] == len(s1)
        assert stats[native.FF_STAT_RADIX_PASSES] == 4
        assert stats[native.FF_STAT_SLOTS["radix"]] > 0
        assert all(int(v) >= 0 for v in stats)

    def test_group_sum_parity_and_fold_time(self, rng):
        lanes = rng.integers(0, 50, size=(10000, 3)).astype(np.uint32)
        vals = rng.integers(0, 1000, size=(10000, 2)).astype(np.uint64)
        r1 = native.group_sum(lanes, vals)
        stats = native.new_stats()
        r2 = native.group_sum(lanes, vals, stats=stats)
        for a, b in zip(r1, r2):
            np.testing.assert_array_equal(a, b)
        assert stats[native.FF_STAT_SLOTS["fold"]] > 0

    @pytest.mark.skipif(not HAVE_SKETCH, reason="no hostsketch engine")
    def test_sketch_kernels_parity_with_stats(self, rng):
        depth, width, planes = 4, 1 << 10, 3
        keys = rng.integers(0, 500, size=(600, 2)).astype(np.uint32)
        vals = rng.integers(1, 100, size=(600, planes)).astype(np.float32)
        cms_a = np.zeros((planes, depth, width), np.uint64)
        cms_b = np.zeros((planes, depth, width), np.uint64)
        stats = native.new_stats()
        native.hs_cms_update(cms_a, keys, vals, None, True, 1)
        native.hs_cms_update(cms_b, keys, vals, None, True, 1,
                             stats=stats)
        np.testing.assert_array_equal(cms_a, cms_b)
        assert stats[native.FF_STAT_SLOTS["cms"]] > 0
        q1 = native.hs_cms_query(cms_a, keys)
        q2 = native.hs_cms_query(cms_b, keys, stats=stats)
        np.testing.assert_array_equal(q1, q2)
        assert stats[native.FF_STAT_SLOTS["topk"]] > 0

    @pytest.mark.skipif(not HAVE_FUSED, reason="no fused dataplane")
    def test_fused_update_parity_with_stats(self, rng):
        """The whole-tree pass with a stats buffer produces bit-identical
        sketch state AND accumulates every phase it executed."""
        from flow_pipeline_tpu.hostsketch.state import host_hh_init
        from flow_pipeline_tpu.models.heavy_hitter import (
            HeavyHitterConfig,
        )

        cfg_root = HeavyHitterConfig(
            key_cols=("src_addr", "dst_addr"), batch_size=4096,
            width=1 << 10, capacity=64)
        cfg_child = HeavyHitterConfig(
            key_cols=("src_addr",), batch_size=4096,
            width=1 << 10, capacity=64)
        plan = native.FusedPlan(
            parent=np.asarray([-1, 0], np.int64),
            sel=np.asarray([0, 1, 2, 3], np.int64),
            sel_off=np.asarray([0, 0, 4], np.int64),
            depth=np.asarray([4, 4], np.int64),
            width=np.asarray([1 << 10, 1 << 10], np.int64),
            cap=np.asarray([64, 64], np.int64),
            conservative=np.asarray([1, 1], np.uint8),
            prefilter=np.asarray([1, 1], np.uint8),
            admission_plain=np.asarray([0, 0], np.uint8),
        )
        lanes = rng.integers(0, 200, size=(4096, 8)).astype(np.uint32)
        vals = rng.integers(1, 1500, size=(4096, 2)).astype(np.float32)
        sa = [host_hh_init(cfg_root), host_hh_init(cfg_child)]
        sb = [host_hh_init(cfg_root), host_hh_init(cfg_child)]
        native.fused_update(lanes, vals, plan, sa, do_sketch=True)
        stats = native.new_stats()
        native.fused_update(lanes, vals, plan, sb, do_sketch=True,
                            stats=stats)
        for a, b in zip(sa, sb):
            np.testing.assert_array_equal(a.cms, b.cms)
            np.testing.assert_array_equal(a.table_keys, b.table_keys)
            np.testing.assert_array_equal(a.table_vals, b.table_vals)
        assert stats[native.FF_STAT_ROWS] == 4096
        for phase in ("radix", "refine", "regroup", "fold", "cms",
                      "topk"):
            assert stats[native.FF_STAT_SLOTS[phase]] > 0, phase


class TestTraceFlag:
    def test_cli_flag_validation(self):
        from flow_pipeline_tpu.cli import main

        rc = main(["processor", "-obs.trace", "sometimes", "-in",
                   "/nonexistent"])
        assert rc == 2

    def test_env_default(self, monkeypatch):
        monkeypatch.setenv("FLOWTPU_TRACE", "always")
        t = TraceRecorder(capacity=4)
        assert t.mode == "always"
