"""Dotted-flag parser + metrics/observability tests."""

import urllib.request

import pytest

from flow_pipeline_tpu.obs import MetricsRegistry, MetricsServer
from flow_pipeline_tpu.utils.flags import FlagSet


class TestFlags:
    def make(self):
        fs = FlagSet("test")
        fs.string("kafka.brokers", "127.0.0.1:9092", "brokers")
        fs.integer("flush.count", 100, "count")
        fs.number("flush.dur", 5.0, "dur")
        fs.boolean("proto.fixedlen", False, "fixedlen")
        fs.string("postgres.pass", "", "password", env="POSTGRES_PASSWORD")
        return fs

    def test_defaults(self):
        vals = self.make().parse([])
        assert vals["flush.count"] == 100
        assert vals["proto.fixedlen"] is False

    def test_space_and_equals_forms(self):
        vals = self.make().parse(
            ["-kafka.brokers", "k:9092", "-flush.count=7", "-proto.fixedlen"]
        )
        assert vals["kafka.brokers"] == "k:9092"
        assert vals["flush.count"] == 7
        assert vals["proto.fixedlen"] is True

    def test_bool_explicit_false(self):
        vals = self.make().parse(["-proto.fixedlen=false"])
        assert vals["proto.fixedlen"] is False

    def test_double_dash_accepted(self):
        vals = self.make().parse(["--flush.count", "3"])
        assert vals["flush.count"] == 3

    def test_unknown_flag_names_itself(self):
        with pytest.raises(ValueError, match="flag provided but not defined: -nope"):
            self.make().parse(["-nope", "1"])

    def test_missing_value(self):
        with pytest.raises(ValueError, match="needs a value"):
            self.make().parse(["-kafka.brokers"])

    def test_bad_int(self):
        with pytest.raises(ValueError, match="invalid value for -flush.count"):
            self.make().parse(["-flush.count", "abc"])

    def test_env_fallback(self, monkeypatch):
        monkeypatch.setenv("POSTGRES_PASSWORD", "sekret")
        vals = self.make().parse([])
        assert vals["postgres.pass"] == "sekret"
        # explicit flag beats env (reference precedence,
        # ref: inserter/inserter.go:220-224)
        vals = self.make().parse(["-postgres.pass", "flag"])
        assert vals["postgres.pass"] == "flag"

    def test_help_exits_zero(self, capsys):
        with pytest.raises(SystemExit) as e:
            self.make().parse(["-help"])
        assert e.value.code == 0
        assert "kafka.brokers" in capsys.readouterr().out


class TestMetrics:
    def test_counter_inc_and_labels(self):
        reg = MetricsRegistry()
        c = reg.counter("requests_total", "reqs")
        c.inc()
        c.inc(2, path="/metrics")
        assert c.value() == 1
        assert c.value(path="/metrics") == 2
        text = reg.render()
        assert "# TYPE requests_total counter" in text
        assert 'requests_total{path="/metrics"} 2.0' in text

    def test_gauge_set(self):
        reg = MetricsRegistry()
        g = reg.gauge("lag", "lag")
        g.set(42)
        assert "lag 42" in reg.render()
        assert "# TYPE lag gauge" in reg.render()

    def test_gauge_help_mentioning_counter_unmangled(self):
        # regression: naive str.replace corrupted HELP text containing the
        # word "counter" instead of the TYPE line
        reg = MetricsRegistry()
        reg.gauge("queue_depth", "items behind the counter").set(1)
        text = reg.render()
        assert "# HELP queue_depth items behind the counter" in text
        assert "# TYPE queue_depth gauge" in text

    def test_summary_quantiles(self):
        reg = MetricsRegistry()
        s = reg.summary("latency_us", "lat")
        for v in range(100):
            s.observe(float(v))
        assert 45 <= s.quantile(0.5) <= 55
        text = reg.render()
        assert "latency_us_count 100" in text

    def test_summary_labels(self):
        """Labeled summaries: per-label-set windows/quantiles render as
        their own series (the per-router delay panels), while _sum/_count
        keep aggregating across labels."""
        reg = MetricsRegistry()
        s = reg.summary("delay_s", "d")
        for v in (1.0, 3.0):
            s.observe(v, router="a")
        s.observe(100.0, router="b")
        assert s.quantile(0.99, router="a") <= 3.0
        assert s.quantile(0.5, router="b") == 100.0
        assert s.quantile(0.5) == 0.0  # unlabeled series: no observations
        assert s._sum == 104.0 and s._count == 3
        text = reg.render()
        assert 'delay_s{quantile="0.5",router="a"}' in text
        assert 'delay_s_count{router="b"} 1' in text

    def test_summary_label_cardinality_capped(self):
        """Label values can come from spoofable exporter addresses; past
        the cap, unseen label sets fold into _other instead of pinning a
        fresh sample window each (collector OOM guard)."""
        reg = MetricsRegistry()
        s = reg.summary("d_us", "d", max_label_sets=4)
        for i in range(50):
            s.observe(1.0, router=f"10.0.0.{i}")
        assert len(s._obs) <= 5  # 4 real sets + the _other overflow
        assert s._counts[(("router", "_other"),)] == 46
        assert s._count == 50  # totals still see every observation
        assert 'router="_other"' in s.render()

    def test_same_name_same_metric(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_http_endpoint(self):
        reg = MetricsRegistry()
        reg.counter("flows_processed_total", "n").inc(7)
        server = MetricsServer(0, registry=reg).start()
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics"
            ).read().decode()
            assert "flows_processed_total 7.0" in body
            # unknown path -> 404
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"http://127.0.0.1:{server.port}/nope")
        finally:
            server.stop()


class TestTracing:
    def test_stage_timer_exports_summary_family(self):
        from flow_pipeline_tpu.obs import REGISTRY
        from flow_pipeline_tpu.obs.tracing import StageTimer

        t = StageTimer()
        with t.stage("decoding"):
            pass
        rendered = REGISTRY.render()
        assert "flow_summary_decoding_time_us" in rendered

    def test_worker_records_a_span_for_each_stage(self):
        """The worker's batch and flush stages are timed by its spans
        (``apply``, ``flush``: obs/trace.py), no longer by stage
        summaries beside them; ``flow_processing_time_us`` stays."""
        from flow_pipeline_tpu.engine import StreamWorker, WorkerConfig
        from flow_pipeline_tpu.gen import FlowGenerator, MockerProfile
        from flow_pipeline_tpu.models import WindowAggConfig, WindowAggregator
        from flow_pipeline_tpu.sink import MemorySink
        from flow_pipeline_tpu.transport import Consumer, InProcessBus, Producer

        bus = InProcessBus()
        bus.create_topic("flows", 1)
        g = FlowGenerator(MockerProfile(), seed=3, t0=1_699_999_800, rate=20.0)
        Producer(bus, fixedlen=True).send_many(g.batch(1000).to_messages())
        worker = StreamWorker(
            Consumer(bus, fixedlen=True),
            {"flows_5m": WindowAggregator(WindowAggConfig(batch_size=512))},
            [MemorySink()],
            WorkerConfig(poll_max=512),
        )
        from flow_pipeline_tpu.obs.trace import TRACER

        before, mode = worker.m_proc._count, TRACER.mode
        TRACER.configure("always")
        try:
            worker.run(stop_when_idle=True)
            names = [s[0] for s in TRACER.snapshot()]
        finally:
            TRACER.configure(mode)
        assert names.count("apply") == worker.batches_seen > 0
        assert names.count("flush") > 0
        assert worker.m_proc._count - before == worker.batches_seen
        assert not hasattr(worker, "stages")
