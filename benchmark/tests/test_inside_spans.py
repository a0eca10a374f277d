"""The readers of the spans inside the layers the hooks time from outside
(PR 35): nesting by thread and time, a flush's parts by the chunk that
flushed, a publish's parts by publish, and nothing where the program has no
such span. The CPU dry run of the tiny cells that reports every one of the
metrics is in tests/test_benchmark_seam.py (tier-1)."""

import os
import types

import pytest

from benchmark import inside_spans, manifest, program_spans

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = os.path.join(os.path.dirname(HERE), "layer_metrics")

# (name, t0, t1, thread, chunk, args) on the monotonic clock, seconds
SPANS = [
    ("fetch", 0.90, 0.91, "feed", 7, {"rows": 4, "partition": 0}),
    ("decode", 0.91, 0.95, "feed", 7, {"rows": 4, "partition": 0}),
    ("fetch", 0.96, 0.97, "feed", 8, {"rows": 0, "partition": 0}),
    ("apply", 1.00, 2.00, "w", 7, {"rows": 4, "age_ms": 110.0}),
    ("split_parts", 1.00, 1.01, "w", None, {"parts": 1}),
    ("window_close", 1.02, 1.05, "w", None, {"model": "a", "slot": 300}),
    ("window_close", 1.05, 1.06, "w", None, {"model": "b", "slot": 300}),
    ("flush", 1.10, 1.40, "w", 7, {"table": "flows_5m", "rows": 9}),
    ("flush_rows", 1.10, 1.12, "w", 7, {"table": "flows_5m", "rows": 9}),
    ("sink_put", 1.12, 1.38, "w", 7, {"sink": "SQLiteSink", "rows": 9}),
    ("sink_records", 1.12, 1.32, "w", None, {"rows": 9}),
    ("sink_execute", 1.32, 1.38, "w", None, {"rows": 9}),
    ("sink_put", 1.38, 1.40, "w", 7, {"sink": "RangeLedger", "rows": 9}),
    ("flush", 1.40, 1.50, "w", 7, {"table": "top_talkers", "rows": 2}),
    ("sink_records", 1.41, 1.45, "w", None, {"rows": 2}),
    # a query thread's, inside no flush of its own thread
    ("sink_records", 1.42, 1.43, "reader", None, {"rows": 5}),
    ("snapshot_publish", 1.60, 1.70, "w", 7,
     {"reason": "refresh", "late_ms": 30.0, "age_ms": 700.0}),
    ("publish_view", 1.60, 1.65, "w", None, {"model": "a", "bytes": 4e6}),
    ("publish_view", 1.65, 1.68, "w", None, {"model": "b", "bytes": 1e6}),
    ("publish_swap", 1.68, 1.70, "w", None, {"ranges": 1}),
    ("apply", 3.00, 3.50, "w", 9, {"rows": 4}),
    ("flush", 3.10, 3.20, "w", 9, {"table": "flows_5m", "rows": 1}),
    ("sink_records", 3.10, 3.12, "w", None, {"rows": 1}),
    ("snapshot_publish", 3.60, 3.62, "w", 9,
     {"reason": "close", "late_ms": 0.0}),
    ("publish_swap", 3.61, 3.62, "w", None, {"ranges": 2}),
]


def _run(spans=SPANS, t_a=0.5, t_b=5.0):
    run = types.SimpleNamespace(t_a=t_a, t_b=t_b)
    run._program_spans = program_spans.Window(list(spans), t_a, t_b)
    return run


def _reader(name):
    # as the harness loads it (and checks that it defines read())
    return manifest._load_reader(os.path.join(READERS, name + ".py")).read


def test_nesting_is_by_thread_and_start_time():
    w = _run()._program_spans
    got = inside_spans.nested(w, "flush", "sink_records")
    assert [(p[5]["table"], len(c)) for p, c in got] == [
        ("flows_5m", 1), ("top_talkers", 1), ("flows_5m", 1)]
    # the reader thread's span lies inside a flush in time, on no thread
    # of a flush
    assert all(c[3] == "w" for _p, cs in got for c in cs)


@pytest.mark.parametrize("metric,value", [
    ("fetch_ms_p50", 10.0),                      # the empty fetch left out
    ("feed_decode_us_per_kflow", 0.04 * 1e9 / 4),
    ("prefetch_queue_wait_ms_p50.live", 50.0),   # 0.95 -> 1.00, chunk 7
    ("flow_age_at_apply_ms_p50.live", 110.0),
    ("split_parts_ms_p50", 10.0),
    ("close_extract_ms_per_close", 40.0),        # both tables of slot 300
    ("flush_rows_ms_per_close", 10.0),           # chunks 7 and 9: 20, 0
    ("sink_records_ms_per_close", 130.0),        # 200 + 40, and 20
    ("sink_execute_ms_per_close", 30.0),         # 60 and 0
    ("sink_ledger_ms_per_close", 10.0),          # 20 and 0
    ("publish_loop_ms_per_min", 120.0 / (4.5 / 60)),
    ("publish_view_ms_p50", 40.0),               # 80 and 0
    ("publish_view_mb_p50", 2.5),                # 5 and 0
    ("publish_swap_ms_p50", 15.0),
    ("publish_late_ms_p50.live", 30.0),          # the refresh alone
    ("publish_period_s_p50.live", 2.0),
    ("flow_age_at_publish_ms_p50.live", 700.0),
])
def test_reader(metric, value):
    assert _reader(metric)(_run()) == pytest.approx(value)


PARENT = [s for s in SPANS if s[0] in ("apply", "decode", "flush")]


@pytest.mark.parametrize("metric", [
    "fetch_ms_p50", "flow_age_at_apply_ms_p50.live", "split_parts_ms_p50",
    "close_extract_ms_per_close", "flush_rows_ms_per_close",
    "sink_records_ms_per_close", "sink_execute_ms_per_close",
    "sink_ledger_ms_per_close", "publish_loop_ms_per_min",
    "publish_view_ms_p50", "publish_view_mb_p50", "publish_swap_ms_p50",
    "publish_late_ms_p50.live", "publish_period_s_p50.live",
    "flow_age_at_publish_ms_p50.live"])
def test_a_parent_without_the_span_reads_nothing(metric):
    """The driver lays these readers over the parent's checkout: where
    the program has no such span or argument the line leaves the metric
    out, and nothing raises."""
    parent = [(n, a, b, t, c, {k: v for k, v in args.items()
                               if k != "age_ms"})
              for n, a, b, t, c, args in PARENT]
    assert _reader(metric)(_run(parent)) is None


def test_spans_that_cannot_be_read_read_nothing():
    run = types.SimpleNamespace(t_a=0.5, t_b=5.0, _program_spans=None)
    for f in os.listdir(READERS):
        if f[:-3] in ("fetch_ms_p50", "sink_records_ms_per_close",
                      "publish_view_mb_p50", "publish_period_s_p50.live",
                      "prefetch_queue_wait_ms_p50.live"):
            assert _reader(f[:-3])(run) is None


@pytest.mark.parametrize("spans,chain", [
    # (name, start ns, duration ns, thread): a hook, then the program's own
    ([("process", 0, 100e6, "w"), ("apply", 1e6, 98e6, "w"),
      ("publish", 60e6, 30e6, "w"), ("snapshot_publish", 60.1e6, 29.8e6, "w"),
      ("publish_view", 60.2e6, 8e6, "w"), ("publish_view", 68.3e6, 20e6, "w"),
      ("publish_swap", 89e6, 0.5e6, "w")],
     "publish > snapshot_publish > publish_view"),
    ([("process", 0, 1000e6, "w"), ("flush_closed", 10e6, 950e6, "w"),
      ("sink_write", 20e6, 900e6, "w"), ("flush", 21e6, 898e6, "w"),
      ("flush_rows", 21e6, 1e6, "w"), ("sink_put", 23e6, 890e6, "w"),
      ("sink_records", 24e6, 640e6, "w"), ("sink_execute", 665e6, 230e6, "w"),
      ("sink_put", 914e6, 2e6, "w")],
     "flush_closed > sink_write > flush > sink_put > sink_records"),
])
def test_an_idle_gap_is_named_down_to_the_inside_span(spans, chain):
    """``trace_reduce._owner`` as it stands names a gap by hook and span
    alike, outermost first: no span shares a hook's name."""
    from benchmark.trace_reduce import _owner

    a = 70e6 if "publish" in chain else 30e6
    b = 88e6 if "publish" in chain else 660e6
    assert _owner(a, b, spans) == chain
