"""The program's own spans and kernel scopes (ISSUE 24).

(a) a FusedPipeline + StreamWorker run with a checkpoint records every
span of the catalogue (docs/OBSERVABILITY.md), nested where the
catalogue says; (b) the compiled fused step carries every kernel scope
name in its HLO metadata and computes the same bits with and without
them; (c) a jax.profiler session holds the program's spans as host-plane
events; (d) ``off`` records nothing and enters no annotation; (e) the
ring refuses to call a window whole once its start has been overwritten;
(f) the spans inside the layers the benchmark's hooks time from outside
(ISSUE 35): fetch, the cut of a poll, the tumbling close, each sink's
write and the publish, with a flow's age from the bus to the snapshot.
"""

import glob
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

from flow_pipeline_tpu.engine import StreamWorker, WorkerConfig
from flow_pipeline_tpu.engine import fused as fused_mod
from flow_pipeline_tpu.engine.fused import FusedPipeline
from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
from flow_pipeline_tpu.models import HeavyHitterConfig
from flow_pipeline_tpu.engine.windowed import WindowedHeavyHitter
from flow_pipeline_tpu.models.scan import scan_config, scan_model
from flow_pipeline_tpu.obs import trace as trace_mod
from flow_pipeline_tpu.obs.trace import RING_CAPACITY, TRACER, TraceRecorder
from flow_pipeline_tpu.serve import ServeServer
from flow_pipeline_tpu.serve.publisher import attach_worker
from flow_pipeline_tpu.sink import ResilientSink, SQLiteSink
from flow_pipeline_tpu.transport import Consumer

from test_fused import BS, WINDOW, make_models, make_stream
from test_ingest import CollectSink, _stream_to_bus

# the catalogue: span -> the span it nests in on the worker thread
# (None: outside "apply")
WORKER_SPANS = {
    "poll_wait": None,
    "apply": None,
    # PR 47: the spread detectors fold nothing on the host under the
    # fused step ("spread_fold" has no emitter left); a close and a
    # publish decode their planes
    "spread_decode": "apply",
    "lane_build": "apply",
    "h2d": "apply",
    "step_dispatch": "apply",
    "detector_dispatch": "apply",
    "wagg_wait": "apply",
    "wagg_d2h": "apply",
    "wagg_fold": "apply",
    "wagg_rows": "apply",
    "flush": "apply",
    "ckpt_state": "apply",
    "wagg_state": "apply",
    "ckpt_d2h": "apply",
    "ckpt_serialize": "apply",
    "ckpt_write": "apply",
    "ckpt_commit": "apply",
    # ISSUE 35: span -> the span it nests in, directly
    "split_parts": "apply",
    "window_close": "apply",
    "flush_rows": "flush",
    "sink_put": "flush",
    "sink_records": "sink_put",
    "sink_execute": "sink_put",
    "snapshot_publish": "apply",
    "publish_view": "snapshot_publish",
    "publish_swap": "snapshot_publish",
}
# which of them ISSUE 35 added (with "fetch", on the feed thread)
INSIDE_SPANS = ("split_parts", "window_close", "flush_rows", "sink_put",
                "sink_records", "sink_execute", "snapshot_publish",
                "publish_view", "publish_swap")
FEED_THREAD = "feed-prefetch"
CKPT_SPANS = ("ckpt_state", "ckpt_d2h", "ckpt_serialize", "ckpt_write",
              "ckpt_commit")
SPAN_ARGS = {
    "poll_wait": ("depth",), "apply": ("rows", "age_ms"),
    "fetch": ("rows", "partition"), "split_parts": ("parts",),
    "window_close": ("model", "slot", "rows"),
    "spread_decode": ("model", "rows"),
    "flush_rows": ("table", "rows"),
    "sink_put": ("sink", "table", "rows"),
    "sink_records": ("rows",), "sink_execute": ("rows",),
    "snapshot_publish": ("version", "flows_seen", "families", "reason",
                         "late_ms", "age_ms"),
    "publish_view": ("model", "rows", "bytes"),
    "publish_swap": ("ranges",),
    "lane_build": ("rows", "padded"), "h2d": ("bytes", "cols"),
    "step_dispatch": ("rows", "padded", "do_hh", "do_dd", "hh_unit",
                      "dd_unit", "dd_rows"),
    "detector_dispatch": ("rows", "padded", "dd_unit"),
    "wagg_wait": ("folded", "left"),
    "wagg_d2h": ("bytes",),
    "wagg_fold": ("groups", "inserted", "store_groups"),
    "wagg_rows": ("rows",), "wagg_state": ("windows", "groups"),
    "ckpt_state": ("hh_live_rows", "hh_slots"),
    "ckpt_d2h": ("bytes", "leaves"),
    "ckpt_serialize": ("raw_bytes", "npz_bytes", "members"),
    "decode": ("rows", "partition"), "flush": ("rows", "table"),
}
SCOPES = ("hh_chain_sort", "dst_sort", "hh_table_merge", "dense_scatter",
          "ddos_accumulate", "wagg_groupby", "hh_group_own")


def _models(spread=True, sub=WINDOW):
    models = make_models(sub, 100)
    if spread:
        models["portscan"] = scan_model(
            scan_config(depth=2, width=256, registers=16, capacity=32,
                        batch_size=BS), k=10)
    for name, m in models.items():
        if isinstance(m, WindowedHeavyHitter):
            m.name = name  # as cli._build_models names them
    return models


# the calls snapshot_and_commit makes while none of its spans is open:
# path arithmetic, makedirs, mkdtemp and the lag gauge, 111 of the
# 3,500-16,000 a checkpoint of this size makes. The lightest of the five
# parts, ckpt_state, brings 180 or more when it is moved out of its span
UNTILED_CALLS_MAX = 144


def _worker(tmp_path, snapshot_calls, consumer=None, models=None):
    """A worker whose every snapshot_and_commit call appends (start,
    end, thread, calls made outside any span) to ``snapshot_calls``.
    The calls are counted by a profile hook on the worker's thread, so
    that "the spans are the call" is judged by what ran between them
    and not by how long a loaded box took over it."""
    real = StreamWorker.snapshot_and_commit
    enter = trace_mod._Span.__enter__.__code__
    leave = trace_mod._Span.__exit__.__code__

    def timed(self):
        open_spans = untiled = 0

        def hook(frame, event, _arg):
            nonlocal open_spans, untiled
            if event == "call" and frame.f_code is enter:
                open_spans += 1
            elif event == "return" and frame.f_code is leave:
                open_spans -= 1
            elif event in ("call", "c_call") and not open_spans:
                untiled += 1

        before = sys.getprofile()
        t0 = time.time()
        sys.setprofile(hook)
        try:
            return real(self)
        finally:
            sys.setprofile(before)
            snapshot_calls.append((t0, time.time(),
                                   threading.current_thread().name,
                                   untiled))

    worker = StreamWorker(
        consumer or Consumer(_stream_to_bus(make_stream()), fixedlen=True),
        # the detector at 10 s: every batch of the stream spans three of
        # its sub-windows, two of which run its own program alone
        models or _models(sub=10),
        # sqlite as cli._make_sinks hands it over: behind the retry wrapper
        [CollectSink(), ResilientSink(SQLiteSink())],
        WorkerConfig(poll_max=BS, snapshot_every=2, host_assist="off",
                     checkpoint_path=str(tmp_path / "ckpt")))
    assert type(worker.fused) is FusedPipeline
    worker.snapshot_and_commit = timed.__get__(worker)
    # the query surface's publisher: its range ledger is a third sink; a
    # refresh that is always due, so that every batch publishes
    attach_worker(worker, refresh=1e-6)
    return worker


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """Spans of one worker run (8 batches, 3 window slots, a checkpoint
    every 2 batches and after each close) under ``always``, with what
    ``_worker`` notes of every snapshot_and_commit call."""
    calls: list = []
    TRACER.configure("always")
    try:
        _worker(tmp_path_factory.mktemp("spans"), calls).run(
            stop_when_idle=True)
        spans = TRACER.snapshot()
    finally:
        TRACER.configure("off")
    return spans, calls


def _inside(inner, outer) -> bool:
    return (outer[3] == inner[3] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


# ---- (a) the catalogue ---------------------------------------------------------


@pytest.mark.parametrize("name", sorted(WORKER_SPANS) + ["decode", "fetch"])
def test_span_occurs_with_its_args(traced_run, name):
    spans, _ = traced_run
    mine = [s for s in spans if s[0] == name]
    assert mine, f"no {name} span in {sorted({s[0] for s in spans})}"
    for s in mine:
        assert s[2] >= s[1]
        for key in SPAN_ARGS.get(name, ()):
            assert key in (s[5] or {}), (name, key, s[5])


@pytest.mark.parametrize(
    "name", sorted(n for n, parent in WORKER_SPANS.items() if parent))
def test_span_lies_inside_an_apply_on_the_worker_thread(traced_run, name):
    spans, _ = traced_run
    applies = [s for s in spans if s[0] == "apply"]
    assert len({s[3] for s in applies}) == 1
    final = max(a[2] for a in applies)  # finalize() runs after the loop
    mine = [s for s in spans if s[0] == name and s[1] < final]
    assert mine
    for s in mine:
        assert any(_inside(s, a) for a in applies), s


def test_poll_wait_lies_outside_apply(traced_run):
    spans, _ = traced_run
    applies = [s for s in spans if s[0] == "apply"]
    for s in spans:
        if s[0] == "poll_wait":
            assert s[3] == applies[0][3]
            assert not any(_inside(s, a) for a in applies)


@pytest.mark.parametrize("name", CKPT_SPANS)
def test_ckpt_spans_tile_their_snapshot_and_commit(traced_run, name):
    spans, calls = traced_run
    assert len(calls) >= 3
    for t0, t1, thread, untiled in calls:
        parts = sorted((s for s in spans if s[0] in CKPT_SPANS
                        and t0 <= s[1] and s[2] <= t1), key=lambda s: s[1])
        assert [s[0] for s in parts] == list(CKPT_SPANS)
        mine = next(s for s in parts if s[0] == name)
        assert mine[3] == thread  # the worker's own, which made the call
        i = parts.index(mine)
        if i:  # no overlap with the part before
            assert parts[i - 1][2] <= mine[1]
        # the five parts are the call: what runs between them is a few
        # statements, not work. Counted, not timed: the share of a ~40 ms
        # CPU checkpoint that one descheduling between two spans takes
        # says nothing of the program. What a count cannot see, one slow
        # call outside the spans, the chip's sum check does (PERF.md §5:
        # the five checkpoint_*_ms_p50 against checkpoint_ms_p50)
        assert 0 < untiled <= UNTILED_CALLS_MAX


def test_step_dispatch_counts_steps_and_fill(traced_run):
    spans, _ = traced_run
    steps = [s for s in spans if s[0] == "step_dispatch"]
    for s in steps:
        assert 0 < s[5]["rows"] <= s[5]["padded"] == BS
    applies = [s for s in spans if s[0] == "apply"]
    per_apply = [sum(_inside(s, a) for s in steps) for a in applies]
    assert all(n >= 1 for n in per_apply)
    # batch 5 of the stream holds late rows: its slot run is a mask of
    # its own and runs a padded step of its own; no other batch crosses
    # a slot, and a sub-window crossing runs no second step
    assert sorted(per_apply)[-2:] == [1, 2]
    assert sum(s[5]["rows"] for s in steps) == sum(
        a[5]["rows"] for a in applies)


def test_wagg_wait_says_whether_the_drain_lagged(traced_run):
    spans, calls = traced_run
    waits = [s for s in spans if s[0] == "wagg_wait"]
    assert {s[5]["left"] for s in waits} == {0, 1}
    assert all(s[5]["folded"] >= 1 for s in waits)
    # a drain inside a checkpoint is a reader's: it leaves nothing
    for s in waits:
        if any(t0 <= s[1] and s[2] <= t1 for t0, t1, *_ in calls):
            assert s[5]["left"] == 0, s
    # flows_5m is the only aggregator: one partial a device step, each
    # folded exactly once
    steps = [s for s in spans if s[0] == "step_dispatch"]
    assert sum(s[5]["folded"] for s in waits) == len(steps)


def test_ckpt_state_says_the_live_bound_of_the_last_step(traced_run):
    """PR 37: the largest family's live bound in the last device step
    that fed the tables, beside the slots a family has there; read where
    the checkpoint has already waited for that step."""
    spans, _ = traced_run
    states = [s for s in spans if s[0] == "ckpt_state"]
    assert len(states) >= 3
    for s in states:
        assert 0 < s[5]["hh_live_rows"] <= s[5]["hh_slots"] == BS
    # Zipf over 100 keys: a batch's groups are far fewer than its slots
    assert max(s[5]["hh_live_rows"] for s in states) < BS // 2


def test_checkpoint_bytes_are_what_was_written(traced_run):
    spans, _ = traced_run
    d2h = [s for s in spans if s[0] == "ckpt_d2h"]
    ser = [s for s in spans if s[0] == "ckpt_serialize"]
    assert all(s[5]["leaves"] > 0 and s[5]["bytes"] > 0 for s in d2h)
    # the device leaves are part of what is serialized (host window
    # stores are the rest)
    for a, b in zip(d2h, ser):
        assert 0 < a[5]["bytes"] <= b[5]["raw_bytes"]
        assert 0 < b[5]["npz_bytes"]


# ---- (b) the kernel scopes ------------------------------------------------------


def _own_family_models():
    """A family on its own sort: (dst_addr, dst_port) is neither the
    shared dst-keyed family nor a prefix of another's key."""
    return {"dst_services": WindowedHeavyHitter(
        HeavyHitterConfig(key_cols=("dst_addr", "dst_port"), batch_size=BS,
                          width=1 << 10, capacity=128), k=10)}


def _step_and_args(models):
    pipe = FusedPipeline(models)
    batch = FlowGenerator(ZipfProfile(n_keys=100, alpha=1.2),
                          seed=3).batch(BS - 7)
    padded, mask = batch.pad_to(BS)
    cols = {k: jax.numpy.asarray(v)
            for k, v in padded.device_columns(pipe._cols).items()}
    valid = jax.numpy.asarray(mask)

    def states():
        return (tuple(w.model.state for _, w in pipe._hh),
                tuple(w.model.totals for _, w in pipe._dense),
                tuple(d.state for _, d in pipe._ddos))

    return pipe, states, (cols, valid, valid, valid)


@pytest.fixture(scope="module")
def step_hlo():
    """The compiled step's text as the program hands it out, asked for
    AFTER the step has run: the executable JAX then holds for the step
    may come from a cache that is keyed without metadata."""
    out = {}
    for key, models in (("default", _models(spread=False)),
                        ("own", _own_family_models())):
        pipe, states, args = _step_and_args(models)
        pipe._step(states(), *args)
        out[key] = pipe.compiled_step_text()
    return out


@pytest.mark.parametrize("scope", SCOPES)
def test_compiled_step_names_the_scope(step_hlo, scope):
    import re

    hlo = step_hlo["own" if scope == "hh_group_own" else "default"]
    names = re.findall(r'op_name="([^"]*)"', hlo)
    assert any(f"/{scope}" in n for n in names), (
        f"no instruction of the compiled step carries {scope}")


def test_scopes_change_no_output_bit(monkeypatch):
    import contextlib

    def run(scoped: bool):
        fused_mod._cached_step.cache_clear()
        if not scoped:
            monkeypatch.setattr(jax, "named_scope",
                                lambda name: contextlib.nullcontext())
        try:
            pipe, states, args = _step_and_args(_models(spread=False))
            out = pipe._step(states(), *args)
            if not scoped:
                hlo = pipe._step.lower(states(), *args).as_text(
                    debug_info=True)
                assert "hh_chain_sort" not in hlo
            return jax.tree_util.tree_leaves(out)
        finally:
            monkeypatch.undo()
            fused_mod._cached_step.cache_clear()

    with_scopes, without = run(True), run(False)
    assert len(with_scopes) == len(without) > 10
    for a, b in zip(with_scopes, without):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


# ---- (c) the profiler's trace holds the program's spans -------------------------


@pytest.fixture(scope="module")
def profiled_names(tmp_path_factory):
    """Names of the host-plane events of a short jax.profiler session
    round a few worker batches, and the thread line each was on."""
    from jax.profiler import ProfileData

    tmp = tmp_path_factory.mktemp("prof")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    TRACER.configure("ring")
    try:
        worker = _worker(tmp, [])
        worker.run(max_batches=1)  # compile outside the session
        jax.profiler.start_trace(str(tmp / "trace"), profiler_options=opts)
        try:
            worker.run(stop_when_idle=True)
        finally:
            jax.profiler.stop_trace()
    finally:
        TRACER.configure("off")
    (path,) = glob.glob(os.path.join(str(tmp / "trace"), "plugins",
                                     "profile", "*", "*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    lines.setdefault(ev.name, set()).add(line.name)
    return lines


@pytest.mark.parametrize("name", sorted(set(WORKER_SPANS) - {"poll_wait"}))
def test_profiler_session_holds_the_span(profiled_names, name):
    assert name in profiled_names, sorted(profiled_names)
    # on the worker's thread line, with "apply"
    assert profiled_names[name] & profiled_names["apply"]


# ---- (d) off -----------------------------------------------------------------------


def test_off_records_nothing_and_enters_no_annotation(monkeypatch):
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace_mod, "_ANNOTATION", Annotation)
    tracer = TraceRecorder(mode="off")
    with tracer.span("quiet", chunk=1, rows=2) as args:
        args["bytes"] = 3
    assert tracer.snapshot() == [] and entered == []
    tracer.configure("ring")
    tracer.paused = True  # flowguard level >= 1
    with tracer.span("paused"):
        pass
    assert tracer.snapshot() == [] and entered == []
    tracer.paused = False
    with tracer.span("loud", rows=2) as args:
        args["bytes"] = 3
    assert entered == ["loud"]
    ((name, _t0, _t1, thread, _chunk, args),) = tracer.snapshot()
    assert (name, args) == ("loud", {"rows": 2, "bytes": 3})
    assert thread == threading.current_thread().name


def test_tracer_imports_no_jax():
    import subprocess
    import sys

    code = ("import sys; from flow_pipeline_tpu.obs.trace import TRACER\n"
            "TRACER.configure('ring')\n"
            "with TRACER.span('s'): pass\n"
            "assert len(TRACER.snapshot()) == 1\n"
            "assert 'jax' not in sys.modules, 'the tracer imported jax'\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   cwd=os.path.dirname(os.path.dirname(__file__)))


# ---- (f) inside the layers the hooks time from outside (ISSUE 35) ----------------


def _children(spans, parent, name):
    return sorted((s for s in spans if s[0] == name and _inside(s, parent)),
                  key=lambda s: s[1])


def _tiles(parts, whole) -> bool:
    """In order, none overlapping the next, all inside ``whole``."""
    return all(_inside(p, whole) for p in parts) and all(
        a[2] <= b[1] for a, b in zip(parts, parts[1:]))


@pytest.mark.parametrize("name", INSIDE_SPANS)
def test_inside_span_nests_directly_in_its_parent(traced_run, name):
    """Each new span lies in a span of the name the catalogue gives, on
    that span's thread, after the loop too (finalize's flush and publish
    run under no ``apply``)."""
    spans, _ = traced_run
    parent = WORKER_SPANS[name]
    parents = [s for s in spans if s[0] == parent]
    final = max(s[2] for s in spans if s[0] == "apply")
    mine = [s for s in spans if s[0] == name
            and (parent != "apply" or s[1] < final)]
    assert mine
    for s in mine:
        assert any(_inside(s, p) for p in parents), s


def test_no_program_span_takes_a_hooks_name(traced_run):
    """The hook and the span sit in one .xplane.pb and one owner chain:
    only ``decode``, which both had before ISSUE 35, is in both."""
    from benchmark.sut import HOOKS

    spans, _ = traced_run
    hooks = {hook[3] for hook in HOOKS}
    assert {"bus_fetch", "sink_write", "publish", "process"} <= hooks
    assert {s[0] for s in spans} & hooks == {"decode"}


def test_fetch_decode_and_apply_of_a_chunk_share_its_id(traced_run):
    spans, _ = traced_run
    fetched = {s[4]: s for s in spans if s[0] == "fetch" and s[5]["rows"]}
    decoded = {s[4]: s for s in spans if s[0] == "decode"}
    applies = [s for s in spans if s[0] == "apply"]
    assert len(applies) == 8 == len(decoded)
    for a in applies:
        f, d = fetched[a[4]], decoded[a[4]]
        assert f[3] == d[3] == FEED_THREAD != a[3]
        assert f[2] <= d[1] and d[2] <= a[1]  # fetch, decode, then apply
        assert f[5]["rows"] == d[5]["rows"] == a[5]["rows"]
        assert f[5]["partition"] == d[5]["partition"] == 0
        # the bus stamped the frames before the run: a batch is at least
        # as old at pick-up as its decode took
        assert a[5]["age_ms"] >= (d[2] - d[1]) * 1e3
    # a fetch that found nothing says so, and spent an id of its own
    empty = [s for s in spans if s[0] == "fetch" and not s[5]["rows"]]
    assert empty and not {s[4] for s in empty} & set(decoded)


def test_split_parts_counts_the_parts_of_a_poll(traced_run):
    spans, _ = traced_run
    cuts = [s for s in spans if s[0] == "split_parts"]
    applies = [s for s in spans if s[0] == "apply"]
    assert len(cuts) == len(applies)  # one a polled batch
    steps = [s for s in spans if s[0] == "step_dispatch"]
    alone = [s for s in spans if s[0] == "detector_dispatch"]
    builds = [s for s in spans if s[0] == "lane_build"]
    for cut, a in zip(cuts, applies):
        assert _inside(cut, a)
        mine = [s for s in steps if _inside(s, a)]
        # a batch of the stream spans three of the detector's
        # sub-windows; batch 5 holds late rows, two slots back, beside
        # them. A slot run is one step, which carries the run's newest
        # sub-window; each other part is the detector's program alone
        assert cut[5]["parts"] >= 3
        assert len(mine) + sum(_inside(s, a) for s in alone) \
            == cut[5]["parts"]
        assert len(mine) == (2 if cut[5]["parts"] == 4 else 1)
        # the lanes are built once a batch whatever the cut
        assert sum(_inside(s, a) for s in builds) == 1
        # every row rides one of the two for the detector, unless it is
        # late (batch 5's 25 rows: the step that carries them says so)
        late = sum(s[5]["rows"] for s in mine if not s[5]["do_dd"])
        assert (sum(s[5]["dd_rows"] for s in mine)
                + sum(s[5]["rows"] for s in alone if _inside(s, a))
                + late) == a[5]["rows"]
    assert sorted(s[5]["parts"] for s in cuts)[-2:] == [3, 4]


def test_a_tumbling_close_is_one_window_close_a_ranked_table(traced_run):
    spans, _ = traced_run
    closes = [s for s in spans if s[0] == "window_close"]
    ranked = {"top_talkers", "top_src_ips", "top_dst_ips", "top_src_ports",
              "portscan"}
    by_slot: dict = {}
    for s in closes:
        by_slot.setdefault(s[5]["slot"], set()).add(s[5]["model"])
        assert s[5]["rows"] >= 0
    # three slots: two rolls in the stream and the forced close at its end
    assert len(by_slot) == 3 and set(by_slot) == {6000, 6300, 6600}
    assert all(models == ranked for models in by_slot.values())
    assert not [s for s in spans if s[0] == "slide_close"]
    # a roll's extraction runs inside the pipeline's update, before the
    # batch's first device step
    steps = [s for s in spans if s[0] == "step_dispatch"]
    final = max(s[2] for s in spans if s[0] == "apply")
    for a in (s for s in spans if s[0] == "apply"):
        mine = [c for c in closes if _inside(c, a) and c[1] < final]
        if mine:
            first_step = min(s[1] for s in steps if _inside(s, a))
            assert min(c[2] for c in mine) <= first_step


def test_flush_rows_and_a_sink_put_a_sink_tile_the_flush(traced_run):
    spans, _ = traced_run
    flushes = [s for s in spans if s[0] == "flush"]
    assert flushes
    for f in flushes:
        (made,) = _children(spans, f, "flush_rows")
        puts = _children(spans, f, "sink_put")
        assert [p[5]["sink"] for p in puts] == [
            "CollectSink", "SQLiteSink", "RangeLedger"]
        assert _tiles([made, *puts], f)
        for part in (made, *puts):
            assert part[4] == f[4]  # the chunk that closed the window
            assert part[5]["table"] == f[5]["table"]
            assert part[5]["rows"] == f[5]["rows"]


def test_sink_records_and_sink_execute_tile_sqlites_sink_put(traced_run):
    spans, _ = traced_run
    puts = [s for s in spans if s[0] == "sink_put"]
    wrote = 0
    for put in puts:
        records = _children(spans, put, "sink_records")
        execute = _children(spans, put, "sink_execute")
        if put[5]["sink"] != "SQLiteSink":
            assert not records and not execute
            continue
        (records,) = records
        assert records[5]["rows"] == put[5]["rows"]
        if records[5]["rows"]:  # nothing to write: no statement
            (execute,) = execute
            assert execute[5]["rows"] == records[5]["rows"]
            assert _tiles([records, execute], put)
            wrote += 1
    assert wrote >= 3
    # every one of them is the sink's own, inside its sink_put
    for name in ("sink_records", "sink_execute"):
        for s in (s for s in spans if s[0] == name):
            assert any(_inside(s, p) for p in puts), s


def test_a_query_thread_records_no_sink_records(traced_run, tmp_path):
    """``rows_to_records`` is the query threads' too: the span is in the
    sink, so a reader's answer is not booked to the sinks' layer."""
    worker = _worker(tmp_path, [])
    worker.run(max_batches=2)
    TRACER.configure("always")
    try:
        server, out = ServeServer(worker.serve.store, port=0), []
        reader = threading.Thread(
            name="query-reader",
            target=lambda: out.append(server._respond("/query/topk?k=5",
                                                      None)))
        reader.start()
        reader.join()
        with TRACER.span("heard"):
            pass
        names = {s[0] for s in TRACER.snapshot()}
    finally:
        TRACER.configure("off")
    assert b"200 OK" in out[0] and b'"rows"' in out[0]
    assert names == {"heard"}


def test_views_and_the_swap_tile_the_snapshot_publish(traced_run):
    spans, _ = traced_run
    publishes = [s for s in spans if s[0] == "snapshot_publish"]
    ranked = ["top_talkers", "top_src_ips", "top_dst_ips", "top_src_ports",
              "portscan"]
    for p in publishes:
        views = _children(spans, p, "publish_view")
        (swap,) = _children(spans, p, "publish_swap")
        assert [v[5]["model"] for v in views] == ranked
        assert p[5]["families"] == len(ranked)
        assert _tiles([*views, swap], p)
        for v in views:
            assert v[5]["bytes"] > 0 and 0 <= v[5]["rows"] <= 50
        # a sketch family's capture is its count-min planes, the dense
        # table's is its rows alone
        by_model = {v[5]["model"]: v[5]["bytes"] for v in views}
        assert by_model["top_talkers"] > 10 * by_model["top_src_ports"]
    # the ledger's frozen slots grow with the closes
    assert [s[5]["ranges"] for s in spans if s[0] == "publish_swap"][-1] >= 2


def test_a_publish_says_why_how_late_and_how_old(traced_run):
    spans, _ = traced_run
    publishes = sorted((s for s in spans if s[0] == "snapshot_publish"),
                       key=lambda s: s[1])
    applies = [s for s in spans if s[0] == "apply"]
    # one a batch (a refresh of 1 us is always due) and finalize's
    assert len(publishes) == len(applies) + 1
    reasons = [p[5]["reason"] for p in publishes]
    assert reasons[0] == "first" and reasons[-1] == "forced"
    assert reasons.count("close") >= 2 and "refresh" in reasons
    versions = [p[5]["version"] for p in publishes]
    assert versions == list(range(1, len(publishes) + 1))
    for p, a in zip(publishes, applies):
        assert p[4] == a[4] and _inside(p, a)  # the batch's chunk
        assert p[5]["flows_seen"] > 0
        if p[5]["reason"] == "refresh":
            # asked once a batch, after the flush and the checkpoint
            assert 0 <= p[5]["late_ms"] < (p[1] - a[1]) * 1e3 + 60e3
        else:
            assert p[5]["late_ms"] == 0
        # older at the swap than its batch was at pick-up
        assert p[5]["age_ms"] > a[5]["age_ms"]


class _Unstamped(Consumer):
    """A transport that does not stamp its messages (Kafka)."""

    def poll(self, max_messages: int = 8192):
        batch = super().poll(max_messages)
        if batch is not None:
            batch.produced_at = 0.0
        return batch


def test_age_is_left_out_for_an_unstamped_transport(tmp_path):
    TRACER.configure("always")
    try:
        _worker(tmp_path, [], consumer=_Unstamped(
            _stream_to_bus(make_stream()), fixedlen=True)).run(
                stop_when_idle=True)
        spans = TRACER.snapshot()
    finally:
        TRACER.configure("off")
    for name in ("apply", "snapshot_publish"):
        mine = [s for s in spans if s[0] == name]
        assert len(mine) >= 8
        assert not [s for s in mine if "age_ms" in s[5]]
        assert all("rows" in s[5] or "reason" in s[5] for s in mine)


def _sliding_models():
    def hh(name, key_cols):
        return WindowedHeavyHitter(
            HeavyHitterConfig(key_cols=key_cols, batch_size=BS,
                              width=1 << 10, capacity=128),
            k=50, slide_seconds=100, slide_name=name)

    models = _models(spread=False)
    for name, key_cols in (("top_src_ips", ("src_addr",)),
                           ("top_dst_ips", ("dst_addr",))):
        models[name] = hh(name, key_cols)
    del models["top_talkers"], models["top_src_ports"]
    return models


def test_under_a_slide_the_close_is_a_slide_close(tmp_path):
    """``window_close`` is the tumbling branch's: under ``-window.slide``
    its twin records, and it does not."""
    TRACER.configure("always")
    try:
        _worker(tmp_path, [], models=_sliding_models()).run(
            stop_when_idle=True)
        spans = TRACER.snapshot()
    finally:
        TRACER.configure("off")
    names = {s[0] for s in spans}
    assert "slide_close" in names and "window_close" not in names
    # the rest of the catalogue is a tumbling run's
    assert {"fetch", "split_parts", "flush_rows", "sink_put",
            "sink_records", "snapshot_publish", "publish_view",
            "publish_swap"} <= names


def test_off_enters_no_annotation_for_any_inside_span(monkeypatch,
                                                      tmp_path):
    entered = []

    class Annotation:
        def __init__(self, name):
            entered.append(name)

        def __enter__(self):
            pass

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace_mod, "_ANNOTATION", Annotation)
    TRACER.configure("off")
    worker = _worker(tmp_path, [])
    worker.run(stop_when_idle=True)
    assert worker.batches_seen == 8 and worker.serve.store.current
    assert TRACER.snapshot() == [] and entered == []
    # the same run under "ring" enters one a span, the new ones among them
    TRACER.configure("ring")
    try:
        _worker(tmp_path / "ring", []).run(stop_when_idle=True)
        recorded = [s[0] for s in TRACER.snapshot()]
    finally:
        TRACER.configure("off")
    assert sorted(entered) == sorted(recorded)
    assert set(INSIDE_SPANS) | {"fetch"} <= set(entered)


# ---- (e) the ring knows whether it still holds a window ------------------------------


@pytest.mark.parametrize("recorded,whole", [(8, True), (9, False),
                                            (40, False)])
def test_ring_refuses_a_window_whose_start_was_overwritten(recorded, whole):
    tracer = TraceRecorder(capacity=8, mode="ring")
    for i in range(recorded):
        tracer.record("s", 100.0 + i, 100.5 + i, chunk=i)
    # the window opened at 100.25: span 0 ended inside it
    assert tracer.whole_since(100.25) is whole
    # a window that opened after everything overwritten had ended is whole
    oldest_end = tracer.snapshot()[0][2]
    assert tracer.whole_since(oldest_end) is True
    other = tracer.chrome_trace()["otherData"]
    assert other["dropped_spans"] == max(0, recorded - 8)
    assert other["oldest_span_start"] == tracer.snapshot()[0][1]


def test_default_ring_holds_a_window_at_four_times_the_span_rate():
    # 51 s x ~20 batches/s x ~15 spans a batch x 4
    assert TraceRecorder().capacity == RING_CAPACITY >= 51 * 20 * 15 * 4
