"""The program's own spans and kernel scopes (ISSUE 24).

(a) a FusedPipeline + StreamWorker run with a checkpoint records every
span of the catalogue (docs/OBSERVABILITY.md), nested where the
catalogue says; (b) the compiled fused step carries every kernel scope
name in its HLO metadata and computes the same bits with and without
them; (c) a jax.profiler session holds the program's spans as host-plane
events; (d) ``off`` records nothing and enters no annotation; (e) the
ring refuses to call a window whole once its start has been overwritten.
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
from flow_pipeline_tpu.transport import Consumer

from test_fused import BS, WINDOW, make_models, make_stream
from test_ingest import CollectSink, _stream_to_bus

# the catalogue: span -> the span it nests in on the worker thread
# (None: outside "apply")
WORKER_SPANS = {
    "poll_wait": None,
    "apply": None,
    "spread_fold": "apply",
    "lane_build": "apply",
    "h2d": "apply",
    "step_dispatch": "apply",
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
}
CKPT_SPANS = ("ckpt_state", "ckpt_d2h", "ckpt_serialize", "ckpt_write",
              "ckpt_commit")
SPAN_ARGS = {
    "poll_wait": ("depth",), "apply": ("rows",),
    "lane_build": ("rows", "padded"), "h2d": ("bytes", "cols"),
    "step_dispatch": ("rows", "padded", "do_hh", "do_dd"),
    "wagg_wait": ("folded", "left"),
    "wagg_d2h": ("bytes",),
    "wagg_fold": ("groups", "inserted", "store_groups"),
    "wagg_rows": ("rows",), "wagg_state": ("windows", "groups"),
    "ckpt_d2h": ("bytes", "leaves"),
    "ckpt_serialize": ("raw_bytes", "npz_bytes", "members"),
    "decode": ("rows", "partition"), "flush": ("rows", "table"),
}
SCOPES = ("hh_chain_sort", "dst_sort", "hh_table_merge", "dense_scatter",
          "ddos_accumulate", "wagg_groupby", "hh_group_own")


def _models(spread=True):
    models = make_models(WINDOW, 100)
    if spread:
        models["portscan"] = scan_model(
            scan_config(depth=2, width=256, registers=16, capacity=32,
                        batch_size=BS), k=10)
    return models


# the calls snapshot_and_commit makes while none of its spans is open:
# path arithmetic, makedirs, mkdtemp and the lag gauge, 111 of the
# 3,500-16,000 a checkpoint of this size makes. The lightest of the five
# parts, ckpt_state, brings 180 or more when it is moved out of its span
UNTILED_CALLS_MAX = 144


def _worker(tmp_path, snapshot_calls):
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
        Consumer(_stream_to_bus(make_stream()), fixedlen=True),
        _models(), [CollectSink()],
        WorkerConfig(poll_max=BS, snapshot_every=2, host_assist="off",
                     checkpoint_path=str(tmp_path / "ckpt")))
    assert type(worker.fused) is FusedPipeline
    worker.snapshot_and_commit = timed.__get__(worker)
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


@pytest.mark.parametrize("name", sorted(WORKER_SPANS) + ["decode"])
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
    for s in spans:
        if s[0] == name and s[1] < final:
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
    # batch 5 of the stream holds late rows: its slot group is split off
    # and runs a padded step of its own
    assert max(per_apply) >= 2
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
