"""Sliding windows on the device (``-window.slide``, engine/windowed.py).

W = 300 s, S = 30 s, K = 10: every ranked table's rows for the last W
seconds at every slide end, from a ring of K sub-window sketch states
folded by the monoid the four-chip close runs over its replicas
(ops/fold.py). Held here, at small size on the CPU, seeded:

- K = 1 is a tumbling window bit for bit;
- K = 10 is bit-equal to the fold of ten independent one-sub-window
  models and agrees with the plain reference (``exact_sliding`` below:
  exact integer sums by key over the flows of the last W seconds,
  ranked; it imports nothing of the program);
- a batch that straddles a slide, sub-windows with no flow, a jump of
  several sub-windows;
- ``FusedPipeline`` against the per-model path;
- a checkpoint's round trip (closed states written once, as members)
  and a restart mid-ring against the uninterrupted run;
- the flag's refusals;
- the shared fold against the body ``sharded_hh_merge`` had before it.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flow_pipeline_tpu.engine import (
    FusedPipeline,
    StreamWorker,
    WindowedHeavyHitter,
    WorkerConfig,
)
from flow_pipeline_tpu.engine.checkpoint import load_checkpoint
from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
from flow_pipeline_tpu.models import (
    DDoSConfig,
    DDoSDetector,
    DenseTopConfig,
    DenseTopKModel,
    HeavyHitterConfig,
    HeavyHitterModel,
    WindowAggConfig,
    WindowAggregator,
)
from flow_pipeline_tpu.models import heavy_hitter as hh
from flow_pipeline_tpu.obs.trace import TRACER
from flow_pipeline_tpu.ops import topk as topk_ops
from flow_pipeline_tpu.ops.fold import fold_planes, fold_tables
from flow_pipeline_tpu.schema.batch import FlowBatch
from flow_pipeline_tpu.transport import Consumer, InProcessBus

W, S, K = 300, 30, 10
BS = 512
T0 = 1_700_000_100  # slot-aligned: T0 % 300 == 0
HH_KEY = ("src_addr", "dst_port")
TABLES = ("top_pairs", "top_src_ports")


# ---- the plain reference ----------------------------------------------------


def exact_sliding(times, keys, nbytes, window=W, slide=S) -> dict:
    """{timeslot: [(key tuple, bytes)] ranked by bytes (ties by key)}:
    for every slide end e = (j + 1) * slide the stream has reached
    (every sub-window j from the first flow's to the last flow's), the
    exact sums by key over the flows with e - window <= t < e, under
    ``timeslot`` e - window; a window that holds no flow has no entry."""
    times = np.asarray(times, np.int64)
    keys = np.asarray(keys).reshape(len(times), -1)
    nbytes = np.asarray(nbytes, np.uint64)
    out = {}
    first, last = times.min() // slide, times.max() // slide
    for j in range(first, last + 1):
        end = (j + 1) * slide
        sel = (times >= end - window) & (times < end)
        if not sel.any():
            continue
        sums: dict = {}
        for key, b in zip(map(tuple, keys[sel].tolist()),
                          nbytes[sel].tolist()):
            sums[key] = sums.get(key, 0) + b
        out[end - window] = sorted(sums.items(),
                                   key=lambda kv: (-kv[1], kv[0]))
    return out


# ---- streams and models -----------------------------------------------------


def hh_config() -> HeavyHitterConfig:
    return HeavyHitterConfig(key_cols=HH_KEY, batch_size=BS,
                             width=1 << 10, capacity=128)


def windowed(name: str, slide: int):
    kw = {"slide_seconds": slide, "slide_name": name} if slide else {}
    if name == "top_pairs":
        return WindowedHeavyHitter(hh_config(), k=128, **kw)
    return WindowedHeavyHitter(
        DenseTopConfig(key_col="src_port", batch_size=BS), k=128,
        model_cls=DenseTopKModel, **kw)


def make_models(slide: int) -> dict:
    return {
        "flows_5m": WindowAggregator(WindowAggConfig(batch_size=BS)),
        **{name: windowed(name, slide) for name in TABLES},
        "ddos_alerts": DDoSDetector(DDoSConfig(
            n_buckets=1 << 10, sub_window_seconds=10, warmup_windows=0,
            batch_size=BS)),
    }


def make_stream(times_of_batch, seed: int = 7, n_keys: int = 60) -> list:
    """One batch of BS flows for each array of event times (or scalar
    start: the batch then spans 20 s from it)."""
    gen = FlowGenerator(ZipfProfile(n_keys=n_keys, alpha=1.2), seed=seed)
    batches = []
    for t in times_of_batch:
        b = gen.batch(BS)
        times = (t + np.arange(BS) % 20 if np.isscalar(t)
                 else np.asarray(t))
        b.columns["time_received"] = times.astype(np.uint64)
        batches.append(b)
    return batches


def steady_stream(n_batches: int = 32, step: int = 25) -> list:
    """Event time advances ``step`` s a batch from T0: batches straddle
    slides (20 s of rows from an offset that is no multiple of 30)."""
    return make_stream([T0 + i * step for i in range(n_batches)])


def drive(models: dict, batches: list, fused: bool = True) -> dict:
    if fused:
        pipe = FusedPipeline(models)
        for b in batches:
            pipe.update(b)
    else:
        for b in batches:
            for m in models.values():
                m.update(b)
    return models


def flushed(model) -> list:
    return model.flush(force=True)


def assert_same_rows(a: list, b: list):
    assert [int(w["timeslot"][0]) for w in a] == \
        [int(w["timeslot"][0]) for w in b]
    for wa, wb in zip(a, b):
        assert sorted(wa) == sorted(wb)
        for name in wa:
            np.testing.assert_array_equal(
                np.asarray(wa[name]), np.asarray(wb[name]),
                err_msg=f"column {name!r} of timeslot "
                        f"{int(wa['timeslot'][0])} diverged")


def columns(batches: list, *names):
    whole = FlowBatch.concat(batches)
    return [whole.columns[n] for n in names]


def hh_rows(window: dict) -> list:
    """A flushed top_pairs window as [(key tuple, bytes)] in rank order."""
    valid = np.asarray(window["valid"])
    src = np.asarray(window["src_addr"])[valid]
    port = np.asarray(window["dst_port"])[valid]
    nbytes = np.asarray(window["bytes"])[valid]
    return [((*map(int, a), int(p)), float(b))
            for a, p, b in zip(src, port, nbytes)]


# ---- K = 1 ------------------------------------------------------------------


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "per_model"])
def test_slide_equal_to_window_is_tumbling_bit_for_bit(fused):
    batches = steady_stream()
    tumbling = drive(make_models(0), batches, fused)
    sliding = drive(make_models(W), batches, fused)
    for name in TABLES:
        assert sliding[name].ring.k == 1
        assert_same_rows(flushed(tumbling[name]), flushed(sliding[name]))


# ---- K = 10 -----------------------------------------------------------------


def test_ring_rows_equal_the_fold_of_ten_one_sub_window_models():
    """The same monoid: ten independent models, each fed the flows of
    one sub-window, folded by the ring's own program, give the rows the
    ring emitted for the window that holds them."""
    batches = steady_stream()
    models = drive({name: windowed(name, S) for name in TABLES}, batches,
                   fused=False)
    whole = FlowBatch.concat(batches)
    subs = whole.columns["time_received"].astype(np.int64) // S * S
    for name in TABLES:
        got = {int(w["timeslot"][0]): w for w in flushed(models[name])}
        ring = models[name].ring
        # a full window well inside the stream
        end = int(np.unique(subs)[K + 3]) + S
        parts = []
        for sub in range(end - W, end, S):
            one = windowed(name, 0).model
            idx = np.flatnonzero(subs == sub)
            one.update(FlowBatch({k: v[idx]
                                  for k, v in whole.columns.items()}))
            parts.append(one.window_state())
        folded = ring._fold(tuple(parts))
        want = ring.model.top_from(folded, models[name].k)
        for col, values in want.items():
            np.testing.assert_array_equal(
                np.asarray(values), np.asarray(got[end - W][col]),
                err_msg=f"{name}: column {col!r}")


def test_ring_rows_agree_with_the_exact_reference():
    """Every slide end the stream reached has rows, and they are the
    reference's: the dense table exactly; the sketch table's bytes within
    1e-6 of exact (float32 planes over integer bytes far below 2^24, and
    60 keys in a table of 128: nothing is evicted or estimated)."""
    batches = steady_stream()
    models = drive(make_models(S), batches)
    t, src, port, sport, nbytes = columns(
        batches, "time_received", "src_addr", "dst_port", "src_port",
        "bytes")
    want_hh = exact_sliding(t, np.concatenate(
        [src.reshape(len(t), -1), port[:, None]], axis=1), nbytes)
    want_dense = exact_sliding(t, sport, nbytes)
    got_hh = {int(w["timeslot"][0]): w for w in flushed(models["top_pairs"])}
    got_dense = {int(w["timeslot"][0]): w
                 for w in flushed(models["top_src_ports"])}
    assert sorted(got_hh) == sorted(want_hh)
    assert sorted(got_dense) == sorted(want_dense)
    assert len(want_hh) > 2 * K  # windows that grow, full ones, the last
    for slot, ranked in want_hh.items():
        rows = dict(hh_rows(got_hh[slot]))
        assert set(rows) == {k for k, _b in ranked}
        for key, b in ranked:
            assert abs(rows[key] - b) <= 1e-6 * b
    for slot, ranked in want_dense.items():
        w = got_dense[slot]
        valid = np.asarray(w["valid"])
        got = list(zip(np.asarray(w["src_port"])[valid].tolist(),
                       np.asarray(w["bytes"])[valid].tolist()))
        assert dict(got) == {k[0]: b for k, b in ranked}  # exact, u64
        assert [b for _k, b in got] == [b for _k, b in ranked]


# ---- the lifecycle's corners ------------------------------------------------


def rows_by_slot(model) -> dict:
    return {int(w["timeslot"][0]): hh_rows(w) for w in flushed(model)}


def reference_rows(batches) -> dict:
    t, src, port, nbytes = columns(batches, "time_received", "src_addr",
                                   "dst_port", "bytes")
    return exact_sliding(t, np.concatenate(
        [src.reshape(len(t), -1), port[:, None]], axis=1), nbytes)


def assert_matches_reference(model, batches, got=None):
    got = rows_by_slot(model) if got is None else got
    want = reference_rows(batches)
    assert sorted(got) == sorted(want)
    for slot, ranked in want.items():
        assert dict(got[slot]) == {k: float(b) for k, b in ranked}


def test_a_batch_that_straddles_a_slide():
    """One batch whose rows lie either side of a slide end is cut there:
    the first sub-window closes with its rows alone."""
    times = np.where(np.arange(BS) < 200, T0 + 25, T0 + 31)
    batches = make_stream([times])
    model = drive({"top_pairs": windowed("top_pairs", S)}, batches)[
        "top_pairs"]
    assert model.current_slot == T0 + S and model.late_flows_dropped == 0
    assert [sub for sub, _s, _m in model.ring.closed] == [T0]
    assert_matches_reference(model, batches)


def test_sub_windows_with_no_flow_still_end_windows():
    """Flows in sub-windows 0 and 3 only: the slide ends of 1 and 2 are
    emitted from what the ring holds, and every window is right."""
    batches = make_stream([T0 + 2, T0 + 3 * S + 2])
    model = drive({"top_pairs": windowed("top_pairs", S)}, batches)[
        "top_pairs"]
    assert [(sub, s is None) for sub, s, _m in model.ring.closed] == \
        [(T0, False), (T0 + S, True), (T0 + 2 * S, True)]
    got = rows_by_slot(model)
    assert sorted(got) == [T0 + (j + 1) * S - W for j in range(4)]
    assert got[T0 + S - W] == got[T0 + 2 * S - W] == got[T0 + 3 * S - W]
    assert_matches_reference(model, batches, got)


def test_a_jump_of_more_sub_windows_than_the_ring_holds():
    """After a gap longer than the window nothing of before it is left:
    K windows end over the old flows, then the ring starts over."""
    batches = make_stream([T0 + 2, T0 + 14 * S + 2, T0 + 15 * S + 2])
    model = drive({"top_pairs": windowed("top_pairs", S)}, batches)[
        "top_pairs"]
    got = rows_by_slot(model)
    assert sorted(got) == sorted(
        [T0 + (j + 1) * S - W for j in range(K)]
        + [T0 + (j + 1) * S - W for j in (14, 15)])
    assert_matches_reference(model, batches, got)


def test_late_rows_are_dropped_and_counted_at_the_slide():
    batches = make_stream([T0 + 2 * S + 1, T0 + 1])  # second: a sub late
    model = drive({"top_pairs": windowed("top_pairs", S)}, batches)[
        "top_pairs"]
    assert model.late_flows_dropped == BS
    assert_matches_reference(model, batches[:1])


@pytest.mark.parametrize("case", ["steady", "gaps"])
def test_fused_pipeline_against_the_per_model_path(case):
    batches = (steady_stream() if case == "steady" else make_stream(
        [T0 + 2, T0 + 40, T0 + 4 * S + 5, T0 + 16 * S, T0 + 16 * S + 20]))
    fused = drive(make_models(S), batches, fused=True)
    serial = drive(make_models(S), batches, fused=False)
    for name in TABLES:
        assert fused[name].late_flows_dropped == \
            serial[name].late_flows_dropped
        assert_same_rows(flushed(fused[name]), flushed(serial[name]))
    rows_f, rows_s = (m["flows_5m"].flush(force=True)
                      for m in (fused, serial))
    for col in rows_f:
        np.testing.assert_array_equal(rows_f[col], rows_s[col])


def test_the_view_between_slides_is_the_ring_folded_with_the_open_state():
    """What a publish or a query reads: the kept fold of the closed
    states merged with the open one, once for each open state."""
    batches = steady_stream(12)
    model = drive({"top_pairs": windowed("top_pairs", S)}, batches)[
        "top_pairs"]
    ring, open_state = model.ring, model.model.window_state()
    want = ring.model.top_from(ring.fold(open_state), 128)
    TRACER.configure("always")
    try:
        got = model.top(128)
        again = model.top(128)
        folds = [s for s in TRACER.snapshot() if s[0] == "slide_fold"]
    finally:
        TRACER.configure("off")
    assert len(folds) == 1  # the closed states, once; then two a view
    assert model.window_start == model.current_slot + S - W
    for col in want:
        # same states, same monoid; the fold's association differs
        # (closed first), which integer-valued float32 sums cannot tell
        np.testing.assert_array_equal(np.asarray(want[col]),
                                      np.asarray(got[col]))
        np.testing.assert_array_equal(np.asarray(got[col]),
                                      np.asarray(again[col]))


def test_spans_of_a_slide():
    TRACER.configure("always")
    try:
        drive({"top_pairs": windowed("top_pairs", S)}, steady_stream(6))
        spans = TRACER.snapshot()
    finally:
        TRACER.configure("off")
    by_name = {}
    for name, _t0, _t1, _thread, _chunk, args in spans:
        by_name.setdefault(name, []).append(args or {})
    closes, folds = by_name["slide_close"], by_name["slide_fold"]
    assert len(closes) == len(folds) == len(by_name["ring_rotate"]) == 4
    assert [c["window_end"] for c in closes] == \
        [T0 + (j + 1) * S for j in range(4)]
    assert [c["states"] for c in closes] == [1, 2, 3, 4]
    assert all(c["rows"] > 0 for c in closes)
    assert all(f["model"] == "top_pairs" and f["bytes"] > 0 for f in folds)
    assert by_name["ring_rotate"][-1]["ring_bytes"] == \
        5 * folds[0]["bytes"] // (K + 1)


# ---- checkpoint -------------------------------------------------------------


class CollectSink:
    def __init__(self):
        self.rows: dict = {}

    def write(self, table, rows):
        self.rows.setdefault(table, []).append(rows)


def bus_of(batches):
    from flow_pipeline_tpu.schema import wire

    bus = InProcessBus()
    bus.create_topic("flows", 1)
    for b in batches:
        for frame in wire.iter_raw_frames(b.to_wire()):
            bus.produce("flows", frame)
    return bus


def worker_on(bus, path, sink):
    return StreamWorker(
        Consumer(bus, fixedlen=True), make_models(S), [sink],
        WorkerConfig(poll_max=BS, snapshot_every=4, checkpoint_path=path,
                     host_assist="off"))


def table_rows(sink, name) -> dict:
    """{timeslot: the last rows written for it}."""
    return {int(w["timeslot"][0]): w for w in sink.rows.get(name, [])}


def test_closed_states_are_written_once_and_restore(tmp_path):
    """A checkpoint names the ring's closed states as members; each is
    written by the first checkpoint after its slide and by none after;
    a member that has left the ring is removed once a checkpoint that
    no longer names it is in place; the whole ring comes back."""
    path = str(tmp_path / "ckpt")
    worker = worker_on(bus_of(steady_stream(24)), path, CollectSink())
    assert isinstance(worker.fused, FusedPipeline)
    TRACER.configure("always")
    try:
        for _ in range(24):
            worker.run_once()
        spans = TRACER.snapshot()
    finally:
        TRACER.configure("off")
    written = [(s[5]["sub"]) for s in spans if s[0] == "ckpt_member"]
    model = worker.models["top_pairs"]
    subs = [sub for sub, _s, _m in model.ring.closed]
    assert len(subs) == K - 1
    # both tables write each closed sub-window once
    assert sorted(written) == sorted(
        2 * list(range(T0, subs[-1] + S, S)))
    files = sorted(os.listdir(path + ".members"))
    assert files == sorted(f"{name}.{sub}.npz"
                           for name in TABLES for sub in subs)
    raw = [s[5]["raw_bytes"] for s in spans if s[0] == "ckpt_serialize"]
    assert max(raw) < 1.5 * min(raw)  # the open state alone, ring or not
    snap = load_checkpoint(path)
    fresh = worker_on(bus_of([]), path, CollectSink())
    assert fresh.restore()
    for name in TABLES:
        a, b = worker.models[name], fresh.models[name]
        assert snap["models"][name]["ring"]["subs"] == subs
        assert [sub for sub, _s, _m in b.ring.closed] == subs
        assert b.ring.last_sub == a.ring.last_sub
        assert b.current_slot == a.current_slot
        assert all(m.written for _sub, _s, m in b.ring.closed)
        for (_, sa, _m), (_, sb, _n) in zip(a.ring.closed, b.ring.closed):
            for xa, xb in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
                np.testing.assert_array_equal(np.asarray(xa),
                                              np.asarray(xb))


def test_restart_mid_ring_equals_the_uninterrupted_run(tmp_path):
    batches = steady_stream(28)
    whole_sink = CollectSink()
    whole = worker_on(bus_of(batches), str(tmp_path / "a"), whole_sink)
    whole.run(stop_when_idle=True)

    bus, path = bus_of(batches), str(tmp_path / "b")
    first_sink, second_sink = CollectSink(), CollectSink()
    first = worker_on(bus, path, first_sink)
    for _ in range(13):  # a slide and its checkpoint behind, mid-ring
        first.run_once()
    first.consumer.stop()  # the process dies here: no finalize
    second = worker_on(bus, path, second_sink)
    assert second.restore()
    assert 0 < second.flows_seen <= 13 * BS
    second.run(stop_when_idle=True)
    for name in TABLES:
        want = table_rows(whole_sink, name)
        got = {**table_rows(first_sink, name),
               **table_rows(second_sink, name)}
        assert sorted(got) == sorted(want)
        for slot, w in want.items():
            for col in w:
                np.testing.assert_array_equal(
                    np.asarray(w[col]), np.asarray(got[slot][col]),
                    err_msg=f"{name} timeslot {slot} column {col!r}")


def test_a_tumbling_worker_drops_a_checkpointed_ring(tmp_path, caplog):
    path = str(tmp_path / "ckpt")
    worker = worker_on(bus_of(steady_stream(8)), path, CollectSink())
    for _ in range(8):
        worker.run_once()
    worker.consumer.stop()
    tumbling = StreamWorker(
        Consumer(bus_of([]), fixedlen=True), make_models(0), [],
        WorkerConfig(poll_max=BS, checkpoint_path=path, host_assist="off"))
    assert tumbling.restore()
    assert tumbling.models["top_pairs"].ring is None


# ---- the flag ---------------------------------------------------------------


def _vals(*argv):
    from flow_pipeline_tpu import cli
    from flow_pipeline_tpu.utils.flags import FlagSet

    fs = cli._processor_flags(cli._common_flags(FlagSet("processor")))
    return fs.parse(["-processor.batch", "512", *argv])


def test_the_flag_builds_rings_of_ten_on_the_fused_path():
    from flow_pipeline_tpu import cli

    models = cli._build_models(_vals("-window.slide", "30"))
    ranked = [m for m in models.values()
              if isinstance(m, WindowedHeavyHitter)]
    assert len(ranked) == 5
    assert all(m.ring.k == K and m.slot_seconds == S
               and m.window_seconds == W for m in ranked)
    assert {m.ring.name for m in ranked} == {
        "top_talkers", "top_src_ips", "top_dst_ips", "top_src_ports",
        "top_dst_ports"}
    assert FusedPipeline.supported(models)
    tumbling = cli._build_models(_vals())
    assert all(m.ring is None and m.slot_seconds == W
               for m in tumbling.values()
               if isinstance(m, WindowedHeavyHitter))


@pytest.mark.parametrize("argv,match", [
    (("-window.slide", "45"), "must divide"),
    (("-window.slide", "-30"), "must divide"),
    (("-window.slide", "30", "-processor.mesh", "4"), "-processor.mesh"),
    (("-window.slide", "30", "-sketch.backend", "host"),
     "-sketch.backend host"),
    (("-window.slide", "30", "-spread.enabled"), "-spread.enabled"),
    (("-window.slide", "30", "-mesh.role", "member"), "-mesh.role"),
    (("-window.slide", "30", "-hh.sketch", "invertible"), "hh_sketch"),
], ids=["no_divisor", "negative", "mesh", "host_backend", "spread",
        "mesh_role", "invertible"])
def test_the_flag_is_refused_loudly(argv, match):
    from flow_pipeline_tpu import cli

    with pytest.raises(ValueError, match=match):
        cli._build_models(_vals(*argv))


def test_a_sharded_model_cannot_hold_a_ring():
    from flow_pipeline_tpu.parallel import ShardedDenseTopK, make_mesh

    with pytest.raises(ValueError, match="no single-chip state"):
        WindowedHeavyHitter(
            DenseTopConfig(key_col="src_port", batch_size=BS), k=10,
            model_cls=ShardedDenseTopK, slide_seconds=S,
            mesh=make_mesh(2), name="ports")


# ---- the shared fold --------------------------------------------------------


def _old_merge_body(cms, tk, tv):
    """``sharded_hh_merge``'s body as it stood before ops/fold.py, with
    the psum written as the sum it is."""
    merged = jnp.sum(cms, axis=0)
    mk, mv = tk[0], tv[0]
    for d in range(1, tk.shape[0]):
        cand_valid = jnp.ones(tk[d].shape[0], bool)
        mk, mv = topk_ops.topk_merge(mk, mv, tk[d], tv[d], cand_valid)
    return merged, mk, mv


@pytest.mark.parametrize("n", [2, 4, 10])
def test_shared_fold_is_the_mesh_merges_old_body_bit_for_bit(n, rng):
    c, w, p = 64, 5, 3
    cms = rng.random((n, p, 4, 256), np.float32) * 1e6
    keys = rng.integers(0, 40, (n, c, w)).astype(np.uint32)
    keys[:, c // 2:, :] = np.uint32(0xFFFFFFFF)  # half-empty tables
    for d in range(n):  # a table's keys are unique
        keys[d, :c // 2, 0] = rng.permutation(200)[:c // 2]
    vals = (rng.random((n, c, p), np.float32) * 1e5).astype(np.float32)
    vals[:, c // 2:, :] = 0.0
    want = jax.jit(_old_merge_body)(cms, keys, vals)
    got = jax.jit(lambda a, b, c_: (fold_planes(a), *fold_tables(b, c_)))(
        cms, keys, vals)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the ring's program over the same states, unstacked
    program = hh.hh_fold_program("slide_fold_test", n)
    states = tuple(hh.HHState(jnp.asarray(cms[d]), jnp.asarray(keys[d]),
                              jnp.asarray(vals[d])) for d in range(n))
    ring = program(states)
    for a, b in zip(want, ring):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dense_fold_is_the_mesh_merges_sum(rng):
    from flow_pipeline_tpu.models.dense_top import dense_fold_program

    totals = rng.integers(0, 1 << 16, (4, 1 << 10, 3, 2)).astype(np.int32)
    got = dense_fold_program("slide_fold_test", 4)(
        tuple(jnp.asarray(t) for t in totals))
    np.testing.assert_array_equal(np.asarray(got), totals.sum(axis=0))


def test_only_table_sketches_on_one_chip_hold_a_ring():
    with pytest.raises(ValueError, match="hh_sketch"):
        HeavyHitterModel(HeavyHitterConfig(
            hh_sketch="invertible")).fold_program("x", 2)


# ---- the benchmark's copy of the reference (benchmark/tables) ---------------


def test_benchmark_kind_windows_are_running_sums_of_k_sub_windows(rng):
    from benchmark.tables import ranked_bytes_sliding as kind

    n, subs = 50, {}
    for j in (0, 2, 13, 14):  # gaps shorter and longer than K
        subs[T0 + j * S] = (rng.integers(0, 1500, n).astype(np.uint64),
                            rng.integers(0, 3, n).astype(np.uint64))
    got = {slot: (b.copy(), c.copy())
           for slot, b, c in kind._windows(subs, W, S)}
    for j in range(0, 15):
        end = T0 + (j + 1) * S
        inside = [s for s in subs if end - W <= s < end]
        if not inside or not sum(subs[s][1] for s in inside).any():
            assert end - W not in got
            continue
        np.testing.assert_array_equal(
            got[end - W][0], sum(subs[s][0] for s in inside))
        np.testing.assert_array_equal(
            got[end - W][1], sum(subs[s][1] for s in inside))
    assert T0 + 13 * S - W not in got  # sub-windows 3..12 saw no flow


def test_benchmark_kind_top_is_the_stable_sort_it_stands_for(rng):
    from benchmark.tables import ranked_bytes_sliding as kind

    tot = rng.integers(0, 40, 3 * kind.KEEP).astype(np.float64)  # ties
    live = np.flatnonzero(rng.random(len(tot)) < 0.9)
    want = live[np.argsort(-tot[live], kind="stable")[:kind.KEEP]]
    np.testing.assert_array_equal(kind._top(tot, live), want)
    few = live[:100]
    np.testing.assert_array_equal(
        kind._top(tot, few), few[np.argsort(-tot[few], kind="stable")])


def test_benchmark_kind_counts_missing_and_stray_slide_ends():
    from benchmark.tables import ranked_bytes_sliding as kind

    entry = {"top_n": 2, "limit": 1e-5}
    wanted = {10: {(1,): 100, (2,): 50}, 40: {(1,): 70}}
    exact = {10: [((1,), 100), ((2,), 50)], 40: [((1,), 70)]}
    assert kind.compare(entry, wanted, exact, 0) == {
        "topk_bytes_max_rel_err": (0.0, 1e-5),
        "slide_windows_missing": (0, 0)}
    found = kind.compare(entry, wanted, {10: exact[10], 70: exact[40]}, 0)
    assert found["slide_windows_missing"] == (2, 0)
    assert found["topk_bytes_max_rel_err"][0] == 1.0


def test_fold_roofline_bytes_are_k_states_read_and_one_written():
    """From shapes alone, and equal to what the ring's states measure."""
    from benchmark import slide_roofline

    flags = ["-processor.batch", "512", "-sketch.width", "1024",
             "-sketch.capacity", "128", "-window.slide", "30"]
    config = {"processor_flags": flags}
    from flow_pipeline_tpu import cli

    models = cli._build_models(_vals(*flags[2:]))
    measured = sum(m.ring.state_bytes for m in models.values()
                   if isinstance(m, WindowedHeavyHitter))
    assert slide_roofline.fold_bytes(config) == (K + 1) * measured
    assert slide_roofline.fold_bytes(
        {"processor_flags": flags[:-2]}) == 2 * measured  # K = 1


def test_benchmark_kind_finds_sub_windows_by_bisection():
    """Event time is monotone in the flow index, with the warm-up's jump
    in it: the edges found by bisection are where the sub-window of
    consecutive flows changes."""
    from benchmark import manifest
    from benchmark.tables import ranked_bytes_sliding as kind

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    stream = manifest.load_stream(root, ["benchmark"], {
        "event_rate": 7, "slot_seconds": 300})  # no kind: zipf-ranks
    spec = stream.spec(1, 100, 95)
    n = 5000
    sub = spec.event_ts(np.arange(n)).astype(np.int64) // S
    want = [0, *(np.flatnonzero(sub[1:] != sub[:-1]) + 1).tolist(), n]
    got = [0]
    while got[-1] < n:
        got.append(kind._first_flow_at(
            spec, (kind._ts(spec, got[-1]) // S + 1) * S, got[-1], n))
    assert got == want and len(want) > 20
