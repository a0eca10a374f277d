"""Fused-pipeline equivalence (engine.fused vs the per-model path).

The fused step shares one master sort across every prefix-keyed model and
one dst-keyed sort between the top-dst sketch and the DDoS accumulate; it
must be OUTPUT-IDENTICAL to the serial per-model path — same flows_5m
rows, same top-K tables, same DDoS alerts, same late-row drops. Window
lifecycles are driven host-side exactly like the unfused wrappers, so the
comparison covers slot rolls and late data too.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from flow_pipeline_tpu.engine import (
    FusedPipeline,
    StreamWorker,
    WindowedHeavyHitter,
    WorkerConfig,
)
from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
from flow_pipeline_tpu.models import (
    DDoSConfig,
    DDoSDetector,
    DenseTopConfig,
    DenseTopKModel,
    HeavyHitterConfig,
    WindowAggConfig,
    WindowAggregator,
)
from flow_pipeline_tpu.ops.segment import (
    presorted_groupby_float,
    sort_groupby_float,
    sort_rows_float,
)
from flow_pipeline_tpu.transport import Consumer, InProcessBus

WINDOW = 300
BS = 512


def make_models(sub_seconds: int, n_keys: int, width: int = 1 << 10):
    """The cli's default model family at test scale (cli._build_models)."""
    def hh_cfg(key_cols):
        return HeavyHitterConfig(key_cols=key_cols, batch_size=BS,
                                 width=width, capacity=128)

    return {
        "flows_5m": WindowAggregator(WindowAggConfig(batch_size=BS)),
        "top_talkers": WindowedHeavyHitter(
            hh_cfg(("src_addr", "dst_addr", "src_port", "dst_port",
                    "proto")), k=50),
        "top_src_ips": WindowedHeavyHitter(hh_cfg(("src_addr",)), k=50),
        "top_dst_ips": WindowedHeavyHitter(hh_cfg(("dst_addr",)), k=50),
        "top_src_ports": WindowedHeavyHitter(
            DenseTopConfig(key_col="src_port", batch_size=BS), k=50,
            model_cls=DenseTopKModel),
        "ddos_alerts": DDoSDetector(DDoSConfig(
            n_buckets=1 << 10, sub_window_seconds=sub_seconds,
            warmup_windows=0, batch_size=BS)),
    }


def make_stream(n_keys: int = 100):
    """8 batches crossing 3 window slots, with late rows in batch 5."""
    gen = FlowGenerator(ZipfProfile(n_keys=n_keys, alpha=1.2), seed=7)
    t0 = 6000  # slot-aligned (6000 % 300 == 0)
    batches = []
    for i in range(8):
        b = gen.batch(BS)
        times = t0 + i * 90 + (np.arange(BS) % 30)
        if i == 5:
            times[:25] = t0  # two slots behind current by then: late
        b.columns["time_received"] = times.astype(np.uint64)
        batches.append(b)
    return batches


def drive_fused(models, batches):
    pipe = FusedPipeline(models)
    for b in batches:
        pipe.update(b)
    return models


def drive_serial(models, batches):
    for b in batches:
        for m in models.values():
            m.update(b)
    return models


def canon_rows(rows: dict) -> list[tuple]:
    """Columnar rows dict -> sorted list of per-row tuples."""
    names = sorted(rows)
    cols = [np.asarray(rows[n]).reshape(len(rows[names[0]]), -1)
            for n in names]
    return sorted(tuple(x for c in cols for x in c[i]) for i in
                  range(len(cols[0])))


def assert_same_windows(a: list[dict], b: list[dict], keys=None):
    assert len(a) == len(b)
    for wa, wb in zip(a, b):
        names = keys or sorted(set(wa) | set(wb))
        for name in names:
            np.testing.assert_array_equal(
                np.asarray(wa[name]), np.asarray(wb[name]),
                err_msg=f"window column {name!r} diverged")


def test_prefix_groupby_matches_direct(rng):
    """Grouping presorted rows by a key PREFIX == sorting by that prefix
    directly (integer-valued floats: order-independent sums)."""
    keys = rng.integers(0, 5, size=(64, 3)).astype(np.uint32)
    vals = rng.integers(0, 100, size=(64, 2)).astype(np.float32)
    valid = rng.random(64) < 0.8
    sk, sv, sc = sort_rows_float(jnp.asarray(keys), jnp.asarray(vals),
                                 jnp.asarray(valid))
    for width in (1, 2, 3):
        got = presorted_groupby_float(sk, sv, sc, width)
        want = sort_groupby_float(jnp.asarray(keys[:, :width]),
                                  jnp.asarray(vals), jnp.asarray(valid))
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


class TestFusedEquivalence:
    def test_aligned_cadence_bit_exact(self):
        """DDoS cadence == window: identical chunking everywhere, so every
        output must match bit-for-bit (CMS estimates included)."""
        batches = make_stream()
        fused = drive_fused(make_models(WINDOW, 100), batches)
        serial = drive_serial(make_models(WINDOW, 100), batches)

        assert canon_rows(fused["flows_5m"].flush(True)) == \
            canon_rows(serial["flows_5m"].flush(True))
        for name in ("top_talkers", "top_src_ips", "top_dst_ips",
                     "top_src_ports"):
            assert_same_windows(fused[name].flush(True),
                                serial[name].flush(True))
            assert fused[name].late_flows_dropped == \
                serial[name].late_flows_dropped
        fa, sa = fused["ddos_alerts"], serial["ddos_alerts"]
        assert fa.late_flows_dropped == sa.late_flows_dropped
        assert len(fa.alerts) == len(sa.alerts)
        for x, y in zip(fa.alerts, sa.alerts):
            assert x.keys() == y.keys()
            for k in x:
                np.testing.assert_array_equal(np.asarray(x[k]),
                                              np.asarray(y[k]))

    @pytest.mark.parametrize("sub, width", [
        (30, 1 << 10), (10, 1 << 10), (10, 1 << 7)],
        ids=["sub30", "sub10", "sub10-narrow-cms"])
    def test_finer_ddos_cadence(self, sub, width):
        """DDoS sub-windows finer than the sketch window. A family is
        cut at its own unit only (engine/lifecycle.py: runs), so the
        tables take a batch that crosses sub-windows in ONE conservative
        update, as the per-model path does, and every output matches on
        every column, CMS estimates included, as in the aligned case.
        A batch of make_stream spans 30 s: one sub-window of 30, three of
        10. At width 128 the count-min rows collide, so tables cut at
        the detector's boundary (two updates where the per-model path
        makes one) would estimate otherwise."""
        batches = make_stream(n_keys=100)  # 100 < capacity 128: no eviction
        fused = drive_fused(make_models(sub, 100, width), batches)
        serial = drive_serial(make_models(sub, 100, width), batches)

        assert canon_rows(fused["flows_5m"].flush(True)) == \
            canon_rows(serial["flows_5m"].flush(True))
        for name in ("top_talkers", "top_src_ips", "top_dst_ips",
                     "top_src_ports"):
            assert_same_windows(fused[name].flush(True),
                                serial[name].flush(True))
            assert fused[name].late_flows_dropped == \
                serial[name].late_flows_dropped
        fa, sa = fused["ddos_alerts"], serial["ddos_alerts"]
        assert fa.late_flows_dropped == sa.late_flows_dropped
        assert fa.folds == sa.folds
        assert len(fa.alerts) == len(sa.alerts)
        for x, y in zip(fa.alerts, sa.alerts):
            for k in x:
                np.testing.assert_array_equal(np.asarray(x[k]),
                                              np.asarray(y[k]))
        for xa, xb in zip(fa.state, sa.state):
            np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))

    def test_mixed_scale_col_dst_families_demoted(self):
        """Two dst-keyed sketch families with DIFFERENT scale_col: the
        shared B path scales planes by the first B config's rate, so the
        second family must be demoted to its own groupby — outputs must
        match the serial path for both (ADVICE r4)."""
        def models():
            return {
                "top_dst_ips": WindowedHeavyHitter(HeavyHitterConfig(
                    key_cols=("dst_addr",), batch_size=BS, width=1 << 10,
                    capacity=128), k=50),
                "top_dst_ips_raw": WindowedHeavyHitter(HeavyHitterConfig(
                    key_cols=("dst_addr",), batch_size=BS, width=1 << 10,
                    capacity=128, scale_col=None), k=50),
            }

        batches = make_stream()
        # vary the rate so a wrong scaling actually changes sums
        for i, b in enumerate(batches):
            b.columns["sampling_rate"] = np.full(BS, 1 + i % 3, np.uint64)
        fused = drive_fused(models(), batches)
        serial = drive_serial(models(), batches)
        for name in ("top_dst_ips", "top_dst_ips_raw"):
            assert_same_windows(fused[name].flush(True),
                                serial[name].flush(True))

    def test_unsupported_model_set_falls_back(self):
        class Opaque:
            def update(self, batch):
                pass

        assert not FusedPipeline.supported({"x": Opaque()})
        worker = StreamWorker(None, {"x": Opaque()},
                              config=WorkerConfig(fused=True))
        assert worker.fused is None


def _dispatches(pipe, batch) -> dict:
    """{span name: [args]} of what one poll dispatched."""
    from flow_pipeline_tpu.obs.trace import TRACER

    TRACER.configure("always")
    try:
        pipe.update(batch)
        spans = TRACER.snapshot()
    finally:
        TRACER.configure("off")
    out = {"step_dispatch": [], "detector_dispatch": [], "split_parts": [],
           "lane_build": [], "h2d": []}
    for name, *_rest, args in spans:
        if name in out:
            out[name].append(args)
    return out


def _poll_at(gen, times):
    b = gen.batch(len(times))
    b.columns["time_received"] = np.asarray(times, np.uint64)
    return b


@pytest.mark.parametrize("crossing, steps, detectors", [
    ("none", 1, 0), ("sub", 1, 1), ("two-subs", 1, 2), ("slot", 2, 0),
    ("slot-and-sub", 2, 1)])
def test_a_run_is_a_dispatch(crossing, steps, detectors):
    """What a cut poll runs (ISSUE 40): one fused step a slot run, which
    carries the run's rows for flows_5m, the tables and the ports and
    the rows of the run's newest sub-window for the detector, and the
    detector's own program alone for each older sub-window of the run.
    Rows stay where they are: the lanes are built and placed once."""
    gen = FlowGenerator(ZipfProfile(n_keys=100, alpha=1.2), seed=3)
    t0 = 6000  # slot- and sub-aligned
    pipe = FusedPipeline(make_models(10, 100))
    pipe.update(_poll_at(gen, np.full(BS, t0 + 275)))
    n = np.arange(BS)
    times = {
        "none": np.full(BS, t0 + 276),
        # 100 rows of the sub-window that is open, the rest of the next
        "sub": np.where(n < 100, t0 + 279, t0 + 281),
        "two-subs": np.where(n < 100, t0 + 279,
                             np.where(n < 300, t0 + 285, t0 + 291)),
        # the next slot begins a sub-window too
        "slot": np.where(n < 100, t0 + 279, t0 + 301),
        "slot-and-sub": np.where(n < 100, t0 + 279,
                                 np.where(n < 300, t0 + 301, t0 + 311)),
    }[crossing]
    # rows in no order: a run is a mask, not a range
    times = np.random.default_rng(5).permutation(times)
    got = _dispatches(pipe, _poll_at(gen, times))
    assert len(got["step_dispatch"]) == steps
    assert len(got["detector_dispatch"]) == detectors
    assert len(got["lane_build"]) == len(got["h2d"]) == 1
    (cut,) = got["split_parts"]
    assert cut["parts"] == {"none": 1, "sub": 2, "two-subs": 3, "slot": 2,
                            "slot-and-sub": 3}[crossing]
    # every row rides exactly one step and exactly one detector program
    assert sum(s["rows"] for s in got["step_dispatch"]) == BS
    assert (sum(s["dd_rows"] for s in got["step_dispatch"])
            + sum(d["rows"] for d in got["detector_dispatch"])) == BS
    for s in got["step_dispatch"]:
        assert s["padded"] == BS and s["do_hh"] and s["do_dd"]
        assert (s["hh_unit"], s["dd_unit"]) == ("open", "open")
    for d in got["detector_dispatch"]:
        assert d["padded"] == BS and d["dd_unit"] == "open"
    if crossing == "sub":
        assert got["step_dispatch"][0]["dd_rows"] == BS - 100
        assert got["detector_dispatch"][0]["rows"] == 100


def test_the_detectors_program_is_compiled_in_the_first_batch():
    """Nothing compiles at the first crossing, which may come inside a
    measured window (compiles_in_window): the pipeline's first batch has
    already run ddos_accumulate once, on a scratch state."""
    import jax

    from flow_pipeline_tpu.models.ddos import ddos_accumulate

    gen = FlowGenerator(ZipfProfile(n_keys=100, alpha=1.2), seed=4)
    models = make_models(10, 100)
    # a batch size no other test compiles the detector's program at
    for m in models.values():
        m.config = type(m.config)(**{**m.config.__dict__,
                                     "batch_size": BS - 128})
    bs = BS - 128
    pipe = FusedPipeline(models)
    before = ddos_accumulate._cache_size()
    pipe.update(_poll_at(gen, np.full(bs, 6000)))
    assert ddos_accumulate._cache_size() == before + 1
    # a roll in a poll of its own: the close's program is the warm-up's
    pipe.update(_poll_at(gen, np.full(bs, 6011)))
    state = [np.asarray(x) for x in models["ddos_alerts"].state]
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _d, **_kw: compiles.append(name)
        if "compil" in name else None)
    try:
        pipe.update(_poll_at(gen, np.where(np.arange(bs) < 50, 6019, 6021)))
    finally:
        jax.monitoring.clear_event_listeners()
    assert ddos_accumulate._cache_size() == before + 1
    assert not [c for c in compiles if "backend_compile" in c], compiles
    # the warm-up touched no state of the detector's
    assert any((np.asarray(a) != b).any()
               for a, b in zip(models["ddos_alerts"].state, state))


def test_worker_fused_vs_serial_sink_rows():
    """Integration: the same stream through two workers (fused on/off)
    lands identical flows_5m rows in the sink."""
    class CollectSink:
        def __init__(self):
            self.rows: dict[str, list] = {}

        def write(self, table, rows):
            self.rows.setdefault(table, []).append(rows)

    out = {}
    for fused in (True, False):
        from flow_pipeline_tpu.schema import wire

        bus = InProcessBus()
        bus.create_topic("flows", 1)
        for b in make_stream():
            for frame in wire.iter_raw_frames(b.to_wire()):
                bus.produce("flows", frame)
        sink = CollectSink()
        worker = StreamWorker(
            Consumer(bus, fixedlen=True),
            make_models(WINDOW, 100),
            [sink],
            WorkerConfig(poll_max=BS, snapshot_every=0, fused=fused),
        )
        assert (worker.fused is not None) == fused
        worker.run(stop_when_idle=True)
        rows = [canon_rows(r) for r in sink.rows.get("flows_5m", [])]
        out[fused] = sorted(sum(rows, []))
    assert out[True] == out[False]


# ---- the live bound (PR 37) -------------------------------------------------


def _hh_states_after_every_batch(models, batches):
    """[(live bound of each family, every hh family's state arrays)], one
    entry a batch: a window roll resets the tables, so the comparison is
    made as the stream goes."""
    pipe = FusedPipeline(models)
    out = []
    for b in batches:
        pipe.update(b)
        out.append((np.asarray(pipe._live_rows),
                    {name: [np.asarray(x) for x in w.model.state]
                     for name, w in pipe._hh}))
    return out


@pytest.mark.parametrize("reference", ["bound_forced_to_n", "plain_gather"])
def test_live_bound_changes_no_bit_of_any_family(monkeypatch, reference):
    """Eight batches through the fused step with the conservative
    update's estimate gathered under the live bound (a chunk an eighth
    of the batch, so the loop runs at this size; batch 4 is cut in two
    at a slot roll, batch 6 holds late rows), and through a step that gathers every slot: with the bound forced to N,
    and in the parent's own form, one plain gather. `cms`, `table_keys`
    and `table_vals` of the three families agree bit for bit after every
    batch."""
    from flow_pipeline_tpu.engine import fused as fused_mod
    from flow_pipeline_tpu.models import heavy_hitter as hh
    from flow_pipeline_tpu.ops import cms as cms_ops

    def run(chunk, forced):
        fused_mod._cached_step.cache_clear()
        monkeypatch.setattr(cms_ops, "LIVE_CHUNK", chunk)
        if forced:
            monkeypatch.setattr(
                hh, "live_rows",
                lambda row_valid: jnp.int32(row_valid.shape[0]))
        batches = make_stream(2000)
        batches[3].columns["time_received"] += np.uint64(15)
        try:
            return _hh_states_after_every_batch(
                make_models(WINDOW, 2000), batches)
        finally:
            monkeypatch.undo()
            fused_mod._cached_step.cache_clear()

    chunk = BS // 8
    got = run(chunk, forced=False)
    want = (run(chunk, forced=True) if reference == "bound_forced_to_n"
            else run(BS, forced=False))
    bounds = np.stack([live for live, _ in got])
    # the bound bites: several trips, and whole chunks skipped
    assert chunk < bounds.min() and bounds.max() <= BS - chunk
    if reference == "bound_forced_to_n":
        assert all((live == BS).all() for live, _ in want)
    for i, ((_, new), (_, ref)) in enumerate(zip(got, want)):
        assert sorted(new) == ["top_dst_ips", "top_src_ips", "top_talkers"]
        for family in new:
            for field, a, b in zip(hh.HHState._fields, new[family],
                                   ref[family]):
                assert a.tobytes() == b.tobytes(), (
                    f"{family}.{field} after batch {i + 1}")


# ---- padding out of the scatter (PR 45) -------------------------------------


def _steps_of(kind: str, steps: int = 6):
    """``steps`` polls inside one window slot: ``zipf`` (full batches,
    about a third of the group slots real), ``part_full`` (an eighth of
    a batch, the rest padding rows) and ``all_distinct`` (every row its
    own 5-tuple, source and destination: no padding slot in any
    family)."""
    gen = FlowGenerator(ZipfProfile(n_keys=2000, alpha=1.1), seed=45)
    batches = []
    for i in range(steps):
        b = gen.batch(BS // 8 if kind == "part_full" else BS)
        n = len(b.columns["bytes"])
        if kind == "all_distinct":
            row = np.arange(i * BS, i * BS + n, dtype=np.uint32)
            b.columns["src_addr"][:, 3] = row
            b.columns["dst_addr"][:, 3] = row + np.uint32(1 << 20)
        b.columns["time_received"] = (
            6000 + i * 20 + np.arange(n) % 20).astype(np.uint64)
        batches.append(b)
    return batches


@pytest.mark.parametrize("width", [1 << 10, 1 << 14])
@pytest.mark.parametrize("kind", ["zipf", "part_full", "all_distinct"])
def test_padding_out_of_the_scatter_changes_no_bit_of_any_family(
        monkeypatch, kind, width):
    """Six steps of the fused pipeline with the padding slots dropped
    from the conservative update's scatters, and six of a pipeline built
    on the update that gathers and scatters every slot (PR 37's
    parent): every family's `cms`, `table_keys` and
    `table_vals` agree bit for bit after every step, and the live bounds
    the step hands out are the same."""
    from test_sketches import _conservative_as_the_parent_wrote_it

    from flow_pipeline_tpu.engine import fused as fused_mod
    from flow_pipeline_tpu.models import heavy_hitter as hh
    from flow_pipeline_tpu.ops import cms as cms_ops

    def run(parents_update):
        fused_mod._cached_step.cache_clear()
        if parents_update:
            monkeypatch.setattr(cms_ops, "cms_add_conservative",
                                _conservative_as_the_parent_wrote_it)
        try:
            return _hh_states_after_every_batch(
                make_models(WINDOW, 2000, width=width), _steps_of(kind))
        finally:
            monkeypatch.undo()
            fused_mod._cached_step.cache_clear()

    got, want = run(False), run(True)
    bounds = np.stack([live for live, _ in got])
    if kind == "all_distinct":
        assert (bounds == BS).all()  # nothing to drop
    else:
        assert 0 < bounds.min() and bounds.max() < BS // 2
    for i, ((live, new), (ref_live, ref)) in enumerate(zip(got, want)):
        assert live.tobytes() == ref_live.tobytes(), f"step {i + 1}"
        assert sorted(new) == ["top_dst_ips", "top_src_ips", "top_talkers"]
        for family in new:
            assert np.asarray(new[family][0]).any()
            for field, a, b in zip(hh.HHState._fields, new[family],
                                   ref[family]):
                assert a.tobytes() == b.tobytes(), (
                    f"{family}.{field} after step {i + 1}")
