"""The distinct-count (spread) family inside the fused device step
(ISSUE 47): under ``-spread.enabled`` on the device backend
``FusedPipeline`` moves the detectors' state to the device and its jitted
step updates it (``ops/spread.py``: ``spread_scatter``,
``spread_table_admit``), where the parent folded host numpy between
dispatches.

Held, at small shapes on the CPU backend: (i) the registers to the numpy
twin ``hostsketch.engine.np_spread_update`` bit for bit; (ii) the rows of
a close to the host path's; (iii) the decoded spreads to the plain
reference ``models.oracle.distinct_exact``; (iv) checkpoints, the
parent's among them; (v) ``-window.lateness`` to
``models.oracle.late_unit_sums``; (vi) the flag off: the step the parent
lowered (the pins of tests/test_lateness.py and tests/test_pairs.py, and
here that no spread state reaches the step).
"""

from __future__ import annotations

import os
import sys

import jax
import numpy as np
import pytest

from flow_pipeline_tpu import cli
from flow_pipeline_tpu.engine import FusedPipeline, StreamWorker, WorkerConfig
from flow_pipeline_tpu.engine.checkpoint import (
    load_checkpoint,
    save_checkpoint,
)
from flow_pipeline_tpu.engine.hostfused import _key_lanes_np
from flow_pipeline_tpu.engine.worker import (
    restore_spread_state,
    save_spread_state,
)
from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
from flow_pipeline_tpu.hostsketch.engine import (
    np_spread_query,
    np_spread_update,
)
from flow_pipeline_tpu.models.oracle import distinct_exact, late_unit_sums
from flow_pipeline_tpu.models.scan import SCAN_MODEL, scan_config, scan_model
from flow_pipeline_tpu.models.superspreader import (
    SUPERSPREADER_MODEL,
    superspreader_config,
    superspreader_model,
)
from flow_pipeline_tpu.schema.batch import FlowBatch
from flow_pipeline_tpu.utils.flags import FlagSet

sys.path.insert(0, os.path.dirname(__file__))
import test_lateness as tl  # noqa: E402 -- its streams and model set

BS = 256
DETECTORS = (SUPERSPREADER_MODEL, SCAN_MODEL)
SHAPE = dict(width=256, registers=16, capacity=64)
# where the step finds a batch's sources, once each (engine/fused.py:
# key_groups): the groups of an hh family keyed as the detector
# (top_src_ips, alone or in a chain under the host pairs), or a sort of
# the detector's own where the estate runs no such family
SOURCES = {"shared-groups": [], "shared-under-pairs": ["-model.pairs=true"],
           "own-sort": ["-model.ips=false"]}


def models_of(*flags, depth: int = 2) -> dict:
    """``cli._build_models``'s own set at a small size, for the device
    dataplane (``-processor.hostassist off``: FusedPipeline on the CPU
    too)."""
    fs = cli._processor_flags(cli._common_flags(FlagSet("processor")))
    return cli._build_models(fs.parse([
        "-processor.batch", str(BS), "-processor.hostassist", "off",
        "-sketch.width", "1024", "-sketch.capacity", "128",
        "-spread.enabled=true", "-spread.depth", str(depth),
        "-spread.width", str(SHAPE["width"]),
        "-spread.regs", str(SHAPE["registers"]),
        "-spread.capacity", str(SHAPE["capacity"]), *flags]))


def stream(seed: int = 7, n_keys: int = 300) -> list:
    """Seven polls of one partition: four in the first window (the third
    half full, so the step pads it), then a roll, then two more. Zipf
    ranks, so a poll repeats most of its (source, element) pairs; a
    quarter of the flows are the generator's spreaders and scanners."""
    gen = FlowGenerator(ZipfProfile(n_keys=n_keys, alpha=1.2,
                                    spread_fraction=0.25), seed=seed)
    polls = []
    for i, (n, t) in enumerate([(BS, 6000), (BS, 6001), (BS // 2, 6002),
                                (BS, 6003), (BS, 6300), (BS, 6301),
                                (BS, 6302)]):
        b = gen.batch(n)
        b.columns["time_received"] = np.full(n, t, np.uint64)
        polls.append(b)
    return polls


def twin_registers(cfg, polls) -> np.ndarray:
    regs = np.zeros((cfg.depth, cfg.width, cfg.registers), np.uint8)
    for b in polls:
        np_spread_update(regs, _key_lanes_np(b.columns, cfg.key_cols),
                         _key_lanes_np(b.columns, (cfg.elem_col,)))
    return regs


def rows_of(window: dict) -> list:
    ok = window["valid"]
    return [(tuple(int(x) for x in k), float(s))
            for k, s in zip(window["src_addr"][ok], window["spread"][ok])]


# ---- (i) the registers are the numpy twin's ---------------------------------


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("source", SOURCES)
def test_the_fused_steps_registers_are_the_numpy_twins(source, depth):
    models = models_of(*SOURCES[source], depth=depth)
    pipe = FusedPipeline(models)
    assert pipe.spread_families == DETECTORS
    polls = stream()
    for upto, window in ((4, polls[:4]), (7, polls[4:])):
        for b in polls[upto - len(window):upto]:
            pipe.update(b)
        for name in DETECTORS:
            model = models[name].model
            assert model.on_device
            assert isinstance(model.state.regs, jax.Array)
            assert model.state.regs.ndim == 1  # the flat device plane
            host = model.host_state()
            assert host.regs.dtype == np.uint8
            want = twin_registers(models[name].config, window)
            assert want.any()
            assert host.regs.tobytes() == want.tobytes(), (name, upto)


def test_a_poll_that_fills_no_step_leaves_padding_out_of_the_planes():
    models = models_of()
    pipe = FusedPipeline(models)
    b = stream()[0].slice(0, 5)
    pipe.update(b)
    for name in DETECTORS:
        cfg = models[name].config
        got = models[name].model.host_state().regs
        assert got.tobytes() == twin_registers(cfg, [b]).tobytes()
        # five rows raise at most five registers a depth row
        assert 0 < np.count_nonzero(got) <= 5 * cfg.depth


@pytest.mark.parametrize("n, live", [(5000, 5000), (5000, 1300),
                                     (4096, 2049), (300, 300)])
def test_rows_that_are_not_valid_leave_the_scatter(n, live):
    """``ops.spread.spread_scatter``: one scatter-max over every depth
    row of the flat plane; a row whose ``valid`` is False goes out of
    range and is dropped, so the plane is the numpy twin's over the
    valid rows alone, holes and a padded tail included."""
    import jax.numpy as jnp

    from flow_pipeline_tpu.ops import spread as ops

    rng = np.random.default_rng(n + live)
    shape = (2, 128, 16)
    keys = rng.integers(0, 2**32, (n, 4), dtype=np.uint32)
    elems = rng.integers(0, 2**32, (n, 1), dtype=np.uint32)
    valid = (np.arange(n) < live) & (rng.random(n) < 0.7)
    want = np.zeros(shape, np.uint8)
    np_spread_update(want, keys[valid], elems[valid])
    flat = jnp.zeros(int(np.prod(shape)), ops.DEVICE_REG_DTYPE)
    got = ops.host_regs(
        jax.jit(ops.spread_scatter, static_argnums=1)(
            flat, shape, jnp.asarray(keys), jnp.asarray(elems),
            jnp.asarray(valid)), shape=shape)
    assert want.any() and np.asarray(got).tobytes() == want.tobytes()


# ---- (ii) the rows of a close are the host path's ---------------------------


@pytest.mark.parametrize("source", SOURCES)
def test_a_closes_rows_are_the_host_paths(source):
    """Keys and decoded spreads equal the per-model host path's
    (``SpreadModel.update``: group to unique pairs, scatter, table merge)
    on the same polls, in rank order. **The admission metric departs, as
    ISSUE 47 allows** (it only decides which sources are tracked, here
    fewer than the table holds; a close reports what the registers
    decode to): the host accumulates a source's count of pairs a chunk,
    the step keeps what the registers decoded to when the source was
    last seen (``ops.spread.spread_table_admit``), so on the device
    ``pairs`` is a row's ``spread`` as of that poll: never above it, and
    equal for a source the window's last poll held."""
    dev, host = models_of(*SOURCES[source]), models_of(*SOURCES[source])
    pipe = FusedPipeline(dev)
    polls = stream(n_keys=24)
    sources = {bytes(a) for b in polls for a in b.columns["src_addr"]}
    assert 24 < len(sources) < SHAPE["capacity"]  # every one is tracked
    for b in polls:
        pipe.update(b)
        for name in DETECTORS:
            host[name].update(b)
    last = {bytes(a) for a in polls[-1].columns["src_addr"]}
    for name in DETECTORS:
        got = dev[name].flush(force=True)
        want = host[name].flush(force=True)
        assert [int(w["timeslot"][0]) for w in got] == [6000, 6300]
        for g, w in zip(got, want):
            assert rows_of(g) == rows_of(w) and rows_of(g)
            ok = g["valid"]
            assert (g["pairs"][ok] <= g["spread"][ok] * (1 + 1e-5)).all()
        seen_last = np.array([bytes(a) in last
                              for a in got[-1]["src_addr"][ok]])
        assert seen_last.any()
        np.testing.assert_allclose(got[-1]["pairs"][ok][seen_last],
                                   got[-1]["spread"][ok][seen_last],
                                   rtol=1e-5)


def _slow_spreader_polls(polls: int = 40, busy: int = 96):
    """``busy`` sources that send the same eight flows every poll, and
    one that shows ONE new destination a poll: after ``polls`` polls it
    has touched ``polls`` distinct hosts, five times any other."""
    def addr(last):
        a = np.zeros((len(last), 4), np.uint32)
        a[:, 0], a[:, 3] = 0x20010DB8, last
        return a

    out = []
    base = stream()[0]
    for p in range(polls):
        src = np.concatenate([np.repeat(np.arange(1, busy + 1), 8), [9999]])
        dst = np.concatenate([np.tile(np.arange(8), busy) + 100, [5000 + p]])
        n = len(src)
        cols = {k: (np.zeros((n,) + v.shape[1:], v.dtype))
                for k, v in base.columns.items()}
        cols["src_addr"], cols["dst_addr"] = addr(src), addr(dst)
        cols["dst_port"] = dst.astype(cols["dst_port"].dtype)
        cols["time_received"] = np.full(n, 6000, np.uint64)
        out.append(FlowBatch(cols))
    return out


@pytest.mark.parametrize("name", DETECTORS)
def test_a_spreader_that_shows_one_target_a_poll_is_tracked(name):
    """The blind spot the first chip runs of ISSUE 47 found: a candidate
    table that admits by a batch's count of pairs never holds a source
    that shows one new target a batch once its places are taken by busy
    sources (the host twin's rule, shown here on the same polls). The
    step admits by what the registers decode to, so the slow spreader is
    in the table, and first among the rows."""
    flags = ["-spread.capacity", "64"]
    dev, host = models_of(*flags), models_of(*flags)
    pipe = FusedPipeline(dev)
    polls = _slow_spreader_polls()
    assert all(len(b) > BS for b in polls)  # more than one step a poll
    for b in polls:
        pipe.update(b)
        host[name].update(b)
    slow = (0x20010DB8, 0, 0, 9999)
    got = rows_of(dev[name].flush(force=True)[0])
    assert got[0][0] == slow and 30 < got[0][1] < 50
    assert got[1][1] < 12  # the busy sources: eight targets each
    assert slow not in dict(rows_of(host[name].flush(force=True)[0]))


@pytest.mark.parametrize("shape, n", [((1, 64, 16), 40), ((2, 256, 64), 300),
                                      ((3, 128, 256), 500),
                                      ((2, 512, 32), 2000)])
def test_the_devices_decode_is_the_hosts_to_float32(shape, n):
    """``ops.spread.spread_decode_device`` (float32, what the table's
    admission reads) against ``np_spread_query`` (float64, what every
    row and query reports) on planes from empty to crowded: linear
    counting and the raw estimate both."""
    import jax.numpy as jnp

    from flow_pipeline_tpu.ops import spread as ops

    rng = np.random.default_rng(n)
    keys = rng.integers(0, 2**32, (64, 4), dtype=np.uint32)
    regs = np.zeros(shape, np.uint8)
    for fill in (n // 8, n):  # a sparse plane, then a crowded one
        rows = rng.integers(0, 64, fill)
        np_spread_update(regs, keys[rows],
                         rng.integers(0, 2**32, (fill, 1), dtype=np.uint32))
        want = np_spread_query(regs, keys)
        got = jax.jit(ops.spread_decode_device, static_argnums=1)(
            ops.device_regs(regs), shape, jnp.asarray(keys))
        assert want.max() > 1 and got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5)


@pytest.mark.parametrize("n", [6, 40])  # under and past 2 x capacity
def test_the_table_keeps_the_largest_decoded_spreads(n):
    """``ops.spread.spread_table_admit`` with capacity 8: a key the
    batch holds is worth what it decodes to now, a resident the batch
    lacks keeps its worth, the eight largest stay in order, and padding
    and the all-ones key never enter."""
    import jax.numpy as jnp

    from flow_pipeline_tpu.ops import spread as ops

    def key(i):
        return [7, 0, 0, i]

    none = [0xFFFFFFFF] * 4
    table_keys = np.array([key(i) for i in (1, 2, 3, 4, 5)] + [none] * 3,
                          np.uint32)
    table_metric = np.array([90, 70, 50, 30, 10, 0, 0, 0], np.float32)
    cand = [(key(2), 75.0, True),    # a resident, risen
            (key(5), 10.0, True),    # a resident, as it was
            (key(9), 60.0, True),    # new, enters among them
            (key(8), 999.0, False),  # padding
            (none, 500.0, True)]     # the key no table can hold
    cand += [(key(100 + i), float(i), True) for i in range(n - len(cand))]
    tk, tm = jax.jit(ops.spread_table_admit)(
        jnp.asarray(table_keys), jnp.asarray(table_metric),
        jnp.asarray(np.array([c[0] for c in cand], np.uint32)),
        jnp.asarray(np.array([c[1] for c in cand], np.float32)),
        jnp.asarray(np.array([c[2] for c in cand])))
    got = [(int(k[3]), float(v)) for k, v in zip(np.asarray(tk),
                                                 np.asarray(tm))]
    rest = sorted(((100 + i, float(i)) for i in range(n - 5)),
                  key=lambda kv: -kv[1])
    want = sorted([(1, 90.0), (2, 75.0), (3, 50.0), (4, 30.0), (5, 10.0),
                   (9, 60.0)] + rest, key=lambda kv: -kv[1])[:8]
    want += [(0xFFFFFFFF, 0.0)] * (8 - len(want))  # places still empty
    assert got == want


@pytest.mark.parametrize("plane", ["device", "host"])
def test_a_host_fold_is_a_spread_fold_span_and_the_step_has_none(plane):
    """``spread_fold`` (what ``spread_fold_ms_p50`` reads) is the host
    fold between two dispatches: the host-grouped pipelines record one a
    chunk round their ``_fold_spread``; ``FusedPipeline``, whose step
    updates the planes, records none."""
    from flow_pipeline_tpu.engine.hostfused import HostGroupPipeline
    from flow_pipeline_tpu.obs.trace import TRACER

    models = models_of()
    pipe = (FusedPipeline if plane == "device" else HostGroupPipeline)(
        models)
    assert pipe.spread_in_step == (plane == "device")
    TRACER.configure("always")
    try:
        for b in stream()[:3]:
            pipe.update(b)
        folds = [s for s in TRACER.snapshot() if s[0] == "spread_fold"]
    finally:
        TRACER.configure("off")
    assert len(folds) == (0 if plane == "device" else 3)
    assert models[SCAN_MODEL].model.on_device == (plane == "device")


def test_update_is_the_pipelines_once_the_state_is_on_the_device():
    models = models_of()
    FusedPipeline(models)
    with pytest.raises(RuntimeError, match="on the device"):
        models[SUPERSPREADER_MODEL].model.update(stream()[0])


# ---- (iii) against the plain reference --------------------------------------


def test_decoded_spreads_lie_within_three_standard_errors_of_exact():
    """One window of a stream with spreaders, registers of 64: the
    largest sources' decoded spreads against ``distinct_exact``, and the
    control (a source's flow count, what a sum in the place of the max
    reports) far outside."""
    fs = cli._processor_flags(cli._common_flags(FlagSet("processor")))
    models = cli._build_models(fs.parse([
        "-processor.batch", "2048", "-processor.hostassist", "off",
        "-sketch.width", "1024", "-sketch.capacity", "128",
        "-spread.enabled=true", "-spread.width", "4096",
        "-spread.regs", "64", "-model.ports=false", "-model.ddos=false"]))
    pipe = FusedPipeline(models)
    gen = FlowGenerator(ZipfProfile(n_keys=20_000, alpha=1.05,
                                    spread_fraction=0.3), seed=11)
    once = gen.batch(4 * 2048)
    once.columns["time_received"] = np.full(len(once), 6000, np.uint64)
    # every flow three times: a source's flows are thrice its pairs
    whole = FlowBatch({k: np.concatenate([v] * 3)
                       for k, v in once.columns.items()})
    for at in range(0, len(whole), 2048):
        pipe.update(whole.slice(at, at + 2048))
    sigma = 1.04 / np.sqrt(64)
    for name in DETECTORS:
        cfg = models[name].config
        exact = distinct_exact(whole, list(cfg.key_cols), cfg.elem_col,
                               timeslot=False)
        by_key = {tuple(int(x) for x in k): (int(d), int(c))
                  for k, d, c in zip(exact["src_addr"], exact["distinct"],
                                     exact["count"])}
        top = sorted(by_key.values(), reverse=True)[:5]
        assert top[0][0] > 100  # a spreader, not the background
        rows = dict(rows_of(models[name].flush(force=True)[0]))
        checked = 0
        for key, (distinct, count) in by_key.items():
            if distinct < top[-1][0]:
                continue
            checked += 1
            assert abs(rows[key] - distinct) <= 3 * sigma * distinct, (
                name, distinct, rows[key])
            # the control reads the flows: far outside the same bound
            assert count >= 3 * distinct
            assert abs(count - distinct) > 3 * sigma * distinct
        assert checked >= 5


# ---- (iv) checkpoints ----------------------------------------------------------


def _detector(name: str, lateness: int = 0):
    make, config = ((superspreader_model, superspreader_config)
                    if name == SUPERSPREADER_MODEL
                    else (scan_model, scan_config))
    return make(config(batch_size=BS, **SHAPE), window_seconds=300, k=64,
                lateness=lateness)


@pytest.mark.parametrize("name", DETECTORS)
def test_a_checkpoint_round_trips_through_the_host_form(name, tmp_path):
    models = models_of()
    pipe = FusedPipeline(models)
    for b in stream()[:4]:
        pipe.update(b)
    saved = save_spread_state(models[name])
    # the leaves are device arrays still, a byte a register: ckpt_d2h's
    assert isinstance(saved["spread"].regs, jax.Array)
    assert saved["spread"].regs.dtype == np.uint8
    assert saved["spread"].regs.shape == (2, SHAPE["width"],
                                          SHAPE["registers"])
    save_checkpoint(str(tmp_path / "ckpt"), {"m": saved})
    loaded = load_checkpoint(str(tmp_path / "ckpt"))["m"]
    want = models[name].model.host_state()
    for on_device in (True, False):
        fresh = _detector(name)
        if on_device:
            fresh.model.to_device()
        restore_spread_state(fresh, loaded, name)
        assert fresh.current_slot == 6000
        assert fresh.model.on_device == on_device
        assert isinstance(fresh.model.state.regs,
                          jax.Array if on_device else np.ndarray)
        got = fresh.model.host_state()
        for a, b in zip(got, want):
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
        assert rows_of(fresh.top(64)) == rows_of(models[name].top(64))


def test_a_checkpoint_the_parent_wrote_restores_onto_the_device(tmp_path):
    """Before PR 47 the state was host numpy and a checkpoint held it as
    it was: ``{"kind", "spread": SpreadState(numpy), "current_slot"}``.
    This build reads that file and places it where its dataplane keeps
    state; the step then goes on from it: the registers are the host
    path's bit for bit and the largest rows are its rows. (The file's
    admission metric is the host's count of pairs, which is no smaller
    than a decoded spread would be: a restored resident keeps it, as
    ``spread_table_admit`` keeps the larger, until the window closes.)"""
    polls = stream()
    host = _detector(SUPERSPREADER_MODEL)
    for b in polls[:2]:
        host.update(b)
    assert isinstance(host.model.state.regs, np.ndarray)
    parents = {"kind": "windowed_spread", "spread": host.model.state,
               "current_slot": host.current_slot}
    save_checkpoint(str(tmp_path / "ckpt"), {"m": parents})
    loaded = load_checkpoint(str(tmp_path / "ckpt"))["m"]
    models = models_of()
    pipe = FusedPipeline(models)
    restore_spread_state(models[SUPERSPREADER_MODEL], loaded, "m")
    for w in (models[n] for n in models if n != SUPERSPREADER_MODEL):
        if hasattr(w, "current_slot"):
            w.current_slot = 6000
    for b in polls[2:4]:
        pipe.update(b)
        host.update(b)
    got = models[SUPERSPREADER_MODEL].model.host_state()
    assert got.regs.tobytes() == host.model.state.regs.tobytes()
    assert rows_of(models[SUPERSPREADER_MODEL].top(8)) == rows_of(
        host.top(8))


def test_a_workers_checkpoint_counts_the_planes_and_restores(tmp_path):
    """Through ``StreamWorker``: ``ckpt_state`` says how many bytes of
    register planes the checkpoint holds (a byte a register, both
    detectors), and a worker built anew restores them to the device."""
    from flow_pipeline_tpu.obs.trace import TRACER, _Span

    told = {}

    def span(name, chunk=None, **args):
        return _Span(TRACER, name, chunk, told.setdefault(name, args))

    def worker():
        return StreamWorker(None, models_of(), [], WorkerConfig(
            checkpoint_path=str(tmp_path / "ckpt"),
            host_assist="off"))

    first = worker()
    assert type(first.fused) is FusedPipeline
    assert first.fused.spread_in_step
    for b in stream()[:4]:
        first.fused.update(b)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(TRACER, "span", span)
        first.snapshot_and_commit()
    plane = 2 * SHAPE["width"] * SHAPE["registers"]
    assert told["ckpt_state"]["spread_plane_bytes"] == 2 * plane
    again = worker()
    assert again.restore()
    for name in DETECTORS:
        got, want = (w.models[name].model for w in (again, first))
        assert got.on_device
        assert got.host_state().regs.tobytes() == \
            want.host_state().regs.tobytes()
        assert want.host_state().regs.any()


def test_a_publish_copies_the_planes_once():
    """``top()`` and the snapshot's view parts share one device->host
    copy for as long as the state is the model's; the next step's state
    is copied anew."""
    from flow_pipeline_tpu.serve.publisher import spread_view_parts

    models = models_of()
    pipe = FusedPipeline(models)
    polls = stream()
    pipe.update(polls[0])
    w = models[SUPERSPREADER_MODEL]
    rows = w.top(8)
    _cms, lanes, regs = spread_view_parts(w)
    assert lanes == 4 and regs is w.model.host_state().regs
    assert rows["valid"].any()
    # a checkpoint that finds the copy made takes it, not the device's
    assert save_spread_state(w)["spread"] is w.model.host_state()
    pipe.update(polls[1])
    assert isinstance(save_spread_state(w)["spread"].regs, jax.Array)
    assert spread_view_parts(w)[2] is not regs


# ---- (v) -window.lateness -------------------------------------------------------


def test_late_rows_reach_the_held_plane_on_two_partitions():
    """Two partitions polled alternately, a tenth of the flows 1-3 s
    behind, ``-window.lateness`` 7: at every close each detector's
    registers are the numpy twin's over exactly the rows
    ``late_unit_sums`` admits to that window, the late ones folded into
    the held plane among them."""
    lateness, closed = 7, {n: [] for n in DETECTORS}
    models = tl.make_models(lateness)
    for name in DETECTORS:
        w = _detector(name, lateness)
        w.window_seconds = w.slot_seconds = tl.WINDOW
        w.audit_hook = (lambda slot, model, name=name: closed[name].append(
            (slot, model.host_state().regs.copy())))
        models[name] = w
    polls = tl.two_partition_polls(5, 40 * tl.BS)
    pipe = FusedPipeline(models)
    for poll in polls:
        pipe.update(poll)
    for name in DETECTORS:
        models[name].flush(force=True)
        cfg = models[name].config
        want = late_unit_sums(polls, tl.WINDOW, lateness,
                              [*cfg.key_cols, cfg.elem_col])
        assert [slot for slot, _r in closed[name]] == want["order"]
        assert models[name].late_flows_folded == want["folded"] > 0
        assert models[name].late_flows_dropped == want["dropped"]
        for slot, regs in closed[name]:
            pairs = want["units"][slot]
            twin = np.zeros_like(regs)
            np_spread_update(twin, _key_lanes_np(pairs, cfg.key_cols),
                             _key_lanes_np(pairs, (cfg.elem_col,)))
            assert regs.tobytes() == twin.tobytes(), (name, slot)


# ---- (vi) the flag off ----------------------------------------------------------


def test_without_the_flag_no_spread_state_reaches_the_step():
    """The lowered text is pinned in tests/test_lateness.py and
    tests/test_pairs.py; here: the step of a model set without the
    detectors takes the parent's three families of states, and the one
    with them a fourth."""
    fs = cli._processor_flags(cli._common_flags(FlagSet("processor")))
    off = FusedPipeline(cli._build_models(fs.parse(
        ["-processor.batch", str(BS), "-processor.hostassist", "off"])))
    on = FusedPipeline(models_of())
    assert len(off._states()) == 3 and off.spread_families == ()
    assert len(on._states()) == 4
    assert off._step is not on._step
    text = tl.step_text(off)
    assert "spread_" not in text


def test_the_host_grouped_dataplane_keeps_the_planes_in_host_memory():
    """``-sketch.backend host`` and the CPU's default keep today's path:
    numpy registers, folded by the host pipeline."""
    from flow_pipeline_tpu.engine.hostfused import HostGroupPipeline

    models = models_of()
    pipe = HostGroupPipeline(models)
    assert pipe.spread_families == () and not pipe.spread_in_step
    polls = stream()[:4]
    for b in polls:
        pipe.update(b)
    for name in DETECTORS:
        model = models[name].model
        assert not model.on_device
        assert isinstance(model.state.regs, np.ndarray)
        assert model.state.regs.tobytes() == twin_registers(
            models[name].config, polls).tobytes()
