"""The flows_5m drain lags one step behind the dispatch loop (ISSUE 25).

A device partial queued with a host-known slot bound lets the per-batch
flush probe prove "nothing closable" without a device read, and the probe
then folds every pending partial but the newest. A batch that fills a
device step gets the bound (the loop is behind); a part-full one does not
(the loop keeps up) and is drained by its own probe. (a) after a full
batch N's probe exactly one partial is pending and the store holds the rest;
(b) a close happens at the same batch with the same rows, bit for bit, as
with an aggregator that drains every batch — over late rows inside and
beyond ``allowed_lateness``, a batch that straddles two slots, padded
batches and a forced hash collision; (c) every reader of the store leaves
nothing pending; (d) a partial queued without a bound is drained by the
probe that follows it, as before; (e) all of it over the per-model
``update()`` path and the fused pipeline.
"""

import json
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from flow_pipeline_tpu.engine import FusedPipeline, StreamWorker, WorkerConfig
from flow_pipeline_tpu.engine import fused as fused_mod
from flow_pipeline_tpu.engine.query_api import QueryServer
from flow_pipeline_tpu.engine.worker import save_wagg_state
from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
from flow_pipeline_tpu.models import WindowAggConfig, WindowAggregator
from flow_pipeline_tpu.models import window_agg as wa
from flow_pipeline_tpu.ops import segment
from flow_pipeline_tpu.transport import Consumer

from test_fused import BS as ESTATE_BS
from test_fused import make_models
from test_ingest import CollectSink, _stream_to_bus

BS = 64
PATHS = ("per_model", "fused", "fused_estate")
# batches of make_stream whose probe emits rows: 4 closes slot 0, 5 holds
# rows for slot 0 after its close (it reopens and closes at once), 7
# closes slot 1
EMITS = {4, 5, 7}
# batches that do not fill a device step: no bound, drained by their probe
PART_FULL = {1, 6}


def make_stream(window: int, bs: int = BS):
    """Nine batches of ``bs`` rows (two of them short) over three slots
    of ``window`` seconds, for an aggregator with ``allowed_lateness =
    window // 5``."""
    gen = FlowGenerator(ZipfProfile(n_keys=40, alpha=1.2), seed=11)
    late = window // 5
    s0 = 20 * window
    s1, s2 = s0 + window, s0 + 2 * window
    i = np.arange(bs)
    times = [
        s0 + i % 30,
        (s0 + window // 2 + i % 10)[:bs - 14],          # part-full
        np.where(i < bs // 2, s1 - 5 + i % 5, s1 + i % 5),  # straddles
        np.where(i < 5, s0 + 10, s1 + 5 + i % (late - 6)),  # late, allowed
        s1 + late + i % 5,                              # closes slot 0
        np.where(i < 3, s0 + 1, s1 + late + 5),         # late, beyond
        (s1 + window // 2 + i % 7)[:bs - 1],            # part-full
        s2 + late + 1 + i % 3,                          # closes slot 1
        s2 + late + 4 + i % 3,
    ]
    batches = []
    for t in times:
        b = gen.batch(len(t))
        b.columns["time_received"] = t.astype(np.uint64)
        batches.append(b)
    return batches


def batch_rows(path: str) -> int:
    return ESTATE_BS if path == "fused_estate" else BS


def build(path: str, window: int = 300):
    """(aggregator, feed) for one dataplane path."""
    agg = WindowAggregator(WindowAggConfig(
        window_seconds=window, allowed_lateness=window // 5,
        batch_size=batch_rows(path)))
    if path == "per_model":
        return agg, agg.update
    if path == "fused":
        models = {"flows_5m": agg}
    else:  # every default model: slot and sub-window splits, late drops
        models = make_models(window // 10, 40)
        models["flows_5m"] = agg
    return agg, FusedPipeline(models).update


def stored_flows(agg) -> int:
    return sum(int(acc[-1]) for store in agg.windows.values()
               for _key, acc in store.items())


def pending_flows(agg) -> int:
    total = 0
    for partial, _, _ in agg._pending_partials:
        n = int(np.asarray(partial[3]))
        total += int(np.asarray(partial[2])[:n].sum())
    return total


def assert_rows_identical(got: dict, want: dict):
    assert got.keys() == want.keys()
    for name in want:
        assert got[name].dtype == want[name].dtype, name
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


# ---- (a) the probe leaves the newest partial, and only that -------------------


@pytest.mark.parametrize("path", PATHS)
def test_probe_leaves_exactly_the_newest_partial(path):
    agg, feed = build(path)
    fed = emitted = 0
    for n, batch in enumerate(make_stream(300, batch_rows(path))):
        feed(batch)
        fed += len(batch)
        rows = agg.flush()
        emitted += int(rows["count"].sum())
        assert bool(len(rows["timeslot"])) == (n in EMITS), n
        # a close drains everything, and so does the probe of a batch
        # that came without a bound; any other leaves the newest
        drained = n in EMITS or n in PART_FULL
        assert len(agg._pending_partials) == (0 if drained else 1), n
        assert stored_flows(agg) + pending_flows(agg) + emitted == fed
        if path != "fused_estate" and not drained:
            # one chunk a batch: the store holds batches <= n - 1
            assert pending_flows(agg) == len(batch)
    agg._drain()
    assert not agg._pending_partials


def test_the_bound_is_over_valid_rows_and_only_for_a_loop_behind():
    agg = WindowAggregator(WindowAggConfig(batch_size=BS))
    batch = make_stream(300)[1]
    assert len(batch) < BS
    padded, mask = batch.pad_to(BS)
    cols = padded.device_columns(["time_received"])
    assert cols["time_received"][~mask].min() == 0  # padding rows
    assert agg._min_slot(cols, mask, behind=True) == 20 * 300
    assert agg._min_slot(cols, mask, behind=False) is None


# ---- (b) a close drains fully: same batch, same rows --------------------------


@pytest.fixture
def collide(monkeypatch):
    """Every row hashes alike, so every chunk with two keys reports a
    collision and is recomputed by its exact fallback at drain time. The
    jitted steps are cached by configuration: the caller uses a window no
    other test uses, and the caches are dropped on both sides."""
    def degenerate(keys):
        one = jnp.ones(keys.shape[0], jnp.uint32)
        return one, one

    def clear():
        wa._cached_update.cache_clear()
        wa._cached_update_exact.cache_clear()
        fused_mod._cached_step.cache_clear()

    clear()
    monkeypatch.setattr(segment, "hash_lanes", degenerate)
    fallbacks = []
    real = WindowAggregator._exact_fallback

    def counted(self, host_cols, mask):
        run = real(self, host_cols, mask)

        def once():
            fallbacks.append(1)
            return run()

        return once

    monkeypatch.setattr(WindowAggregator, "_exact_fallback", counted)

    def undo():
        monkeypatch.undo()
        clear()

    return fallbacks, undo


def emitted_per_batch(path, window, drain_every_batch):
    agg, feed = build(path, window)
    out = []
    for batch in make_stream(window, batch_rows(path)):
        feed(batch)
        if drain_every_batch:
            agg._drain()
        out.append(agg.flush())
    out.append(agg.flush(force=True))
    assert not agg._pending_partials and not agg.windows
    return out


@pytest.mark.parametrize("path", PATHS)
def test_closes_emit_the_same_rows_at_the_same_batch(path):
    lagged = emitted_per_batch(path, 300, drain_every_batch=False)
    eager = emitted_per_batch(path, 300, drain_every_batch=True)
    for n, (got, want) in enumerate(zip(lagged, eager)):
        assert bool(len(want["timeslot"])) == (n in EMITS or n == 9), n
        assert_rows_identical(got, want)
    total = sum(int(r["count"].sum()) for r in lagged)
    assert total == sum(len(b) for b in make_stream(300, batch_rows(path)))


@pytest.mark.parametrize("path", ("per_model", "fused"))
def test_collision_fallback_runs_at_the_lagged_drain(path, collide):
    fallbacks, undo = collide
    window = 84 if path == "per_model" else 96
    lagged = emitted_per_batch(path, window, drain_every_batch=False)
    assert len(fallbacks) == 9  # every chunk collided, each recomputed once
    undo()
    eager = emitted_per_batch(path, window, drain_every_batch=True)
    for got, want in zip(lagged, eager):
        assert_rows_identical(got, want)


# ---- (c) every reader sees a fully folded store -------------------------------


def run_worker(fused: bool, batches):
    """A worker that has taken ``batches`` (nothing closes in the first
    four of the stream) and not finalized."""
    agg = WindowAggregator(WindowAggConfig(
        allowed_lateness=60, batch_size=BS))
    sink = CollectSink()
    worker = StreamWorker(
        Consumer(_stream_to_bus(batches), fixedlen=True),
        {"flows_5m": agg}, [sink],
        WorkerConfig(poll_max=BS, snapshot_every=0, fused=fused,
                     host_assist="off"))
    assert (type(worker.fused) is FusedPipeline) == fused
    assert fused or worker.fused is None
    while worker.run_once():
        pass
    return worker, agg, sink


def lagging_worker(fused: bool):
    """Three full batches: one partial is still on the device."""
    worker, agg, sink = run_worker(
        fused, [b for b in make_stream(300)[:4] if len(b) == BS])
    assert len(agg._pending_partials) == 1
    assert stored_flows(agg) < worker.flows_seen
    return worker, agg, sink


@pytest.mark.parametrize("fused", (True, False), ids=("fused", "per_model"))
def test_a_worker_that_drained_its_source_leaves_nothing_pending(fused):
    # the bus hands over 64, 64, 64 and then the 50 rows that are left:
    # the last poll is part-full, the loop has caught up
    worker, agg, _ = run_worker(fused, make_stream(300)[:4])
    assert worker.batches_seen == 4
    assert not agg._pending_partials
    assert stored_flows(agg) == worker.flows_seen


# each reader returns the flows the store has to hold once it has read


def read_checkpoint_state(worker, agg, sink):
    # the saved arrays cover every partial that was pending: the save
    # drained first, so what it holds is what the offsets will cover
    state = save_wagg_state(agg)
    assert [s["slot"] for s in state["stores"]] == list(agg.windows)
    assert sum(int(s["sums"][:, -1].sum())
               for s in state["stores"]) == worker.flows_seen
    return worker.flows_seen


def read_query_api(worker, agg, sink):
    server = QueryServer(worker, port=0).start()
    try:
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/windows").read())
    finally:
        server.stop()
    assert [w["timeslot"] for w in doc["open_windows"]] == sorted(agg.windows)
    return worker.flows_seen


def read_closed_slots(worker, agg, sink):
    assert agg.closed_slots() == []
    return worker.flows_seen


def read_forced_flush(worker, agg, sink):
    assert int(agg.flush(force=True)["count"].sum()) == worker.flows_seen
    return 0


def read_finalize(worker, agg, sink):
    worker.finalize()
    assert sum(int(r["count"].sum())
               for r in sink.rows["flows_5m"]) == worker.flows_seen
    return 0


@pytest.mark.parametrize("fused", (True, False), ids=("fused", "per_model"))
@pytest.mark.parametrize("reader", (
    read_checkpoint_state, read_query_api, read_closed_slots,
    read_forced_flush, read_finalize), ids=lambda f: f.__name__)
def test_reader_leaves_nothing_pending(reader, fused):
    worker, agg, sink = lagging_worker(fused)
    want = reader(worker, agg, sink)
    assert not agg._pending_partials
    assert stored_flows(agg) == want


# ---- (d) a partial without a bound: drained by the next probe -----------------


def device_partial(agg, batch, exact: bool):
    padded, mask = batch.pad_to(BS)
    host_cols = padded.device_columns(
        ["time_received", *wa.group_cols(agg.config),
         *agg.config.value_cols])
    build_step = wa._cached_update_exact if exact else wa._cached_update
    step = build_step(agg.config.window_seconds, wa.group_cols(agg.config),
                      agg.config.value_cols)
    cols = {k: jnp.asarray(v) for k, v in host_cols.items()}
    return (step(cols, jnp.asarray(mask)),
            agg._exact_fallback(host_cols, mask),
            agg._min_slot(host_cols, mask, behind=True))


@pytest.mark.parametrize("exact", (False, True), ids=("hashed", "exact"))
def test_partial_without_a_bound_is_drained_by_the_probe(exact):
    agg = WindowAggregator(WindowAggConfig(
        allowed_lateness=60, batch_size=BS))
    batches = make_stream(300)[:3]
    fed = 0
    for batch in batches:
        partial, fallback, _ = device_partial(agg, batch, exact)
        agg.add_partial(partial, fallback=fallback)
        agg.watermark = int(batch.columns["time_received"].max())
        fed += len(batch)
        assert not agg._nothing_closable()  # "maybe closable"
        assert len(agg.flush()["timeslot"]) == 0
        assert not agg._pending_partials
        assert stored_flows(agg) == fed


def test_one_unbounded_partial_drains_the_bounded_ones_too():
    agg = WindowAggregator(WindowAggConfig(
        allowed_lateness=60, batch_size=BS))
    b0, b1, b2 = make_stream(300)[:3]
    agg.update(b0)
    agg.flush()
    assert len(agg._pending_partials) == 1
    partial, fallback, _ = device_partial(agg, b1, exact=False)
    agg.add_partial(partial, fallback=fallback)
    agg.flush()
    assert not agg._pending_partials
    assert stored_flows(agg) == len(b0) + len(b1)
    agg.update(b2)  # bounded again: the probe lags again
    agg.flush()
    assert len(agg._pending_partials) == 1
    assert stored_flows(agg) == len(b0) + len(b1)


def test_queue_without_probes_is_bounded():
    agg = WindowAggregator(WindowAggConfig(batch_size=BS))
    batch = make_stream(300)[0]
    for _ in range(wa.DRAIN_PENDING_MAX - 1):
        agg.update(batch)
    assert len(agg._pending_partials) == wa.DRAIN_PENDING_MAX - 1
    agg.update(batch)
    assert not agg._pending_partials
    assert stored_flows(agg) == wa.DRAIN_PENDING_MAX * len(batch)
