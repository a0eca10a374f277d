"""The window store at a routed-Internet AS universe (ISSUE 31): 256 ASes a
side, over 10^4 active (SrcAS, DstAS) pairs a window, through
``cli.processor_main`` on the in-process bus with every default model.

(a) ``flows_5m`` is bit-exact against a plain reference over two closes
and the end, and ``/query/range`` answers what the sink holds; (b) a
checkpoint holds a window's store as one key array and one sums array:
its count of npz members and its ``meta.json`` do not grow with the
groups, one taken mid-window restores key for key and sum for sum, and a
worker killed after it and resumed ends with the uninterrupted run's sink
rows (at-least-once: rows merged by key); (c) the form the builds before
this one wrote, a dict of key tuples, still restores; a store whose keys
have another layout is skipped loudly in both forms; (d) the spans say
what they counted. Counts only: nothing here is timed.
"""

import copy
import json
import os
import socket
import sqlite3
import tempfile
import urllib.request

import numpy as np
import pytest

from flow_pipeline_tpu import cli, transport
from flow_pipeline_tpu.engine.checkpoint import load_checkpoint, save_checkpoint
from flow_pipeline_tpu.engine.worker import (StreamWorker, restore_wagg_state,
                                             save_wagg_state)
from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
from flow_pipeline_tpu.models import WindowAggregator
from flow_pipeline_tpu.models.window_agg import WindowStore
from flow_pipeline_tpu.obs.trace import TRACER
from flow_pipeline_tpu.schema import wire
from flow_pipeline_tpu.transport import InProcessBus

AS_BASE, AS_COUNT = 65_000, 256
BATCH, EVERY = 2048, 3                # a checkpoint every three batches
FLOWS, RATE, SLOT = 48_000, 60, 300   # 18,000 flows to a window: 2 closes
T0 = 1_700_000_100                    # slot-aligned
KILL_AT = 16 * BATCH                  # 14,768 flows into the second window
BIG = 10_000                          # groups "a deployment holds", here


def _argv(tmp, port=0):
    return ["-processor.backend", "cpu", "-processor.hostassist", "off",
            "-processor.batch", str(BATCH), "-sketch.width", "4096",
            "-sketch.capacity", "256", "-flush.count", str(EVERY),
            "-window.lateness", "0", "-obs.trace", "always",
            "-metrics.addr", "", "-listen.feed", "127.0.0.1:0",
            "-serve.addr", f"127.0.0.1:{port}" if port else "",
            "-sink", f"sqlite:{tmp / 'sink.db'}",
            "-checkpoint.path", str(tmp / "ckpt")]


@pytest.fixture(scope="module")
def stream():
    """Both AS labels drawn alike and apart from the 5-tuple, as the
    benchmark's key table draws them: 18,000 flows reach ~15,700 of the
    65,536 pairs."""
    batch = FlowGenerator(ZipfProfile(n_keys=2000, alpha=1.1),
                          seed=31).batch(FLOWS)
    rng = np.random.default_rng(31)
    for col in ("src_as", "dst_as"):
        batch.columns[col] = (AS_BASE + rng.integers(0, AS_COUNT, FLOWS)
                              ).astype(batch.columns[col].dtype)
    batch.columns["time_received"] = (
        T0 + np.arange(FLOWS) // RATE).astype(np.uint64)
    return batch


def _bus(stream) -> InProcessBus:
    bus = InProcessBus()
    bus.create_topic("flows", 1)
    bus.produce_many("flows", wire.iter_raw_frames(stream.to_wire()),
                     partition=0)
    return bus


class Killed(BaseException):
    """A kill between two batches: no finalize, no flush, no commit."""


def _run(bus, argv, kill_at=None, on_start=None, on_checkpoint=None,
         after_finalize=None):
    """One ``processor_main`` on ``bus`` to the end of the stream (the
    operator's interrupt, drained through ``finalize``), or killed once
    ``kill_at`` flows are folded. Nothing of the program is replaced:
    every wrapper calls what it wraps."""
    run_once, snap, finalize = (StreamWorker.run_once,
                                StreamWorker.snapshot_and_commit,
                                StreamWorker.finalize)
    seen = {}

    def run_once_(worker):
        if "worker" not in seen:
            seen["worker"] = worker
            if on_start is not None:
                on_start(worker)
        if kill_at is not None and worker.flows_seen >= kill_at:
            raise Killed
        if worker.flows_seen >= FLOWS:
            raise KeyboardInterrupt
        return run_once(worker)

    def snap_(worker):
        snap(worker)
        if on_checkpoint is not None:
            on_checkpoint(worker)

    def finalize_(worker):
        finalize(worker)
        if after_finalize is not None:
            after_finalize(worker)

    with pytest.MonkeyPatch.context() as m:
        m.setattr(transport, "InProcessBus", lambda: bus)
        m.setattr(StreamWorker, "run_once", run_once_)
        m.setattr(StreamWorker, "snapshot_and_commit", snap_)
        m.setattr(StreamWorker, "finalize", finalize_)
        try:
            assert cli.processor_main(argv) == 0
        except Killed:
            # the worker's flight recorder dumps on the way down
            dump = os.path.join(tempfile.gettempdir(),
                                f"flowtrace-worker-{os.getpid()}.json")
            if os.path.isfile(dump):
                os.remove(dump)
    return seen["worker"]


def _flows5m(tmp) -> dict:
    """The sink's flows_5m merged by key, as a reader of the table sums
    it: {(timeslot, src_as, dst_as, etype): (bytes, packets, count)}."""
    con = sqlite3.connect(tmp / "sink.db")
    try:
        return {tuple(r[:4]): tuple(r[4:]) for r in con.execute(
            "SELECT timeslot, src_as, dst_as, etype, SUM(bytes), "
            "SUM(packets), SUM(count) FROM flows_5m GROUP BY 1, 2, 3, 4")}
    finally:
        con.close()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture(scope="module")
def whole(stream, tmp_path_factory):
    """The uninterrupted run: (worker, flows_5m by key, the final
    ``/query/range`` answer, spans)."""
    tmp = tmp_path_factory.mktemp("as64k")
    port = _free_port()
    answer = {}

    def ask(_worker):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/query/range?model=flows_5m",
                timeout=10) as r:
            answer.update(json.loads(r.read()))

    worker = _run(_bus(stream), _argv(tmp, port), after_finalize=ask)
    spans = TRACER.snapshot()
    TRACER.configure("off")
    return worker, _flows5m(tmp), answer, spans


# ---- (a) exact at this cardinality ----------------------------------------------


@pytest.fixture(scope="module")
def reference(stream):
    """(exact flows_5m by key, groups a slot) from a plain loop."""
    c = stream.columns
    slots = c["time_received"].astype(np.int64) // SLOT * SLOT
    want: dict = {}
    for slot, sa, da, et, b, p in zip(slots, c["src_as"], c["dst_as"],
                                      c["etype"], c["bytes"], c["packets"]):
        acc = want.setdefault((int(slot), int(sa), int(da), int(et)),
                              [0, 0, 0])
        acc[0] += int(b)
        acc[1] += int(p)
        acc[2] += 1
    per_slot: dict = {}
    for key in want:
        per_slot[key[0]] = per_slot.get(key[0], 0) + 1
    return {k: tuple(v) for k, v in want.items()}, per_slot


def test_flows_5m_is_bit_exact_over_two_closes_of_10k_groups(whole,
                                                             reference):
    worker, got, _answer, _spans = whole
    assert worker.flows_seen == FLOWS
    want, per_slot = reference
    assert sorted(per_slot) == [T0, T0 + SLOT, T0 + 2 * SLOT]
    assert per_slot[T0] >= BIG and per_slot[T0 + SLOT] >= BIG
    assert got == want


def test_query_range_answers_what_the_sink_holds(whole):
    _worker, sink, answer, _spans = whole
    got: dict = {}
    for r in answer["rows"]:
        k = (int(r["timeslot"]), int(r["src_as"]), int(r["dst_as"]),
             int(r["etype"]))
        v = got.get(k, (0, 0, 0))
        got[k] = (v[0] + int(r["bytes"]), v[1] + int(r["packets"]),
                  v[2] + int(r["count"]))
    slots = {int(s) for s in answer["slots"]}
    assert slots
    assert got == {k: v for k, v in sink.items() if k[0] in slots}
    assert len(got) >= BIG


# ---- (b) the persistent form ------------------------------------------------------


def _aggregator(groups: int, slots=(T0,), seed=5) -> WindowAggregator:
    agg = WindowAggregator()
    rng = np.random.default_rng(seed)
    for slot in slots:
        keys = rng.integers(0, 2**32, (groups, agg.store_key_lanes),
                            dtype=np.uint64)
        agg.windows[slot] = WindowStore.from_rows(
            keys, rng.integers(0, 2**50, (groups, 3), dtype=np.uint64))
    agg.watermark = max(slots) + 17
    return agg


def _assert_stores_equal(got: dict, want: dict):
    assert sorted(got) == sorted(want)
    for slot, store in want.items():
        assert set(got[slot]) == set(store)
        for key, acc in store.items():
            have = got[slot][key]
            assert have.dtype == np.uint64
            assert have.tolist() == acc.tolist(), (slot, key)


@pytest.mark.parametrize("groups", [0, 100, BIG])
def test_saved_form_is_two_arrays_a_window_and_restores_equal(tmp_path,
                                                              groups):
    agg = _aggregator(groups, slots=(T0, T0 + SLOT))
    state = save_wagg_state(agg)
    assert [s["slot"] for s in state["stores"]] == [T0, T0 + SLOT]
    for s in state["stores"]:
        assert s["keys"].shape == (groups, agg.store_key_lanes)
        assert s["keys"].dtype == np.uint32
        assert s["sums"].shape == (groups, 3)
        assert s["sums"].dtype == np.uint64
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, {"models": {"flows_5m": state}})
    fresh = WindowAggregator()
    restore_wagg_state(fresh, load_checkpoint(path)["models"]["flows_5m"],
                       "flows_5m")
    _assert_stores_equal(fresh.windows, agg.windows)
    assert fresh.watermark == agg.watermark
    # the fold adds into what was restored, and a close turns it to rows
    key = next(iter(agg.windows[T0]), None)
    if key is not None:
        fresh._fold_rows(np.array([[T0, *key]], np.uint32),
                         np.array([[1, 2, 3]], np.uint64))
        assert (fresh.windows[T0][key] - agg.windows[T0][key]).tolist() \
            == [1, 2, 3]
    assert len(fresh.flush(force=True)["timeslot"]) == 2 * groups


def test_checkpoint_members_and_meta_do_not_grow_with_the_groups(tmp_path):
    sizes = {}
    for groups in (100, BIG):
        path = str(tmp_path / f"ckpt{groups}")
        save_checkpoint(path, {"models": {
            "flows_5m": save_wagg_state(_aggregator(groups))}})
        with np.load(os.path.join(path, "arrays.npz")) as z:
            sizes[groups] = (len(z.files), os.path.getsize(
                os.path.join(path, "meta.json")))
    assert sizes[100] == sizes[BIG]
    assert sizes[BIG][0] == 2  # one key array, one sums array


def _parents_form(agg) -> dict:
    """What ``save_wagg_state`` returned before this form: the store
    itself, an npz member a group and a JSON list a key once encoded."""
    return {"kind": "window_agg", "watermark": agg.watermark,
            "windows": {slot: dict(store.items())
                        for slot, store in agg.windows.items()}}


@pytest.mark.parametrize("form", ["parent", "arrays", "arrays_unsorted"])
def test_both_forms_restore_equal(tmp_path, form):
    agg = _aggregator(300, slots=(T0, T0 + SLOT))
    want = copy.deepcopy(agg.windows)
    state = _parents_form(agg) if form == "parent" else save_wagg_state(agg)
    if form == "arrays_unsorted":
        # as the builds wrote it whose store was a dict: rows in the
        # order the keys were first seen, not in key order
        for s in state["stores"]:
            order = np.random.default_rng(7).permutation(len(s["keys"]))
            s["keys"], s["sums"] = s["keys"][order], s["sums"][order]
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, {"models": {"flows_5m": state}})
    with np.load(os.path.join(path, "arrays.npz")) as z:
        assert len(z.files) == (600 if form == "parent" else 4)
    fresh = WindowAggregator()
    restore_wagg_state(fresh, load_checkpoint(path)["models"]["flows_5m"],
                       "flows_5m")
    _assert_stores_equal(fresh.windows, want)
    assert fresh.watermark == agg.watermark


@pytest.mark.parametrize("form", ["parent", "arrays"])
def test_a_store_of_another_key_layout_is_skipped_loudly(tmp_path, form):
    agg = _aggregator(5)
    lanes = agg.store_key_lanes
    store = agg.windows[T0]
    agg.windows = {T0: WindowStore.from_rows(store.key_rows[:, :-1],
                                             store.sums)}
    if form == "parent":
        state = _parents_form(agg)
    else:  # written by a build whose grouping had a lane less
        state = save_wagg_state(agg)
        assert state["stores"][0]["keys"].shape == (5, lanes - 1)
    path = str(tmp_path / "ckpt")
    save_checkpoint(path, {"m": state})
    fresh = WindowAggregator()
    restore_wagg_state(fresh, load_checkpoint(path)["m"], "flows_5m")
    assert fresh.windows == {} and fresh.watermark == agg.watermark


@pytest.fixture(scope="module")
def resumed(stream, tmp_path_factory):
    """Killed 14,768 flows into the second window, one batch after a
    checkpoint of the cadence, and started again on the same bus and
    directory: (the store as each checkpoint left it, the store the
    second worker restored, its sink's flows_5m by key, spans)."""
    tmp = tmp_path_factory.mktemp("as64k-resume")
    bus = _bus(stream)
    at_checkpoint, restored = {}, {}

    def note(worker):
        at_checkpoint[worker.flows_seen] = copy.deepcopy(
            worker.models["flows_5m"].windows)

    _run(bus, _argv(tmp), kill_at=KILL_AT, on_checkpoint=note)

    def note_restored(worker):
        restored[worker.flows_seen] = copy.deepcopy(
            worker.models["flows_5m"].windows)

    TRACER.configure("off")  # drop the first worker's spans
    _run(bus, _argv(tmp), on_start=note_restored)
    spans = TRACER.snapshot()
    TRACER.configure("off")
    return at_checkpoint, restored, _flows5m(tmp), spans


def test_a_mid_window_checkpoint_restores_key_for_key(resumed):
    at_checkpoint, restored, _sink, _spans = resumed
    (flows, store), = restored.items()
    # the newest checkpoint of the cadence, a batch before the kill
    assert flows == max(at_checkpoint) == KILL_AT - BATCH
    assert list(store) == [T0 + SLOT] and len(store[T0 + SLOT]) >= BIG
    _assert_stores_equal(store, at_checkpoint[flows])


def test_the_resumed_worker_ends_with_the_uninterrupted_runs_rows(resumed,
                                                                  whole):
    assert resumed[2] == whole[1]


# ---- (d) what the spans count -----------------------------------------------------


def _args(spans, name) -> list:
    return [s[5] for s in spans if s[0] == name]


def test_checkpoint_members_stay_while_the_store_grows(whole):
    spans = whole[3]
    states, sers = _args(spans, "wagg_state"), _args(spans, "ckpt_serialize")
    assert len(states) == len(sers) >= FLOWS // (BATCH * EVERY)
    one_window = [(st["groups"], se["members"])
                  for st, se in zip(states, sers) if st["windows"] == 1]
    groups = [g for g, _ in one_window]
    assert min(groups) < BIG / 2 and max(groups) >= BIG
    assert len({m for _, m in one_window}) == 1


def test_fold_and_close_spans_count_groups_and_rows(whole, reference):
    spans, per_slot = whole[3], reference[1]
    folds = _args(spans, "wagg_fold")
    assert max(f["groups"] for f in folds) > AS_COUNT
    assert max(f["store_groups"] for f in folds) >= BIG
    # every group of every window was new to its store once (a rate's
    # row of a key is a group of its own, so at least the rows emitted)
    assert all(0 <= f["inserted"] <= f["groups"] for f in folds)
    assert sum(f["inserted"] for f in folds) >= sum(per_slot.values())
    # a store that fills leaves a drain fewer groups to insert: the rest
    # are added into rows that are there (a poll is one partial since a
    # sub-window crossing runs no second step: no drain is a late
    # group's handful any more)
    assert min(f["inserted"] for f in folds) \
        < min(f["groups"] for f in folds) - AS_COUNT
    assert sorted(s["rows"] for s in _args(spans, "wagg_rows")) \
        == sorted(per_slot.values())
    assert sorted(s["rows"] for s in _args(spans, "flush")
                  if s["table"] == "flows_5m") == sorted(per_slot.values())


def test_a_restart_records_what_it_loaded(resumed):
    (load,) = _args(resumed[3], "ckpt_load")
    assert load["members"] > 0
    assert load["bytes"] > 4 * BIG * 4  # the keys alone
