"""ShardedPipeline (ISSUE 27): a poll under ``-processor.mesh`` is cut
once for every model, by row masks and not by copies, and every model's
own sharded program is dispatched through ``update_device_columns``.
Held here, on 4 of the 8 virtual CPU
devices, to the per-model loop it replaces in the worker's hot path
(``model.update(batch)`` for each model): the same batches give the same
``flows_5m`` rows, merged sketch state, top-K rows, late-row counters,
watermark, sub-window closes and alerts.

The model sets are built by ``cli._build_models`` from the processor's
own flags, as ``processor_main`` builds them.
"""

import logging

import numpy as np
import pytest

from flow_pipeline_tpu import cli
from flow_pipeline_tpu.engine.fused import FusedPipeline
from flow_pipeline_tpu.engine.worker import StreamWorker, WorkerConfig
from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
from flow_pipeline_tpu.models import heavy_hitter as hh_mod
from flow_pipeline_tpu.models.dense_top import _planes_to_uint64
from flow_pipeline_tpu.obs.trace import TRACER
from flow_pipeline_tpu.ops import cms as cms_ops
from flow_pipeline_tpu.parallel.pipeline import ShardedPipeline
from flow_pipeline_tpu.schema.batch import FlowBatch

CHIPS, BATCH = 4, 256
GB = CHIPS * BATCH                    # a global step is 1,024 rows
SLOT, SUB = 300, 10                   # the defaults: window, sub-window
T0 = 1_700_000_100                    # slot-aligned
FAMILIES = ("top_talkers", "top_src_ips", "top_dst_ips")
PORTS = ("top_src_ports", "top_dst_ports")
MODELS = ["flows_5m", *FAMILIES, *PORTS, "ddos_alerts"]


def _models(mesh=CHIPS, extra=()):
    fs = cli._processor_flags(cli._common_flags(cli.FlagSet("processor")))
    argv = ["-processor.batch", str(BATCH), "-sketch.width", "2048",
            "-sketch.capacity", "512", "-window.lateness", "0", *extra]
    if mesh:
        argv += ["-processor.mesh", str(mesh)]
    return cli._build_models(fs.parse(argv))


def _rows(n, seed, times) -> FlowBatch:
    """``n`` flows of a small key universe (no table fills, so the rows
    kept do not depend on which chip saw a key first) at ``times``."""
    batch = FlowGenerator(ZipfProfile(n_keys=300, alpha=1.1),
                          seed=seed).batch(n)
    batch.columns["time_received"] = np.asarray(times, dtype=np.uint64)
    return batch


def _steady(n, seed, at):
    return _rows(n, seed, np.full(n, at))


def _cut(n, seed, before, after, at=None):
    """Rows in time order that change from ``before`` to ``after`` at
    row ``at`` (the middle by default)."""
    at = n // 2 if at is None else at
    return _rows(n, seed, np.where(np.arange(n) < at, before, after))


# name -> the polled batches, in order. Every case starts inside the
# slot at T0 and ends with rows the next case does not share.
CASES = {
    "inside_one_slot": lambda: [
        _steady(GB, 1, T0 + 3), _steady(GB, 2, T0 + 4)],
    "straddles_a_slot_roll": lambda: [
        _steady(GB, 1, T0 + SLOT - 2),
        _cut(GB, 2, T0 + SLOT - 1, T0 + SLOT, at=300),
        _steady(GB, 3, T0 + SLOT + 1)],
    "straddles_a_sub_window": lambda: [
        _steady(GB, 1, T0 + SUB - 1),
        _cut(GB, 2, T0 + SUB - 1, T0 + SUB, at=700),
        _steady(GB, 3, T0 + SUB + 1)],
    "late_rows_of_a_closed_slot": lambda: [
        _steady(GB, 1, T0 + SLOT + 5),
        # time runs backwards here: 100 rows of the slot before
        _cut(GB, 2, T0 + SLOT + 5, T0 + 7, at=GB - 100),
        _steady(GB, 3, T0 + SLOT + 6)],
    "part_full_last_global_step": lambda: [
        _steady(GB, 1, T0 + 3), _steady(GB // 2 + 37, 2, T0 + 4)],
    "several_global_steps": lambda: [
        _cut(3 * GB + 100, 1, T0 + SUB - 1, T0 + SUB, at=GB + 50),
        _cut(2 * GB, 2, T0 + SLOT - 1, T0 + SLOT, at=GB + 9)],
}


def _drive(models, batches, update):
    """Feed ``batches`` through ``update``; what a worker would read of
    the models afterwards, and at every poll on the way."""
    polls = []
    for batch in batches:
        update(batch)
        ddos = models["ddos_alerts"]
        polls.append({
            "watermark": models["flows_5m"].watermark,
            "late": {n: models[n].late_flows_dropped
                     for n in MODELS[1:]},
            "slot": {n: models[n].current_slot for n in FAMILIES + PORTS},
            "sub": ddos.current_sub, "folds": ddos.folds,
        })
    ddos = models["ddos_alerts"]
    out = {
        "polls": polls,
        "alerts": list(ddos.alerts),
        "entropy": (ddos.entropy, ddos.entropy_baseline),
        "ddos": _merged_detector(ddos.state),
        "merged": {}, "ports": {}, "closed": {},
    }
    for name in FAMILIES:
        merged = models[name].model.merged_state()
        out["merged"][name] = {f: np.asarray(getattr(merged, f))
                               for f in merged._fields}
    for name in PORTS:
        out["ports"][name] = _planes_to_uint64(  # the chips' sum
            np.asarray(models[name].model.totals)).sum(axis=0)
    for name in FAMILIES + PORTS:  # the windows closed on the way
        out["closed"][name] = models[name].flush(force=False)
    out["flows_5m"] = models["flows_5m"].flush(force=True)
    return out


def _merged_detector(state) -> dict:
    """The stacked detector state as a sub-window's close would merge
    it: the open sub-window's rates summed over the chips and chip 0's
    replica of what a close folds. Not the witness: a bucket's witness
    is the destination with the largest sum within one chip's rows of
    one step, which is a property of where rows sit."""
    s = {f: np.asarray(getattr(state, f)) for f in state._fields}
    out = {f: s[f][0] for f in ("mean", "var", "seen", "hist")}
    out["rates"] = s["rates"].sum(axis=0)
    return out


def _per_model(models, batches):
    def update(batch):
        for model in models.values():
            model.update(batch)

    return _drive(models, batches, update)


def _sorted_table(keys, vals):
    """A candidate table's live rows, ordered by key: the rows a chip
    holds sit where its admissions put them."""
    keys, vals = np.asarray(keys), np.asarray(vals)
    live = ~np.all(keys == np.uint32(0xFFFFFFFF), axis=1)
    order = np.lexsort(keys[live].T[::-1])
    return keys[live][order], vals[live][order]


def _own_cells(batches, key_cols, shape) -> np.ndarray:
    """The cells of a [planes, depth, width] sketch that at most one key
    of the stream hashes to. The planes are updated conservatively (a
    cell rises to its key's least estimate plus the addend), so a cell
    that two keys share holds what their order of arrival on one chip
    made of it; a cell with one key holds that chip's exact sum."""
    cols = {c: np.concatenate([b.columns[c] for b in batches])
            for c in key_cols}
    keys = np.unique(np.asarray(hh_mod._key_lanes(cols, key_cols)), axis=0)
    _, depth, width = shape
    buckets = np.asarray(cms_ops.cms_buckets(keys, depth, width))
    return np.stack([np.bincount(row, minlength=width) <= 1
                     for row in buckets])


def _same_float(a, b):
    # float32 planes: rows that sit on another chip are summed in
    # another order
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=0)


def _same_top(got: dict, want: dict):
    """Two windows' top-K rows: the same keys with the same sums (rows
    ordered by key: two sums a rounding apart may rank either way)."""
    assert set(got) == set(want)

    def by_key(top):
        live = np.asarray(top["valid"], bool)
        ints = [np.asarray(top[c])[live].reshape(int(live.sum()), -1)
                for c in sorted(top)
                if np.asarray(top[c]).dtype.kind in "iu"]
        order = np.lexsort(np.concatenate(ints, axis=1).T[::-1])
        return {c: np.asarray(top[c])[live][order] for c in top}

    got, want = by_key(got), by_key(want)
    for col in got:
        if got[col].dtype.kind == "f":
            _same_float(got[col], want[col])
        else:
            np.testing.assert_array_equal(got[col], want[col])


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_pipeline_gives_what_the_per_model_loop_gives(case):
    batches = CASES[case]()
    want = _per_model(_models(), batches)
    models = _models()
    pipeline = ShardedPipeline(models)
    got = _drive(models, batches, pipeline.update)
    _assert_same(got, want, batches, models)


def _assert_same(got, want, batches, models):
    assert got["polls"] == want["polls"]
    assert got["alerts"] == want["alerts"]
    assert got["entropy"] == pytest.approx(want["entropy"], rel=1e-6)
    # flows_5m: exact, row for row
    assert set(got["flows_5m"]) == set(want["flows_5m"])
    rows = lambda t: sorted(zip(*(np.asarray(t[c]).tolist()
                                  for c in sorted(t))))
    assert rows(got["flows_5m"]) == rows(want["flows_5m"])
    assert len(got["flows_5m"]["timeslot"]) > 0
    # the port planes are integers: identical
    for name in PORTS:
        np.testing.assert_array_equal(got["ports"][name],
                                      want["ports"][name])
    # merged sketches: the same keys hold the same sums
    for name in FAMILIES:
        g, w = got["merged"][name], want["merged"][name]
        own = _own_cells(batches, models[name].config.key_cols,
                         g["cms"].shape)
        assert own.mean() > 0.9 and g["cms"].any()
        _same_float(g["cms"][:, own], w["cms"][:, own])
        gk, gv = _sorted_table(g["table_keys"], g["table_vals"])
        wk, wv = _sorted_table(w["table_keys"], w["table_vals"])
        np.testing.assert_array_equal(gk, wk)
        _same_float(gv, wv)
    for f in got["ddos"]:
        if got["ddos"][f].dtype.kind == "f":
            _same_float(got["ddos"][f], want["ddos"][f])
        else:
            np.testing.assert_array_equal(got["ddos"][f], want["ddos"][f])
    # the windows that closed on the way: the same top-K rows
    for name in FAMILIES + PORTS:
        assert len(got["closed"][name]) == len(want["closed"][name])
        for g, w in zip(got["closed"][name], want["closed"][name]):
            _same_top(g, w)


def _two_partition_polls(n_polls: int, rate: int = 512) -> list:
    """Polls of one global step each, alternately from two partitions
    that share the positions round-robin; the clock advances a second
    every ``rate`` positions (a poll spans 4 s of it), starts 14 s before
    a slot's end, and a tenth of the flows lie 1-3 s behind it."""
    n = n_polls * GB
    pos = np.arange(n)
    h = (pos * 2654435761 + 12345) % 1000
    times = (T0 + SLOT - 14 + pos // rate
             - np.where(h < 100, 1 + h % 3, 0))
    whole = _rows(n, 5, times)
    parts = [np.flatnonzero(pos % 2 == p) for p in (0, 1)]
    return [FlowBatch({k: v[parts[p][at:at + GB]]
                       for k, v in whole.columns.items()}, p)
            for at in range(0, n // 2, GB) for p in (0, 1)]


@pytest.mark.parametrize("lateness, drops", [(8, False), (2, True)])
def test_late_rows_fold_into_held_replicas_under_the_mesh(lateness, drops):
    """`-window.lateness` under `-processor.mesh`: the held unit is a
    second set of stacked replicas, a late group runs the family's own
    sharded programs on it, and the deferred close merges it over the
    chips. Held to the per-model loop, poll by poll, and to the plain
    reference's counts."""
    from flow_pipeline_tpu.models.oracle import late_unit_sums

    batches = _two_partition_polls(14)
    extra = ["-window.lateness", str(lateness)]
    want = _per_model(_models(extra=extra), batches)
    models = _models(extra=extra)
    assert {models[n].lateness for n in MODELS[1:]} == {lateness}
    pipeline = ShardedPipeline(models)
    got = _drive(models, batches, pipeline.update)
    _assert_same(got, want, batches, models)
    slots = late_unit_sums(batches, SLOT, lateness, ["src_port"])
    subs = late_unit_sums(batches, SUB, lateness, ["dst_addr"],
                          ["packets"])
    ports, ddos = models["top_src_ports"], models["ddos_alerts"]
    assert (ports.late_flows_dropped, ports.late_flows_folded) == (
        slots["dropped"], slots["folded"])
    assert (ddos.late_flows_dropped, ddos.late_flows_folded) == (
        subs["dropped"], subs["folded"])
    assert (slots["dropped"] + subs["dropped"] > 0) == drops
    assert slots["folded"] > 0 and subs["folded"] > 0
    # the slot before the roll closed on the way, late rows and all
    (closed,) = got["closed"]["top_src_ports"]
    exact = slots["units"][T0]
    live = np.asarray(closed["valid"], bool)
    assert int(closed["timeslot"][0]) == T0
    counts = dict(zip(exact["src_port"].tolist(), exact["count"].tolist()))
    top = dict(zip(np.asarray(closed["src_port"])[live].tolist(),
                   np.asarray(closed["count"])[live].tolist()))
    assert len(top) == 100 and all(counts[k] == n for k, n in top.items())


def test_the_cases_hold_what_their_names_say():
    """A close, a sub-window close, late rows and a part-full step do
    happen in the cases named for them."""
    def after(case):
        models = _models()
        return _drive(models, CASES[case](),
                      ShardedPipeline(models).update)

    assert after("inside_one_slot")["polls"][-1]["folds"] == 0
    roll = after("straddles_a_slot_roll")
    assert all(len(v) == 1 for v in roll["closed"].values())
    assert roll["polls"][-1]["slot"]["top_talkers"] == T0 + SLOT
    assert after("straddles_a_sub_window")["polls"][-1]["folds"] == 1
    late = after("late_rows_of_a_closed_slot")["polls"][-1]["late"]
    assert set(late.values()) == {100}
    several = after("several_global_steps")
    assert several["polls"][-1]["folds"] >= 2
    assert all(len(v) == 1 for v in several["closed"].values())


# ---- spans ------------------------------------------------------------------


def _spans_of(batches):
    """The worker-thread spans of each ``update``, a list a poll."""
    models = _models()
    pipeline = ShardedPipeline(models)
    TRACER.configure("always")
    try:
        polls = []
        for batch in batches:
            before = len(TRACER.snapshot())
            pipeline.update(batch)
            polls.append(TRACER.snapshot()[before:])
    finally:
        TRACER.configure("off")
    return polls


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _dispatches(monkeypatch):
    """Count ``update_device_columns`` calls by model name."""
    from flow_pipeline_tpu.parallel import sharded

    calls = []
    for cls in (sharded.ShardedHeavyHitter, sharded.ShardedDenseTopK,
                sharded.ShardedDDoSDetector,
                sharded.ShardedWindowAggregator):
        real = cls.update_device_columns

        def spy(self, cols, *a, _real=real, **kw):
            calls.append((self.name, tuple(sorted(cols))))
            return _real(self, cols, *a, **kw)

        monkeypatch.setattr(cls, "update_device_columns", spy)
    return calls


def test_a_poll_is_cut_once_and_every_model_is_placed_once(monkeypatch):
    calls = _dispatches(monkeypatch)
    (poll,) = _spans_of([_steady(2 * GB + 10, 1, T0 + 3)])
    shards = _named(poll, "mesh_shard")
    # a placement is still a model's own: steps x models of them, each
    # read by one program (PERF.md 6, PR 27 says what one for all gave)
    assert len(shards) == 3 * len(MODELS)
    assert all(s[5]["models"] == 1 for s in shards)
    for s in shards:
        assert len(s[5]["chip_rows"]) == CHIPS
        assert sum(s[5]["chip_rows"]) == s[5]["rows"]
    per_model = {}
    for s in shards:
        per_model.setdefault(s[5]["model"], []).append(s[5]["rows"])
    assert per_model == {n: [GB, GB, 10] for n in MODELS}
    assert shards[-1][5]["chip_rows"] == [10, 0, 0, 0]
    (update,) = _named(poll, "mesh_update")
    assert update[5] == {"model": MODELS, "steps": 3}
    for s in shards:  # mesh_shard nests in mesh_update
        assert update[1] <= s[1] and s[2] <= update[2]
    (split,) = _named(poll, "mesh_split")
    assert split[5] == {"parts": 1, "copied_rows": 0}
    assert split[2] <= update[1]
    # every model's program got its own columns, flows_5m first
    assert [name for name, _ in calls[:3]] == ["flows_5m"] * 3
    assert sorted({name for name, _ in calls}) == sorted(MODELS)
    assert len(calls) == 3 * len(MODELS)
    want = {name: tuple(sorted(m.input_cols)) for name, m in
            [(n, getattr(m, "model", m)) for n, m in _models().items()]}
    assert all(cols == want[name] for name, cols in calls)


def test_a_poll_that_crosses_a_sub_window_runs_only_the_detector_twice(
        monkeypatch):
    calls = _dispatches(monkeypatch)
    warm, poll = _spans_of([_steady(GB, 1, T0 + SUB - 1),
                            _cut(GB, 2, T0 + SUB - 1, T0 + SUB)])
    del warm
    (split,) = _named(poll, "mesh_split")
    assert split[5] == {"parts": 2, "copied_rows": 0}
    # the second part reads the first's placement under another mask
    shards = _named(poll, "mesh_shard")
    assert sorted(s[5]["model"] for s in shards) == sorted(MODELS)
    first, second = _named(poll, "mesh_update")
    assert first[5] == {"model": MODELS, "steps": 1}
    assert second[5] == {"model": ["ddos_alerts"], "steps": 1}
    assert all(first[1] <= s[1] and s[2] <= first[2] for s in shards)
    (merge,) = _named(poll, "mesh_merge")  # the sub-window's close
    assert first[2] <= merge[1] and merge[2] <= second[1]
    per_model = {n: sum(1 for name, _ in calls[len(MODELS):] if name == n)
                 for n in MODELS}
    assert per_model == dict({n: 1 for n in MODELS}, ddos_alerts=2)


def test_a_poll_that_crosses_a_slot_runs_flows_5m_once(monkeypatch):
    calls = _dispatches(monkeypatch)
    _warm, poll = _spans_of([_steady(GB, 1, T0 + SLOT - 1),
                             _cut(GB, 2, T0 + SLOT - 1, T0 + SLOT)])
    (split,) = _named(poll, "mesh_split")
    assert split[5] == {"parts": 2, "copied_rows": 0}
    assert len(_named(poll, "mesh_shard")) == len(MODELS)
    first, second = _named(poll, "mesh_update")
    assert first[5]["model"] == MODELS
    assert second[5]["model"] == MODELS[1:]
    per_model = {n: sum(1 for name, _ in calls[len(MODELS):] if name == n)
                 for n in MODELS}
    assert per_model == dict({n: 2 for n in MODELS}, flows_5m=1)


# ---- the worker's choice ----------------------------------------------------


def _worker(models):
    """(the worker, what it logged while it chose its pipeline)."""
    lines = []

    class Capture(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    # the flowtpu root logger does not propagate; attach directly
    logger = logging.getLogger("flowtpu.worker")
    handler = Capture(level=logging.INFO)
    logger.addHandler(handler)
    try:
        worker = StreamWorker(None, models, [], WorkerConfig(prefetch=0))
    finally:
        logger.removeHandler(handler)
    return worker, lines


def test_a_sharded_set_gets_the_sharded_pipeline():
    worker, lines = _worker(_models())
    assert type(worker.fused) is ShardedPipeline
    assert not any("per-model" in line for line in lines)


def test_a_plain_set_gets_the_fused_pipeline():
    worker, lines = _worker(
        _models(mesh=0, extra=["-processor.hostassist", "off"]))
    assert isinstance(worker.fused, FusedPipeline)
    assert not any("per-model" in line for line in lines)


@pytest.mark.parametrize("odd", ["mixed", "unknown", "two_meshes"])
def test_a_set_it_does_not_know_keeps_the_per_model_loop(odd):
    models = _models()
    if odd == "mixed":
        models["top_src_ports"] = _models(mesh=0)["top_src_ports"]
    elif odd == "unknown":
        class Other:
            def update(self, batch):
                pass

        models["other"] = Other()
    else:
        models["ddos_alerts"] = _models(mesh=2)["ddos_alerts"]
    assert not ShardedPipeline.supported(models)
    worker, lines = _worker(models)
    assert worker.fused is None
    assert "model set not fusable; using per-model updates" in lines
