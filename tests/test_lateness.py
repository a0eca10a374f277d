"""``-window.lateness`` for the families whose closed state cannot reopen
(``models/held.py``): the ranked tables' windows and the detector's
sub-windows hold the unit that rolled open for its late rows.

Held to the plain reference ``models.oracle.late_unit_sums`` on seeded
streams dealt to two partitions with NEXmark-style delay, polled
alternately: the per-model path and ``FusedPipeline`` (the mesh:
tests/test_mesh_pipeline.py). With lateness 0 every row, counter and the
lowered text of the fused step equal the parent commit's, recorded from
it in ``tests/data/lateness0_parent.json`` by this file run as a script
(the step's text re-recorded at PR 45, which took the padding slots out
of the count-min scatters on purpose: the four digests of rows, counters
and detector state stayed what PR 39's parent left).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import sys

import jax
import numpy as np
import pytest

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
from flow_pipeline_tpu.schema.batch import FlowBatch
from flow_pipeline_tpu.transport import Consumer, InProcessBus

BS = 256
WINDOW = 60        # a ranked table's window, s
SUB = 10           # the detector's sub-window, s
RATE = 128         # flows a second of event time
T0 = 6000          # slot-aligned for both
KEYS = 48          # under every table's capacity: sums are exact
FIVE = ("src_addr", "dst_addr", "src_port", "dst_port", "proto")
TABLES = {"top_talkers": FIVE, "top_src_ips": ("src_addr",),
          "top_dst_ips": ("dst_addr",), "top_src_ports": ("src_port",)}
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "lateness0_parent.json")


def make_models(lateness: int, sub_seconds: int = SUB) -> dict:
    """The cli's default families at test scale, every windowed one at
    ``lateness`` (cli._build_models)."""
    def hh(key_cols):
        return WindowedHeavyHitter(
            HeavyHitterConfig(key_cols=key_cols, batch_size=BS,
                              width=1 << 10, capacity=128),
            window_seconds=WINDOW, k=128, lateness=lateness,
            slide_name="hh")

    return {
        "flows_5m": WindowAggregator(WindowAggConfig(
            window_seconds=WINDOW, batch_size=BS,
            allowed_lateness=lateness)),
        "top_talkers": hh(FIVE),
        "top_src_ips": hh(("src_addr",)),
        "top_dst_ips": hh(("dst_addr",)),
        "top_src_ports": WindowedHeavyHitter(
            DenseTopConfig(key_col="src_port", batch_size=BS),
            window_seconds=WINDOW, k=128, model_cls=DenseTopKModel,
            lateness=lateness),
        "ddos_alerts": DDoSDetector(DDoSConfig(
            n_buckets=1 << 10, sub_window_seconds=sub_seconds,
            warmup_windows=0, batch_size=BS), lateness=lateness),
    }


def _mix(x: np.ndarray, salt: int) -> np.ndarray:
    """splitmix64's finalizer over ``x + salt``: a hash a position."""
    u = np.uint64
    x = x.astype(u) + u(salt)
    x = (x ^ (x >> u(30))) * u(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> u(27))) * u(0x94D049BB133111EB)
    return x ^ (x >> u(31))


def two_partition_polls(seed: int, n_flows: int, delayed_share=0.1,
                        delay_max=3) -> list[FlowBatch]:
    """``n_flows`` flows whose clock advances a second every RATE
    positions, a tenth of them 1..``delay_max`` s behind it, dealt
    position mod 2 to two partitions and polled alternately, BS flows a
    poll: what a worker on upstream's two-partition topic folds."""
    gen = FlowGenerator(ZipfProfile(n_keys=KEYS, alpha=1.1), seed=seed)
    whole = gen.batch(n_flows)
    pos = np.arange(n_flows)
    h = _mix(pos, seed * 2 + 1)
    late = (h % np.uint64(1000)) < np.uint64(int(delayed_share * 1000))
    by = 1 + ((h >> np.uint64(20)) % np.uint64(delay_max)).astype(np.int64)
    whole.columns["time_received"] = (
        T0 + pos // RATE - np.where(late, by, 0)).astype(np.uint64)
    polls = []
    parts = [np.flatnonzero(pos % 2 == p) for p in (0, 1)]
    for at in range(0, max(len(p) for p in parts), BS):
        for p, idx in enumerate(parts):
            rows = idx[at:at + BS]
            if len(rows):
                polls.append(FlowBatch(
                    {k: v[rows] for k, v in whole.columns.items()}, p))
    return polls


def record_detector(det: DDoSDetector) -> list:
    """[(sub-window, the sum of its rates)] as the detector closes them:
    all its state says of which rows a sub-window admitted."""
    closed, inner = [], det._emit_alerts

    def emit(z, rates, hist, addrs):
        closed.append((det.current_sub, float(np.asarray(rates).sum())))
        return inner(z, rates, hist, addrs)

    det._emit_alerts = emit
    return closed


def drive(models: dict, polls: list, fused: bool) -> dict:
    """Fold ``polls`` and end the stream. Returns each table's rows a
    window, in closing order with the poll that closed them, the
    detector's sub-windows and every counter."""
    pipe = FusedPipeline(models) if fused else None
    closed_subs = record_detector(models["ddos_alerts"])
    windows = {name: [] for name in TABLES}
    for i, poll in enumerate(polls):
        if pipe is not None:
            pipe.update(poll)
        else:
            for m in models.values():
                m.update(poll)
        for name in TABLES:
            windows[name] += [(i, w) for w in models[name].flush()]
    for name in TABLES:
        windows[name] += [(len(polls), w)
                          for w in models[name].flush(force=True)]
    models["ddos_alerts"].close_sub_window()
    return {"windows": windows, "subs": closed_subs,
            "dropped": {n: models[n].late_flows_dropped
                        for n in (*TABLES, "ddos_alerts")},
            "folded": {n: models[n].late_flows_folded
                       for n in (*TABLES, "ddos_alerts")}}


def _key_tuples(cols: dict, key_cols, rows) -> list[tuple]:
    parts = [np.asarray(cols[c])[rows].reshape(len(rows), -1)
             for c in key_cols]
    return [tuple(int(x) for p in parts for x in p[i])
            for i in range(len(rows))]


def table_sums(top: dict, key_cols) -> dict:
    """{key tuple: (bytes, packets, count)} of one closed window."""
    rows = np.flatnonzero(top["valid"])
    keys = _key_tuples(top, key_cols, rows)
    return {k: (int(top["bytes"][r]), int(top["packets"][r]),
                int(top["count"][r])) for k, r in zip(keys, rows)}


def oracle_sums(exact: dict, key_cols) -> dict:
    rows = np.arange(len(exact["count"]))
    keys = _key_tuples(exact, key_cols, rows)
    return {k: (int(exact["bytes"][r]), int(exact["packets"][r]),
                int(exact["count"][r])) for k, r in zip(keys, rows)}


def assert_matches_oracle(got: dict, polls: list, lateness: int) -> dict:
    """Every table's windows and the detector's sub-windows admit exactly
    the rows the plain reference admits, close in its order and in the
    poll it names; the counters are its counts. Returns the reference's
    answer for the windows."""
    from flow_pipeline_tpu.models.oracle import late_unit_sums

    for name, key_cols in TABLES.items():
        want = late_unit_sums(polls, WINDOW, lateness, list(key_cols))
        wins = got["windows"][name]
        assert [int(w["timeslot"][0]) for _i, w in wins] == want["order"]
        assert [i for i, _w in wins] == [want["closed_at"][u]
                                         for u in want["order"]]
        for _i, w in wins:
            unit = int(w["timeslot"][0])
            assert table_sums(w, key_cols) == oracle_sums(
                want["units"][unit], key_cols), (name, unit)
        assert got["dropped"][name] == want["dropped"], name
        assert got["folded"][name] == want["folded"], name
    subs = late_unit_sums(polls, SUB, lateness, ["dst_addr"], ["packets"])
    assert [s for s, _r in got["subs"]] == subs["order"]
    for sub, rate_sum in got["subs"]:
        exact = subs["units"].get(sub)
        assert rate_sum == (float(exact["packets"].sum())
                            if exact else 0.0), sub
    assert got["dropped"]["ddos_alerts"] == subs["dropped"]
    assert got["folded"]["ddos_alerts"] == subs["folded"]
    return late_unit_sums(polls, WINDOW, lateness, list(FIVE))


# ---- the plain reference itself ---------------------------------------------


def _rows(times, partition=0) -> FlowBatch:
    b = FlowBatch.empty(len(times))
    b.columns["time_received"] = np.asarray(times, np.uint64)
    b.columns["bytes"] = np.arange(1, len(times) + 1, dtype=np.uint64)
    b.columns["src_port"] = np.full(len(times), 7, np.uint32)
    b.partition = partition
    return b


@pytest.mark.parametrize("lateness, dropped, folded, order, closed_at", [
    # a poll's rows are taken oldest unit first, so the 8 beside 10, 11
    # and the 12 beside 21 are in time; the roll closes unit 0 at once
    # and the 9 that comes a poll later is dropped
    (0, 1, 0, [0, 10, 20], {0: 1, 10: 3, 20: 4}),
    # unit 0 is held until the watermark reaches 10 + 3: poll 2 brings 13
    (3, 0, 1, [0, 10, 20], {0: 2, 10: 4, 20: 4}),
    # lateness past the unit's length: the roll to 20 closes unit 0 first
    (30, 0, 1, [0, 10, 20], {0: 3, 10: 4, 20: 4}),
])
def test_reference_semantics_by_hand(lateness, dropped, folded, order,
                                     closed_at):
    from flow_pipeline_tpu.models.oracle import late_unit_sums

    polls = [_rows([1, 2, 9]), _rows([10, 11, 8], 1), _rows([13, 9], 0),
             _rows([21, 12], 1)]
    got = late_unit_sums(polls, 10, lateness, ["src_port"], ["bytes"])
    assert (got["dropped"], got["folded"]) == (dropped, folded)
    assert got["order"] == order and got["closed_at"] == closed_at
    counts = {u: int(g["count"].sum()) for u, g in got["units"].items()}
    assert sum(counts.values()) == 10 - dropped


def test_reference_drops_rows_older_than_the_held_unit():
    from flow_pipeline_tpu.models.oracle import late_unit_sums

    polls = [_rows([5]), _rows([15]), _rows([25]), _rows([6, 16], 1)]
    got = late_unit_sums(polls, 10, 8, ["src_port"], ["bytes"])
    # 25 rolls: unit 0 (held) closes, 10 is held; the row at 6 is older
    assert (got["dropped"], got["folded"]) == (1, 1)
    assert got["order"] == [0, 10, 20]


# ---- two partitions with delay, against the reference ------------------------


@pytest.mark.parametrize("fused", [False, True],
                         ids=["per-model", "fused"])
@pytest.mark.parametrize("lateness, drops", [(0, True), (8, False),
                                             (2, True)],
                         ids=["L0", "within", "exceeded"])
@pytest.mark.parametrize("seed", [11, 3700001001])
def test_two_partitions_against_the_reference(seed, lateness, drops,
                                              fused):
    """A poll spans 4 s of the clock and a flow lies up to 3 s behind
    it, so lateness 8 covers everything, 2 does not, and 0 is the drop
    of before: whichever it is, what is admitted, dropped and when each
    unit closes are the plain reference's."""
    polls = two_partition_polls(seed % 2**31, 3 * WINDOW * RATE // 2)
    got = drive(make_models(lateness), polls, fused)
    assert_matches_oracle(got, polls, lateness)
    dropped = sum(got["dropped"].values())
    assert (dropped > 0) == drops
    if lateness == 8:
        assert all(v > 0 for v in got["folded"].values())


@pytest.mark.parametrize("lateness", [8, 2, 0],
                         ids=["within", "exceeded", "L0"])
def test_fused_equals_the_per_model_path_bit_for_bit(lateness):
    """Beyond the sums: est columns, ranks, the detector's state and
    every model's counters. Two polls in five hold rows of two of the
    detector's sub-windows: the fused path cuts a family at its own
    unit only, as the per-model path does (the tables take such a poll
    in one step, the other sub-window's rows the detector's own program
    alone), so nothing may differ, whatever the lateness holds or
    drops."""
    polls = two_partition_polls(5, 2 * WINDOW * RATE)
    a, b = make_models(lateness), make_models(lateness)
    fa, fb = drive(a, polls, True), drive(b, polls, False)
    for name in TABLES:
        assert len(fa["windows"][name]) == len(fb["windows"][name])
        for (ia, wa), (ib, wb) in zip(fa["windows"][name],
                                      fb["windows"][name]):
            assert ia == ib
            for col in wa:
                np.testing.assert_array_equal(np.asarray(wa[col]),
                                              np.asarray(wb[col]), col)
    assert fa["subs"] == fb["subs"]
    assert fa["dropped"] == fb["dropped"]
    assert fa["folded"] == fb["folded"]
    if lateness == 8:
        assert all(v > 0 for v in fa["folded"].values())
    for xa, xb in zip(jax.tree.leaves(a["ddos_alerts"].state),
                      jax.tree.leaves(b["ddos_alerts"].state)):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


def test_close_lands_in_the_poll_that_passes_the_lateness():
    """Whichever partition brings the first flow ``lateness`` past a
    unit's end, that poll's flush holds the unit's rows: the close
    check.py models (``close_lateness_s``) and the one flows_5m makes."""
    polls = two_partition_polls(9, 2 * WINDOW * RATE)
    lateness, end = 8, T0 + WINDOW
    newest = np.maximum.accumulate(
        [int(p.columns["time_received"].max()) for p in polls])
    first = int(np.argmax(newest >= end + lateness))
    for closing_partition in (0, 1):
        if polls[first].partition != closing_partition:
            # let the other partition bring it: its poll of the same
            # positions comes first
            polls[first], polls[first + 1] = polls[first + 1], polls[first]
            newest = np.maximum.accumulate(
                [int(p.columns["time_received"].max()) for p in polls])
            first = int(np.argmax(newest >= end + lateness))
        assert polls[first].partition == closing_partition
        models = make_models(lateness)
        got = drive(models, polls, True)
        at = {int(w["timeslot"][0]): i
              for i, w in got["windows"]["top_talkers"]}
        assert at[T0] == first
        # flows_5m closes its window in the same poll
        wagg = make_models(lateness)["flows_5m"]
        for i, p in enumerate(polls):
            wagg.update(p)
            if T0 in wagg.flush()["timeslot"]:
                assert i == first
                break
        else:
            raise AssertionError("flows_5m never closed the window")


@pytest.mark.parametrize("fused", [False, True],
                         ids=["per-model", "fused"])
def test_a_roll_over_a_held_unit(fused):
    """A jump in event time, and a lateness past the detector's
    sub-window: the roll closes the unit still held first, so closes
    stay in order and never more than two units are alive."""
    polls = two_partition_polls(4, WINDOW * RATE)
    jump = two_partition_polls(6, 4 * BS)
    for p in jump:  # three windows ahead, no flow between
        p.columns["time_received"] = (
            p.columns["time_received"] + np.uint64(4 * WINDOW))
    back = two_partition_polls(8, 2 * BS)  # far too late: dropped
    polls = polls + jump + back
    models = make_models(25)  # > SUB: every sub-window roll finds one held
    got = drive(models, polls, fused)
    assert_matches_oracle(got, polls, 25)
    slots = [int(w["timeslot"][0]) for _i, w in
             got["windows"]["top_talkers"]]
    assert slots == sorted(slots) and len(slots) == 4
    assert got["dropped"]["top_talkers"] == 2 * BS
    subs = [s for s, _r in got["subs"]]
    assert subs == sorted(subs)
    assert models["ddos_alerts"].folds == len(subs)


def test_sub_windows_are_scored_in_order_against_the_same_baselines():
    """The detector's baselines after a stream with late rows folded are
    those of the same rows fed in event-time order at lateness 0: n is
    scored before n + 1, against the baselines n - 1 left."""
    polls = two_partition_polls(12, WINDOW * RATE)
    held = make_models(9)["ddos_alerts"]
    for p in polls:
        held.update(p)
    held.close_sub_window()
    whole = {k: np.concatenate([p.columns[k] for p in polls])
             for k in polls[0].columns}
    order = np.argsort(whole["time_received"], kind="stable")
    plain = make_models(0)["ddos_alerts"]
    for at in range(0, len(order), BS):
        rows = order[at:at + BS]
        plain.update(FlowBatch({k: v[rows] for k, v in whole.items()}))
    plain.close_sub_window()
    assert held.late_flows_dropped == 0 and held.late_flows_folded > 0
    assert held.folds == plain.folds
    for name in ("mean", "var", "seen", "hist"):
        np.testing.assert_allclose(
            np.asarray(getattr(held.state, name)),
            np.asarray(getattr(plain.state, name)), rtol=1e-6,
            err_msg=name)


# ---- lateness 0 is the parent, bit for bit -----------------------------------


def _digest(obj) -> str:
    h = hashlib.sha256()

    def feed(x):
        if isinstance(x, dict):
            for k in sorted(x):
                h.update(str(k).encode())
                feed(x[k])
        elif isinstance(x, (list, tuple)):
            for y in x:
                feed(y)
        elif isinstance(x, (str, int, float, bool)) or x is None:
            h.update(repr(x).encode())
        else:
            a = np.ascontiguousarray(np.asarray(x))
            h.update(str(a.dtype).encode() + str(a.shape).encode())
            h.update(a.tobytes())

    feed(obj)
    return h.hexdigest()


def step_text(pipe: FusedPipeline) -> str:
    """The lowered text of ``pipe``'s fused step."""
    def shape(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    padded, mask = FlowBatch.empty(0).pad_to(pipe._bs)
    cols = {k: shape(v)
            for k, v in padded.device_columns(pipe._cols).items()}
    states = jax.tree_util.tree_map(shape, (
        tuple(w.model.state for _, w in pipe._hh),
        tuple(w.model.totals for _, w in pipe._dense),
        tuple(d.state for _, d in pipe._ddos)))
    valid = shape(mask)
    return pipe._step.lower(states, cols, valid, valid, valid).as_text()


def lateness0_record() -> dict:
    """What tests/test_fused.py's streams leave behind at lateness 0,
    and the fused step's lowered text: run from the parent commit to
    record, from this tree to compare. Uses nothing the parent lacks."""
    sys.path.insert(0, os.path.dirname(__file__))
    import test_fused as tf

    out = {"jax": jax.__version__}
    for case, sub in (("aligned", tf.WINDOW), ("ddos10", 10)):
        for path in ("fused", "serial"):
            models = tf.make_models(sub, 100)
            (tf.drive_fused if path == "fused" else tf.drive_serial)(
                models, tf.make_stream())
            rows = {"flows_5m": models["flows_5m"].flush(force=True)}
            for name, m in models.items():
                if isinstance(m, WindowedHeavyHitter):
                    rows[name] = m.flush(force=True)
            det = models["ddos_alerts"]
            det.close_sub_window()
            rows["alerts"] = [{k: v for k, v in a.items()}
                              for a in det.alerts]
            rows["ddos_state"] = det.state._asdict()
            rows["late"] = {n: int(getattr(m, "late_flows_dropped", 0))
                            for n, m in models.items()}
            out[f"{case}.{path}"] = _digest(rows)
    text = step_text(FusedPipeline(tf.make_models(10, 100)))
    out["step_text_sha256"] = hashlib.sha256(text.encode()).hexdigest()
    out["step_text_lines"] = text.count("\n")
    return out


def test_lateness_0_is_the_parent_bit_for_bit():
    with open(RECORDED) as f:
        recorded = json.load(f)
    if recorded["jax"] != jax.__version__:
        pytest.skip(f"recorded under jax {recorded['jax']}; the lowered "
                    f"text and float order are that version's")
    assert lateness0_record() == recorded


def test_the_step_is_the_same_program_whatever_the_lateness():
    """Lateness is the host lifecycle's: the step that folds a late
    group into a held state is the text of the step at lateness 0."""
    a, b = FusedPipeline(make_models(0)), FusedPipeline(make_models(5))
    assert step_text(a) == step_text(b)


# ---- checkpoint: a crash while units are held ---------------------------------


class CollectSink:
    def __init__(self):
        self.rows: dict = {}

    def write(self, table, rows):
        self.rows.setdefault(table, []).append(rows)


def bus_of(polls) -> InProcessBus:
    from flow_pipeline_tpu.schema import wire

    bus = InProcessBus()
    bus.create_topic("flows", 2)
    for p in polls:
        bus.produce_many("flows", list(wire.iter_raw_frames(p.to_wire())),
                         partition=p.partition)
    return bus


def worker_on(bus, path, sink, lateness=8) -> StreamWorker:
    return StreamWorker(
        Consumer(bus, fixedlen=True), make_models(lateness), [sink],
        WorkerConfig(poll_max=BS, snapshot_every=1, checkpoint_path=path,
                     host_assist="off", prefetch=0))


def sink_tables(*sinks) -> dict:
    """{table: {timeslot: the last rows written for it}}."""
    out: dict = {}
    for sink in sinks:
        for table, writes in sink.rows.items():
            if table in TABLES:
                for w in writes:
                    out.setdefault(table, {})[int(w["timeslot"][0])] = w
    return out


def test_a_crash_while_a_window_and_a_sub_window_are_held(tmp_path):
    """A checkpoint taken while both are held carries both held states:
    a restart from it writes the rows of the uninterrupted run, and the
    detector's baselines come out equal."""
    from flow_pipeline_tpu.engine.checkpoint import load_checkpoint
    from flow_pipeline_tpu.obs.trace import TRACER

    polls = two_partition_polls(21, 2 * WINDOW * RATE)
    whole_sink = CollectSink()
    whole = worker_on(bus_of(polls), str(tmp_path / "a"), whole_sink)
    assert isinstance(whole.fused, FusedPipeline)
    whole.run(stop_when_idle=True)
    whole.finalize()

    bus, path = bus_of(polls), str(tmp_path / "b")
    first_sink, second_sink = CollectSink(), CollectSink()
    first = worker_on(bus, path, first_sink)
    TRACER.configure("always")
    try:
        for done in range(1, len(polls)):
            first.run_once()
            m, d = first.models["top_talkers"], first.models["ddos_alerts"]
            if m.held_unit == T0 and d.held_unit is not None:
                break
        spans = TRACER.snapshot()
    finally:
        TRACER.configure("off")
    assert m.held_unit == T0 and d.held_unit == T0 + WINDOW - SUB
    # the batch's checkpoint saw four tables' windows and a sub-window
    assert [s[5]["held_units"] for s in spans
            if s[0] == "ckpt_state"][-1] == 5
    snap = load_checkpoint(path)
    assert snap["models"]["top_talkers"]["held"]["unit"] == T0
    assert snap["models"]["ddos_alerts"]["held"]["unit"] == d.held_unit
    # the process dies here: no finalize. The next one's polls go on
    # alternating where these stopped (another order is another stream)
    second = worker_on(bus, path, second_sink)
    assert second.restore()
    second.consumer._rr_idx = done
    assert second.models["top_src_ports"].held_unit == T0
    assert second.models["ddos_alerts"].held_unit == d.held_unit
    second.run(stop_when_idle=True)
    second.finalize()
    want, got = sink_tables(whole_sink), sink_tables(first_sink,
                                                     second_sink)
    assert sorted(want) == sorted(TABLES)
    for table in TABLES:
        assert sorted(got[table]) == sorted(want[table])
        for slot, w in want[table].items():
            for col in w:
                np.testing.assert_array_equal(
                    np.asarray(w[col]), np.asarray(got[table][slot][col]),
                    err_msg=f"{table} timeslot {slot} column {col!r}")
    for name in (*TABLES, "ddos_alerts"):
        assert whole.models[name].late_flows_dropped == 0 == \
            second.models[name].late_flows_dropped
    for xa, xb in zip(jax.tree.leaves(whole.models["ddos_alerts"].state),
                      jax.tree.leaves(second.models["ddos_alerts"].state)):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


def test_a_held_checkpoint_restored_at_lateness_0_closes_at_once(tmp_path):
    polls = two_partition_polls(21, 2 * WINDOW * RATE)
    bus, path = bus_of(polls), str(tmp_path / "c")
    first = worker_on(bus, path, CollectSink())
    while first.models["top_talkers"].held_unit != T0:
        first.run_once()
    sink = CollectSink()
    second = worker_on(bus, path, sink, lateness=0)
    assert second.restore()
    assert second.models["top_talkers"].held_unit == T0
    second.run_once()
    assert second.models["top_talkers"].held_unit is None
    assert T0 in sink_tables(sink)["top_talkers"]


# ---- start-up says where a family cannot ---------------------------------------


def test_the_flag_reaches_every_windowed_family():
    from flow_pipeline_tpu.cli import _build_models, _processor_flags
    from flow_pipeline_tpu.utils.flags import FlagSet

    fs = _processor_flags(FlagSet("processor"))
    vals = fs.parse(["-processor.batch", "512", "-window.lateness", "5"])
    models = _build_models(vals)
    assert models["flows_5m"].config.allowed_lateness == 5
    assert {n: m.lateness for n, m in models.items()
            if n != "flows_5m"} == {
        "top_talkers": 5, "top_src_ips": 5, "top_dst_ips": 5,
        "top_src_ports": 5, "top_dst_ports": 5, "ddos_alerts": 5}
    assert FusedPipeline.supported(models)


class _Said(logging.Handler):
    """What the program's loggers said while it is attached (they do not
    propagate to the root logger caplog listens on)."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.lines: list = []

    def emit(self, record):
        self.lines.append(record.getMessage())

    def __enter__(self):
        logging.getLogger("flowtpu").addHandler(self)
        return self.lines

    def __exit__(self, *exc):
        logging.getLogger("flowtpu").removeHandler(self)


@pytest.mark.parametrize("argv, words", [
    (["-window.slide", "30"], "-window.slide"),
    (["-sketch.backend", "host"], "-sketch.backend host"),
])
def test_where_a_family_cannot_hold_start_up_says_so(argv, words):
    from flow_pipeline_tpu.cli import _build_models, _processor_flags
    from flow_pipeline_tpu.utils.flags import FlagSet

    fs = _processor_flags(FlagSet("processor"))
    vals = fs.parse(["-processor.batch", "512", "-window.lateness", "5",
                     *argv])
    with _Said() as lines:
        models = _build_models(vals)
    said = [line for line in lines if "-window.lateness 5" in line]
    assert said and words in said[0] and "late_flows_dropped" in said[0]
    assert models["flows_5m"].config.allowed_lateness == 5
    assert all(m.lateness == 0 for n, m in models.items()
               if n != "flows_5m")


def test_the_host_grouped_dataplane_says_so_and_runs_at_0():
    with _Said() as lines:
        worker = StreamWorker(
            Consumer(bus_of([]), fixedlen=True), make_models(5), [],
            WorkerConfig(poll_max=BS, host_assist="on"))
    assert type(worker.fused).__name__ == "HostGroupPipeline"
    assert all(getattr(m, "lateness", 0) == 0
               for m in worker.models.values())
    assert any("HostGroupPipeline" in line and "late_flows_dropped" in line
               for line in lines)


def test_spans_and_gauges_of_a_late_group():
    from flow_pipeline_tpu.obs import REGISTRY
    from flow_pipeline_tpu.obs.trace import TRACER

    polls = two_partition_polls(31, WINDOW * RATE + 8 * BS)
    worker = StreamWorker(
        Consumer(bus_of(polls), fixedlen=True), make_models(8),
        [CollectSink()],
        WorkerConfig(poll_max=BS, snapshot_every=0, host_assist="off"))
    TRACER.configure("always")
    try:
        worker.run(stop_when_idle=True)
        spans = TRACER.snapshot()
    finally:
        TRACER.configure("off")
    steps = [s[5] for s in spans if s[0] == "step_dispatch"]
    alone = [s[5] for s in spans if s[0] == "detector_dispatch"]
    assert {"open", "held"} <= {s["hh_unit"] for s in steps}
    # a poll's older sub-window, held more often than not, takes the
    # detector's own program; the newest rides the step
    assert {"open", "held"} <= {s["dd_unit"] for s in alone}
    assert "open" in {s["dd_unit"] for s in steps}
    assert all(0 < s["rows"] <= s["padded"] == BS for s in alone)
    # every row the worker applied rode one step, and one of the two
    # kinds of dispatch for the detector
    applied = sum(s[5]["rows"] for s in spans if s[0] == "apply")
    assert sum(s["rows"] for s in steps) == applied
    assert (sum(s["dd_rows"] for s in steps)
            + sum(s["rows"] for s in alone)) == applied
    closes = [s[5] for s in spans if s[0] == "held_close"]
    tables = [c for c in closes if c["model"] == "hh" and c["unit"] == T0]
    assert tables and all(c["late_rows"] > 0 and c["held_ms"] > 0
                          for c in tables)
    assert any(c["model"] == "ddos" for c in closes)
    applies = [s[5] for s in spans if s[0] == "apply"]
    assert all(a["skew_s"] >= 0 and a["watermark"] >= T0
               for a in applies) and max(a["skew_s"]
                                         for a in applies) <= 8
    text = REGISTRY.render()
    assert 'late_flows_folded{model="top_talkers"}' in text
    assert 'late_flows_folded{model="ddos_alerts"}' in text


if __name__ == "__main__":
    # record the parent: PYTHONPATH=<a checkout of the parent commit>
    # python tests/test_lateness.py > tests/data/lateness0_parent.json
    json.dump(lateness0_record(), sys.stdout, indent=1)
