"""The host-pair family ``top_pairs`` (``-model.pairs``, ISSUE 42) on the
normal path, with what its deployment brings: three sampling rates and
both address families in every window.

(a) ``cli.processor_main`` on the in-process bus, the fused step and the
per-model path: every ranked table's bytes are the u64 oracle's
(``models.oracle.topk_exact`` ranked by ``bytes * sampling_rate``) and
``flows_5m``'s ``*_scaled`` columns are ``exact_groupby``'s, exactly (the
stream is sized so that every float32 sum is exact: rates are powers of
two and a key's sum in units of 2^10 stays under 2^24); (b) the
three-member chain of the fused step (``src_addr`` < pair < 5-tuple on
one sort) gives each member the rows its own group-by gives; (c) with
the flag off every existing configuration's step lowers to the parent's
text, byte for byte (``tests/data/pairs_off_parent.json``, recorded from
the parent commit by this file run as a script; re-recorded at PR 45,
which changed every configuration's step on purpose); (d) the flag builds the
family under ``-window.slide``, ``-window.lateness`` and
``-processor.mesh`` like the others; (e) the spans say what they
counted. Counts only: nothing here is timed.
"""

import hashlib
import ipaddress
import json
import os
import sqlite3
import sys

import jax
import numpy as np
import pytest

from flow_pipeline_tpu import cli, transport
from flow_pipeline_tpu.engine import (FusedPipeline, StreamWorker,
                                      WindowedHeavyHitter)
from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
from flow_pipeline_tpu.models import HeavyHitterConfig
from flow_pipeline_tpu.models.oracle import exact_groupby, topk_exact
from flow_pipeline_tpu.models.window_agg import _distinct_rates
from flow_pipeline_tpu.obs.trace import TRACER, _Span
from flow_pipeline_tpu.schema import wire
from flow_pipeline_tpu.schema.batch import FlowBatch
from flow_pipeline_tpu.transport import InProcessBus
from flow_pipeline_tpu.utils.flags import FlagSet

sys.path.insert(0, os.path.dirname(__file__))
from test_lateness import step_text as _step_text  # noqa: E402

BATCH, EVERY = 2048, 3
FLOWS, RATE = 12_000, 20              # 6,000 flows to a 300 s window
T0 = 1_700_000_100                    # slot-aligned
KEYS = 200                            # under every table's capacity (256)
RATES = np.array([1024, 2048, 4096], np.uint64)
FIVE = ("src_addr", "dst_addr", "src_port", "dst_port", "proto")
TABLES = {"top_talkers": FIVE, "top_pairs": ("src_addr", "dst_addr"),
          "top_src_ips": ("src_addr",), "top_dst_ips": ("dst_addr",)}
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "pairs_off_parent.json")
CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "benchmark", "configs")


@pytest.fixture(scope="module")
def stream() -> FlowBatch:
    """A seeded Zipf stream whose keys are of both families (a v4 host is
    left-padded: three zero words) and carry one of three rates each;
    one source address is seen under several rates."""
    batch = FlowGenerator(ZipfProfile(n_keys=KEYS, alpha=1.1),
                          seed=42).batch(FLOWS)
    cols = batch.columns
    v4 = (cols["src_addr"][:, 3] ^ cols["dst_addr"][:, 3]) % 5 < 3
    for name in ("src_addr", "dst_addr"):
        words = cols[name].copy()
        words[v4, :3] = 0
        cols[name] = words
    cols["etype"] = np.where(v4, 0x0800, 0x86DD).astype(cols["etype"].dtype)
    cols["sampling_rate"] = RATES[cols["src_port"] % 3]
    cols["time_received"] = (T0 + np.arange(FLOWS) // RATE).astype(np.uint64)
    assert 0.2 < v4.mean() < 0.8
    return batch


def _argv(tmp, *more):
    return ["-processor.backend", "cpu", "-processor.hostassist", "off",
            "-processor.batch", str(BATCH), "-sketch.width", "4096",
            "-sketch.capacity", "256", "-flush.count", str(EVERY),
            "-window.lateness", "0", "-obs.trace", "always",
            "-metrics.addr", "", "-listen.feed", "127.0.0.1:0",
            "-model.pairs=true", "-model.ports=false", "-model.ddos=false",
            "-sink", f"sqlite:{tmp / 'sink.db'}",
            "-checkpoint.path", str(tmp / "ckpt"), *more]


def _run(stream, argv):
    """One ``processor_main`` on a bus that holds ``stream``, to its end
    (the operator's interrupt, drained through ``finalize``)."""
    bus = InProcessBus()
    bus.create_topic("flows", 1)
    bus.produce_many("flows", wire.iter_raw_frames(stream.to_wire()),
                     partition=0)
    run_once, seen = StreamWorker.run_once, {}

    def run_once_(worker):
        seen["worker"] = worker
        if worker.flows_seen >= FLOWS:
            raise KeyboardInterrupt
        return run_once(worker)

    with pytest.MonkeyPatch.context() as m:
        m.setattr(transport, "InProcessBus", lambda: bus)
        m.setattr(StreamWorker, "run_once", run_once_)
        assert cli.processor_main(argv) == 0
    return seen["worker"]


@pytest.fixture(scope="module", params=["fused", "per_model"])
def ran(request, stream, tmp_path_factory):
    """(path, the sink, the worker, its spans) of one run a path."""
    tmp = tmp_path_factory.mktemp(request.param)
    more = [] if request.param == "fused" else ["-processor.fused=false"]
    TRACER.configure("always")
    worker = _run(stream, _argv(tmp, *more))
    spans = TRACER.snapshot()
    TRACER.configure("off")
    return request.param, tmp / "sink.db", worker, spans


def _words(text: str) -> tuple:
    """A sink's printed address back to its four lanes (a dotted quad is
    a v4 address in the trailing four bytes)."""
    a = ipaddress.ip_address(text)
    return tuple(int(a).to_bytes(16, "big")[i:i + 4] for i in range(0, 16, 4))


def _sink_rows(db, table: str, key_cols) -> dict:
    """{timeslot: [(key lanes, bytes)] in rank order}."""
    con = sqlite3.connect(db)
    try:
        out: dict = {}
        for row in con.execute(
                f"SELECT timeslot, {', '.join(key_cols)}, bytes FROM {table} "
                f"ORDER BY timeslot, rank"):
            key = tuple(_words(v) if c.endswith("_addr") else int(v)
                        for c, v in zip(key_cols, row[1:-1]))
            out.setdefault(int(row[0]), []).append((key, int(row[-1])))
        return out
    finally:
        con.close()


def _oracle_rows(stream, key_cols) -> dict:
    """{timeslot: {key lanes: exact bytes x rate}}."""
    exact = exact_groupby(stream, list(key_cols), ["bytes"], timeslot=True,
                          scale_col="sampling_rate")
    sums: dict = {}
    for i, slot in enumerate(exact["timeslot"].tolist()):
        key = tuple(
            tuple(int(w).to_bytes(4, "big") for w in exact[c][i])
            if c.endswith("_addr") else int(exact[c][i]) for c in key_cols)
        sums.setdefault(slot, {})[key] = int(exact["bytes_scaled"][i])
    return sums


@pytest.mark.parametrize("table", TABLES)
def test_a_ranked_tables_bytes_are_the_u64_oracles(ran, stream, table):
    path, db, worker, _spans = ran
    assert (type(worker.fused).__name__ if worker.fused else None) == (
        "FusedPipeline" if path == "fused" else None)
    key_cols = TABLES[table]
    got = _sink_rows(db, table, key_cols)
    want = _oracle_rows(stream, key_cols)
    assert sorted(got) == sorted(want) and len(got) == 2
    for slot, rows in got.items():
        assert len(rows) == min(100, len(want[slot]))
        # every emitted row carries the exact corrected sum of its key
        assert all(want[slot][key] == value for key, value in rows)
        # in rank order, and the oracle's largest keys are all there
        values = [v for _k, v in rows]
        assert values == sorted(values, reverse=True)
        floor = values[-1]
        assert {k for k, v in want[slot].items() if v > floor} <= {
            k for k, _v in rows}
        assert max(values) > 1 << 24  # past what a float32 counts by ones


def test_topk_exact_ranks_by_the_scaled_sum(stream):
    """The oracle's own ranking: by bytes x rate where asked, by raw bytes
    otherwise, and the two orders differ on this stream."""
    pair = ["src_addr", "dst_addr"]
    raw = topk_exact(stream, pair, 20)
    scaled = topk_exact(stream, pair, 20, scale_col="sampling_rate")
    assert "bytes_scaled" in scaled and "bytes_scaled" not in raw
    assert list(np.diff(scaled["bytes_scaled"].astype(np.int64)) <= 0) \
        == [True] * 19
    assert list(np.diff(raw["bytes"].astype(np.int64)) <= 0) == [True] * 19
    assert not np.array_equal(raw["src_addr"], scaled["src_addr"])
    whole = exact_groupby(stream, pair, ["bytes"], timeslot=False,
                          scale_col="sampling_rate")
    assert scaled["bytes_scaled"][0] == whole["bytes_scaled"].max()


def test_flows_5m_scaled_columns_are_exact_under_three_rates(ran, stream):
    _path, db, _worker, _spans = ran
    exact = exact_groupby(stream, ["src_as", "dst_as", "etype"],
                          timeslot=True, scale_col="sampling_rate")
    want = {tuple(int(exact[c][i]) for c in ("timeslot", "src_as", "dst_as",
                                             "etype")):
            tuple(int(exact[c][i]) for c in (
                "bytes", "packets", "count", "bytes_scaled",
                "packets_scaled"))
            for i in range(len(exact["timeslot"]))}
    con = sqlite3.connect(db)
    try:
        got = {tuple(r[:4]): tuple(r[4:]) for r in con.execute(
            "SELECT timeslot, src_as, dst_as, etype, SUM(bytes), "
            "SUM(packets), SUM(count), SUM(bytes_scaled), "
            "SUM(packets_scaled) FROM flows_5m GROUP BY 1, 2, 3, 4")}
    finally:
        con.close()
    assert got == want
    assert {k[3] for k in got} == {0x0800, 0x86DD}
    # not a sum times one rate: the groups mix rates
    assert any(v[3] not in {v[0] * int(r) for r in RATES}
               for v in got.values())


def test_the_spans_say_what_they_counted(ran, stream):
    path, _db, worker, spans = ran
    closes = [s[5] for s in spans if s[0] == "window_close"]
    assert {c["model"] for c in closes} >= set(TABLES)
    assert all(c["bytes_max"] > 1 << 24 for c in closes if c["rows"])
    folds = [s[5] for s in spans if s[0] == "wagg_fold"]
    assert folds and max(f["rates"] for f in folds) == 3
    if path != "fused":
        return
    builds = [s[5] for s in spans if s[0] == "lane_build"]
    assert sum(b["v4_rows"] for b in builds) == int(
        (stream.columns["etype"] == 0x0800).sum())
    assert worker.fused.hh_families == tuple(TABLES)
    said = [s[5] for s in spans
            if s[0] == "ckpt_state" and "hh_admitted" in s[5]]
    counted = [a["hh_admitted"] for a in said]
    assert counted and all(set(c) == set(TABLES) for c in counted)
    # the first checkpoint finds tables that were empty: every key is new
    assert all(0 < n <= 256 for n in counted[0].values())
    assert all(0 <= n <= 256 for c in counted for n in c.values())
    # every device step fed the tables, and each is counted once
    steps = sum(s[0] == "step_dispatch" for s in spans)
    assert steps - EVERY < sum(a["hh_steps"] for a in said) <= steps
    assert said[0]["hh_steps"] >= EVERY


# ---- (b) the three-member chain ----------------------------------------------


def _chain_models(bs: int) -> dict:
    def hh(key_cols):
        return WindowedHeavyHitter(
            HeavyHitterConfig(key_cols=key_cols, batch_size=bs,
                              width=1 << 10, capacity=128), k=50)

    return {name: hh(cols) for name, cols in TABLES.items()}


def test_a_three_member_chain_gives_the_rows_three_own_groupbys_give(stream):
    bs = 512
    batches = [stream.slice(i, i + bs) for i in range(0, 8 * bs, bs)]
    fused, serial = _chain_models(bs), _chain_models(bs)
    pipe = FusedPipeline(fused)
    for b in batches:
        pipe.update(b)
        for m in serial.values():
            m.update(b)
    for name in TABLES:
        a, b = fused[name].flush(force=True), serial[name].flush(force=True)
        assert len(a) == len(b) >= 1
        for wa, wb in zip(a, b):
            assert sorted(wa) == sorted(wb)
            for col in wa:
                np.testing.assert_array_equal(
                    np.asarray(wa[col]), np.asarray(wb[col]), err_msg=col)


def test_a_read_counts_the_keys_the_tables_took_since_the_last(stream):
    """Counted on the host from the tables' keys at two reads, under a
    recorder that keeps it: the first read finds every key new, a read
    after the same flows none, and a recorder that is off is told
    nothing (no key crosses to the host for it)."""
    bs = 512
    models = _chain_models(bs)
    pipe = FusedPipeline(models)
    pipe.update(stream.slice(0, bs))
    assert "hh_admitted" not in pipe.hh_live()
    TRACER.configure("ring")
    try:
        first = pipe.hh_live()
        distinct = {name: len(np.unique(np.concatenate(
            [stream.slice(0, bs).columns[c].reshape(bs, -1) for c in cols],
            axis=1), axis=0)) for name, cols in TABLES.items()}
        assert first["hh_admitted"] == {n: min(d, 128)
                                        for n, d in distinct.items()}
        assert first["hh_steps"] == 1
        pipe.update(stream.slice(0, bs))   # the same flows: nothing is new
        again = pipe.hh_live()
        assert set(again["hh_admitted"].values()) == {0}
        assert again["hh_steps"] == 1
        pipe.update(stream.slice(bs, 2 * bs))
        pipe.update(stream.slice(2 * bs, 3 * bs))
        later = pipe.hh_live()
        assert later["hh_steps"] == 2
        assert all(0 < later["hh_admitted"][n] < first["hh_admitted"][n]
                   for n in TABLES)
        assert pipe.hh_live()["hh_steps"] == 0   # no step since
    finally:
        TRACER.configure("off")


@pytest.mark.parametrize("lanes, want", [
    ([], 0), ([[]], 0), ([[1, 1, 1]], 1), ([[1, 1], [], [1]], 1),
    ([[1024, 4096, 1024], [2048]], 3), ([[1], [2]], 2)])
def test_a_drains_rates_are_counted_over_all_its_parts(lanes, want):
    parts = [np.stack([np.arange(len(lane)), np.asarray(lane, np.int64)],
                      axis=1).astype(np.uint32) for lane in lanes]
    assert _distinct_rates(parts) == want


def test_a_recorder_that_is_off_is_not_counted_for(stream):
    """`lane_build`, `wagg_fold` and `ckpt_state` are told the new args
    only by a recorder that keeps spans: with it off nothing is counted
    (`span()` hands back the args it was given)."""
    bs, told = 512, {}

    def span(name, chunk=None, **args):
        return _Span(TRACER, name, chunk, told.setdefault(name, args))

    pipe = FusedPipeline(_chain_models(bs))
    with pytest.MonkeyPatch.context() as m:
        m.setattr(TRACER, "span", span)
        pipe.update(stream.slice(0, bs))
    assert TRACER.mode == "off" and not TRACER.recording
    assert told["lane_build"] == {"rows": bs, "padded": bs}
    assert "hh_admitted" not in pipe.hh_live()


# ---- (c) the flag off: the parent's step ---------------------------------------


def _models_of(flags: list) -> dict:
    fs = cli._processor_flags(cli._common_flags(FlagSet("processor")))
    return cli._build_models(fs.parse(
        [*flags, "-processor.hostassist", "off"]))


EXISTING = ("default-estate", "estate-as64k", "estate-sliding",
            "estate-2part")


def pairs_off_record() -> dict:
    """sha256 of the lowered step of each existing configuration's own
    flags at its real size: run from the parent commit to record, from
    this tree to compare. Uses nothing the parent lacks."""
    out = {"jax": jax.__version__}
    for name in EXISTING:
        with open(os.path.join(CONFIGS, name + ".json")) as f:
            flags = json.load(f)["processor_flags"]
        text = _step_text(FusedPipeline(_models_of(flags)))
        out[name] = hashlib.sha256(text.encode()).hexdigest()
    return out


def test_with_the_flag_off_every_configuration_lowers_to_the_parents_step():
    with open(RECORDED) as f:
        recorded = json.load(f)
    if recorded["jax"] != jax.__version__:
        pytest.skip(f"recorded under jax {recorded['jax']}; the lowered "
                    f"text is that version's")
    assert pairs_off_record() == recorded


def test_the_flag_changes_the_step_and_nothing_else_of_the_model_set():
    base = ["-processor.batch", "256"]
    off, on = _models_of(base), _models_of([*base, "-model.pairs=true"])
    assert list(on) == ["flows_5m", "top_talkers", "top_pairs",
                        "top_src_ips", "top_dst_ips", "top_src_ports",
                        "top_dst_ports", "ddos_alerts"]
    assert [n for n in on if n != "top_pairs"] == list(off)
    assert on["top_pairs"].config.key_cols == ("src_addr", "dst_addr")
    assert on["top_pairs"].config == HeavyHitterConfig(
        batch_size=256, width=off["top_talkers"].config.width,
        capacity=off["top_talkers"].config.capacity)
    assert _step_text(FusedPipeline(off)) == _step_text(FusedPipeline(
        _models_of([*base, "-model.pairs=false"])))
    assert _step_text(FusedPipeline(on)) != _step_text(FusedPipeline(off))


# ---- (d) it honours what the other families honour -----------------------------


def test_the_family_slides_holds_late_rows_and_shards_like_the_others():
    base = ["-processor.batch", "256", "-model.pairs=true"]
    slid = _models_of([*base, "-window.slide", "30"])
    assert slid["top_pairs"].ring is not None
    assert slid["top_pairs"].slot_seconds == 30 == slid[
        "top_talkers"].slot_seconds
    held = _models_of([*base, "-window.lateness", "7"])
    assert held["top_pairs"].lateness == 7 == held["top_talkers"].lateness
    from flow_pipeline_tpu.parallel import ShardedHeavyHitter
    from flow_pipeline_tpu.parallel.pipeline import ShardedPipeline

    mesh = _models_of([*base, "-processor.mesh", "4"])
    assert type(mesh["top_pairs"].model) is ShardedHeavyHitter
    assert ShardedPipeline.supported(mesh)


if __name__ == "__main__":
    # record the parent: PYTHONPATH=<a checkout of the parent commit>
    # python tests/test_pairs.py > tests/data/pairs_off_parent.json
    json.dump(pairs_off_record(), sys.stdout, indent=1)
