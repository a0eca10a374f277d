"""The four-chip host (ISSUE 26): ``processor -processor.mesh 4`` through
``cli.processor_main`` on 4 of the 8 virtual CPU devices, at a small
size, against a plain numpy reference on a seeded stream.

(a) ``flows_5m`` is bit-exact and every ranked table's top 20 is within
1x10^-5, both ways; (b) the four per-chip shares add up to the one-chip run's
whole (the port planes exactly, the conservatively updated CMS planes as
far as they can); (c) checkpoint, kill and restore under the mesh gives the
uninterrupted run's rows; (d) no offset is committed ahead of a flush;
(e) the mesh spans and the per-chip row counter are recorded under the
names of docs/OBSERVABILITY.md, and every sharded program carries its
family's and model's name. The worker runs ``ShardedPipeline`` (ISSUE 27:
one cut a poll for every model; tests/test_mesh_pipeline.py holds it to
the per-model loop).

Nothing of the program is replaced: ``processor_main`` is given a bus
that already holds the stream (``-listen.feed`` makes it build one), and
``StreamWorker.run_once`` is wrapped to end the loop the way an
operator's interrupt does, or the way a kill does.
"""

import os
import sqlite3
import tempfile
import zipfile

import numpy as np
import pytest

from flow_pipeline_tpu import cli, transport
from flow_pipeline_tpu.engine.worker import StreamWorker
from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
from flow_pipeline_tpu.obs.trace import TRACER
from flow_pipeline_tpu.parallel import sharded
from flow_pipeline_tpu.schema import wire
from flow_pipeline_tpu.sink.base import _addr_str
from flow_pipeline_tpu.transport import InProcessBus

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CHIPS, BATCH = 4, 512                 # a global batch is 2,048 rows
FLOWS, RATE, SLOT = 24_000, 24, 300   # 7,200 flows to a window: 3 closes
T0 = 1_700_000_100                    # slot-aligned
LIMIT, TOP_N = 1e-5, 20
RANKED = {"top_talkers": ("src_addr", "dst_addr", "src_port", "dst_port",
                          "proto"),
          "top_src_ips": ("src_addr",), "top_dst_ips": ("dst_addr",),
          "top_src_ports": ("src_port",), "top_dst_ports": ("dst_port",)}
MESH_SPANS = {"mesh_split": ("parts", "copied_rows"),
              "mesh_update": ("model", "steps"),
              "mesh_shard": ("model", "models", "rows", "chip_rows",
                             "bytes"),
              "mesh_merge": ("model", "bytes"),
              "mesh_drain": ("partials", "left")}
MODELS = ["flows_5m", "top_talkers", "top_src_ips", "top_dst_ips",
          "top_src_ports", "top_dst_ports", "ddos_alerts"]


def _argv(tmp, mesh=CHIPS):
    argv = ["-processor.backend", "cpu", "-processor.hostassist", "off",
            "-processor.batch", str(BATCH), "-sketch.width", "4096",
            "-sketch.capacity", "256", "-flush.count", "3",
            "-window.lateness", "0", "-obs.trace", "always",
            "-metrics.addr", "", "-listen.feed", "127.0.0.1:0",
            "-sink", f"sqlite:{tmp / 'sink.db'}",
            "-checkpoint.path", str(tmp / "ckpt")]
    return argv + (["-processor.mesh", str(mesh)] if mesh else [])


@pytest.fixture(scope="module")
def stream():
    batch = FlowGenerator(ZipfProfile(n_keys=2000, alpha=1.1),
                          seed=26).batch(FLOWS)
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


class Harness:
    """One ``processor_main`` on ``bus``, ended after ``stop_at`` flows:
    by the operator's interrupt (drained through ``finalize``) or by a
    kill. Records, in order, every commit and every sink write."""

    def __init__(self, bus, monkeypatch):
        self.bus, self.monkeypatch = bus, monkeypatch
        self.worker = None
        self.events: list = []  # ("commit", next_offset) | ("write", table,
        #                          timeslots)

    def run(self, argv, stop_at=FLOWS, kill=False, before_stop=None):
        harness = self
        run_once = StreamWorker.run_once
        commit, write_rows = InProcessBus.commit, StreamWorker._write_rows

        def run_once_(worker):
            harness.worker = worker
            if worker.flows_seen >= stop_at:
                if before_stop is not None:
                    before_stop(worker)
                raise Killed if kill else KeyboardInterrupt
            return run_once(worker)

        def commit_(bus, group, topic, partition, next_offset):
            harness.events.append(("commit", int(next_offset)))
            return commit(bus, group, topic, partition, next_offset)

        def write_rows_(worker, table, rows, *a, **kw):
            out = write_rows(worker, table, rows, *a, **kw)
            slots = rows.get("timeslot", ()) if isinstance(rows, dict) \
                else ()
            harness.events.append(
                ("write", table, sorted({int(s) for s in slots})))
            return out

        with self.monkeypatch.context() as m:
            m.setattr(transport, "InProcessBus", lambda: self.bus)
            m.setattr(StreamWorker, "run_once", run_once_)
            m.setattr(StreamWorker, "_write_rows", write_rows_)
            m.setattr(InProcessBus, "commit", commit_)
            try:
                assert cli.processor_main(argv) == 0
            except Killed:
                assert kill
                # the worker's flight recorder dumps on the way down
                dump = os.path.join(tempfile.gettempdir(),
                                    f"flowtrace-worker-{os.getpid()}.json")
                if os.path.isfile(dump):
                    os.remove(dump)
            else:
                assert not kill
        return self.worker


def _sink(tmp) -> dict:
    """{table: sorted row tuples} of the run's sqlite sink."""
    con = sqlite3.connect(tmp / "sink.db")
    try:
        tables = [r[0] for r in con.execute(
            "SELECT name FROM sqlite_master WHERE type='table'")]
        return {t: sorted(con.execute(f"SELECT * FROM {t}").fetchall(),
                          key=repr) for t in tables}
    finally:
        con.close()


@pytest.fixture(scope="module")
def whole(stream, tmp_path_factory):
    """The uninterrupted mesh run: (worker, sink rows, events, spans,
    the sharded programs' names)."""
    tmp = tmp_path_factory.mktemp("mesh4")
    names = []
    real = sharded._program

    def spy(name):
        names.append(name)
        return real(name)

    with pytest.MonkeyPatch.context() as m:
        m.setattr(sharded, "_program", spy)
        h = Harness(_bus(stream), m)
        worker = h.run(_argv(tmp))
        spans = TRACER.snapshot()
    TRACER.configure("off")
    return worker, _sink(tmp), h.events, spans, names


# ---- the plain reference ----------------------------------------------------


def _slots(stream):
    ts = stream.columns["time_received"].astype(np.int64)
    return ts // SLOT * SLOT


def _key_strings(stream, cols) -> list:
    """One printable key a flow, as the sink prints it."""
    parts = []
    for c in cols:
        v = stream.columns[c]
        parts.append([_addr_str(w) for w in v] if v.ndim == 2
                     else [str(int(x)) for x in v])
    return list(zip(*parts))


def _exact_bytes(stream, cols) -> dict:
    """{slot: {key: exact bytes}}."""
    out: dict = {}
    nbytes = stream.columns["bytes"]
    for slot, key, b in zip(_slots(stream), _key_strings(stream, cols),
                            nbytes):
        per = out.setdefault(int(slot), {})
        per[key] = per.get(key, 0) + int(b)
    return out


def test_it_ran_the_sharded_pipeline_on_four_devices(whole):
    from flow_pipeline_tpu.parallel.pipeline import ShardedPipeline

    worker, sink, _events, _spans, _names = whole
    assert type(worker.fused) is ShardedPipeline
    assert worker.flows_seen == FLOWS
    mesh = worker.models["top_talkers"].model.mesh
    assert len({str(d) for d in mesh.devices.flat}) == CHIPS
    assert worker.config.poll_max == CHIPS * BATCH
    assert len({r[0] for r in sink["flows_5m"]}) == 4  # 3 closes + the end


def test_flows_5m_is_bit_exact(whole, stream):
    c = stream.columns
    want: dict = {}
    for slot, sa, da, et, b, p in zip(_slots(stream), c["src_as"],
                                      c["dst_as"], c["etype"], c["bytes"],
                                      c["packets"]):
        acc = want.setdefault((int(slot), int(sa), int(da), int(et)),
                              [0, 0, 0])
        acc[0] += int(b)
        acc[1] += int(p)
        acc[2] += 1
    got = {tuple(r[:4]): list(r[4:7]) for r in whole[1]["flows_5m"]}
    assert len(got) == len(whole[1]["flows_5m"])  # one row a group
    assert got == want


@pytest.mark.parametrize("table", sorted(RANKED))
def test_ranked_top_20_is_within_the_limit_both_ways(whole, stream, table):
    cols = RANKED[table]
    exact = _exact_bytes(stream, cols)
    rows: dict = {}
    for r in sorted(whole[1][table], key=lambda r: (r[0], r[1])):
        key = tuple(str(v) for v in r[2:2 + len(cols)])
        rows.setdefault(int(r[0]), []).append((key, int(r[2 + len(cols)])))
    assert set(rows) == set(exact)
    worst = 0.0
    for slot, keys in exact.items():
        have = dict(rows[slot])
        for key, b in rows[slot][:TOP_N]:           # the sink's top 20
            assert key in keys, (table, slot, key)
            worst = max(worst, abs(b - keys[key]) / keys[key])
        for key, b in sorted(keys.items(),          # the reference's
                             key=lambda kv: -kv[1])[:TOP_N]:
            assert key in have, (table, slot, key)
            worst = max(worst, abs(have[key] - b) / b)
    assert worst <= LIMIT


def test_no_offset_is_committed_ahead_of_a_flush(whole, stream):
    """When a commit covers the first flow of a new slot, the slot it
    closed is already in the sink's flows_5m."""
    _worker, _sink_rows, events, _spans, _names = whole
    slots = _slots(stream)
    closes = {int(i): int(slots[i - 1])  # first flow of a slot -> closed
              for i in np.flatnonzero(np.diff(slots)) + 1}
    assert len(closes) == 3
    written, commits = set(), 0
    for ev in events:
        if ev[0] == "write" and ev[1] == "flows_5m":
            written.update(ev[2])
        elif ev[0] == "commit":
            commits += 1
            ahead = [s for i, s in closes.items()
                     if i < ev[1] and s not in written]
            assert not ahead, (ev, ahead)
    assert commits >= 6
    assert max(ev[1] for ev in events if ev[0] == "commit") == FLOWS


def test_the_per_chip_shares_add_up_to_the_whole(
        stream, tmp_path, monkeypatch):
    """Stopped mid-window at the same flow as a one-chip run. The port
    planes are a sum monoid: the four chips' add up to the one chip's,
    exactly. The CMS planes are updated conservatively (a cell rises only
    to the key's least estimate plus its addend), which is not linear:
    the sum of the shares is what the close's psum gives, equals the one
    chip's plane wherever no two keys met in a cell, and never under-counts
    a key."""
    from flow_pipeline_tpu.models import heavy_hitter as hh_mod
    from flow_pipeline_tpu.models.dense_top import _planes_to_uint64
    from flow_pipeline_tpu.ops import cms as cms_ops

    stop = 2 * CHIPS * BATCH  # 4,096 flows: inside the first window
    seen = {}

    def grab(worker):
        got = seen.setdefault(worker.config.poll_max, {})
        for name in ("top_talkers", "top_src_ips", "top_dst_ips"):
            model = worker.models[name].model
            got[name] = np.asarray(model.state.cms)
            if hasattr(model, "merged_state"):
                got[name + ".merged"] = np.asarray(model.merged_state().cms)
        for name in ("top_src_ports", "top_dst_ports"):
            got[name] = _planes_to_uint64(
                np.asarray(worker.models[name].model.totals))

    for mesh in (CHIPS, 0):
        tmp = tmp_path / f"mesh{mesh}"
        tmp.mkdir()
        Harness(_bus(stream), monkeypatch).run(
            _argv(tmp, mesh), stop_at=stop, kill=True, before_stop=grab)
    TRACER.configure("off")
    four, one = seen[CHIPS * BATCH], seen[BATCH]
    for name in ("top_src_ports", "top_dst_ports"):
        assert four[name].shape == (CHIPS,) + one[name].shape
        assert all(four[name][d].any() for d in range(CHIPS))
        np.testing.assert_array_equal(four[name].sum(axis=0), one[name])
        assert int(one[name][:, -1].sum()) == stop  # the count plane
    head = {k: v[:stop] for k, v in stream.columns.items()}
    for name in ("top_talkers", "top_src_ips", "top_dst_ips"):
        stacked, whole_plane = four[name], one[name]
        assert stacked.shape == (CHIPS,) + whole_plane.shape
        assert all(stacked[d].any() for d in range(CHIPS))  # every chip fed
        summed = stacked.sum(axis=0)
        np.testing.assert_array_equal(four[name + ".merged"], summed)
        assert np.mean(summed == whole_plane) > 0.95
        keys = np.asarray(hh_mod._key_lanes(head, RANKED[name]))
        uniq, inverse = np.unique(keys, axis=0, return_inverse=True)
        exact = np.stack([np.bincount(inverse.reshape(-1), weights=w)
                          for w in (head["bytes"], head["packets"],
                                    np.ones(stop))], axis=1)
        for plane in (summed, whole_plane):
            assert np.all(np.asarray(cms_ops.cms_query(plane, uniq))
                          >= exact)


def test_checkpoint_kill_and_restore_give_the_uninterrupted_rows(
        whole, stream, tmp_path, monkeypatch):
    """Killed after 7 global batches (the last checkpoint after 6, in the
    second window), then started again on the same bus, checkpoint and
    sink."""
    bus = _bus(stream)
    first = Harness(bus, monkeypatch)
    killed = first.run(_argv(tmp_path), stop_at=7 * CHIPS * BATCH,
                       kill=True)
    assert killed.batches_seen == 7
    committed = max(ev[1] for ev in first.events if ev[0] == "commit")
    assert committed == 6 * CHIPS * BATCH
    state = np.load(tmp_path / "ckpt" / "arrays.npz")
    assert any(a.shape[:1] == (CHIPS,) for a in state.values())
    with zipfile.ZipFile(tmp_path / "ckpt" / "arrays.npz") as archive:
        # written whole (ShardedPipeline.checkpoint_whole): no member is
        # in the streamed form, whose CRC and sizes follow its bytes
        assert not any(i.flag_bits & 0x08 for i in archive.infolist())
    second = Harness(bus, monkeypatch)
    restored = second.run(_argv(tmp_path))
    TRACER.configure("off")
    assert restored.flows_seen == FLOWS
    # the replayed batch came from the bus, not from the state
    assert restored.batches_seen > killed.batches_seen
    assert _sink(tmp_path) == whole[1]


def test_the_mesh_spans_and_the_row_counter_are_recorded(whole):
    _worker, _sink_rows, _events, spans, _names = whole
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s[0], []).append(s)
    with open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")) as f:
        catalogue = f.read()
    for name, args in MESH_SPANS.items():
        assert name in by_name, name
        assert f"`{name}` [{', '.join(args)}]" in catalogue, name
        for s in by_name[name]:
            assert set(args) <= set(s[5] or {}), (name, s[5])
    applies = sorted(by_name["apply"], key=lambda s: s[1])
    inside = lambda s: any(a[3] == s[3] and a[1] <= s[1] and s[2] <= a[2]
                           for a in applies)
    for name in MESH_SPANS:
        in_loop = [s for s in by_name[name] if inside(s)]
        assert in_loop, name
    # a placement a model and global step, read by its one program
    assert {s[5]["model"] for s in by_name["mesh_shard"]} == set(MODELS)
    assert all(s[5]["models"] == 1 for s in by_name["mesh_shard"])
    assert len(by_name["mesh_shard"]) == len(MODELS) * -(
        -FLOWS // (CHIPS * BATCH))
    # a part's mesh_update names the models it runs: all of them, the
    # windowed families and the detector after a slot roll, the detector
    # alone after a sub-window
    served = {tuple(s[5]["model"]) for s in by_name["mesh_update"]}
    assert served == {tuple(MODELS), tuple(MODELS[1:]), ("ddos_alerts",)}
    assert {s[5]["model"] for s in by_name["mesh_merge"]} == set(
        MODELS[1:])
    # mesh_shard nests in the mesh_update of the poll's first part
    for sh in by_name["mesh_shard"]:
        assert any(u[5]["model"] == MODELS and u[1] <= sh[1]
                   and sh[2] <= u[2] for u in by_name["mesh_update"])
    assert all(s[5]["steps"] == 1 for s in by_name["mesh_update"])
    # one cut a poll, by masks: no row is copied
    polls = len(by_name["apply"])
    assert len(by_name["mesh_split"]) == polls
    assert all(s[5]["copied_rows"] == 0 for s in by_name["mesh_split"])
    # 2,048 flows at 24 a second: every poll spans several sub-windows
    parts = [s[5]["parts"] for s in by_name["mesh_split"]]
    assert min(parts) > 1
    assert len(by_name["mesh_update"]) == sum(parts)
    # the drain under a mesh folds everything at once
    assert all(s[5]["left"] == 0 and s[5]["partials"] >= 1
               for s in by_name["mesh_drain"])


def test_chip_rows_count_the_valid_rows_each_chip_got(whole):
    _worker, _sink_rows, _events, spans, _names = whole
    shards = [s[5] for s in spans if s[0] == "mesh_shard"]
    for a in shards:
        assert len(a["chip_rows"]) == CHIPS
        assert sum(a["chip_rows"]) == a["rows"] <= CHIPS * BATCH
        assert max(a["chip_rows"]) <= BATCH
        # pad_to pads at the end: the leading chips fill first
        assert a["chip_rows"] == sorted(a["chip_rows"], reverse=True)
    per_model = {}
    for a in shards:
        per_model[a["model"]] = per_model.get(a["model"], 0) + a["rows"]
    assert per_model == {m: FLOWS for m in MODELS}  # each row once a model
    # a poll cut at a window slot is cut by masks: its rows stay where
    # they are, and only the stream's last, part-full poll is padded
    short = [a for a in shards if a["chip_rows"] != [BATCH] * CHIPS]
    assert len(short) == len(MODELS) and short == shards[-len(MODELS):]
    assert {a["rows"] for a in short} == {FLOWS % (CHIPS * BATCH)}


def test_every_sharded_program_is_named_for_its_family_and_model(whole):
    names = set(whole[4])
    want = {"mesh_wagg_update", "mesh_wagg_update_exact",
            "mesh_ddos_update", "mesh_ddos_close"}
    for model in ("top_talkers", "top_src_ips", "top_dst_ips"):
        want |= {f"mesh_hh_update_{model}", f"mesh_hh_merge_{model}"}
    for model in ("top_src_ports", "top_dst_ports"):
        want |= {f"mesh_dense_update_{model}", f"mesh_dense_merge_{model}"}
    # the wagg programs are cached by mesh and configuration: another
    # test of this process may have built them already
    assert want - {"mesh_wagg_update", "mesh_wagg_update_exact"} <= names
    assert names <= want
    worker = whole[0]
    hh = worker.models["top_talkers"].model
    text = hh._update.lower(hh.state, *_placed(hh)).as_text()
    assert "jit_mesh_hh_update_top_talkers" in text


def _placed(hh):
    """Global columns of zeros, placed as a step's would be."""
    from flow_pipeline_tpu.models import heavy_hitter as hh_mod
    from flow_pipeline_tpu.schema.batch import FlowBatch

    empty = FlowGenerator(ZipfProfile(n_keys=4), seed=1).batch(1)
    padded, mask = FlowBatch(empty.columns, 0).pad_to(hh.global_batch)
    cols = padded.device_columns(hh_mod.input_cols(hh.config))
    return sharded.shard_batch_columns(hh.mesh, cols, mask)
