"""flowmesh: merge codec, monoid merges, coordinator protocol, and the
N-worker mesh's oracle-exactness — parity (N in {1, 2, 4}), worker
churn (kill one mid-stream: no loss, no double count), and the
mesh-aware /topk fan-out. `make mesh-parity` runs this file."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from flow_pipeline_tpu.cli import (_build_models, _common_flags,
                                   _gen_flags, _processor_flags)
from flow_pipeline_tpu.engine import StreamWorker, WorkerConfig
from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
from flow_pipeline_tpu.mesh import (InProcessMesh, MeshCoordinator,
                                    MeshCoordinatorServer, ModelSpec,
                                    produce_sharded, shard_ids,
                                    spec_from_models)
from flow_pipeline_tpu.mesh import codec
from flow_pipeline_tpu.mesh import merge as merge_ops
from flow_pipeline_tpu.models.heavy_hitter import (HeavyHitterConfig,
                                                   hh_init)
from flow_pipeline_tpu.models.oracle import exact_groupby
from flow_pipeline_tpu.models.window_agg import WindowAggConfig, WindowStore
from flow_pipeline_tpu.transport import Consumer, InProcessBus
from flow_pipeline_tpu.utils.flags import KNOWN_FLAGS, FlagSet

N_KEYS = 200  # << capacity: admission is collision-free, tables exact
N_FLOWS = 24_000
PARTITIONS = 8
BATCH = 4096

TOP_COLS = ("src_addr", "dst_addr", "src_port", "dst_port", "proto",
            "bytes", "packets", "count", "timeslot")


def _vals(*extra):
    fs = _processor_flags(_gen_flags(_common_flags(FlagSet("test"))))
    return fs.parse([
        "-produce.profile", "zipf", "-zipf.keys", str(N_KEYS),
        "-model.ports=false", "-model.ddos=false", "-model.ips=false",
        "-processor.batch", str(BATCH), "-sketch.capacity", "512",
        *extra,
    ])


def _stream_batches(n_flows=N_FLOWS, seed=0):
    gen = FlowGenerator(ZipfProfile(n_keys=N_KEYS, alpha=1.2), seed=seed,
                        rate=100_000.0)
    out, done = [], 0
    while done < n_flows:
        n = min(8192, n_flows - done)
        out.append(gen.batch(n))
        done += n
    return out


def _make_bus(n_flows=N_FLOWS, partitions=PARTITIONS):
    bus = InProcessBus()
    bus.create_topic("flows", partitions)
    for batch in _stream_batches(n_flows):
        produce_sharded(bus, "flows", batch, partitions)
    return bus


class ListSink:
    def __init__(self):
        self.tables = {}

    def write(self, table, rows):
        self.tables.setdefault(table, []).append(rows)


def _fold_flows5m(tables):
    """Partial flows_5m rows -> {(timeslot, src_as, dst_as, etype):
    (bytes, packets, count)} — the merging-sink semantics."""
    acc = {}
    for rows in tables.get("flows_5m", []):
        for i in range(len(rows["timeslot"])):
            key = (int(rows["timeslot"][i]), int(rows["src_as"][i]),
                   int(rows["dst_as"][i]), int(rows["etype"][i]))
            v = acc.setdefault(key, np.zeros(3, np.uint64))
            v += np.array([rows["bytes"][i], rows["packets"][i],
                           rows["count"][i]], np.uint64)
    return acc


def _oracle_flows5m():
    from flow_pipeline_tpu.schema.batch import FlowBatch

    full = FlowBatch.concat(_stream_batches())
    o = exact_groupby(full, ["src_as", "dst_as", "etype"],
                      ["bytes", "packets"])
    return {
        (int(o["timeslot"][i]), int(o["src_as"][i]), int(o["dst_as"][i]),
         int(o["etype"][i])):
        np.array([o["bytes"][i], o["packets"][i], o["count"][i]],
                 np.uint64)
        for i in range(len(o["timeslot"]))
    }


def _run_single_worker(vals, sink):
    worker = StreamWorker(
        Consumer(_make_bus(), "flows", fixedlen=True),
        _build_models(vals), [sink],
        WorkerConfig(poll_max=BATCH, snapshot_every=0,
                     sketch_backend=vals["sketch.backend"]))
    worker.run(stop_when_idle=True)
    return worker


def _run_mesh(vals, n_workers, sink, **mesh_kw):
    mesh = InProcessMesh(
        _make_bus(), "flows", n_workers,
        model_factory=lambda: _build_models(vals),
        config=WorkerConfig(poll_max=BATCH, snapshot_every=0,
                            sketch_backend=vals["sketch.backend"]),
        sinks=[sink], **mesh_kw)
    mesh.run()
    return mesh


def _assert_topk_equal(r1, r2):
    v1, v2 = np.asarray(r1["valid"]), np.asarray(r2["valid"])
    assert int(v1.sum()) == int(v2.sum())
    for col in TOP_COLS:
        a, b = np.asarray(r1[col])[v1], np.asarray(r2[col])[v2]
        assert a.shape == b.shape and (a == b).all(), col
    # est columns are CMS upper bounds in both legs; the merged sum-of-
    # sketches bound must still dominate the exact table values
    for col in ("bytes", "count"):
        est = np.asarray(r2[f"{col}_est"])[v2].astype(np.float64)
        val = np.asarray(r2[col])[v2].astype(np.float64)
        assert (est >= val - 1e-3).all()


# ---------------------------------------------------------------------------
# merge codec
# ---------------------------------------------------------------------------


class TestCodec:
    def test_uint64_envelope_extremes(self):
        arr = np.array([0, 1, 2**24, 2**53 + 1, 2**63, 2**64 - 1],
                       np.uint64)
        out = codec.decode(codec.encode({"a": arr}))["a"]
        assert out.dtype == np.uint64
        assert (out == arr).all()

    def test_bad_magic_rejected(self):
        with pytest.raises(ValueError, match="magic"):
            codec.decode(b"not a payload")

    def test_hh_state_round_trip_bit_exact(self):
        cfg = HeavyHitterConfig(width=1024, capacity=32, batch_size=256)
        state = hh_init(cfg)
        payload = codec.hh_payload(state)
        out = codec.decode(codec.encode(payload))
        for field in ("cms", "table_keys", "table_vals"):
            a, b = payload[field], out[field]
            assert a.dtype == b.dtype and a.shape == b.shape
            assert (a == b).all()
        assert out["cms"].dtype == np.uint64

    def test_hostsketch_state_round_trip(self):
        from flow_pipeline_tpu.hostsketch.state import host_hh_init

        cfg = HeavyHitterConfig(width=512, capacity=16, batch_size=256)
        st = host_hh_init(cfg)
        st.cms[:] = np.uint64(2**40 + 7)
        st.table_vals[:] = np.float32(3.25)
        payload = codec.hh_payload(st)
        out = codec.decode(codec.encode(payload))
        assert (out["cms"] == st.cms).all()
        assert (out["table_vals"] == st.table_vals).all()
        assert (out["table_keys"] == st.table_keys).all()

    def test_wagg_store_round_trip(self):
        store = {(1, 2, 3, 7): np.array([10, 20, 5], np.uint64),
                 (9, 9, 9, 1): np.array([2**63, 1, 1], np.uint64)}
        payload = codec.wagg_payload(
            WindowStore.from_rows(list(store), list(store.values())))
        out = codec.decode(codec.encode(payload))
        merged = merge_ops.merge_wagg([out])
        assert set(merged) == set(store)
        for k in store:
            assert (merged[k] == store[k]).all()

    def test_contribution_structure_round_trip(self):
        payload = {"member": "w0", "ranges": {3: [5, 17]},
                   "watermark": 1_700_000_000, "final": False,
                   "closed": {1200: {"m": {"kind": "dense",
                                           "totals": np.ones((4, 3, 2),
                                                             np.int64)}}}}
        out = codec.decode(codec.encode(payload))
        assert out["member"] == "w0"
        assert out["ranges"][3] == [5, 17]
        assert (out["closed"][1200]["m"]["totals"] == 1).all()

    def test_random_payload_property(self, rng):
        """Random dtype/shape arrays survive the envelope bit-exactly
        (seeded variant; the hypothesis property below runs where
        hypothesis is installed)."""
        for _ in range(25):
            dt = rng.choice([np.uint64, np.uint32, np.float32, np.int64])
            shape = tuple(rng.integers(0, 5, size=rng.integers(1, 4)))
            if dt == np.float32:
                arr = rng.standard_normal(shape).astype(np.float32)
            else:
                arr = rng.integers(0, 2**31, size=shape).astype(dt)
            out = codec.decode(codec.encode({"x": arr}))["x"]
            assert out.dtype == arr.dtype and out.shape == arr.shape
            assert (out == arr).all()


try:  # property test where hypothesis exists (repo convention)
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.integers(min_value=0, max_value=2**64 - 1),
                    min_size=0, max_size=64))
    def test_codec_u64_property(values):
        arr = np.array(values, dtype=np.uint64)
        out = codec.decode(codec.encode(
            {"a": arr, "meta": {"n": len(values)}}))
        assert out["meta"]["n"] == len(values)
        assert out["a"].dtype == np.uint64
        assert (out["a"] == arr).all()
except ImportError:  # pragma: no cover - env without hypothesis
    pass


# ---------------------------------------------------------------------------
# monoid merges
# ---------------------------------------------------------------------------


class TestMerges:
    def test_wagg_merge_sums_by_key(self):
        a = codec.wagg_payload(WindowStore.from_rows([(1, 2)], [(10, 1)]))
        b = codec.wagg_payload(WindowStore.from_rows(
            [(3, 4), (1, 2)], [(7, 1), (5, 2)]))
        merged = merge_ops.merge_wagg([a, b])
        assert (merged[(1, 2)] == np.array([15, 3], np.uint64)).all()
        assert (merged[(3, 4)] == np.array([7, 1], np.uint64)).all()

    def test_cms_merge_is_linear(self, rng):
        """sum of per-shard plain-CMS sketches == CMS of the union
        stream (the linear-sketch property the mesh merge leans on)."""
        from flow_pipeline_tpu.hostsketch.engine import np_cms_update

        cfg = HeavyHitterConfig(width=256, depth=2, capacity=8,
                                conservative=False, batch_size=64)
        keys = rng.integers(0, 50, size=(200, 2)).astype(np.uint32)
        vals = rng.integers(1, 100, size=(200, 3)).astype(np.float32)
        whole = np.zeros((3, cfg.depth, cfg.width), np.uint64)
        np_cms_update(whole, keys, vals, conservative=False)
        shard_of = keys[:, 0] % 2
        parts = []
        for s in (0, 1):
            cms = np.zeros_like(whole)
            sel = shard_of == s
            np_cms_update(cms, keys[sel], vals[sel], conservative=False)
            parts.append(cms)
        assert (parts[0] + parts[1] == whole).all()

    def test_hh_table_merge_disjoint_ranks_and_ties(self):
        cfg = HeavyHitterConfig(key_cols=("proto",),
                                value_cols=("bytes",), width=128,
                                depth=2, capacity=4, batch_size=64)
        empty = codec.hh_payload(hh_init(cfg))

        def table(rows):
            p = {k: v.copy() for k, v in empty.items() if k != "kind"}
            p["kind"] = "hh"
            for i, (key, val) in enumerate(rows):
                p["table_keys"][i] = key
                p["table_vals"][i] = val
            return p

        a = table([((5,), (100.0, 1.0)), ((9,), (50.0, 2.0))])
        b = table([((3,), (100.0, 3.0)), ((7,), (10.0, 4.0))])
        merged = merge_ops.merge_hh([a, b], cfg)
        keys = merged["table_keys"][:, 0].tolist()
        # rank by value desc; the 100.0 tie breaks lexicographically
        assert keys == [3, 5, 9, 7]
        assert merged["table_vals"][0, 0] == 100.0

    def test_hh_merge_sums_duplicate_keys(self):
        """Carry + successor contributions for the SAME key (churn
        mid-window) sum — the table-table fold semantics."""
        cfg = HeavyHitterConfig(key_cols=("proto",),
                                value_cols=("bytes",), width=128,
                                depth=2, capacity=4, batch_size=64)
        base = codec.hh_payload(hh_init(cfg))

        def table(val):
            p = {k: v.copy() for k, v in base.items() if k != "kind"}
            p["kind"] = "hh"
            p["table_keys"][0] = (6,)
            p["table_vals"][0] = (val, 1.0)
            return p

        merged = merge_ops.merge_hh([table(30.0), table(12.0)], cfg)
        assert merged["table_keys"][0, 0] == 6
        assert merged["table_vals"][0, 0] == 42.0

    def test_dense_merge_sums_planes(self):
        a = codec.dense_payload(np.full((8, 3, 2), 3, np.int32))
        b = codec.dense_payload(np.full((8, 3, 2), 4, np.int32))
        assert (merge_ops.merge_dense([a, b]) == 7).all()


# ---------------------------------------------------------------------------
# coordinator protocol units (no jax, synthetic payloads)
# ---------------------------------------------------------------------------


def _wagg_spec():
    cfg = WindowAggConfig(key_cols=("src_as",), value_cols=("bytes",),
                          window_seconds=300, scale_col=None,
                          batch_size=256)
    return ModelSpec("flows_5m", "wagg", cfg, 0, 300)


def _contrib(ranges, wm, closed=None, open_=None, final=False,
             release=False, flows=0):
    return {"ranges": ranges, "watermark": wm, "closed": closed or {},
            "open": open_ or {}, "final": final, "release": release,
            "flows": flows}


def _wagg_win(key, val):
    return {"flows_5m": codec.wagg_payload(
        WindowStore.from_rows([(key,)], [(val, 1)]))}


class TestCoordinatorProtocol:
    def make(self, partitions=2, **kw):
        return MeshCoordinator([_wagg_spec()], partitions, **kw)

    def test_join_assign_epoch(self):
        c = self.make()
        assert c.join("a")["epoch"] == 1
        s = c.sync("a")
        assert s["action"] == "run"
        assert sorted(s["assign"]) == [0, 1]
        assert c.join("b")["epoch"] == 2
        assert c.sync("a")["action"] == "resync"

    def test_submit_advances_frontier_and_merges(self):
        c = self.make(partitions=1)
        c.join("a")
        c.sync("a")
        r = c.submit("a", _contrib({0: [0, 10]}, wm=900,
                                   closed={300: _wagg_win(7, 50)}))
        assert r["ok"]
        assert c.status()["covered"] == [10]
        rows = c.merged_rows("flows_5m", 300)
        assert len(rows) == 1
        assert int(rows[0]["bytes"][0]) == 50

    def test_merge_waits_for_every_partition(self):
        c = self.make(partitions=2)
        c.join("a")
        c.join("b")
        sa, sb = c.sync("a"), c.sync("b")
        pa = list(sa["assign"])[0]
        c.submit("a", _contrib({pa: [0, 5]}, wm=900,
                               closed={300: _wagg_win(1, 10)}))
        assert not c.merged_rows("flows_5m", 300)  # b's watermark at 0
        pb = list(sb["assign"])[0]
        c.submit("b", _contrib({pb: [0, 5]}, wm=900,
                               closed={300: _wagg_win(1, 5)}))
        rows = c.merged_rows("flows_5m", 300)
        assert len(rows) == 1
        assert int(rows[0]["bytes"][0]) == 15  # summed across members

    def test_zombie_submission_fenced(self):
        c = self.make(partitions=1)
        c.join("a")
        c.sync("a")
        c.fence("a")
        r = c.submit("a", _contrib({0: [0, 10]}, wm=900))
        assert not r["ok"] and r["reason"] == "fenced"
        assert c.status()["covered"] == [0]  # nothing accepted
        assert c.sync("a")["action"] == "rejoin"

    def test_range_gap_fences(self):
        c = self.make(partitions=1)
        c.join("a")
        c.sync("a")
        r = c.submit("a", _contrib({0: [5, 10]}, wm=0))  # gap: covered=0
        assert not r["ok"]
        assert c.sync("a")["action"] == "rejoin"

    def test_death_promotes_carry_and_successor_resumes(self):
        c = self.make(partitions=1)
        c.join("a")
        c.sync("a")
        c.submit("a", _contrib({0: [0, 8]}, wm=100,
                               open_={300: _wagg_win(2, 30)}))
        c.join("b")
        c.fence("a")
        s = c.sync("b")
        assert s["action"] == "run"
        assert s["assign"] == {0: 8}  # resumes at the carry frontier
        c.submit("b", _contrib({0: [8, 12]}, wm=700,
                               closed={300: _wagg_win(2, 12)},
                               final=True))
        rows = c.merged_rows("flows_5m", 300)
        assert len(rows) == 1
        # carry (30) + successor (12): no loss, no double count
        assert int(rows[0]["bytes"][0]) == 42

    def test_resubmission_replaces_carry(self):
        c = self.make(partitions=1)
        c.join("a")
        c.sync("a")
        c.submit("a", _contrib({0: [0, 4]}, wm=100,
                               open_={300: _wagg_win(2, 10)}))
        # the second submission's open state COVERS the first's rows
        c.submit("a", _contrib({0: [4, 9]}, wm=100,
                               open_={300: _wagg_win(2, 25)}))
        c.fence("a")
        c.join("b")
        c.sync("b")
        c.submit("b", _contrib({0: [9, 9]}, wm=700, final=True))
        rows = c.merged_rows("flows_5m", 300)
        assert int(rows[0]["bytes"][0]) == 25  # replaced, not summed

    def test_heartbeat_expiry_fences(self):
        now = [0.0]
        c = self.make(partitions=1, heartbeat_timeout=1.0,
                      time_fn=lambda: now[0])
        c.join("a")
        c.sync("a")
        now[0] = 10.0
        assert c.expire() == ["a"]
        assert c.sync("a")["action"] == "rejoin"

    def test_late_wagg_contribution_emits_extra_partials(self):
        c = self.make(partitions=1)
        # delta, not absolute: the late counter is process-global
        late0 = c._m["late"].value(model="flows_5m")
        c.join("a")
        c.sync("a")
        c.submit("a", _contrib({0: [0, 5]}, wm=900,
                               closed={300: _wagg_win(3, 10)}))
        assert len(c.merged_rows("flows_5m", 300)) == 1
        c.submit("a", _contrib({0: [5, 6]}, wm=901,
                               closed={300: _wagg_win(3, 4)}))
        rows = c.merged_rows("flows_5m", 300)
        assert len(rows) == 2  # late partial emitted, not dropped
        assert c._m["late"].value(model="flows_5m") - late0 == 1.0

    def test_rejoin_fence_completes_barrier_and_emits(self):
        """A crashed member rejoining under its pinned id fences the old
        incarnation; if its promoted carry is the LAST contribution a
        window needed, that window must still be emitted (regression:
        join() discarded the ready-merge list — silent window loss)."""
        c = self.make(partitions=1)
        c.join("a")
        c.sync("a")
        c.submit("a", _contrib({0: [0, 5]}, wm=900,
                               open_={300: _wagg_win(7, 33)}))
        assert not c.merged_rows("flows_5m", 300)  # carried, not pending
        c.join("a")  # restart before expiry: death-then-join
        rows = c.merged_rows("flows_5m", 300)
        assert len(rows) == 1
        assert int(rows[0]["bytes"][0]) == 33

    def test_leave_fence_completes_barrier_and_emits(self):
        """Same loss mode via leave() while owning non-final partitions
        (the fence branch): the promoted carry's merges must emit."""
        c = self.make(partitions=1)
        c.join("a")
        c.sync("a")
        c.submit("a", _contrib({0: [0, 5]}, wm=900,
                               open_={300: _wagg_win(2, 21)}))
        c.leave("a")
        rows = c.merged_rows("flows_5m", 300)
        assert len(rows) == 1
        assert int(rows[0]["bytes"][0]) == 21

    def test_query_topk_live_carry_not_double_counted(self):
        """A live member's carry is a SUBSET of its provider state; the
        /topk fan-out must count it once (regression: carries were
        folded next to provider states — up to 2x inflation)."""
        cfg = HeavyHitterConfig(key_cols=("proto",),
                                value_cols=("bytes",), width=128,
                                depth=2, capacity=4, batch_size=64)
        spec = ModelSpec("talkers", "hh", cfg, 4, 300)
        c = MeshCoordinator([spec], 1)

        def table(val):
            p = codec.hh_payload(hh_init(cfg))
            p["table_keys"][0] = (6,)
            p["table_vals"][0] = (val, 1.0)
            return p

        provider = lambda model: {"slot": 300, "payload": table(30.0)}
        c.join("a", provider=provider)
        c.sync("a")
        # progress submission: the carry holds an earlier subset (20)
        c.submit("a", _contrib({0: [0, 4]}, wm=100,
                               open_={300: {"talkers": table(20.0)}}))
        out = c.query_topk("talkers")
        assert out["window_start"] == 300
        assert out["rows"][0]["bytes"] == 30.0  # not 50.0

    def test_merged_ledger_retention_bounded(self):
        """The merged-rows ledger keeps only the newest slots per model
        (sinks are the durable home; an endless stream must not grow
        coordinator RAM per window) while late detection keeps working
        for evicted windows."""
        from flow_pipeline_tpu.mesh.coordinator import \
            MERGED_LEDGER_SLOTS

        c = self.make(partitions=1)
        c.join("a")
        c.sync("a")
        n = MERGED_LEDGER_SLOTS + 4
        for i in range(n):
            slot = 300 * (i + 1)
            c.submit("a", _contrib(
                {0: [i, i + 1]}, wm=slot + 600,
                closed={slot: _wagg_win(1, 10)}))
        kept = sorted(s for (name, s) in c.merged if name == "flows_5m")
        assert len(kept) == MERGED_LEDGER_SLOTS
        assert kept[0] == 300 * (n - MERGED_LEDGER_SLOTS + 1)  # oldest gone
        assert not c.merged_rows("flows_5m", 300)  # evicted
        # a late contribution for an EVICTED window still registers late
        late_before = c._m["late"].value(model="flows_5m")
        c.submit("a", _contrib({0: [n, n]}, wm=10**9,
                               closed={300: _wagg_win(1, 4)}))
        assert c._m["late"].value(model="flows_5m") == late_before + 1

    def test_more_members_than_partitions_idles_extra(self):
        c = self.make(partitions=1)
        c.join("a")
        c.join("b")
        acts = {m: c.sync(m)["action"] for m in ("a", "b")}
        assert sorted(acts.values()) == ["run", "run"]
        owned = [len(v["owned"]) for v in c.status()["members"].values()]
        assert sorted(owned) == [0, 1]


# ---------------------------------------------------------------------------
# sharding
# ---------------------------------------------------------------------------


class TestSharding:
    def test_shard_ids_deterministic_and_key_consistent(self):
        batch = _stream_batches(n_flows=8192)[0]
        a = shard_ids(batch, 8)
        b = shard_ids(batch, 8)
        assert (a == b).all()
        # same 5-tuple -> same shard: group rows by key, check constancy
        from flow_pipeline_tpu.engine.hostfused import _key_lanes_np

        lanes = _key_lanes_np(
            batch.columns,
            ("src_addr", "dst_addr", "src_port", "dst_port", "proto"))
        seen = {}
        for i in range(len(batch)):
            key = lanes[i].tobytes()
            assert seen.setdefault(key, a[i]) == a[i]

    def test_produce_sharded_covers_all_rows(self):
        bus = InProcessBus()
        bus.create_topic("flows", 4)
        batch = _stream_batches(n_flows=8192)[0]
        n = produce_sharded(bus, "flows", batch, 4)
        assert n == len(batch)
        total = sum(bus.end_offset("flows", p) for p in range(4))
        assert total == len(batch)


# ---------------------------------------------------------------------------
# end-to-end oracle exactness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_workers", [1, 2, 4])
def test_mesh_parity_vs_single_worker(n_workers):
    """The acceptance gate: an N-worker mesh's merged flows_5m and top-K
    outputs are bit-exact to a single worker consuming the identical
    sharded bus — and flows_5m additionally matches the pure-numpy exact
    oracle over the whole stream."""
    from flow_pipeline_tpu.obs import REGISTRY

    merged_before = REGISTRY.counter(
        "mesh_windows_merged_total").value(model="top_talkers")
    vals = _vals()
    sink1, sink2 = ListSink(), ListSink()
    _run_single_worker(vals, sink1)
    mesh = _run_mesh(vals, n_workers, sink2)
    oracle = _oracle_flows5m()
    for fold in (_fold_flows5m(sink1.tables), _fold_flows5m(sink2.tables)):
        assert set(fold) == set(oracle)
        for k in oracle:
            assert (fold[k] == oracle[k]).all()
    _assert_topk_equal(sink1.tables["top_talkers"][0],
                       sink2.tables["top_talkers"][0])
    # exactly one merged top-K window for THIS mesh (the registry is
    # process-global, so assert the delta)
    assert mesh.coordinator._m["merged"].value(
        model="top_talkers") - merged_before == 1.0


def test_mesh_parity_hostsketch_backend():
    """Members on the host sketch engine (its export seam feeds the
    merge codec) stay oracle-exact through the mesh."""
    vals = _vals("-sketch.backend", "host")
    sink1, sink2 = ListSink(), ListSink()
    _run_single_worker(vals, sink1)
    _run_mesh(vals, 2, sink2)
    _assert_topk_equal(sink1.tables["top_talkers"][0],
                       sink2.tables["top_talkers"][0])
    f1, f2 = _fold_flows5m(sink1.tables), _fold_flows5m(sink2.tables)
    assert set(f1) == set(f2)
    for k in f1:
        assert (f1[k] == f2[k]).all()


def test_mesh_churn_kill_one_worker_stays_exact():
    """The churn acceptance criterion: kill a member mid-stream (abrupt,
    no final submission), fence it, let the rebalanced mesh finish —
    merged flows_5m and top-K stay oracle-exact (no loss, no double
    count). submit_every=2 keeps progress carries flowing so the death
    promotes a real mid-window carry."""
    vals = _vals()
    sink1, sink2 = ListSink(), ListSink()
    _run_single_worker(vals, sink1)
    mesh = InProcessMesh(
        _make_bus(), "flows", 3,
        model_factory=lambda: _build_models(vals),
        config=WorkerConfig(poll_max=BATCH, snapshot_every=0),
        sinks=[sink2], submit_every=2)
    mesh.start()
    victim = mesh.members[1]
    deadline = time.time() + 120
    while time.time() < deadline:
        w = victim.worker
        if w is not None and w.flows_seen >= BATCH:
            break
        time.sleep(0.002)
    else:
        pytest.fail("victim never processed a batch")
    mesh.kill_member(1)
    mesh.wait_idle()
    mesh.finalize()
    oracle = _oracle_flows5m()
    fold = _fold_flows5m(sink2.tables)
    assert set(fold) == set(oracle)
    for k in oracle:
        assert (fold[k] == oracle[k]).all()
    _assert_topk_equal(sink1.tables["top_talkers"][0],
                       sink2.tables["top_talkers"][0])
    assert mesh.coordinator._m["rebalance"].value(reason="death") >= 1.0


def test_mesh_topk_query_equals_single_worker_oracle():
    """Satellite: the coordinator's fanned-out /topk over the merged
    open-window view equals the single-worker answer at the same
    consumed point (everything ingested, window still open)."""
    vals = _vals()
    # single worker: consume everything but do NOT finalize
    worker = StreamWorker(
        Consumer(_make_bus(), "flows", fixedlen=True),
        _build_models(vals), [],
        WorkerConfig(poll_max=BATCH, snapshot_every=0))
    while worker.run_once():
        pass
    with worker.lock:
        worker.sync_sketch_states()
        model = worker.models["top_talkers"]
        single = model.model.top(10)
        single["timeslot"] = np.full(len(single["valid"]),
                                     model.current_slot, np.uint64)
    # mesh: consume everything, query BEFORE finalize
    mesh = InProcessMesh(
        _make_bus(), "flows", 2,
        model_factory=lambda: _build_models(vals),
        config=WorkerConfig(poll_max=BATCH, snapshot_every=0))
    server = MeshCoordinatorServer(mesh.coordinator, port=0).start()
    mesh.start()
    try:
        mesh.wait_idle()
        url = (f"http://127.0.0.1:{server.port}/topk"
               f"?model=top_talkers&k=10")
        remote = json.load(urllib.request.urlopen(url))
        direct = mesh.coordinator.query_topk("top_talkers", 10)
    finally:
        mesh.finalize()
        server.stop()
    assert remote["window_start"] == direct["window_start"] \
        == int(single["timeslot"][0])
    from flow_pipeline_tpu.sink.base import rows_to_records

    single_records = rows_to_records(single)
    for got in (direct["rows"], ):
        assert len(got) == len(single_records)
        for g, s in zip(got, single_records):
            for col in ("src_addr", "dst_addr", "src_port", "dst_port",
                        "proto", "bytes", "packets", "count"):
                assert g[col] == s[col], col
    # the HTTP answer is the same fan-out JSON-encoded
    assert len(remote["rows"]) == len(single_records)
    assert [r["bytes"] for r in remote["rows"]] == \
        [r["bytes"] for r in single_records]


@pytest.mark.parametrize("n_workers", [1, 2, 4])
def test_mesh_parity_invertible_vs_single_worker(n_workers):
    """Invertible-family mesh citizenship (r16 acceptance): an N-worker
    mesh running -hh.sketch=invertible merges by a PLAIN element-wise
    u64 sum (merge_hh_inv — no table folds, no device-rank semantics)
    and its decoded merged output is bit-exact to a single worker
    consuming the identical sharded bus; flows_5m stays oracle-exact."""
    vals = _vals("-sketch.backend", "host", "-hh.sketch", "invertible")
    sink1, sink2 = ListSink(), ListSink()
    _run_single_worker(vals, sink1)
    _run_mesh(vals, n_workers, sink2)
    oracle = _oracle_flows5m()
    for fold in (_fold_flows5m(sink1.tables), _fold_flows5m(sink2.tables)):
        assert set(fold) == set(oracle)
        for k in oracle:
            assert (fold[k] == oracle[k]).all()
    _assert_topk_equal(sink1.tables["top_talkers"][0],
                       sink2.tables["top_talkers"][0])


def test_mesh_churn_invertible_kill_one_worker_stays_exact():
    """Kill-one-worker churn in invertible mode: carry promotion ships
    the dead member's u64 planes, the successor replays the rest, and
    the merged decode stays bit-exact to the single-worker answer."""
    vals = _vals("-sketch.backend", "host", "-hh.sketch", "invertible")
    sink1, sink2 = ListSink(), ListSink()
    _run_single_worker(vals, sink1)
    mesh = InProcessMesh(
        _make_bus(), "flows", 3,
        model_factory=lambda: _build_models(vals),
        config=WorkerConfig(poll_max=BATCH, snapshot_every=0,
                            sketch_backend="host"),
        sinks=[sink2], submit_every=2)
    mesh.start()
    victim = mesh.members[1]
    deadline = time.time() + 120
    while time.time() < deadline:
        w = victim.worker
        if w is not None and w.flows_seen >= BATCH:
            break
        time.sleep(0.002)
    else:
        pytest.fail("victim never processed a batch")
    mesh.kill_member(1)
    mesh.wait_idle()
    mesh.finalize()
    oracle = _oracle_flows5m()
    fold = _fold_flows5m(sink2.tables)
    assert set(fold) == set(oracle)
    for k in oracle:
        assert (fold[k] == oracle[k]).all()
    _assert_topk_equal(sink1.tables["top_talkers"][0],
                       sink2.tables["top_talkers"][0])
    assert mesh.coordinator._m["rebalance"].value(reason="death") >= 1.0


def test_mesh_flags_registered_and_validated():
    for flag in ("mesh.workers", "mesh.role", "mesh.coordinator",
                 "mesh.id", "mesh.listen", "mesh.heartbeat"):
        assert flag in KNOWN_FLAGS
    from flow_pipeline_tpu.cli import processor_main

    with pytest.raises(ValueError, match="mesh.role"):
        processor_main(["-mesh.role", "bogus", "-in", "/nonexistent"])


def test_spec_from_models_skips_ddos():
    vals = _vals("-model.ddos=true")
    specs = spec_from_models(_build_models(vals))
    names = {s.name for s in specs}
    assert "flows_5m" in names and "top_talkers" in names
    assert "ddos_alerts" not in names  # per-shard detection stays local
