"""flowgate tests: the replicated, delta-fed serve gateway (gateway/).

The contracts pinned here, per docs/ARCHITECTURE.md "flowgate":

- the delta codec reconstructs snapshots BIT-EXACTLY: a full frame
  followed by any chain of deltas equals the directly-encoded target
  state, array for array, dtype for dtype (uint64 extremes included);
- every ``/query/{topk,estimate,range,audit}`` answer served through a
  gateway is byte-identical to the direct snapshot path's at the same
  version — worker AND mesh publishers, table AND invertible sketches;
- damage never guesses: a torn frame, CRC mismatch, or chain gap
  forces a FULL resync, and the serving store keeps its last good
  snapshot (versions monotone) while the mirror recovers;
- replication: killing one of K gateway replicas is invisible to a
  :class:`GatewayClient` (zero 5xx, zero surfaced errors, versions
  monotone through the failover), and killing a mesh WORKER under
  gateway read load stays invisible too;
- the hot query set is pre-rendered at snapshot-landing time (the p99
  path is a cache hit before the first reader asks).
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from flow_pipeline_tpu.engine import (StreamWorker, WindowedHeavyHitter,
                                      WorkerConfig)
from flow_pipeline_tpu.gateway import (DeltaError, DeltaGapError,
                                       GatewayClient, HashRing,
                                       SnapshotFeed, SnapshotGateway,
                                       apply_delta, decode_frames,
                                       diff_states, encode_delta,
                                       encode_full, snapshot_state,
                                       state_to_snapshot)
from flow_pipeline_tpu.gateway import delta as delta_mod
from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
from flow_pipeline_tpu.models import (HeavyHitterConfig, WindowAggConfig,
                                      WindowAggregator)
from flow_pipeline_tpu.serve import ServeServer, SnapshotStore, attach_worker
from flow_pipeline_tpu.sink import MemorySink
from flow_pipeline_tpu.transport import Consumer, InProcessBus, Producer
from flow_pipeline_tpu.utils.faults import FAULTS

T0 = 1_699_999_800  # window-aligned stream start


@pytest.fixture(autouse=True)
def _faults_disarmed():
    yield
    FAULTS.configure(None)


def _fill_bus(batches=8, per=500, rate=5.0, seed=91, partitions=1):
    bus = InProcessBus()
    bus.create_topic("flows", partitions)
    gen = FlowGenerator(ZipfProfile(n_keys=100, alpha=1.3), seed=seed,
                        t0=T0, rate=rate)
    prod = Producer(bus, fixedlen=True)
    for _ in range(batches):
        prod.send_many(gen.batch(per).to_messages())
    return bus


def _models(hh_sketch="table"):
    return {
        "flows_5m": WindowAggregator(WindowAggConfig(batch_size=512)),
        "top_talkers": WindowedHeavyHitter(
            HeavyHitterConfig(batch_size=512, width=1 << 12, capacity=64,
                              hh_sketch=hh_sketch),
            k=10),
    }


def _get_raw(port, path):
    return urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10).read()


def _get(port, path):
    return json.loads(_get_raw(port, path))


def _run_worker(hh_sketch="table", **worker_kw):
    """Quiesced worker + per-window-close publishes; returns (worker,
    publisher) with the final snapshot at the exact consumed point."""
    worker = StreamWorker(
        Consumer(_fill_bus(), fixedlen=True), _models(hh_sketch),
        [MemorySink()],
        WorkerConfig(snapshot_every=0, poll_max=512, **worker_kw))
    pub = attach_worker(worker, refresh=0.0)
    while worker.run_once():
        with worker.lock:
            pub.publish(worker)
    with worker.lock:
        pub.publish(worker)
    # the bus is drained and later tests only read worker state, so stop
    # the pipeline threads here: leaked daemon pollers keep hitting the
    # bus.poll fault seam and pollute FAULTS counters suite-wide
    if worker.executor is not None:
        worker.executor.stop()
    if worker.flusher is not None:
        worker.flusher.stop()
    stop_feed = getattr(worker.consumer, "stop", None)
    if stop_feed is not None:
        stop_feed()
    return worker, pub


# ---- delta codec (unit, synthetic states) ----------------------------------


def _mk_state(version, *, width=8, bump=0, extremes=False):
    """Synthetic canonical state: one hh family (+u64 CMS planes), one
    dense family (no CMS), one range table, an audit blob."""
    rng = np.random.default_rng(7)  # same base every version: deltas
    cms = rng.integers(0, 1000, size=(3, 2, width)).astype(np.uint64)
    if extremes:
        cms[0, 0, 0] = np.uint64(2**64 - 1)
        cms[1, 0, 1] = np.uint64(2**53 + 1)
        cms[2, 1, width - 1] = np.uint64(0)
    if bump:
        cms[0, 1, bump % width] += np.uint64(bump)
    rows = {
        "src_addr": np.arange(4, dtype=np.uint32) + np.uint32(bump),
        "bytes": np.asarray([9.0, 5.0, 3.0, 1.0], np.float32),
        "valid": np.asarray([True, True, True, False]),
    }
    return {
        "version": int(version), "created": 100.0 + version,
        "watermark": float(T0 + 300 * version), "flows_seen": 10 * version,
        "source": "worker",
        "families": {
            "hh": {"kind": "hh", "window_start": T0, "depth": 4,
                   "key_lanes": 2, "value_cols": ["bytes"],
                   "rows": rows, "cms": cms},
            "dense": {"kind": "dense", "window_start": T0, "depth": 4,
                      "key_lanes": 1, "value_cols": [],
                      "rows": {"port": np.arange(4, dtype=np.uint32)},
                      "cms": None},
        },
        "ranges": {"flows_5m": [
            [T0, {"timeslot": np.asarray([T0, T0], np.int64),
                  "bytes": np.asarray([1, 2], np.uint64)}],
            [T0 + 300 * max(1, bump),
             {"timeslot": np.asarray([T0 + 300], np.int64),
              "bytes": np.asarray([3 + bump], np.uint64)}],
        ]},
        "audit": {"hh": {"cms_err": 0.0, "windows": version}},
    }


def _assert_states_equal(a, b):
    assert a["version"] == b["version"]
    assert a["watermark"] == b["watermark"]
    assert a["flows_seen"] == b["flows_seen"]
    assert set(a["families"]) == set(b["families"])
    for name, f in a["families"].items():
        g = b["families"][name]
        for k in ("kind", "window_start", "depth", "key_lanes"):
            assert f[k] == g[k], (name, k)
        assert list(f["value_cols"]) == list(g["value_cols"])
        assert set(f["rows"]) == set(g["rows"])
        for c in f["rows"]:
            x, y = np.asarray(f["rows"][c]), np.asarray(g["rows"][c])
            assert x.dtype == y.dtype and np.array_equal(x, y), (name, c)
        if f["cms"] is None:
            assert g["cms"] is None
        else:
            assert g["cms"] is not None
            assert f["cms"].dtype == g["cms"].dtype
            assert np.array_equal(f["cms"], g["cms"])
    assert set(a["ranges"]) == set(b["ranges"])
    for t, slots in a["ranges"].items():
        gslots = b["ranges"][t]
        assert [int(s) for s, _ in slots] == [int(s) for s, _ in gslots]
        for (_, rows), (_, grows) in zip(slots, gslots):
            assert set(rows) == set(grows)
            for c in rows:
                assert np.array_equal(np.asarray(rows[c]),
                                      np.asarray(grows[c]))
    assert a["audit"] == b["audit"]


class TestDeltaCodec:
    def test_full_round_trip_bit_exact(self):
        st = _mk_state(3, extremes=True)
        tree = next(decode_frames(encode_full(st)))
        assert tree["t"] == "full"
        _assert_states_equal(st, tree["state"])

    def test_delta_chain_reconstructs_bit_exact(self):
        states = [_mk_state(v, bump=v) for v in range(1, 6)]
        cur = next(decode_frames(encode_full(states[0])))["state"]
        for i in range(1, len(states)):
            tree = next(decode_frames(encode_delta(states[i - 1],
                                                   states[i])))
            assert tree["t"] == "delta"
            cur = apply_delta(cur, tree)
            _assert_states_equal(states[i], cur)

    def test_u64_extreme_tiles_patch_exactly(self):
        a = _mk_state(1)
        b = _mk_state(2, bump=0, extremes=True)
        b["version"] = 2
        d = diff_states(a, b)
        got = apply_delta(a, d)
        _assert_states_equal(b, got)
        assert int(got["families"]["hh"]["cms"][0, 0, 0]) == 2**64 - 1
        assert int(got["families"]["hh"]["cms"][1, 0, 1]) == 2**53 + 1

    def test_unchanged_cms_travels_as_nothing(self):
        a = _mk_state(1)
        b = _mk_state(2)  # same arrays, new metadata
        d = diff_states(a, b)
        hh = d["families"]["hh"]
        assert "cms" not in hh and "cms_tiles" not in hh
        assert "rows" not in hh  # ranked rows identical too
        got = apply_delta(a, d)
        # carried forward BY REFERENCE, not copied
        assert got["families"]["hh"]["cms"] is a["families"]["hh"]["cms"]
        _assert_states_equal(b, got)

    def test_sparse_rows_ship_only_touched_columns(self):
        a = _mk_state(1, width=512)
        b = _mk_state(2, width=512)
        b["families"]["hh"]["cms"] = a["families"]["hh"]["cms"].copy()
        b["families"]["hh"]["cms"][0, 0, 5] += np.uint64(1)
        b["families"]["hh"]["cms"][2, 0, 300] = np.uint64(2**64 - 1)
        hh = diff_states(a, b)["families"]["hh"]
        assert "cms_tiles" not in hh  # nothing dense enough for slabs
        sparse = hh["cms_sparse"]
        assert len(sparse) == 1  # one dirty depth row
        d, cols, vals = sparse[0]
        assert (d, list(cols)) == (0, [5, 300])
        assert vals.shape == (3, 2) and vals.dtype == np.uint64
        _assert_states_equal(b, apply_delta(a, diff_states(a, b)))

    def test_dense_rows_fall_back_to_tiles(self):
        a = _mk_state(1, width=512)
        b = _mk_state(2, width=512)
        cms = a["families"]["hh"]["cms"].copy()
        cms[:, 1, :] += np.uint64(1)  # whole depth row dirty
        b["families"]["hh"]["cms"] = cms
        hh = diff_states(a, b)["families"]["hh"]
        assert "cms_sparse" not in hh
        assert {int(d) for d, _, _ in hh["cms_tiles"]} == {1}
        _assert_states_equal(b, apply_delta(a, diff_states(a, b)))

    def test_gap_rejected(self):
        a, b, c = (_mk_state(v, bump=v) for v in (1, 2, 3))
        d_bc = diff_states(b, c)
        with pytest.raises(DeltaGapError):
            apply_delta(a, d_bc)

    def test_reordered_chain_rejected(self):
        a, b, c = (_mk_state(v, bump=v) for v in (1, 2, 3))
        d_ab, d_bc = diff_states(a, b), diff_states(b, c)
        mid = apply_delta(a, d_ab)
        assert mid["version"] == 2
        with pytest.raises(DeltaGapError):
            apply_delta(apply_delta(a, d_ab), d_ab)  # replayed link
        with pytest.raises(DeltaGapError):
            apply_delta(a, d_bc)  # skipped link

    def test_torn_and_corrupt_frames_rejected(self):
        frame = encode_full(_mk_state(1))
        with pytest.raises(DeltaError):
            list(decode_frames(frame[:-3]))  # torn body
        bad = bytearray(frame)
        bad[-1] ^= 0xFF
        with pytest.raises(DeltaError):
            list(decode_frames(bytes(bad)))  # CRC mismatch
        with pytest.raises(DeltaError):
            list(decode_frames(b"NOPE" + frame))  # bad magic

    def test_concatenated_frames_decode_in_order(self):
        a, b = _mk_state(1, bump=1), _mk_state(2, bump=2)
        data = encode_full(a) + encode_delta(a, b)
        kinds = [t["t"] for t in decode_frames(data)]
        assert kinds == ["full", "delta"]


try:  # property test where hypothesis exists (repo convention)
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.integers(0, 2**64 - 1), min_size=8, max_size=8),
           st.lists(st.integers(0, 2**64 - 1), min_size=8, max_size=8))
    def test_delta_property_u64_planes(base_words, new_words):
        """Any pair of u64 plane states diff+apply to the target
        exactly — wraparound extremes included."""
        a, b = _mk_state(1), _mk_state(2)
        a["families"]["hh"]["cms"] = np.asarray(
            base_words, np.uint64).reshape(1, 1, 8)
        b["families"]["hh"]["cms"] = np.asarray(
            new_words, np.uint64).reshape(1, 1, 8)
        got = apply_delta(a, diff_states(a, b))
        assert np.array_equal(got["families"]["hh"]["cms"],
                              b["families"]["hh"]["cms"])
except ImportError:  # pragma: no cover
    pass


# ---- feed ------------------------------------------------------------------


class TestSnapshotFeed:
    def _store_at(self, versions):
        store = SnapshotStore()
        for v in versions:
            store.publish_snapshot(state_to_snapshot(_mk_state(v, bump=v)))
        return store

    def test_none_delta_full_decisions(self):
        store = self._store_at([1])
        feed = SnapshotFeed(store)
        kind, cur, frames = feed.frame_since(0)
        assert (kind, cur) == ("full", 1)
        assert next(decode_frames(frames))["t"] == "full"
        kind, cur, _ = feed.frame_since(1)
        assert (kind, cur) == ("none", 1)
        store.publish_snapshot(state_to_snapshot(_mk_state(2, bump=2)))
        kind, cur, frames = feed.frame_since(1)
        assert (kind, cur) == ("delta", 2)
        assert next(decode_frames(frames))["t"] == "delta"
        # an unknown since (never observed) -> full
        kind, _, _ = feed.frame_since(99)
        assert kind == "full"

    def test_chain_spans_multiple_observed_versions(self):
        store = self._store_at([1])
        feed = SnapshotFeed(store)
        feed.frame_since(0)  # observe v1
        for v in (2, 3, 4):
            store.publish_snapshot(
                state_to_snapshot(_mk_state(v, bump=v)))
            feed.frame_since(v)  # observe each
        kind, cur, frames = feed.frame_since(1)
        assert (kind, cur) == ("delta", 4)
        trees = list(decode_frames(frames))
        assert [t["from"] for t in trees] == [1, 2, 3]
        assert [t["to"] for t in trees] == [2, 3, 4]

    def test_history_eviction_forces_full(self):
        store = self._store_at([1])
        feed = SnapshotFeed(store, history=2)
        feed.frame_since(0)
        for v in (2, 3, 4, 5):
            store.publish_snapshot(
                state_to_snapshot(_mk_state(v, bump=v)))
            feed.frame_since(v)
        kind, _, _ = feed.frame_since(1)  # evicted link
        assert kind == "full"
        kind, _, _ = feed.frame_since(3)  # still in history
        assert kind == "delta"

    def test_byte_budget_evicts_oldest_links(self):
        """Count-only retention holds ~FEED_HISTORY full-snapshot-sized
        frames when every CMS tile is dirty (delta ~= full): the byte
        budget evicts the oldest links first, widening the full-resync window instead of growing
        resident memory (the r17 journal lesson, on RAM)."""
        store = self._store_at([1])
        feed = SnapshotFeed(store, history_bytes=0)  # hold no deltas
        feed.frame_since(0)
        store.publish_snapshot(state_to_snapshot(_mk_state(2, bump=2)))
        kind, cur, _ = feed.frame_since(1)
        assert (kind, cur) == ("full", 2)  # the only link was evicted
        assert not feed._deltas and feed._delta_bytes_held == 0
        # the held-bytes ledger stays consistent through the COUNT cap
        store2 = self._store_at([1])
        feed2 = SnapshotFeed(store2, history=2)
        feed2.frame_since(0)
        for v in (2, 3, 4, 5):
            store2.publish_snapshot(
                state_to_snapshot(_mk_state(v, bump=v)))
            feed2.frame_since(v)
        assert len(feed2._deltas) == 2
        assert feed2._delta_bytes_held == sum(
            len(f) for _, _, f in feed2._deltas)

    def test_stats_ledger_counts_both_codings(self):
        store = self._store_at([1])
        feed = SnapshotFeed(store)
        feed.frame_since(0)
        store.publish_snapshot(state_to_snapshot(_mk_state(2, bump=2)))
        feed.frame_since(1)
        s = feed.stats()
        assert s["publishes"] == 2 and s["deltas"] == 1
        assert 0 < s["delta_bytes"] < s["full_bytes"]


@pytest.mark.slow  # worker + serve churn; gated by `make gateway-parity`
class TestConditionalPolls:
    """r19: ETag-conditional subscription polls (the r18 named
    follow-on). A subscriber that is already current revalidates with
    If-None-Match and the "none" answer costs HEADERS, NOT BYTES —
    while a stale subscriber's etag can never mask a delta/full ship
    (the etag encodes the CURRENT feed version, so it only matches a
    poll whose since is already current)."""

    def test_304_costs_headers_not_bytes(self):
        _, pub = _run_worker()
        serve = ServeServer(pub.store, port=0).start()
        try:
            cur = pub.store.current.version
            # unconditional "none" poll: a real frame body every time
            uncond = _get_raw(serve.port, f"/sub/snapshot?since={cur}")
            assert len(uncond) > 0
            # conditional: 304 with a ZERO-byte body — that frame's
            # bytes are exactly what the etag saves per quiet poll
            req = urllib.request.Request(
                f"http://127.0.0.1:{serve.port}/sub/snapshot"
                f"?since={cur}",
                headers={"If-None-Match": f'"sub-v{cur}"'})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 304
            assert ei.value.read() == b""
            assert ei.value.headers["ETag"] == f'"sub-v{cur}"'
            # a STALE subscriber sending its own (old) etag still gets
            # the full/delta body — the ship cannot be masked
            req = urllib.request.Request(
                f"http://127.0.0.1:{serve.port}/sub/snapshot?since=0",
                headers={"If-None-Match": '"sub-v0"'})
            resp = urllib.request.urlopen(req, timeout=10)
            assert resp.status == 200 and len(resp.read()) > 0
        finally:
            serve.stop()

    def test_gateway_quiet_polls_ship_zero_bytes(self):
        """The subscriber side: _Upstream.fetch sends the conditional
        header, maps 304 to zero frames, and the mirror loop reads it
        as a clean "none" — byte ledger checked at the fetch seam."""
        worker, pub = _run_worker()
        serve = ServeServer(pub.store, port=0).start()
        gw = SnapshotGateway([f"127.0.0.1:{serve.port}"], poll=60)
        try:
            assert gw.sync_once() == "full"
            up = gw.upstreams[0]
            # quiet upstream: the conditional poll costs zero body bytes
            assert up.fetch(up.version) == b""
            assert gw.sync_once() == "none"
            # a publish immediately lands as a delta — never masked
            with worker.lock:
                pub.publish(worker)
            assert gw.sync_once() == "delta"
            assert gw.store.current.version == pub.store.current.version
        finally:
            serve.stop()


# ---- the bit-exactness gate ------------------------------------------------


PARITY_PATHS = (
    "/query/topk", "/query/topk?k=0", "/query/topk?k=1",
    "/query/topk?k=5", "/query/topk?model=top_talkers&k=10",
    "/query/topk?model=flows_5m&k=3",
    "/query/range", "/query/range?model=flows_5m",
    "/query/audit",
)


def _assert_gateway_parity(direct_port, gw_port, store):
    """Every query answer byte-identical; /query/version identical
    modulo age_seconds (live by definition)."""
    paths = list(PARITY_PATHS)
    snap = store.current
    fam = snap.families["top_talkers"]
    for seedlane in (7, 2**32 - 1):
        key = ",".join(str(seedlane) for _ in range(fam.key_lanes))
        paths.append(f"/query/estimate?model=top_talkers&key={key}")
    slots = [s for s, _ in snap.ranges.get("flows_5m", ())]
    if slots:
        paths.append(f"/query/range?from={slots[0]}&to={slots[-1] + 1}")
        paths.append(f"/query/range?from={slots[-1]}")
    for path in paths:
        try:
            a = _get_raw(direct_port, path)
        except urllib.error.HTTPError as e:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get_raw(gw_port, path)
            assert ei.value.code == e.code, path
            continue
        b = _get_raw(gw_port, path)
        assert a == b, path
    v1, v2 = _get(direct_port, "/query/version"), \
        _get(gw_port, "/query/version")
    v1.pop("age_seconds"), v2.pop("age_seconds")
    assert v1 == v2


class TestGatewayParity:
    """Acceptance: every /query/* answer through a gateway equals the
    direct snapshot-path answer at the same version."""

    @pytest.fixture(scope="class", params=["table", "invertible"])
    def served(self, request):
        kw = {}
        if request.param == "invertible":
            kw = dict(sketch_backend="host", host_assist="on")
        worker, pub = _run_worker(hh_sketch=request.param, **kw)
        serve = ServeServer(pub.store, port=0).start()
        yield worker, pub, serve
        serve.stop()

    def test_http_mirror_is_bit_exact(self, served):
        _, pub, serve = served
        gw = SnapshotGateway([f"127.0.0.1:{serve.port}"], poll=60)
        gws = ServeServer(gw.store, port=0).start()
        gw.serve_on(gws)
        try:
            assert gw.sync_once() == "full"
            assert gw.store.current.version == pub.store.current.version
            _assert_gateway_parity(serve.port, gws.port, pub.store)
        finally:
            gws.stop()

    def test_delta_fed_mirror_is_bit_exact(self, served):
        """The same gate with the mirror built INCREMENTALLY: full
        once, then every subsequent publish applied as a delta."""
        worker, pub, serve = served
        gw = SnapshotGateway([pub.store], poll=60)
        gws = ServeServer(gw.store, port=0).start()
        gw.serve_on(gws)
        try:
            assert gw.sync_once() == "full"
            kinds = []
            for _ in range(3):
                with worker.lock:
                    pub.publish(worker)
                kinds.append(gw.sync_once())
            assert set(kinds) == {"delta"}
            assert gw.store.current.version == pub.store.current.version
            _assert_gateway_parity(serve.port, gws.port, pub.store)
        finally:
            gws.stop()

    def test_prerendered_hot_set_lands_with_the_snapshot(self, served):
        _, pub, serve = served
        gw = SnapshotGateway([pub.store], poll=60)
        gws = ServeServer(gw.store, port=0).start()
        gw.serve_on(gws)
        try:
            gw.sync_once()
            # the hot targets are in the raw-target alias cache BEFORE
            # any reader asked
            assert "/query/topk" in gws._alias
            assert "/query/topk?model=top_talkers" in gws._alias
            assert gw._m["prerendered"].value() >= 2
            # and the pre-rendered body is the served body
            etag, body = gws._alias["/query/topk"]
            assert _get_raw(gws.port, "/query/topk") == body
        finally:
            gws.stop()


@pytest.mark.slow
class TestMeshGatewayParity:
    """Marked slow (an 8k-flow 2-member mesh ingest): runs in
    `make gateway-parity` / CI; the worker-publisher parity class
    below carries the tier-1 bit-exactness gate."""

    def test_merged_view_through_gateway_is_bit_exact(self):
        """Acceptance, mesh leg: a gateway mirroring the COORDINATOR's
        merged snapshot stream answers every endpoint byte-identical
        to the coordinator's own serve surface."""
        from flow_pipeline_tpu.mesh import InProcessMesh, produce_sharded
        from flow_pipeline_tpu.serve import attach_mesh

        def mesh_models():
            return {
                "flows_5m": WindowAggregator(
                    WindowAggConfig(batch_size=512)),
                "top_talkers": WindowedHeavyHitter(
                    HeavyHitterConfig(
                        key_cols=("src_addr", "dst_addr", "src_port",
                                  "dst_port", "proto"),
                        batch_size=512, width=1 << 12, capacity=128),
                    k=10),
            }

        bus = InProcessBus()
        bus.create_topic("flows", 4)
        gen = FlowGenerator(ZipfProfile(n_keys=200, alpha=1.3), seed=7,
                            t0=1_700_000_000, rate=40.0)
        done = 0
        while done < 8000:
            done += produce_sharded(bus, "flows", gen.batch(2048), 4)
        mesh = InProcessMesh(
            bus, "flows", 2, model_factory=mesh_models,
            config=WorkerConfig(poll_max=2048, snapshot_every=0),
            sinks=[MemorySink()])
        pub = attach_mesh(mesh.coordinator, refresh=0.2, start=False)
        mesh.start()
        serve = ServeServer(pub.store, port=0).start()
        gw = SnapshotGateway([pub.store], poll=60)
        gws = ServeServer(gw.store, port=0).start()
        gw.serve_on(gws)
        try:
            mesh.wait_idle()
            snap = pub.publish_now()
            assert snap.source == "mesh"
            assert gw.sync_once() == "full"
            assert gw.store.current.version == pub.store.current.version
            assert gw.store.current.source == "mesh"
            _assert_gateway_parity(serve.port, gws.port, pub.store)
        finally:
            gws.stop()
            serve.stop()
            mesh.finalize()


# ---- resync / damage -------------------------------------------------------


class TestGatewayResync:
    def test_gap_forces_full_resync_and_serving_survives(self):
        store = SnapshotStore()
        store.publish_snapshot(state_to_snapshot(_mk_state(1, bump=1)))
        feed = SnapshotFeed(store, history=1)
        gw = SnapshotGateway([feed], poll=60)
        assert gw.sync_once() == "full"
        v1 = gw.store.current.version
        # the upstream advances PAST the feed history without the
        # gateway observing the links -> its next poll cannot chain
        for v in (2, 3, 4):
            store.publish_snapshot(
                state_to_snapshot(_mk_state(v, bump=v)))
            feed.frame_since(v)  # another subscriber observed them
        assert gw.sync_once() == "full"  # history evicted -> full ship
        assert gw.store.current.version == 4 > v1

    def test_corrupt_frames_resync_without_unpublishing(self):
        store = SnapshotStore()
        store.publish_snapshot(state_to_snapshot(_mk_state(1, bump=1)))
        gw = SnapshotGateway([store], poll=60)
        assert gw.sync_once() == "full"
        up = gw.upstreams[0]
        good_fetch = up.fetch
        resyncs0 = gw._m["resyncs"].value(reason="crc")
        store.publish_snapshot(state_to_snapshot(_mk_state(2, bump=2)))
        up.fetch = lambda since: good_fetch(since)[:-2] + b"XX"
        assert gw.sync_once() == "resync"
        assert gw._m["resyncs"].value(reason="crc") == resyncs0 + 1
        # the serving store kept its last good snapshot
        assert gw.store.current.version == 1
        # transport healed: the next poll is since=0 -> full, and the
        # mirror lands on the upstream's current version
        up.fetch = good_fetch
        assert gw.sync_once() == "full"
        assert gw.store.current.version == 2

    def test_stale_or_replayed_full_never_moves_versions_backwards(self):
        store = SnapshotStore()
        store.publish_snapshot(state_to_snapshot(_mk_state(5, bump=5)))
        gw = SnapshotGateway([store], poll=60)
        gw.sync_once()
        assert gw.store.current.version == 5
        # a replayed older full frame (flapping upstream / proxy cache)
        stale = state_to_snapshot(_mk_state(3, bump=3))
        assert gw.store.publish_snapshot(stale) is None
        assert gw.store.current.version == 5

    def test_upstream_restart_is_counted_not_adopted(self):
        """An upstream that restarts republishes from v1 (its store is
        per-process). Deltas only move forward, so a refused publish is
        the restart signature: the replica keeps serving its
        pre-restart snapshot (monotone by construction) and
        gateway_upstream_restarts_total is the live wedge signal the
        GatewayUpstreamRestarted alert pages on."""
        store = SnapshotStore()
        for v in (1, 2, 3):
            store.publish_snapshot(state_to_snapshot(_mk_state(v, bump=v)))
        gw = SnapshotGateway([store], poll=60)
        assert gw.sync_once() == "full"
        assert gw.store.current.version == 3
        up = gw.upstreams[0]
        r0 = gw._m["upstream_restarts"].value(upstream=up.name)
        # the upstream process restarts: fresh store + feed, v1 again
        fresh = SnapshotStore()
        fresh.publish_snapshot(state_to_snapshot(_mk_state(1, bump=9)))
        up._feed = SnapshotFeed(fresh)
        assert gw.sync_once() == "full"       # the restart's full frame
        assert gw.store.current.version == 3  # ...is never adopted
        assert gw._m["upstream_restarts"].value(
            upstream=up.name) == r0 + 1
        # post-restart deltas keep signalling: a live wedge, not a blip
        fresh.publish_snapshot(state_to_snapshot(_mk_state(2, bump=10)))
        assert gw.sync_once() == "delta"
        assert gw.store.current.version == 3
        assert gw._m["upstream_restarts"].value(
            upstream=up.name) == r0 + 2

    def test_unreachable_upstream_raises_oserror_for_the_loop(self):
        gw = SnapshotGateway(["127.0.0.1:1"], poll=60, timeout=0.2)
        with pytest.raises(OSError):
            gw.sync_once()

    def test_upstream_dying_mid_response_is_a_poll_failure(self):
        """IncompleteRead/BadStatusLine are HTTPException, NOT OSError
        (the r17 member-transport lesson): an upstream severed
        mid-response must normalize into the poll loop's OSError
        outage handling, not kill the mirror thread."""
        import http.client as hc

        gw = SnapshotGateway(["127.0.0.1:1"], poll=60, timeout=0.2)
        up = gw.upstreams[0]

        class _DiesMidResponse:
            def request(self, *a, **k):
                pass

            def getresponse(self):
                raise hc.IncompleteRead(b"partial")

            def close(self):
                pass

        up.conn = _DiesMidResponse()
        with pytest.raises(OSError):
            gw.sync_once()
        assert up.conn is None  # the dead connection was evicted


# ---- consistent hashing + client -------------------------------------------


class TestHashRing:
    def test_deterministic_across_instances(self):
        a = HashRing(["n1:1", "n2:2", "n3:3"])
        b = HashRing(["n1:1", "n2:2", "n3:3"])
        for k in map(str, range(200)):
            assert a.node_for(k) == b.node_for(k)

    def test_kill_remaps_only_the_dead_arc(self):
        ring = HashRing(["n1:1", "n2:2", "n3:3"])
        keys = [f"key-{i}" for i in range(300)]
        before = {k: ring.node_for(k) for k in keys}
        after = {k: ring.node_for(k, skip={"n2:2"}) for k in keys}
        assert all(v != "n2:2" for v in after.values())
        for k in keys:
            if before[k] != "n2:2":
                assert after[k] == before[k], k  # survivors undisturbed
        assert {v for v in before.values()} == {"n1:1", "n2:2", "n3:3"}

    def test_client_fails_over_on_http_exception(self):
        """A replica killed MID-RESPONSE surfaces IncompleteRead /
        BadStatusLine — HTTPException, not OSError. The client's
        contract is 'retried elsewhere, never surfaced'."""
        import http.client as hc

        store = SnapshotStore()
        store.publish_snapshot(state_to_snapshot(_mk_state(1, bump=1)))
        srv = ServeServer(store, port=0).start()
        try:
            good = f"127.0.0.1:{srv.port}"
            bad = "127.0.0.1:59999"
            client = GatewayClient([good, bad])
            real = client._conn_for

            class _Boom:
                def request(self, *a, **k):
                    raise hc.BadStatusLine("killed mid-response")

                def close(self):
                    pass

            client._conn_for = \
                lambda node: _Boom() if node == bad else real(node)
            path = next(p for p in (f"/query/topk?k={i}"
                                    for i in range(100))
                        if client.ring.node_for(p) == bad)
            code, body = client.get(path)
            assert code == 200 and body
            assert client.retries >= 1
        finally:
            srv.stop()

    def test_spread_is_roughly_even(self):
        ring = HashRing([f"n{i}:{i}" for i in range(4)])
        counts: dict = {}
        for i in range(4000):
            n = ring.node_for(f"k{i}")
            counts[n] = counts.get(n, 0) + 1
        assert min(counts.values()) > 4000 / 4 / 3  # no starved node


# ---- replication / churn gates ---------------------------------------------


def _client_reader(client, stop, out, paths):
    last = 0
    i = 0
    while not stop.is_set():
        path = paths[i % len(paths)]
        i += 1
        try:
            code, doc = client.get_json(path)
        except (OSError, ValueError) as e:  # noqa: PERF203 -- teardown race at stop is fine
            if not stop.is_set():
                out["errors"].append(f"{path}: {e}")
            continue
        if code >= 500:
            out["errors"].append(f"{path}: {code}")
            continue
        v = (doc or {}).get("version", 0)
        if v and v < last:
            out["errors"].append(
                f"{path}: version went backwards {last}->{v}")
        last = max(last, v)
        out["n"] += 1


@pytest.mark.slow
class TestGatewayChurn:
    """Marked slow: these are the multi-second live-ingest churn soaks.
    They ALWAYS run in `make gateway-parity` (the CI step filters no
    markers); the tier-1 budget keeps the fast parity/codec gates."""

    def test_kill_one_gateway_is_invisible_to_clients(self):
        """THE replication gate: live ingest, two gateway replicas,
        4 client threads reading through the consistent-hash client;
        one replica dies mid-load — zero 5xx, zero surfaced errors,
        versions monotone, reads keep flowing and versions advance."""
        worker = StreamWorker(
            Consumer(_fill_bus(batches=24, per=500), fixedlen=True),
            _models(), [MemorySink()],
            WorkerConfig(snapshot_every=0, poll_max=256))
        pub = attach_worker(worker, refresh=0.05)
        serve = ServeServer(pub.store, port=0).start()

        gws, servers = [], []
        for _ in range(2):
            gw = SnapshotGateway([f"127.0.0.1:{serve.port}"], poll=0.02)
            srv = ServeServer(gw.store, port=0).start()
            gw.serve_on(srv)
            gws.append(gw)
            servers.append(srv)
        client = GatewayClient(
            [f"127.0.0.1:{s.port}" for s in servers], monotone_wait=5.0)
        stop = threading.Event()
        out = {"errors": [], "n": 0}
        paths = ("/query/topk?model=top_talkers&k=10", "/query/version",
                 "/query/range")
        ingest = threading.Thread(
            target=lambda: worker.run(stop_when_idle=True), daemon=True)
        readers = []
        try:
            ingest.start()
            for gw in gws:
                gw.start()
            deadline = time.monotonic() + 30
            while any(gw.store.current is None for gw in gws) and \
                    time.monotonic() < deadline:
                time.sleep(0.01)
            assert all(gw.store.current is not None for gw in gws)
            readers = [threading.Thread(
                target=_client_reader, args=(client, stop, out, paths),
                daemon=True) for _ in range(4)]
            for t in readers:
                t.start()
            time.sleep(0.4)  # readers overlap live ingest
            # kill the replica the ring actually routes traffic to —
            # killing an arc no path hashes onto would make the gate
            # vacuously green
            victim_node = client.ring.node_for(paths[0])
            victim = next(i for i, s in enumerate(servers)
                          if f"127.0.0.1:{s.port}" == victim_node)
            gws[victim].stop()
            servers[victim].stop()
            survivor = gws[1 - victim]
            time.sleep(0.4)
            n_after_kill = out["n"]
            ingest.join(timeout=120)
            with worker.lock:
                final = pub.publish(worker)
            deadline = time.monotonic() + 10
            while survivor.store.current.version < final.version and \
                    time.monotonic() < deadline:
                time.sleep(0.02)
            time.sleep(0.2)
        finally:
            stop.set()
            for t in readers:
                t.join(timeout=30)
            for i, gw in enumerate(gws):
                if i != victim:
                    gw.stop()
                    servers[i].stop()
            serve.stop()
        assert not out["errors"], out["errors"][:5]
        assert out["n"] > n_after_kill > 20  # reads flowed before AND after
        # the surviving replica reached the final upstream version
        assert survivor.store.current.version == final.version
        assert client.retries >= 1  # the failover actually happened

    def test_kill_one_mesh_worker_under_gateway_read_load(self):
        """THE mesh-churn gate through the gateway: readers hammer a
        gateway mirroring the coordinator's merged stream while a mesh
        MEMBER is killed — zero 5xx, versions monotone, merges keep
        landing and the gateway keeps advancing."""
        from flow_pipeline_tpu.mesh import InProcessMesh, produce_sharded
        from flow_pipeline_tpu.serve import attach_mesh

        def mesh_models():
            return {
                "flows_5m": WindowAggregator(
                    WindowAggConfig(batch_size=512)),
                "top_talkers": WindowedHeavyHitter(
                    HeavyHitterConfig(
                        key_cols=("src_addr", "dst_addr", "src_port",
                                  "dst_port", "proto"),
                        batch_size=512, width=1 << 12, capacity=128),
                    k=10),
            }

        bus = InProcessBus()
        bus.create_topic("flows", 4)
        gen = FlowGenerator(ZipfProfile(n_keys=200, alpha=1.3), seed=11,
                            t0=1_700_000_000, rate=25.0)
        done = 0
        while done < 16000:
            done += produce_sharded(bus, "flows", gen.batch(2048), 4)
        mesh = InProcessMesh(
            bus, "flows", 2, model_factory=mesh_models,
            config=WorkerConfig(poll_max=1024, snapshot_every=0),
            sinks=[], submit_every=2)
        pub = attach_mesh(mesh.coordinator, refresh=0.05, start=True)
        gw = SnapshotGateway([pub.store], poll=0.02).start()
        gws = ServeServer(gw.store, port=0).start()
        gw.serve_on(gws)
        client = GatewayClient([f"127.0.0.1:{gws.port}"])
        stop = threading.Event()
        out = {"errors": [], "n": 0}
        paths = ("/query/topk?model=top_talkers&k=10", "/query/version",
                 "/query/range")
        readers = []
        try:
            mesh.start()
            deadline = time.monotonic() + 30
            while gw.store.current is None and \
                    time.monotonic() < deadline:
                time.sleep(0.01)
            assert gw.store.current is not None
            readers = [threading.Thread(
                target=_client_reader, args=(client, stop, out, paths),
                daemon=True) for _ in range(4)]
            for t in readers:
                t.start()
            time.sleep(0.5)
            mesh.kill_member(1)  # fence + rebalance under read load
            mesh.wait_idle()
            v_before = gw.store.current.version
            pub.publish_now()
            deadline = time.monotonic() + 10
            while gw.store.current.version <= v_before and \
                    time.monotonic() < deadline:
                time.sleep(0.02)
            assert gw.store.current.version > v_before
        finally:
            stop.set()
            mesh.finalize()
            pub.stop()
            gw.stop()
            gws.stop()
        for t in readers:
            t.join(timeout=30)
        assert not out["errors"], out["errors"][:5]
        assert out["n"] > 50
        assert mesh.coordinator._m["rebalance"].value(
            reason="death") >= 1.0


# ---- chaos seam ------------------------------------------------------------


class TestGatewayChaos:
    def test_injected_poll_faults_ride_the_mirror_alive(self):
        """gateway.poll faults (flowchaos seam) surface as poll
        failures: the mirror keeps its last snapshot, versions stay
        monotone, and syncs resume when the plan disarms."""
        store = SnapshotStore()
        store.publish_snapshot(state_to_snapshot(_mk_state(1, bump=1)))
        gw = SnapshotGateway([store], poll=60)
        assert gw.sync_once() == "full"
        FAULTS.configure("gateway.poll:p=1@seed=3")
        store.publish_snapshot(state_to_snapshot(_mk_state(2, bump=2)))
        with pytest.raises(OSError):
            gw.sync_once()
        assert gw.store.current.version == 1  # kept serving
        FAULTS.configure(None)
        assert gw.sync_once() == "delta"
        assert gw.store.current.version == 2


# ---- flags / wiring --------------------------------------------------------


def test_gateway_flags_registered_and_parsed():
    from flow_pipeline_tpu.utils.flags import KNOWN_FLAGS, FlagSet

    assert {"gateway.listen", "gateway.upstream",
            "gateway.poll"} <= KNOWN_FLAGS
    fs = FlagSet("t")
    fs.string("gateway.upstream", "", "h")
    fs.string("gateway.listen", ":8084", "h")
    fs.number("gateway.poll", 0.25, "h")
    vals = fs.parse(["-gateway.upstream", "a:1,b:2",
                     "-gateway.poll", "0.1"])
    assert vals["gateway.upstream"] == "a:1,b:2"
    assert vals["gateway.poll"] == 0.1


def test_sub_snapshot_endpoint_serves_frames():
    """/sub/snapshot on a plain serve server: binary frames, correct
    kinds, and the JSON cache is untouched by the polls."""
    store = SnapshotStore()
    store.publish_snapshot(state_to_snapshot(_mk_state(1, bump=1)))
    serve = ServeServer(store, port=0).start()
    try:
        raw = _get_raw(serve.port, "/sub/snapshot?since=0")
        tree = next(decode_frames(raw))
        assert tree["t"] == "full" and tree["to"] == 1
        raw = _get_raw(serve.port, "/sub/snapshot?since=1")
        assert next(decode_frames(raw))["t"] == "none"
        assert serve._cache == {}  # never cached as JSON entries
    finally:
        serve.stop()
