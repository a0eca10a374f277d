"""meshscope: distributed window lineage, cross-process trace
propagation + clock alignment, mesh SLO metrics, /healthz liveness,
flow_build_info, and the coordinator-side fence/zombie flight-recorder
dump. `make mesh-parity-traced` runs this file next to test_mesh.py
under FLOWTPU_TRACE=always (instrumentation must stay observational).
"""

import json
import os
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from flow_pipeline_tpu.cli import (_build_models, _common_flags,
                                   _gen_flags, _processor_flags)
from flow_pipeline_tpu.engine import StreamWorker, WorkerConfig
from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
from flow_pipeline_tpu.mesh import (ClockSync, InProcessMesh,
                                    MeshCoordinator,
                                    MeshCoordinatorServer,
                                    MemberStateServer, ModelSpec,
                                    TraceLane, aggregate_traces,
                                    estimate_offset, produce_sharded)
from flow_pipeline_tpu.mesh import codec
from flow_pipeline_tpu.models.window_agg import WindowAggConfig, WindowStore
from flow_pipeline_tpu.obs import REGISTRY, MetricsServer
from flow_pipeline_tpu.obs.buildinfo import BUILD_INFO, publish_build_info
from flow_pipeline_tpu.obs.trace import TRACER
from flow_pipeline_tpu.transport import Consumer, InProcessBus
from flow_pipeline_tpu.utils.flags import KNOWN_FLAGS, FlagSet

N_KEYS = 200
N_FLOWS = 24_000
PARTITIONS = 8
BATCH = 4096


@pytest.fixture(autouse=True)
def _restore_tracer():
    yield
    TRACER.configure(os.environ.get("FLOWTPU_TRACE", "ring"))


# ---------------------------------------------------------------------------
# protocol-level helpers (the test_mesh.py shapes)
# ---------------------------------------------------------------------------


def _wagg_spec():
    cfg = WindowAggConfig(key_cols=("src_as",), value_cols=("bytes",),
                          window_seconds=300, scale_col=None,
                          batch_size=256)
    return ModelSpec("flows_5m", "wagg", cfg, 0, 300)


def _contrib(ranges, wm, closed=None, open_=None, final=False,
             release=False, flows=0, span=None):
    out = {"ranges": ranges, "watermark": wm, "closed": closed or {},
           "open": open_ or {}, "final": final, "release": release,
           "flows": flows}
    if span is not None:
        out["span"] = span
    return out


def _wagg_win(key, val):
    return {"flows_5m": codec.wagg_payload(
        WindowStore.from_rows([(key,)], [(val, 1)]))}


def _span(sub, chunk=7, slots=(300,)):
    return {"sub": sub, "member": "x", "sent": time.time(),
            "chunk": chunk, "windows": list(slots)}


# ---------------------------------------------------------------------------
# clock alignment + aggregation (mesh/scope.py)
# ---------------------------------------------------------------------------


class TestClockAlignment:
    def test_estimate_offset_symmetric_trip_is_exact(self):
        # local sends at 100, remote clock runs +5.0s, reply observed
        # at 102: midpoint 101 -> remote_now 106 -> offset exactly +5
        offset, rtt = estimate_offset(100.0, 102.0, 106.0)
        assert offset == pytest.approx(5.0)
        assert rtt == pytest.approx(2.0)

    def test_clock_sync_prefers_min_rtt_sample(self):
        cs = ClockSync()
        cs.add(0.0, 2.0, 6.0)    # rtt 2, offset +5
        cs.add(10.0, 10.1, 15.05)  # rtt 0.1, offset +5.0 (tighter)
        cs.add(20.0, 24.0, 30.0)  # rtt 4, offset +8 (noisy)
        offset, rtt = cs.best()
        assert rtt == pytest.approx(0.1)
        assert offset == pytest.approx(5.0)
        rep = cs.report()
        assert rep["offset"] == pytest.approx(5.0)
        assert rep["rtt"] == pytest.approx(0.1)

    def test_clock_sync_empty_reports_none(self):
        assert ClockSync().best() is None
        assert ClockSync().report() is None

    def test_aggregate_aligns_lanes_monotone(self):
        base = 1_000_000.0
        coord = {"traceEvents": [
            {"name": "mesh_merge", "ph": "X", "ts": base * 1e6,
             "dur": 10.0, "pid": 1, "tid": "t"}],
            "otherData": {"mode": "ring", "dropped_spans": 0}}
        # the member's clock runs +5s ahead; its spans really happened
        # AT base but carry base+5 stamps
        member = {"traceEvents": [
            {"name": "apply", "ph": "X", "ts": (base + 5.0) * 1e6,
             "dur": 5.0, "pid": 1, "tid": "w"},
            {"name": "mesh_submit", "ph": "X",
             "ts": (base + 5.001) * 1e6, "dur": 2.0, "pid": 1,
             "tid": "w"}],
            "otherData": {"mode": "ring", "dropped_spans": 3}}
        doc = aggregate_traces([
            TraceLane("coordinator", coord),
            TraceLane("w0", member, offset_s=5.0, rtt_s=0.004),
        ])
        evs = doc["traceEvents"]
        names = {e["name"] for e in evs}
        assert {"process_name", "process_sort_index",
                "mesh_merge", "apply", "mesh_submit"} <= names
        lanes = {e["args"]["name"]: e["pid"] for e in evs
                 if e["name"] == "process_name"}
        assert lanes["coordinator"] != lanes["w0"]
        by_name = {e["name"]: e for e in evs if e.get("ph") == "X"}
        # aligned onto the coordinator clock: the +5s skew removed
        assert by_name["apply"]["ts"] == pytest.approx(base * 1e6)
        # order within the member lane preserved (monotone shift)
        assert by_name["mesh_submit"]["ts"] > by_name["apply"]["ts"]
        # member events live on the member lane
        assert by_name["apply"]["pid"] == lanes["w0"]
        meta = {l["name"]: l for l in doc["otherData"]["lanes"]}
        assert meta["w0"]["clock_offset_ms"] == pytest.approx(5000.0)
        assert meta["w0"]["alignment_error_bound_ms"] == \
            pytest.approx(2.0)
        assert meta["w0"]["dropped_spans"] == 3
        assert doc["otherData"]["reference"] == "coordinator"


# ---------------------------------------------------------------------------
# coordinator: lineage ledger + SLO metrics + span context
# ---------------------------------------------------------------------------


class TestLineageProtocol:
    def make(self, partitions=2, **kw):
        return MeshCoordinator([_wagg_spec()], partitions, **kw)

    def test_sync_carries_now_and_stores_clock(self):
        c = self.make()
        c.join("a")
        resp = c.sync("a", clock={"offset": -0.5, "rtt": 0.01})
        assert isinstance(resp["now"], float)
        # member reported coordinator-member = -0.5; the aggregator
        # stores member-coordinator = +0.5
        assert c._members["a"].clock_offset == pytest.approx(0.5)
        assert c._members["a"].clock_rtt == pytest.approx(0.01)
        # no trace_url advertised -> not a trace source
        assert c.trace_sources() == []

    def test_join_registers_trace_source(self):
        c = self.make()
        c.join("a", trace_url="http://h:8081/debug/trace")
        c.sync("a", clock={"offset": -1.0, "rtt": 0.002})
        (mid, url, offset, rtt), = c.trace_sources()
        assert mid == "a" and url.endswith("/debug/trace")
        assert offset == pytest.approx(1.0)

    def test_merged_lineage_names_members_ranges_and_path(self):
        c = self.make(partitions=2)
        c.join("a"), c.join("b")
        sa, sb = c.sync("a"), c.sync("b")
        pa, pb = list(sa["assign"])[0], list(sb["assign"])[0]
        c.submit("a", _contrib({pa: [0, 5]}, wm=900,
                               closed={300: _wagg_win(1, 10)},
                               span=_span(1, chunk=11)))
        # not merged yet: record rides the barrier as pending
        pend = c.lineage("flows_5m", 300)
        assert len(pend) == 1 and pend[0]["status"] == "pending"
        c.submit("b", _contrib({pb: [0, 5]}, wm=900,
                               closed={300: _wagg_win(1, 5)},
                               span=_span(1, chunk=12)))
        rec, = c.lineage("flows_5m", 300)
        assert rec["status"] == "merged"
        assert rec["members"] == ["a", "b"]
        assert rec["rows"] == 1
        assert rec["late"] == 0 and rec["carries_promoted"] == []
        assert rec["merged"] >= rec["merge_started"] >= \
            rec["first_contribution"]
        assert rec["emitted"] >= rec["merged"]
        assert rec["barrier_wait_s"] >= 0.0
        kinds = {(con["member"], con["kind"])
                 for con in rec["contributions"]}
        assert kinds == {("a", "closed"), ("b", "closed")}
        by_member = {con["member"]: con for con in rec["contributions"]}
        assert by_member["a"]["ranges"] == {pa: [0, 5]}
        assert by_member["a"]["sub"] == 1
        assert by_member["a"]["chunk"] == 11
        assert by_member["a"]["accepted"] is not None

    def test_lineage_records_carry_promotion_after_death(self):
        c = self.make(partitions=1)
        c.join("a")
        c.sync("a")
        # open-window carry only; then a crashes
        c.submit("a", _contrib({0: [0, 10]}, wm=0,
                               open_={300: _wagg_win(3, 40)},
                               span=_span(2, chunk=9)))
        c.fence("a")
        c.join("b")
        c.sync("b")
        c.submit("b", _contrib({0: [10, 12]}, wm=0,
                               closed={300: _wagg_win(3, 2)},
                               span=_span(1), final=True))
        rec, = c.lineage("flows_5m", 300)
        assert rec["status"] == "merged"
        assert rec["carries_promoted"] == ["a"]
        kinds = {(con["member"], con["kind"])
                 for con in rec["contributions"]}
        assert ("a", "carry-promoted") in kinds
        assert ("b", "closed") in kinds
        # the promoted contribution keeps the dead member's span ids
        carry = next(con for con in rec["contributions"]
                     if con["kind"] == "carry-promoted")
        assert carry["sub"] == 2 and carry["chunk"] == 9
        # no rows lost: 40 (promoted carry) + 2 (successor)
        rows = c.merged_rows("flows_5m", 300)
        assert int(rows[0]["bytes"][0]) == 42

    def test_lineage_retention_bounded(self, monkeypatch):
        from flow_pipeline_tpu.mesh import coordinator as coord_mod

        monkeypatch.setattr(coord_mod, "LINEAGE_SLOTS", 4)
        c = self.make(partitions=1)
        c.join("a")
        c.sync("a")
        for i in range(7):
            c.submit("a", _contrib(
                {0: [i * 10, (i + 1) * 10]}, wm=(i + 2) * 300 + 600,
                closed={(i + 1) * 300: _wagg_win(1, i + 1)},
                span=_span(i + 1)))
        merged = [r for r in c.lineage("flows_5m")
                  if r["status"] == "merged"]
        assert 0 < len(merged) <= 4
        # the newest slots win
        newest = max(r["slot"] for r in c.lineage("flows_5m"))
        assert any(r["slot"] == newest for r in merged) or \
            any(r["slot"] == newest and r["status"] == "pending"
                for r in c.lineage("flows_5m"))

    def test_late_remerge_preserves_original_lineage(self):
        """Review regression: a late wagg partial re-merging a sealed
        window must FOLD INTO the original lineage record, not replace
        it — and must not feed a bogus ~0 barrier-wait sample."""
        c = self.make(partitions=1)
        b0, _ = c._m["barrier_s"].value()
        c.join("a")
        c.sync("a")
        c.submit("a", _contrib({0: [0, 10]}, wm=900,
                               closed={300: _wagg_win(1, 10)},
                               span=_span(1)))
        rec, = c.lineage("flows_5m", 300)
        assert rec["status"] == "merged" and rec["members"] == ["a"]
        first = rec["first_contribution"]
        b1, _ = c._m["barrier_s"].value()
        assert b1 == b0 + 1
        # a second member delivers a LATE partial for the same slot
        c.join("b")
        c.sync("a")  # a resyncs away eventually; keep it simple:
        c.fence("a")
        c.sync("b")
        c.submit("b", _contrib({0: [10, 12]}, wm=900,
                               closed={300: _wagg_win(1, 5)},
                               span=_span(1)))
        rec, = c.lineage("flows_5m", 300)
        assert rec["status"] == "merged"
        assert rec["members"] == ["a", "b"], \
            "the original builder must survive the re-merge"
        assert rec["first_contribution"] == first
        assert rec["late"] == 1
        assert rec["remerges"] == 1
        kinds = {(con["member"], con["kind"])
                 for con in rec["contributions"]}
        assert ("a", "closed") in kinds and ("b", "late") in kinds
        # the re-merge observed submit->merge but NOT barrier-wait
        b2, _ = c._m["barrier_s"].value()
        assert b2 == b1

    def test_barrier_wait_measures_to_release_not_merge_start(self):
        """Review regression: the barrier interval ends at the
        _pop_ready_locked release stamp — when several windows detach
        in one batch, the later ones must not absorb the earlier ones'
        merge+emit wall as 'barrier wait'."""
        c = self.make(partitions=1)
        c.join("a")
        c.sync("a")
        c.submit("a", _contrib({0: [0, 5]}, wm=1500,
                               closed={300: _wagg_win(1, 1),
                                       600: _wagg_win(2, 2)},
                               span=_span(1)))
        recs = {r["slot"]: r for r in c.lineage("flows_5m")}
        assert set(recs) == {300, 600}
        for r in recs.values():
            assert r["status"] == "merged"
            assert r["barrier_wait_s"] == round(
                max(0.0, r["barrier_released"]
                    - r["first_contribution"]), 6)
        # released in the same pop batch: identical release stamp, so
        # neither window's wait includes the other's merge wall
        assert recs[300]["barrier_released"] == \
            recs[600]["barrier_released"]

    def test_midgap_late_annotation_drains_into_seal(self):
        """Review regression: a late (dropped-kind) contribution that
        lands after a window is marked merged but BEFORE its lineage
        record seals (the merge runs lock-free in between) buffers as
        an orphan and drains into the sealed record — ledger and
        mesh_late_contribution_total cannot disagree."""
        c = self.make(partitions=1)
        c.join("a")
        c.sync("a")
        c.submit("a", _contrib({0: [0, 5]}, wm=900,
                               closed={300: _wagg_win(1, 10)},
                               span=_span(1)))
        key = ("flows_5m", 300)
        # simulate the pop->seal gap: the key is merged but no sealed
        # record exists yet
        with c._lock:
            lin = c._lineage_done.pop(key)
        with c._lock:
            c._fold_windows_locked(
                {300: {"flows_5m": {"kind": "hh"}}}, member="b",
                span=_span(9), accepted=time.time(), kind="closed")
            assert key in c._lineage_orphans
            c._finish_lineage_locked("flows_5m", 300, lin,
                                     lin["merge_started"],
                                     lin["merged"], lin["emitted"], 1)
        rec = c._lineage_done[key]
        assert any(x["kind"] == "late-dropped" and x["member"] == "b"
                   for x in rec["contributions"])
        assert rec["late"] == 1
        assert key not in c._lineage_orphans

    def test_fenced_member_gauge_series_removed(self):
        c = self.make(partitions=2)
        c.join("a"), c.join("b")
        sa, sb = c.sync("a"), c.sync("b")
        pa, pb = list(sa["assign"])[0], list(sb["assign"])[0]
        c.submit("a", _contrib({pa: [0, 5]}, wm=1200, span=_span(1)))
        c.submit("b", _contrib({pb: [0, 5]}, wm=300, span=_span(1)))
        assert c._m["commit_wm"].value() == 300.0
        c.fence("b")
        # the laggard's death releases the mesh min AND its own series
        assert c._m["commit_wm"].value() == 1200.0
        assert 'member="b"' not in c._m["wm_skew"].render()
        assert 'member="b"' not in c._m["member_wm"].render()
        assert 'member="a"' in c._m["member_wm"].render()

    def test_left_member_gauge_series_removed(self):
        """Review regression: the GRACEFUL leave path must drop the
        departed member's watermark/skew series exactly like the fence
        path — a clean shutdown must not leave a frozen skew paging."""
        c = self.make(partitions=1)
        c.join("a")
        c.sync("a")
        c.submit("a", _contrib({0: [0, 5]}, wm=900, final=True,
                               span=_span(1)))
        assert 'member="a"' in c._m["member_wm"].render()
        c.leave("a")  # partition final -> the non-fence leave branch
        assert 'member="a"' not in c._m["member_wm"].render()
        assert 'member="a"' not in c._m["wm_skew"].render()

    def test_evicted_window_remerge_skips_barrier_sample(
            self, monkeypatch):
        """Review regression: a late wagg re-merge for a window whose
        lineage record was retention-EVICTED (merged_keys outlives the
        ledger) must still count as a re-merge — no bogus ~0 barrier
        sample, and the re-merge provenance survives."""
        from flow_pipeline_tpu.mesh import coordinator as coord_mod

        monkeypatch.setattr(coord_mod, "LINEAGE_SLOTS", 1)
        c = self.make(partitions=1)
        c.join("a")
        c.sync("a")
        c.submit("a", _contrib({0: [0, 5]}, wm=900,
                               closed={300: _wagg_win(1, 10)},
                               span=_span(1)))
        c.submit("a", _contrib({0: [5, 10]}, wm=1200,
                               closed={600: _wagg_win(1, 10)},
                               span=_span(2)))
        # slot 300's lineage record is now evicted (newest-1 retention)
        assert ("flows_5m", 300) not in c._lineage_done
        b0, _ = c._m["barrier_s"].value()
        c.submit("a", _contrib({0: [10, 11]}, wm=1200,
                               closed={300: _wagg_win(1, 4)},
                               span=_span(3)))
        assert len(c.merged_rows("flows_5m", 300)) == 2  # re-emitted
        b1, _ = c._m["barrier_s"].value()
        assert b1 == b0, "evicted-window re-merge must not feed the " \
                         "barrier-wait histogram"

    def test_unreported_member_excluded_from_watermarks(self):
        c = self.make(partitions=2)
        c.join("a")
        c.sync("a")
        c.submit("a", _contrib({0: [0, 5], 1: [0, 5]}, wm=1200,
                               span=_span(1)))
        assert c._m["commit_wm"].value() == 1200.0
        # a newcomer that never reported (watermark 0) must not crater
        # the mesh watermark to 0 / read as ~epoch skew
        c.join("b")
        c.submit("a", _contrib({}, wm=1201, span=_span(2)))
        assert c._m["commit_wm"].value() == 1201.0
        assert 'member="b"' not in c._m["wm_skew"].render()

    def test_range_rejection_reports_honest_reason(self):
        c = self.make(partitions=1)
        c.join("a")
        c.sync("a")
        r = c.submit("a", _contrib({0: [5, 10]}, wm=0, span=_span(1)))
        assert not r["ok"] and r["reason"] == "range"
        c.join("z")  # never synced/owned
        c.fence("z")
        r = c.submit("z", _contrib({}, wm=0, span=_span(1)))
        assert not r["ok"] and r["reason"] == "fenced"

    def test_watermark_skew_gauges(self):
        c = self.make(partitions=2)
        c.join("a"), c.join("b")
        sa, sb = c.sync("a"), c.sync("b")
        pa, pb = list(sa["assign"])[0], list(sb["assign"])[0]
        c.submit("a", _contrib({pa: [0, 5]}, wm=1200, span=_span(1)))
        c.submit("b", _contrib({pb: [0, 5]}, wm=300, span=_span(1)))
        assert c._m["commit_wm"].value() == 300.0
        assert c._m["member_wm"].value(member="a") == 1200.0
        assert c._m["wm_skew"].value(member="a") == 0.0
        assert c._m["wm_skew"].value(member="b") == 900.0

    def test_slo_histograms_observe_on_merge(self):
        c = self.make(partitions=1)
        b0, _ = c._m["barrier_s"].value()
        # submit->merge is member-labeled (r15: so a fenced member's
        # series can be removed instead of freezing)
        s0, _ = c._m["sub2merge_s"].value(member="a")
        c.join("a")
        c.sync("a")
        c.submit("a", _contrib({0: [0, 10]}, wm=900,
                               closed={300: _wagg_win(7, 50)},
                               span=_span(1)))
        b1, _ = c._m["barrier_s"].value()
        s1, _ = c._m["sub2merge_s"].value(member="a")
        assert b1 == b0 + 1
        assert s1 >= s0 + 1

    def test_rebalance_duration_observed_when_settled(self):
        c = self.make(partitions=2)
        n0, _ = c._m["rebalance_s"].value(reason="join")
        c.join("a")
        c.sync("a")  # acquires both partitions -> settled
        n1, _ = c._m["rebalance_s"].value(reason="join")
        assert n1 == n0 + 1
        # from a settled state, a fence opens a new timeline under its
        # own reason; a join landing mid-flight keeps the FIRST trigger
        # (the duration measures the whole disturbance)
        d0, _ = c._m["rebalance_s"].value(reason="death")
        c.fence("a")
        c.join("b")
        c.sync("b")  # b acquires everything -> settled under "death"
        d1, _ = c._m["rebalance_s"].value(reason="death")
        assert d1 == d0 + 1


class TestFenceFlightRecorderDump:
    def _patch_tmp(self, monkeypatch, tmp_path):
        import tempfile

        monkeypatch.setattr(tempfile, "gettempdir",
                            lambda: str(tmp_path))
        return os.path.join(str(tmp_path),
                            f"flowtrace-coordinator-{os.getpid()}.json")

    def test_zombie_rejection_dumps_with_span_context(
            self, monkeypatch, tmp_path):
        """Satellite regression (crash-restart path): a fenced member's
        replayed submission is rejected AND leaves a coordinator-side
        flight-recorder dump whose ring contains the rejection span
        with the zombie's own span context (sub id, chunk, send
        anchor)."""
        path = self._patch_tmp(monkeypatch, tmp_path)
        TRACER.configure("ring")
        c = MeshCoordinator([_wagg_spec()], 1)
        c.join("a")
        c.sync("a")
        c.fence("a")  # death: dump #1
        assert os.path.exists(path)
        os.unlink(path)
        span = _span(5, chunk=33)
        r = c.submit("a", _contrib({0: [0, 10]}, wm=900, span=span))
        assert not r["ok"]
        assert os.path.exists(path), \
            "zombie rejection must leave the post-mortem dump"
        with open(path) as f:
            doc = json.load(f)
        rejects = [e for e in doc["traceEvents"]
                   if e["name"] == "mesh_submit_reject"]
        assert rejects, "the rejected submission's span must be in it"
        args = rejects[-1]["args"]
        assert args["member"] == "a"
        assert args["sub"] == 5 and args["chunk"] == 33
        assert args["sent"] == pytest.approx(span["sent"])
        assert args["reason"] == "fenced"

    def test_rejoin_while_fenced_alive_dumps(self, monkeypatch,
                                             tmp_path):
        path = self._patch_tmp(monkeypatch, tmp_path)
        TRACER.configure("ring")
        c = MeshCoordinator([_wagg_spec()], 1)
        c.join("a")
        c.sync("a")
        c.join("a")  # crash-restart before expiry: fence + dump
        assert os.path.exists(path)

    def test_no_dump_when_tracing_off(self, monkeypatch, tmp_path):
        path = self._patch_tmp(monkeypatch, tmp_path)
        TRACER.configure("off")
        c = MeshCoordinator([_wagg_spec()], 1)
        c.join("a")
        c.sync("a")
        c.fence("a")
        assert not os.path.exists(path)

    def test_graceful_leave_does_not_dump(self, monkeypatch, tmp_path):
        path = self._patch_tmp(monkeypatch, tmp_path)
        TRACER.configure("ring")
        c = MeshCoordinator([_wagg_spec()], 1)
        c.join("a")
        c.sync("a")
        c.submit("a", _contrib({0: [0, 10]}, wm=900, final=True,
                               span=_span(1)))
        c.leave("a")
        assert not os.path.exists(path)


# ---------------------------------------------------------------------------
# /healthz + /debug endpoints
# ---------------------------------------------------------------------------


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, json.loads(resp.read().decode())


class TestHealthz:
    def test_metrics_server_healthz_and_trace_now(self):
        server = MetricsServer(port=0).start()
        try:
            status, doc = _get_json(
                f"http://127.0.0.1:{server.port}/healthz")
            assert status == 200 and doc == {"ok": True}
            t0 = time.time()
            _, trace = _get_json(
                f"http://127.0.0.1:{server.port}/debug/trace")
            # the clock stamp the meshscope aggregator estimates from
            assert abs(trace["otherData"]["now"] - t0) < 60
        finally:
            server.stop()

    def test_coordinator_server_healthz(self):
        c = MeshCoordinator([_wagg_spec()], 1)
        server = MeshCoordinatorServer(c, port=0).start()
        try:
            status, doc = _get_json(
                f"http://127.0.0.1:{server.port}/healthz")
            assert status == 200 and doc["ok"] is True
        finally:
            server.stop()

    def test_member_state_server_healthz(self):
        class _Dummy:
            def _query_state(self, model):
                return None

        server = MemberStateServer(_Dummy(), port=0).start()
        try:
            status, doc = _get_json(
                f"http://127.0.0.1:{server.port}/healthz")
            assert status == 200 and doc == {"ok": True}
        finally:
            server.stop()


class _FakeTraceEndpoint:
    """A member-shaped /debug/trace endpoint whose clock runs at a
    configurable skew — what the coordinator's aggregator must align."""

    def __init__(self, skew_s: float, span_name: str = "member_span"):
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802
                now = time.time() + outer.skew
                body = json.dumps({
                    "traceEvents": [{
                        "name": outer.span_name, "ph": "X",
                        "ts": round(now * 1e6, 1), "dur": 100.0,
                        "pid": 77, "tid": "w",
                    }],
                    "otherData": {"mode": "ring", "dropped_spans": 0,
                                  "now": now},
                }).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):
                pass

        self.skew = skew_s
        self.span_name = span_name
        self._server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self._server.server_address[1]
        self.url = f"http://127.0.0.1:{self.port}/debug/trace"
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True)
        self._thread.start()

    def stop(self):
        self._server.shutdown()
        self._server.server_close()


class TestAggregatedMeshTrace:
    def test_fan_out_aligns_skewed_member_clock(self):
        TRACER.configure("ring")
        c = MeshCoordinator([_wagg_spec()], 1)
        fake = _FakeTraceEndpoint(skew_s=120.0)
        server = MeshCoordinatorServer(c, port=0).start()
        try:
            c.join("w0", trace_url=fake.url)
            with TRACER.span("coord_probe"):
                pass
            _, doc = _get_json(
                f"http://127.0.0.1:{server.port}/debug/trace")
        finally:
            server.stop()
            fake.stop()
        lanes = {l["name"]: l for l in doc["otherData"]["lanes"]}
        assert set(lanes) == {"coordinator", "w0"}
        # the 120s skew was estimated from the fetch round-trip and
        # removed: the member span lands within the fetch RTT of the
        # coordinator's wall clock, not two minutes ahead
        ev = next(e for e in doc["traceEvents"]
                  if e["name"] == "member_span")
        assert abs(ev["ts"] / 1e6 - time.time()) < 30
        assert lanes["w0"]["clock_offset_ms"] == \
            pytest.approx(120_000.0, abs=5_000)
        # both lanes present with distinct pids
        pids = {e["pid"] for e in doc["traceEvents"]
                if e["name"] == "process_name"}
        assert len(pids) == 2
        probes = [e for e in doc["traceEvents"]
                  if e["name"] == "coord_probe"]
        assert probes

    def test_heartbeat_estimate_wins_over_fetch(self):
        """A member that reported a clock offset via sync() is aligned
        by THAT estimate (tighter: min-RTT of 16 heartbeats), not by
        the one-shot fetch."""
        TRACER.configure("ring")
        c = MeshCoordinator([_wagg_spec()], 1)
        fake = _FakeTraceEndpoint(skew_s=50.0)
        server = MeshCoordinatorServer(c, port=0).start()
        try:
            c.join("w0", trace_url=fake.url)
            # member-measured: coordinator - member = -50s exactly
            c.sync("w0", clock={"offset": -50.0, "rtt": 0.001})
            _, doc = _get_json(
                f"http://127.0.0.1:{server.port}/debug/trace")
        finally:
            server.stop()
            fake.stop()
        lanes = {l["name"]: l for l in doc["otherData"]["lanes"]}
        assert lanes["w0"]["clock_offset_ms"] == pytest.approx(50_000.0)
        assert lanes["w0"]["rtt_ms"] == pytest.approx(1.0)

    def test_unreachable_member_degrades_not_blacks_out(self):
        TRACER.configure("ring")
        c = MeshCoordinator([_wagg_spec()], 1)
        fake = _FakeTraceEndpoint(skew_s=0.0)
        dead_url = fake.url
        fake.stop()  # now nothing listens there
        server = MeshCoordinatorServer(c, port=0).start()
        try:
            c.join("w0", trace_url=dead_url)
            _, doc = _get_json(
                f"http://127.0.0.1:{server.port}/debug/trace")
        finally:
            server.stop()
        lanes = [l["name"] for l in doc["otherData"]["lanes"]]
        assert lanes == ["coordinator"]

    def test_lineage_endpoint_serves_records(self):
        c = MeshCoordinator([_wagg_spec()], 1)
        server = MeshCoordinatorServer(c, port=0).start()
        try:
            c.join("a")
            c.sync("a")
            c.submit("a", _contrib({0: [0, 10]}, wm=900,
                                   closed={300: _wagg_win(7, 50)},
                                   span=_span(1)))
            _, recs = _get_json(
                f"http://127.0.0.1:{server.port}/debug/lineage"
                f"?model=flows_5m&slot=300")
            _, all_recs = _get_json(
                f"http://127.0.0.1:{server.port}/debug/lineage")
            status, _ = _get_json(
                f"http://127.0.0.1:{server.port}/debug/lineage"
                f"?model=nope")
        finally:
            server.stop()
        assert len(recs) == 1
        assert recs[0]["model"] == "flows_5m"
        assert recs[0]["status"] == "merged"
        assert recs[0]["members"] == ["a"]
        assert len(all_recs) >= 1
        assert status == 200  # unknown model -> empty list, not error


# ---------------------------------------------------------------------------
# flow_build_info
# ---------------------------------------------------------------------------


class TestBuildInfo:
    def test_publish_sets_identity_labels(self):
        from flow_pipeline_tpu import native as native_lib

        TRACER.configure("ring")
        g = publish_build_info("coordinator")
        caps = native_lib.capabilities()
        native = ",".join(sorted(f for f, ok in caps.items() if ok)) \
            or "none"
        assert g.value(role="coordinator", native=native, trace="ring",
                       sketch="device", hh_sketch="table") == 1.0
        assert "flow_build_info" in REGISTRY.render()

    def test_worker_publishes_on_construction(self):
        StreamWorker(consumer=None, models={},
                     config=WorkerConfig(sketch_backend="device"))
        g = REGISTRY.gauge(*BUILD_INFO)
        rendered = g.render()
        assert 'role="worker"' in rendered
        assert 'sketch="device"' in rendered
        assert 'trace="' in rendered and 'native="' in rendered

    def test_member_inner_worker_identifies_as_member(self):
        """Review regression: a member process must publish ONE
        identity — the inner StreamWorker's gauge says role=member
        (MeshMember rewrites build_role), not a second role=worker
        series next to it."""
        from flow_pipeline_tpu.mesh import MeshMember

        m = MeshMember("w9", coordinator=None,
                       consumer_factory=lambda parts: None,
                       model_factory=dict,
                       config=WorkerConfig(sketch_backend="device"))
        assert m.config.build_role == "member"


# ---------------------------------------------------------------------------
# lineage CLI
# ---------------------------------------------------------------------------


class TestLineageCLI:
    def test_flags_registered(self):
        for flag in ("lineage.model", "lineage.slot", "lineage.raw"):
            assert flag in KNOWN_FLAGS

    def _serve_one_merged_window(self):
        c = MeshCoordinator([_wagg_spec()], 1)
        server = MeshCoordinatorServer(c, port=0).start()
        c.join("a")
        c.sync("a")
        c.submit("a", _contrib({0: [0, 10]}, wm=900,
                               closed={300: _wagg_win(7, 50)},
                               span=_span(4, chunk=2)))
        return c, server

    def test_summary_output(self, capsys):
        from flow_pipeline_tpu.cli import main

        c, server = self._serve_one_merged_window()
        try:
            rc = main(["lineage", "-mesh.coordinator",
                       f"http://127.0.0.1:{server.port}"])
        finally:
            server.stop()
        assert rc == 0
        out = capsys.readouterr().out
        assert "flows_5m @ 300 [merged]" in out
        assert "members=a" in out
        assert "sub=4" in out
        assert "0:[0,10)" in out

    def test_raw_json_output(self, capsys):
        from flow_pipeline_tpu.cli import main

        c, server = self._serve_one_merged_window()
        try:
            rc = main(["lineage", "-mesh.coordinator",
                       f"http://127.0.0.1:{server.port}",
                       "-lineage.raw", "-lineage.model", "flows_5m"])
        finally:
            server.stop()
        assert rc == 0
        records = json.loads(capsys.readouterr().out)
        assert records[0]["slot"] == 300
        assert records[0]["contributions"][0]["sub"] == 4


# ---------------------------------------------------------------------------
# end-to-end: in-process mesh lineage + churn trace ring parity
# ---------------------------------------------------------------------------


def _vals(*extra):
    fs = _processor_flags(_gen_flags(_common_flags(FlagSet("test"))))
    return fs.parse([
        "-produce.profile", "zipf", "-zipf.keys", str(N_KEYS),
        "-model.ports=false", "-model.ddos=false", "-model.ips=false",
        "-processor.batch", str(BATCH), "-sketch.capacity", "512",
        *extra,
    ])


def _stream_batches(n_flows=N_FLOWS, seed=0):
    gen = FlowGenerator(ZipfProfile(n_keys=N_KEYS, alpha=1.2),
                        seed=seed, rate=100_000.0)
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
    acc = {}
    for rows in tables.get("flows_5m", []):
        for i in range(len(rows["timeslot"])):
            key = (int(rows["timeslot"][i]), int(rows["src_as"][i]),
                   int(rows["dst_as"][i]), int(rows["etype"][i]))
            v = acc.setdefault(key, np.zeros(3, np.uint64))
            v += np.array([rows["bytes"][i], rows["packets"][i],
                           rows["count"][i]], np.uint64)
    return acc


def _run_churn_mesh(vals, sink, monkeypatch_tmp=None):
    """The test_mesh churn leg: 3 workers, kill one mid-stream."""
    mesh = InProcessMesh(
        _make_bus(), "flows", 3,
        model_factory=lambda: _build_models(vals),
        config=WorkerConfig(poll_max=BATCH, snapshot_every=0),
        sinks=[sink], submit_every=2)
    mesh.start()
    victim = mesh.members[1]
    deadline = time.time() + 120
    while time.time() < deadline:
        w = victim.worker
        carry = mesh.coordinator._carry.get(victim.member_id)
        # kill only once a progress carry for an OPEN window is
        # accepted: the death then deterministically promotes a real
        # mid-window carry (the span-continuity story under test)
        if w is not None and w.flows_seen >= BATCH and \
                carry and carry.get("windows"):
            break
        time.sleep(0.002)
    else:
        pytest.fail("victim never got a carry accepted")
    mesh.kill_member(1)
    mesh.wait_idle()
    mesh.finalize()
    return mesh


def test_inprocess_4worker_trace_has_coordinator_and_member_lanes():
    """Acceptance: a 4-worker in-process mesh run with tracing on
    yields ONE aggregated Chrome trace through the coordinator's
    /debug/trace containing the coordinator protocol spans and every
    member's spans (in-process the member lanes are the per-member
    thread tracks of the single process lane; clocks are trivially
    aligned — the HTTP fan-out tests cover cross-process skew)."""
    vals = _vals()
    TRACER.configure("ring")
    mesh = InProcessMesh(
        _make_bus(), "flows", 4,
        model_factory=lambda: _build_models(vals),
        config=WorkerConfig(poll_max=BATCH, snapshot_every=0),
        sinks=[ListSink()])
    server = MeshCoordinatorServer(mesh.coordinator, port=0).start()
    try:
        mesh.run()
        _, doc = _get_json(
            f"http://127.0.0.1:{server.port}/debug/trace")
    finally:
        server.stop()
    tids = {e.get("tid") for e in doc["traceEvents"]}
    for i in range(4):
        assert f"mesh-w{i}" in tids, f"member w{i} lane missing"
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"mesh_submit", "mesh_submit_accept", "mesh_merge",
            "mesh_emit", "mesh_barrier_wait"} <= names
    assert [l["name"] for l in doc["otherData"]["lanes"]] == \
        ["coordinator"]


def test_mesh_lineage_answers_for_every_merged_window():
    """Acceptance: /debug/lineage answers for EVERY merged (model,
    slot) of an in-process mesh run — members, offset ranges, merge
    wall — and the lineage members match the mesh's live set."""
    vals = _vals()
    sink = ListSink()
    mesh = InProcessMesh(
        _make_bus(), "flows", 2,
        model_factory=lambda: _build_models(vals),
        config=WorkerConfig(poll_max=BATCH, snapshot_every=0),
        sinks=[sink])
    mesh.run()
    c = mesh.coordinator
    merged_keys = set(c.merged)
    assert merged_keys, "nothing merged — the leg is vacuous"
    records = {(r["model"], r["slot"]): r for r in c.lineage()
               if r["status"] == "merged"}
    for key in merged_keys:
        rec = records.get(key)
        assert rec is not None, f"no lineage for merged window {key}"
        assert rec["members"], key
        assert set(rec["members"]) <= {"w0", "w1"}
        assert rec["merge_wall_s"] >= 0.0
        assert rec["rows"] >= 0
        # every non-empty contribution names its offset ranges
        assert any(con["ranges"] for con in rec["contributions"])
    # SLO surfaces moved: barrier + submit->merge observed
    assert c._m["barrier_s"].value()[0] >= len(merged_keys)


def test_mesh_churn_ring_trace_continuity_and_bitexact(monkeypatch,
                                                       tmp_path):
    """Satellite: the trace ring under mesh churn. The kill-one-worker
    leg runs with -obs.trace=off and again with ring; sink output must
    be bit-exact across modes (instrumentation is observational), and
    the ring must hold the span story of the carry promotion: the
    victim's submits, the fence, the promotion, and the merge of the
    promoted window."""
    import tempfile

    monkeypatch.setattr(tempfile, "gettempdir", lambda: str(tmp_path))
    vals = _vals()
    TRACER.configure("off")
    sink_off = ListSink()
    _run_churn_mesh(vals, sink_off)
    TRACER.configure("ring")
    sink_ring = ListSink()
    mesh = _run_churn_mesh(vals, sink_ring)
    spans = TRACER.snapshot()
    # bit-exact sink parity off vs ring
    f_off, f_ring = _fold_flows5m(sink_off.tables), \
        _fold_flows5m(sink_ring.tables)
    assert set(f_off) == set(f_ring)
    for k in f_off:
        assert (f_off[k] == f_ring[k]).all()
    t_off = sink_off.tables["top_talkers"][0]
    t_ring = sink_ring.tables["top_talkers"][0]
    v_off = np.asarray(t_off["valid"])
    v_ring = np.asarray(t_ring["valid"])
    assert int(v_off.sum()) == int(v_ring.sum())
    for col in ("src_addr", "bytes", "packets", "count", "timeslot"):
        assert (np.asarray(t_off[col])[v_off] ==
                np.asarray(t_ring[col])[v_ring]).all(), col
    # span continuity across the carry promotion
    names = {}
    for name, t0, t1, thread, chunk, args in spans:
        names.setdefault(name, []).append(args or {})
    assert "mesh_fence" in names
    promos = names.get("mesh_carry_promotion", [])
    assert promos, "the kill must promote a carry"
    assert promos[0]["member"] == "w1"
    assert promos[0]["sub"] is not None  # the dead member's span ids survive
    # the victim submitted before death AND the merge story completed
    submit_members = {a["member"] for a in names.get("mesh_submit", [])}
    assert "w1" in submit_members
    accept_members = {a["member"]
                      for a in names.get("mesh_submit_accept", [])}
    assert accept_members >= {"w0", "w2"}  # survivors kept contributing
    merged_models = {a["model"] for a in names.get("mesh_merge", [])}
    assert {"flows_5m", "top_talkers"} <= merged_models
    # the promoted window's lineage chains to the merge
    promoted = [r for r in mesh.coordinator.lineage()
                if r["carries_promoted"]]
    assert promoted and all(r["status"] == "merged" for r in promoted
                            if r["status"] != "pending")
    # the kill also left the coordinator-side post-mortem dump
    dump = os.path.join(
        str(tmp_path), f"flowtrace-coordinator-{os.getpid()}.json")
    assert os.path.exists(dump)
