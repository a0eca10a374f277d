"""flowchaos: coordinator crash recovery (write-ahead journal), sink
retry + dead-letter + replay, the deterministic fault-injection layer,
and the chaos soak — `make chaos-parity` runs this file.

The r12 exactness-under-churn contract extended from "a worker dies" to
"anything dies": the kill-COORDINATOR-mid-stream leg must keep merged
sink output bit-exact vs the single-worker oracle, injected sink faults
must dead-letter (never crash the worker) and replay back to row-set
equality, and seeded mesh-transport faults must not lose or
double-count a single window."""

import json
import os
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
                                    MeshMember, ModelSpec,
                                    produce_sharded, spec_from_models)
from flow_pipeline_tpu.mesh import codec
from flow_pipeline_tpu.mesh.journal import (CoordinatorJournal,
                                            replay_journal)
from flow_pipeline_tpu.models.oracle import exact_groupby
from flow_pipeline_tpu.models.window_agg import WindowAggConfig, WindowStore
from flow_pipeline_tpu.schema.batch import FlowBatch
from flow_pipeline_tpu.sink import MemorySink, ResilientSink
from flow_pipeline_tpu.sink.resilient import (deadletter_files,
                                              replay_deadletter)
from flow_pipeline_tpu.transport import Consumer, InProcessBus
from flow_pipeline_tpu.utils.faults import FAULTS, parse_plan
from flow_pipeline_tpu.utils.flags import KNOWN_FLAGS, FlagSet
from flow_pipeline_tpu.utils.retry import retry_call

N_KEYS = 200
N_FLOWS = 24_000
PARTITIONS = 8
BATCH = 4096
# Default modeled rate keeps the whole stream inside ONE 5-minute
# window (the r12 oracle regime: the single worker IS a valid top-K
# oracle only when no window closes mid-stream — interleaved partition
# consumption otherwise makes IT drop late rows the per-partition mesh
# members never see as late). The multi-window crash leg below uses
# MULTIWIN_RATE with the flows_5m model only, whose late-partial
# semantics stay exact under any consumption order.
RATE = 100_000.0
MULTIWIN_RATE = 60.0

TOP_COLS = ("src_addr", "dst_addr", "src_port", "dst_port", "proto",
            "bytes", "packets", "count", "timeslot")


@pytest.fixture(autouse=True)
def _faults_disarmed():
    """The fault plan is process state (like TRACER): every test starts
    and ends disarmed, whatever happened before it."""
    FAULTS.configure(None)
    yield
    FAULTS.configure(None)


def _vals(*extra):
    fs = _processor_flags(_gen_flags(_common_flags(FlagSet("test"))))
    return fs.parse([
        "-produce.profile", "zipf", "-zipf.keys", str(N_KEYS),
        "-model.ports=false", "-model.ddos=false", "-model.ips=false",
        "-processor.batch", str(BATCH), "-sketch.capacity", "512",
        *extra,
    ])


def _stream_batches(n_flows=N_FLOWS, seed=0, rate=RATE):
    gen = FlowGenerator(ZipfProfile(n_keys=N_KEYS, alpha=1.2), seed=seed,
                        rate=rate)
    out, done = [], 0
    while done < n_flows:
        n = min(8192, n_flows - done)
        out.append(gen.batch(n))
        done += n
    return out


def _make_bus(n_flows=N_FLOWS, partitions=PARTITIONS, rate=RATE):
    bus = InProcessBus()
    bus.create_topic("flows", partitions)
    for batch in _stream_batches(n_flows, rate=rate):
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


def _oracle_flows5m(rate=RATE):
    full = FlowBatch.concat(_stream_batches(rate=rate))
    o = exact_groupby(full, ["src_as", "dst_as", "etype"],
                      ["bytes", "packets"])
    return {
        (int(o["timeslot"][i]), int(o["src_as"][i]), int(o["dst_as"][i]),
         int(o["etype"][i])):
        np.array([o["bytes"][i], o["packets"][i], o["count"][i]],
                 np.uint64)
        for i in range(len(o["timeslot"]))
    }


def _assert_flows5m_oracle_exact(tables, rate=RATE):
    oracle = _oracle_flows5m(rate)
    fold = _fold_flows5m(tables)
    assert set(fold) == set(oracle)
    for k in oracle:
        assert (fold[k] == oracle[k]).all()


def _assert_topk_tables_equal(t1, t2, table="top_talkers"):
    """Every emitted top-K window matches, slot by slot (the streams
    may span several windows)."""
    def by_slot(windows):
        out = {}
        for rows in windows:
            v = np.asarray(rows["valid"])
            assert v.any()
            out[int(np.asarray(rows["timeslot"])[v][0])] = rows
        return out

    w1, w2 = by_slot(t1[table]), by_slot(t2[table])
    assert set(w1) == set(w2)
    for slot in w1:
        r1, r2 = w1[slot], w2[slot]
        v1, v2 = np.asarray(r1["valid"]), np.asarray(r2["valid"])
        assert int(v1.sum()) == int(v2.sum())
        for col in TOP_COLS:
            a, b = np.asarray(r1[col])[v1], np.asarray(r2[col])[v2]
            assert a.shape == b.shape and (a == b).all(), (slot, col)


def _run_single_worker(vals, sink, rate=RATE):
    worker = StreamWorker(
        Consumer(_make_bus(rate=rate), "flows", fixedlen=True),
        _build_models(vals), [sink],
        WorkerConfig(poll_max=BATCH, snapshot_every=0,
                     sketch_backend=vals["sketch.backend"]))
    worker.run(stop_when_idle=True)
    return worker


# ---------------------------------------------------------------------------
# fault plan parsing + determinism
# ---------------------------------------------------------------------------


class TestFaultPlan:
    def test_parse_plan(self):
        sites, seed = parse_plan(
            "sink.write:p=0.05;mesh.submit:p=0.02@seed=7")
        assert sites == {"sink.write": 0.05, "mesh.submit": 0.02}
        assert seed == 7

    def test_parse_defaults_seed_zero(self):
        sites, seed = parse_plan("sink.write:p=1")
        assert sites == {"sink.write": 1.0} and seed == 0

    @pytest.mark.parametrize("bad", [
        "nope.site:p=0.1", "sink.write", "sink.write:q=0.1",
        "sink.write:p=1.5", "sink.write:p=0.1@tick=3",
    ])
    def test_malformed_plans_rejected(self, bad):
        with pytest.raises(ValueError):
            parse_plan(bad)

    def test_off_mode_is_one_attribute_read(self):
        FAULTS.configure(None)
        assert FAULTS.active is False
        # the guarded call-site pattern short-circuits on the attribute
        assert not (FAULTS.active and FAULTS.should_fail("sink.write"))

    def test_deterministic_per_site_streams(self):
        FAULTS.configure("sink.write:p=0.3;mesh.submit:p=0.3@seed=42")
        a = [FAULTS.should_fail("sink.write") for _ in range(64)]
        FAULTS.configure("sink.write:p=0.3;mesh.submit:p=0.3@seed=42")
        # interleave calls to ANOTHER site: sink.write's stream must not
        # shift (per-site independent RNGs — the determinism contract)
        b = []
        for _ in range(64):
            FAULTS.should_fail("mesh.submit")
            b.append(FAULTS.should_fail("sink.write"))
        assert a == b
        assert any(a) and not all(a)

    def test_check_raises_oserror_subclass(self):
        FAULTS.configure("sink.write:p=1@seed=1")
        with pytest.raises(OSError):
            FAULTS.check("sink.write")
        snap = FAULTS.snapshot()
        assert snap["sink.write"]["injected"] == 1

    def test_env_fallback_arms_the_flag(self, monkeypatch):
        monkeypatch.setenv("FLOWTPU_FAULTS", "sink.write:p=0.5@seed=9")
        vals = _vals()
        assert vals["faults"] == "sink.write:p=0.5@seed=9"

    def test_chaos_flags_registered(self):
        for flag in ("faults", "sink.retries", "sink.deadletter",
                     "mesh.journal", "replay.dir", "replay.delete"):
            assert flag in KNOWN_FLAGS


class TestRetry:
    def test_retries_then_succeeds(self):
        calls = []

        def fn():
            calls.append(1)
            if len(calls) < 3:
                raise ConnectionResetError("transient")
            return "ok"

        slept = []
        assert retry_call(fn, attempts=4, base=0.1, cap=1.0, jitter=0.0,
                          sleep=slept.append) == "ok"
        assert len(calls) == 3
        assert slept == [0.1, 0.2]  # exponential, jitter off

    def test_exhaustion_raises_last_error(self):
        def fn():
            raise ConnectionRefusedError("down")

        slept = []
        with pytest.raises(ConnectionRefusedError):
            retry_call(fn, attempts=3, base=0.1, cap=0.15, jitter=0.0,
                       sleep=slept.append)
        assert slept == [0.1, 0.15]  # capped

    def test_member_retries_http_transport_exceptions(self):
        """A coordinator dying MID-RESPONSE surfaces as
        http.client.HTTPException / json.JSONDecodeError — NOT OSError.
        The member's transport choke point must normalize them into the
        retryable class, or the exact outage flowchaos exists to
        survive kills the member thread (review finding)."""
        import http.client
        import json as _json

        member = MeshMember("t", None, None, None)
        calls = []

        def flaky_sync():
            calls.append(1)
            if len(calls) == 1:
                raise http.client.IncompleteRead(b"partial")
            if len(calls) == 2:
                raise _json.JSONDecodeError("truncated", "{", 1)
            return {"ok": True}

        assert member._coord_call("sync", flaky_sync) == {"ok": True}
        assert len(calls) == 3
        assert member.m_retries.value(op="sync") >= 2

    def test_non_retryable_propagates_immediately(self):
        calls = []

        def fn():
            calls.append(1)
            raise ValueError("deterministic bug")

        with pytest.raises(ValueError):
            retry_call(fn, attempts=5, sleep=lambda _: None)
        assert len(calls) == 1


# ---------------------------------------------------------------------------
# journal wire format
# ---------------------------------------------------------------------------


class TestJournal:
    def test_round_trip(self, tmp_path):
        j = CoordinatorJournal(str(tmp_path))
        j.append("sub", {"member": "w0"}, b"\x00\x01payload")
        j.append("epoch", {"epoch": 3, "reason": "join"})
        j.append("merged", {"model": "flows_5m", "slot": 300})
        j.sync()
        j.close()
        got = list(replay_journal(j.path))
        assert got == [("sub", {"member": "w0"}, b"\x00\x01payload"),
                       ("epoch", {"epoch": 3, "reason": "join"}, b""),
                       ("merged", {"model": "flows_5m", "slot": 300},
                        b"")]

    def test_append_only_across_incarnations(self, tmp_path):
        j1 = CoordinatorJournal(str(tmp_path))
        j1.append("epoch", {"epoch": 1, "reason": "join"})
        j1.close()
        j2 = CoordinatorJournal(str(tmp_path))
        j2.append("epoch", {"epoch": 2, "reason": "recovery"})
        j2.close()
        kinds = [(k, m["epoch"]) for k, m, _ in replay_journal(j2.path)]
        assert kinds == [("epoch", 1), ("epoch", 2)]

    def test_torn_tail_recovers_prefix(self, tmp_path):
        j = CoordinatorJournal(str(tmp_path))
        j.append("sub", {"member": "w0"}, b"A" * 64)
        j.append("sub", {"member": "w1"}, b"B" * 64)
        j.close()
        size = os.path.getsize(j.path)
        with open(j.path, "r+b") as f:
            f.truncate(size - 7)  # crash mid-append of the last record
        got = list(replay_journal(j.path))
        assert [m["member"] for _, m, _ in got] == ["w0"]

    def test_corrupt_record_stops_replay(self, tmp_path):
        j = CoordinatorJournal(str(tmp_path))
        j.append("sub", {"member": "w0"}, b"A" * 32)
        j.append("sub", {"member": "w1"}, b"B" * 32)
        j.close()
        with open(j.path, "r+b") as f:
            f.seek(-5, os.SEEK_END)
            f.write(b"XXXXX")
        got = list(replay_journal(j.path))
        assert [m["member"] for _, m, _ in got] == ["w0"]

    def test_bad_magic_rejected(self, tmp_path):
        p = tmp_path / "coordinator.journal"
        p.write_bytes(b"not a journal")
        with pytest.raises(ValueError, match="magic"):
            list(replay_journal(str(p)))

    def test_torn_magic_starts_fresh(self, tmp_path):
        """A crash during the very FIRST init can tear the 7-byte magic
        write; that must not wedge every later startup (nothing was
        ever acked against the file)."""
        p = tmp_path / "coordinator.journal"
        p.write_bytes(b"FJR")  # torn first write
        assert list(replay_journal(str(p))) == []  # recover to empty
        j = CoordinatorJournal(str(tmp_path))  # re-inits the file
        j.append("epoch", {"epoch": 1, "reason": "join"})
        j.close()
        assert [k for k, _, _ in replay_journal(str(p))] == ["epoch"]


# ---------------------------------------------------------------------------
# coordinator recovery protocol units (synthetic payloads, no jax models)
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


class TestCoordinatorRecovery:
    def make(self, tmp_path, partitions=1, sink=None, **kw):
        return MeshCoordinator([_wagg_spec()], partitions,
                               sinks=[sink] if sink else (),
                               journal=str(tmp_path / "wal"), **kw)

    def test_recovers_frontier_epoch_and_merged_ledger(self, tmp_path):
        s1 = ListSink()
        c = self.make(tmp_path, sink=s1)
        c.join("a")
        c.sync("a")
        # merges immediately (wm past the barrier) -> emitted + journaled
        assert c.submit("a", codec.encode(_contrib(
            {0: [0, 10]}, wm=900, closed={300: _wagg_win(7, 50)})))["ok"]
        assert len(s1.tables["flows_5m"]) == 1
        epoch_before = c.epoch
        # crash: drop c; a fresh coordinator recovers from the journal
        s2 = ListSink()
        c2 = self.make(tmp_path, sink=s2)
        assert c2.status()["covered"] == [10]
        assert c2.epoch > epoch_before
        # the merged window must NOT re-emit (its rows are in the sinks)
        assert "flows_5m" not in s2.tables
        # ...but late contributions for it still register as late
        late0 = c2._m["late"].value(model="flows_5m")
        c2.join("a")
        c2.sync("a")
        c2.submit("a", codec.encode(_contrib(
            {0: [10, 11]}, wm=901, closed={300: _wagg_win(7, 4)})))
        assert c2._m["late"].value(model="flows_5m") == late0 + 1

    def test_pending_window_merges_after_recovery(self, tmp_path):
        """Accepted but unmerged at crash time: the contribution must
        survive into the recovered barrier and merge exactly once."""
        c = self.make(tmp_path)
        c.join("a")
        c.sync("a")
        # wm=100 < slot+window: stays pending
        c.submit("a", codec.encode(_contrib(
            {0: [0, 8]}, wm=100, closed={300: _wagg_win(2, 30)})))
        s2 = ListSink()
        c2 = self.make(tmp_path, sink=s2)
        assert c2.status()["covered"] == [8]
        c2.join("b")
        c2.sync("b")
        c2.submit("b", codec.encode(_contrib(
            {0: [8, 12]}, wm=700, closed={300: _wagg_win(2, 12)},
            final=True)))
        rows = c2.merged_rows("flows_5m", 300)
        assert len(rows) == 1
        # pre-crash contribution (30) + successor (12): nothing lost,
        # nothing double-counted
        assert int(rows[0]["bytes"][0]) == 42

    def test_carry_promoted_at_recovery(self, tmp_path):
        """The open-window carry accepted before the crash is promoted
        by the recovered coordinator (the old incarnation's member is
        presumed dead) and merges exactly once next to the successor's
        replayed rows."""
        c = self.make(tmp_path)
        c.join("a")
        c.sync("a")
        c.submit("a", codec.encode(_contrib(
            {0: [0, 8]}, wm=100, open_={300: _wagg_win(2, 30)})))
        s2 = ListSink()
        c2 = self.make(tmp_path, sink=s2)
        # the old member is unknown to the new incarnation: zombie path
        assert c2.sync("a")["action"] == "rejoin"
        r = c2.submit("a", codec.encode(_contrib({0: [8, 9]}, wm=700)))
        assert not r["ok"] and r["reason"] == "fenced"
        c2.join("b")
        c2.sync("b")
        c2.submit("b", codec.encode(_contrib(
            {0: [8, 12]}, wm=700, closed={300: _wagg_win(2, 12)},
            final=True)))
        rows = c2.merged_rows("flows_5m", 300)
        assert len(rows) == 1
        assert int(rows[0]["bytes"][0]) == 42  # carry 30 + successor 12

    def test_second_crash_replays_identically(self, tmp_path):
        """Recovery journals its own fences, so a coordinator that
        crashes AGAIN after recovering does not double-promote the
        first incarnation's carries."""
        c = self.make(tmp_path)
        c.join("a")
        c.sync("a")
        c.submit("a", codec.encode(_contrib(
            {0: [0, 8]}, wm=100, open_={300: _wagg_win(2, 30)})))
        c2 = self.make(tmp_path)  # crash 1: promotes the carry
        s3 = ListSink()
        c3 = self.make(tmp_path, sink=s3)  # crash 2
        c3.join("b")
        c3.sync("b")
        c3.submit("b", codec.encode(_contrib(
            {0: [8, 12]}, wm=700, closed={300: _wagg_win(2, 12)},
            final=True)))
        rows = c3.merged_rows("flows_5m", 300)
        assert len(rows) == 1
        assert int(rows[0]["bytes"][0]) == 42  # 30 once, not twice
        assert c3.epoch > c2.epoch

    def test_resubmitted_range_rejected_harmlessly(self, tmp_path):
        """The idempotence pin: a retried submission whose ack was lost
        no longer extends the frontier — it is REJECTED (never applied
        twice), the member is fenced, and the rejoin/replay path keeps
        the merge exact."""
        c = self.make(tmp_path)
        c.join("a")
        c.sync("a")
        payload = codec.encode(_contrib(
            {0: [0, 10]}, wm=100, open_={300: _wagg_win(5, 20)}))
        assert c.submit("a", payload)["ok"]
        # the retry of the SAME envelope (lost ack): rejected, frontier
        # and carry untouched
        r = c.submit("a", payload)
        assert not r["ok"] and r["reason"] == "range"
        assert c.status()["covered"] == [10]
        # the member rejoins fresh and replays from the frontier; its
        # carry was promoted by the rejection's fence
        assert c.sync("a")["action"] == "rejoin"
        c.join("a")
        c.sync("a")
        c.submit("a", codec.encode(_contrib(
            {0: [10, 12]}, wm=700, closed={300: _wagg_win(5, 7)},
            final=True)))
        rows = c.merged_rows("flows_5m", 300)
        assert len(rows) == 1
        assert int(rows[0]["bytes"][0]) == 27  # 20 once + 7, not 47

    def test_duplicate_empty_range_submission_acked_idempotently(
            self, tmp_path):
        """The case the frontier-extend check alone cannot catch: a
        final/idle-flush submission carries closed windows but NO new
        offsets (ranges [covered, covered]); its lost-ack retry passes
        the range check. The span.sub dedupe must ack it idempotently
        WITHOUT re-folding the windows (review finding: double-count)."""
        c = self.make(tmp_path)
        c.join("a")
        c.sync("a")
        # advance the frontier first
        assert c.submit("a", codec.encode(dict(
            _contrib({0: [0, 10]}, wm=100), span={"sub": 1})))["ok"]
        # idle-flush: closed window, empty range
        payload = codec.encode(dict(
            _contrib({0: [10, 10]}, wm=700,
                     closed={300: _wagg_win(4, 19)}),
            span={"sub": 2}))
        assert c.submit("a", payload)["ok"]
        r = c.submit("a", payload)  # lost-ack retry, same envelope
        assert r["ok"] and r.get("duplicate")
        # member stays live (no fence) and nothing folded twice
        assert c.sync("a")["action"] == "run"
        c.submit("a", codec.encode(dict(
            _contrib({0: [10, 11]}, wm=701, final=True),
            span={"sub": 3})))
        rows = c.merged_rows("flows_5m", 300)
        assert len(rows) == 1
        assert int(rows[0]["bytes"][0]) == 19  # once, not 38


# ---------------------------------------------------------------------------
# resilient sink: retry + dead-letter + replay
# ---------------------------------------------------------------------------


class _FlakySink:
    """Fails the first ``fail`` write attempts, then accepts."""

    def __init__(self, fail):
        self.fail = fail
        self.inner = MemorySink()
        self.attempts = 0

    def write(self, table, rows):
        self.attempts += 1
        if self.attempts <= self.fail:
            raise ConnectionResetError("transient sink blip")
        self.inner.write(table, rows)


class TestResilientSink:
    ROWS = [{"src_as": 1, "bytes": 10}, {"src_as": 2, "bytes": 20}]

    def test_transient_failure_retried(self):
        flaky = _FlakySink(fail=2)
        rs = ResilientSink(flaky, retries=4, backoff=0.001,
                           backoff_max=0.002, sleep=lambda _: None)
        rs.write("flows_5m", list(self.ROWS))
        assert flaky.inner.tables["flows_5m"] == self.ROWS
        assert flaky.attempts == 3

    def test_exhaustion_without_deadletter_reraises(self):
        rs = ResilientSink(_FlakySink(fail=99), retries=2, backoff=0.001,
                           sleep=lambda _: None)
        with pytest.raises(ConnectionResetError):
            rs.write("flows_5m", list(self.ROWS))

    def test_deterministic_bug_not_retried_or_spilled(self, tmp_path):
        """A schema/shape bug (ValueError & co.) must fail the step
        immediately: retrying triples its latency, and spilling it
        would park a poison file at the head of the dead-letter queue
        (replay stops at the first failure to preserve order)."""
        class Buggy:
            def __init__(self):
                self.attempts = 0

            def write(self, table, rows):
                self.attempts += 1
                raise ValueError("schema mismatch")

        buggy = Buggy()
        rs = ResilientSink(buggy, retries=4, backoff=0.001,
                           deadletter_dir=str(tmp_path),
                           sleep=lambda _: None)
        with pytest.raises(ValueError):
            rs.write("flows_5m", list(self.ROWS))
        assert buggy.attempts == 1  # no retries
        assert deadletter_files(str(tmp_path)) == []  # no poison spill

    def test_exhaustion_spills_and_replays(self, tmp_path):
        flaky = _FlakySink(fail=99)
        rs = ResilientSink(flaky, retries=2, backoff=0.001,
                           deadletter_dir=str(tmp_path),
                           sleep=lambda _: None)
        rs.write("flows_5m", list(self.ROWS))  # survives
        files = deadletter_files(str(tmp_path))
        assert len(files) == 1
        doc = json.loads(open(files[0]).read())
        assert doc["table"] == "flows_5m"
        assert doc["records"] == self.ROWS
        assert rs._m["depth"].value() == 1.0
        # replay into a healthy sink restores the rows and drains disk
        target = MemorySink()
        n_files, n_rows = replay_deadletter(str(tmp_path), [target])
        assert (n_files, n_rows) == (1, 2)
        assert target.tables["flows_5m"] == self.ROWS
        assert deadletter_files(str(tmp_path)) == []

    def test_replay_failure_keeps_files_in_order(self, tmp_path):
        rs = ResilientSink(_FlakySink(fail=99), retries=1,
                           deadletter_dir=str(tmp_path),
                           sleep=lambda _: None)
        rs.write("flows_5m", [{"src_as": 1}])
        rs.write("flows_5m", [{"src_as": 2}])
        dead = _FlakySink(fail=99)
        with pytest.raises(ConnectionResetError):
            replay_deadletter(str(tmp_path), [dead])
        assert len(deadletter_files(str(tmp_path))) == 2

    def test_restart_reports_inherited_backlog(self, tmp_path):
        rs = ResilientSink(_FlakySink(fail=99), retries=1,
                           deadletter_dir=str(tmp_path),
                           sleep=lambda _: None)
        rs.write("flows_5m", [{"src_as": 1}])
        rs2 = ResilientSink(MemorySink(), retries=1,
                            deadletter_dir=str(tmp_path))
        assert rs2._m["depth"].value() == 1.0

    def test_injected_faults_hit_the_seam(self, tmp_path):
        FAULTS.configure("sink.write:p=1@seed=1")
        inner = MemorySink()
        rs = ResilientSink(inner, retries=2, backoff=0.001,
                           deadletter_dir=str(tmp_path),
                           sleep=lambda _: None)
        rs.write("flows_5m", list(self.ROWS))
        FAULTS.configure(None)
        assert "flows_5m" not in inner.tables  # every attempt injected
        assert len(deadletter_files(str(tmp_path))) == 1

    def test_passthrough_surfaces(self):
        class Archiving(MemorySink):
            def archive_raw(self, batch):
                return 0

        rs = ResilientSink(Archiving())
        assert getattr(rs, "archive_raw", None) is not None
        assert getattr(rs, "check_raw_schema", None) is None


# ---------------------------------------------------------------------------
# e2e: sink fault leg — the worker survives, dead-letter + replay
# restore row-set equality with a fault-free run
# ---------------------------------------------------------------------------


def _records_key(rec):
    return json.dumps(rec, sort_keys=True, default=str)


def test_worker_survives_sink_faults_and_replay_restores_rows(tmp_path):
    # the multi-window stream: many window closes -> many sink writes,
    # so the seeded plan deterministically exhausts several batches
    # (both legs consume the IDENTICAL stream, so the row-set compare
    # is valid whatever the windowing)
    vals = _vals()
    cfg = WorkerConfig(poll_max=BATCH, snapshot_every=0)
    clean = MemorySink()
    StreamWorker(Consumer(_make_bus(rate=MULTIWIN_RATE), "flows",
                          fixedlen=True),
                 _build_models(vals), [clean], cfg).run(stop_when_idle=True)
    faulty = MemorySink()
    rs = ResilientSink(faulty, retries=2, backoff=0.0005,
                       backoff_max=0.001,
                       deadletter_dir=str(tmp_path))
    FAULTS.configure("sink.write:p=0.6@seed=11")
    worker = StreamWorker(Consumer(_make_bus(rate=MULTIWIN_RATE),
                                   "flows", fixedlen=True),
                          _build_models(vals), [rs], cfg)
    worker.run(stop_when_idle=True)  # must NOT raise FlushError
    FAULTS.configure(None)
    spilled = deadletter_files(str(tmp_path))
    assert spilled, "seeded plan produced no exhausted batches"
    # before replay the faulty sink is missing the spilled rows
    missing = sum(len(json.loads(open(f).read())["records"])
                  for f in spilled)
    assert missing > 0
    replay_deadletter(str(tmp_path), [faulty])
    assert deadletter_files(str(tmp_path)) == []
    assert set(clean.tables) == set(faulty.tables)
    for table in clean.tables:
        a = sorted(_records_key(r) for r in clean.tables[table])
        b = sorted(_records_key(r) for r in faulty.tables[table])
        assert a == b, f"row-set mismatch in {table}"


# ---------------------------------------------------------------------------
# e2e: kill the COORDINATOR mid-stream — journal recovery keeps the
# merged sink output bit-exact vs the single-worker oracle
# ---------------------------------------------------------------------------


class CrashableCoordinator:
    """The process boundary, simulated: while ``down``, every protocol
    call fails with the OSError a dead HTTP endpoint produces. The
    member-side retry machinery is what rides through."""

    def __init__(self, real):
        self.real = real
        self.down = threading.Event()

    def _check(self):
        if self.down.is_set():
            raise ConnectionRefusedError(
                "coordinator down (simulated crash)")

    def join(self, *a, **kw):
        self._check()
        return self.real.join(*a, **kw)

    def sync(self, *a, **kw):
        self._check()
        return self.real.sync(*a, **kw)

    def submit(self, *a, **kw):
        self._check()
        return self.real.submit(*a, **kw)

    def leave(self, *a, **kw):
        self._check()
        return self.real.leave(*a, **kw)


def test_kill_coordinator_mid_stream_recovers_bit_exact(tmp_path):
    """The headline acceptance leg: the coordinator dies mid-stream
    with accepted-but-unmerged state; a fresh incarnation recovers from
    its journal, fences the old members through the zombie/rejoin
    machinery, and the merged flows_5m + top-K sink rows stay bit-exact
    vs the single-worker oracle — no lost, no double-counted windows."""
    vals = _vals()
    sink1, sink2 = ListSink(), ListSink()
    _run_single_worker(vals, sink1)

    jdir = str(tmp_path / "wal")
    specs = spec_from_models(_build_models(vals))
    coord1 = MeshCoordinator(specs, PARTITIONS, sinks=[sink2],
                             journal=jdir)
    proxy = CrashableCoordinator(coord1)
    bus = _make_bus()
    config = WorkerConfig(poll_max=BATCH, snapshot_every=0)

    def consumer_factory(partitions):
        return Consumer(bus, "flows", group="chaos", fixedlen=True,
                        partitions=list(partitions))

    members = [
        MeshMember(f"w{i}", proxy, consumer_factory,
                   model_factory=lambda: _build_models(vals),
                   config=config, submit_every=2, sync_interval=0.01)
        for i in range(3)
    ]
    # DELTA, not absolute: the submit counter is process-global and
    # earlier mesh tests have already moved it
    submit0 = coord1._m["submit"].value()
    stop = threading.Event()
    threads = [threading.Thread(target=m.run, args=(stop,),
                                name=f"chaos-{m.member_id}", daemon=True)
               for m in members]
    for t in threads:
        t.start()
    # mid-stream: wait until real work is accepted (progress carries are
    # flowing, some windows may already have merged)
    deadline = time.time() + 120
    while time.time() < deadline:
        if coord1._m["submit"].value() - submit0 >= 6:
            break
        time.sleep(0.002)
    else:
        pytest.fail("coordinator never accepted enough submissions")

    # CRASH: the old incarnation's memory dies with it; only the
    # journal survives. Members see connection-refused and retry.
    proxy.down.set()
    coord2 = MeshCoordinator(specs, PARTITIONS, sinks=[sink2],
                             journal=jdir)
    assert coord2.epoch > 0
    proxy.real = coord2
    proxy.down.clear()

    # quiescence: every member idle AND the recovered coordinator owns
    # out the full partition set (rebalance settled after the rejoins)
    deadline = time.time() + 240
    streak = 0
    while time.time() < deadline:
        ok = all(m.idle_streak >= 20 for m in members)
        if ok:
            st = coord2.status()
            owned = sum(len(v["owned"]) for v in st["members"].values())
            ok = owned == st["partitions"]
        streak = streak + 1 if ok else 0
        if streak >= 2:
            break
        time.sleep(0.02)
    else:
        pytest.fail("mesh did not quiesce after coordinator recovery")
    stop.set()
    for t in threads:
        t.join(timeout=60)
    for m in members:
        m.finalize()
    coord2.close()

    _assert_flows5m_oracle_exact(sink2.tables)
    _assert_topk_tables_equal(sink1.tables, sink2.tables)
    # the recovery actually replayed journaled submissions (count the
    # records directly — the metric counter is process-global)
    kinds = [k for k, _, _ in
             replay_journal(os.path.join(jdir, "coordinator.journal"))]
    assert kinds.count("sub") >= 6
    assert "epoch" in kinds


def test_kill_coordinator_multiwindow_merged_windows_survive(tmp_path):
    """Multi-window variant: the stream crosses 5-minute boundaries, so
    windows MERGE (and journal ``merged`` records) before the crash.
    Recovery must re-emit none of them and still merge everything
    pending — the flows_5m fold stays exact vs the numpy oracle.
    (flows_5m only: its late-partial semantics are exact under any
    consumption order, which is what makes the oracle valid here —
    see the RATE comment above.)"""
    vals = _vals("-model.talkers=false")
    jdir = str(tmp_path / "wal")
    specs = spec_from_models(_build_models(vals))
    sink = ListSink()
    coord1 = MeshCoordinator(specs, PARTITIONS, sinks=[sink],
                             journal=jdir)
    proxy = CrashableCoordinator(coord1)
    bus = _make_bus(rate=MULTIWIN_RATE)
    config = WorkerConfig(poll_max=BATCH, snapshot_every=0)

    def consumer_factory(partitions):
        return Consumer(bus, "flows", group="chaos-mw", fixedlen=True,
                        partitions=list(partitions))

    members = [
        MeshMember(f"w{i}", proxy, consumer_factory,
                   model_factory=lambda: _build_models(vals),
                   config=config, submit_every=2, sync_interval=0.01)
        for i in range(3)
    ]
    # DELTA, not absolute: the merged counter is process-global
    merged0 = coord1._m["merged"].value(model="flows_5m")
    stop = threading.Event()
    threads = [threading.Thread(target=m.run, args=(stop,),
                                daemon=True) for m in members]
    for t in threads:
        t.start()
    # crash only after at least one window MERGED network-wide (its
    # `merged` journal record is what the recovery must honor)
    deadline = time.time() + 120
    while time.time() < deadline:
        if coord1._m["merged"].value(model="flows_5m") - merged0 >= 1:
            break
        time.sleep(0.002)
    else:
        pytest.fail("no window merged before the crash point")
    proxy.down.set()
    coord2 = MeshCoordinator(specs, PARTITIONS, sinks=[sink],
                             journal=jdir)
    proxy.real = coord2
    proxy.down.clear()
    deadline = time.time() + 240
    streak = 0
    while time.time() < deadline:
        ok = all(m.idle_streak >= 20 for m in members)
        if ok:
            st = coord2.status()
            owned = sum(len(v["owned"]) for v in st["members"].values())
            ok = owned == st["partitions"]
        streak = streak + 1 if ok else 0
        if streak >= 2:
            break
        time.sleep(0.02)
    else:
        pytest.fail("mesh did not quiesce after coordinator recovery")
    stop.set()
    for t in threads:
        t.join(timeout=60)
    for m in members:
        m.finalize()
    coord2.close()
    _assert_flows5m_oracle_exact(sink.tables, rate=MULTIWIN_RATE)


# ---------------------------------------------------------------------------
# e2e: chaos soak — seeded transport faults across the mesh edges,
# merged output stays oracle-exact
# ---------------------------------------------------------------------------


def test_chaos_soak_mesh_transport_faults_stay_oracle_exact():
    vals = _vals()
    sink1, sink2 = ListSink(), ListSink()
    _run_single_worker(vals, sink1)
    FAULTS.configure("mesh.submit:p=0.08;mesh.sync:p=0.05@seed=7")
    mesh = InProcessMesh(
        _make_bus(), "flows", 3,
        model_factory=lambda: _build_models(vals),
        config=WorkerConfig(poll_max=BATCH, snapshot_every=0),
        sinks=[sink2], submit_every=2)
    mesh.run()
    snap = FAULTS.snapshot()
    FAULTS.configure(None)
    assert sum(s["injected"] for s in snap.values()) > 0, \
        "soak injected nothing — the seams are not wired"
    _assert_flows5m_oracle_exact(sink2.tables)
    _assert_topk_tables_equal(sink1.tables, sink2.tables)


# ---------------------------------------------------------------------------
# serve publisher: failure-path rate limit + zero 5xx under faults
# ---------------------------------------------------------------------------


class TestServePublishFailurePath:
    def _publisher(self, **kw):
        from flow_pipeline_tpu.serve.publisher import MeshServePublisher

        coord = MeshCoordinator([_wagg_spec()], 1)
        return MeshServePublisher(coord, refresh=0.2,
                                  err_backoff_base=0.5,
                                  err_backoff_max=4.0,
                                  err_log_interval=30.0, **kw)

    def test_failure_counter_and_backoff_growth(self):
        pub = self._publisher()
        before = pub.store.m_publish_failures.value()
        delays = []
        for _ in range(6):
            pub._on_publish_error(RuntimeError("member fetch failed"))
            delays.append(pub._error_backoff())
        assert pub.store.m_publish_failures.value() == before + 6
        assert delays == sorted(delays)  # monotone growth
        assert delays[0] == 0.5 and delays[-1] == 4.0  # floored, capped
        pub._fail_streak = 0
        assert pub._error_backoff() == 0.5

    def test_exception_log_rate_limited(self):
        import logging

        records = []

        class Capture(logging.Handler):
            def emit(self, record):
                records.append(record)

        # the flowtpu root logger does not propagate; attach directly
        logger = logging.getLogger("flowtpu.serve")
        handler = Capture(level=logging.DEBUG)
        logger.addHandler(handler)
        old_level = logger.level
        logger.setLevel(logging.DEBUG)
        try:
            pub = self._publisher()
            for _ in range(10):
                pub._on_publish_error(RuntimeError("flap"))
        finally:
            logger.removeHandler(handler)
            logger.setLevel(old_level)
        errors = [r for r in records if r.levelno >= logging.ERROR]
        assert len(errors) == 1  # one traceback per err_log_interval
        assert pub.store.m_publish_failures.value() >= 10


def test_serve_zero_5xx_under_publish_faults():
    """Readers keep getting 2xx answers (the previous snapshot) while
    the mesh publisher's fan-out is failing under injected faults."""
    from flow_pipeline_tpu.serve import ServeServer
    from flow_pipeline_tpu.serve.publisher import MeshServePublisher

    vals = _vals()
    mesh = InProcessMesh(
        _make_bus(n_flows=8192), "flows", 2,
        model_factory=lambda: _build_models(vals),
        config=WorkerConfig(poll_max=BATCH, snapshot_every=0))
    pub = MeshServePublisher(mesh.coordinator, refresh=0.05,
                             err_backoff_base=0.05, err_backoff_max=0.2,
                             err_log_interval=60.0).attach()
    server = ServeServer(pub.store, 0).start()
    pub.start()
    mesh.start()
    codes = []
    versions = []
    try:
        deadline = time.time() + 30
        while pub.store.current is None and time.time() < deadline:
            time.sleep(0.01)
        assert pub.store.current is not None
        FAULTS.configure("serve.publish:p=0.5@seed=3")

        def read(path):
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}{path}")
            try:
                with urllib.request.urlopen(req, timeout=10) as resp:
                    body = resp.read()
                    codes.append(resp.status)
                    if path == "/query/version":
                        versions.append(json.loads(body)["version"])
            except urllib.error.HTTPError as e:
                codes.append(e.code)

        t_end = time.time() + 1.5
        while time.time() < t_end:
            read("/query/version")
            read("/query/topk?k=5")
    finally:
        FAULTS.configure(None)
        try:
            mesh.wait_idle()
        finally:
            mesh.finalize()
            pub.stop()
            server.stop()
    assert codes and all(c < 500 for c in codes), codes
    assert versions == sorted(versions)  # monotone under failures
    assert pub.store.m_publish_failures.value() > 0


# ---------------------------------------------------------------------------
# r18 satellites: journal compaction + in-process-bus / gateway fault seams
# ---------------------------------------------------------------------------


class TestJournalCompaction:
    """r17's named follow-on: at merged-window boundaries the WAL drops
    superseded carry envelopes and checkpoints+truncates. The gate is
    bit-exactness: recovery from a compacted journal must equal
    recovery from the uncompacted replay — same frontier, same pending
    barrier, same carries, same merged-window keys, same sink rows."""

    def _drive(self, path, compact=False, sink=None):
        c = MeshCoordinator([_wagg_spec()], 1,
                            sinks=[sink] if sink else (),
                            journal=path)
        c.join("m")
        c.sync("m")
        c.submit("m", codec.encode(_contrib(
            {0: [0, 10]}, wm=900, closed={300: _wagg_win(7, 50)})))
        c.submit("m", codec.encode(_contrib(
            {0: [10, 20]}, wm=950, open_={600: _wagg_win(3, 9)})))
        c.submit("m", codec.encode(_contrib(
            {0: [20, 25]}, wm=980, closed={600: _wagg_win(3, 2)},
            open_={900: _wagg_win(4, 5)})))
        if compact:
            assert c.compact_journal()
        c.close()
        return c

    @staticmethod
    def _protocol_state(c):
        st = c.status()
        return {k: st[k] for k in ("covered", "watermarks", "final",
                                   "pending_windows")}

    def test_recovery_after_compaction_bit_exact_vs_uncompacted(
            self, tmp_path):
        self._drive(str(tmp_path / "a"), compact=True)
        self._drive(str(tmp_path / "b"), compact=False)
        sa, sb = ListSink(), ListSink()
        ra = MeshCoordinator([_wagg_spec()], 1, sinks=[sa],
                             journal=str(tmp_path / "a"))
        rb = MeshCoordinator([_wagg_spec()], 1, sinks=[sb],
                             journal=str(tmp_path / "b"))
        assert self._protocol_state(ra) == self._protocol_state(rb)
        assert ra._merged_keys == rb._merged_keys
        assert sorted(ra._carry) == sorted(rb._carry)
        # drive both recovered coordinators to completion identically:
        # the pending window and the recovered carries must merge to
        # bit-identical sink rows
        for c in (ra, rb):
            c.join("n")
            c.sync("n")
            c.submit("n", codec.encode(_contrib(
                {0: [25, 30]}, wm=2000, closed={900: _wagg_win(4, 1)},
                final=True)))
        assert set(sa.tables) == set(sb.tables) and sa.tables
        for table in sa.tables:
            wa = [{k: np.asarray(v).tolist() for k, v in r.items()}
                  for r in sa.tables[table]]
            wb = [{k: np.asarray(v).tolist() for k, v in r.items()}
                  for r in sb.tables[table]]
            assert wa == wb

    def test_compaction_drops_superseded_envelopes(self, tmp_path):
        """The 379MB-for-35-records lever: after compaction the file
        holds ONE chk record (+ later appends), and its size is a
        fraction of the replaced history's."""
        c = self._drive(str(tmp_path / "wal"), compact=False)
        big = c._journal.size_bytes()
        c2 = MeshCoordinator([_wagg_spec()], 1,
                             journal=str(tmp_path / "wal"))
        pre = c2._journal.size_bytes()
        assert c2.compact_journal()
        post = c2._journal.size_bytes()
        assert post < pre and post < big
        kinds = [k for k, _, _ in replay_journal(
            str(tmp_path / "wal" / "coordinator.journal"))]
        assert kinds[0] == "chk"
        # recovery fences journaled during c2's own recovery are gone:
        # the checkpoint absorbed them
        assert "sub" not in kinds
        c2.close()

    def test_compaction_defers_while_a_merge_is_in_flight(self, tmp_path):
        """The checkpoint races the lock-free merge path: a window
        popped off the barrier is in _merged_keys BEFORE its rows reach
        any sink or its "merged" record the WAL. A checkpoint taken in
        that gap would record it merged while truncating the sub
        records recovery needs to re-merge it — a crash then loses the
        window silently. compact_journal() must defer until the merge
        lands (the size trigger simply fires at the next boundary)."""
        gate_enter, gate_release = threading.Event(), threading.Event()

        class GateSink:
            def __init__(self):
                self.tables = {}

            def write(self, table, rows):
                gate_enter.set()
                assert gate_release.wait(10)
                self.tables.setdefault(table, []).append(rows)

        sink = GateSink()
        c = MeshCoordinator([_wagg_spec()], 1, sinks=[sink],
                            journal=str(tmp_path / "wal"))
        c.join("m")
        c.sync("m")
        t = threading.Thread(target=c.submit, args=("m", codec.encode(
            _contrib({0: [0, 10]}, wm=900,
                     closed={300: _wagg_win(7, 50)}))))
        t.start()
        assert gate_enter.wait(10)  # popped off the barrier, mid-emit
        try:
            assert not c.compact_journal()  # deferred: merge in flight
        finally:
            gate_release.set()
            t.join(10)
        assert c.compact_journal()  # landed -> checkpoint is safe now
        c.close()
        # the deferral lost nothing: recovery from the checkpoint still
        # knows the window merged (its rows reached the sink first)
        r = MeshCoordinator([_wagg_spec()], 1,
                            journal=str(tmp_path / "wal"))
        assert ("flows_5m", 300) in r._merged_keys
        r.close()

    def test_records_after_checkpoint_replay_on_top(self, tmp_path):
        sink = ListSink()
        c = self._drive(str(tmp_path / "wal"), compact=True, sink=sink)
        # reopen, accept MORE submissions after the checkpoint
        c2 = MeshCoordinator([_wagg_spec()], 1,
                             journal=str(tmp_path / "wal"))
        c2.join("n")
        c2.sync("n")
        c2.submit("n", codec.encode(_contrib(
            {0: [25, 40]}, wm=1000, open_={900: _wagg_win(4, 6)})))
        c2.close()
        # crash again: chk + post-checkpoint subs both replay
        c3 = MeshCoordinator([_wagg_spec()], 1,
                             journal=str(tmp_path / "wal"))
        assert c3.status()["covered"] == [40]
        # both incarnations' carries were promoted into pending
        assert "flows_5m:900" in c3.status()["pending_windows"]

    def test_mesh_journal_bytes_gauge_tracks_the_file(self, tmp_path):
        c = MeshCoordinator([_wagg_spec()], 1,
                            journal=str(tmp_path / "wal"))
        g0 = c._m["journal_bytes"].value()
        assert g0 > 0  # magic written eagerly
        c.join("m")
        c.sync("m")
        c.submit("m", codec.encode(_contrib(
            {0: [0, 5]}, wm=100, open_={300: _wagg_win(1, 1)})))
        grown = c._m["journal_bytes"].value()
        assert grown > g0
        chk0 = c._m["journal_records"].value(kind="chk")
        assert c.compact_journal()
        # the gauge is the file: flush + compare against the on-disk
        # truth (a tiny history can legitimately checkpoint BIGGER —
        # the shrink claim lives in test_compaction_drops_superseded_
        # envelopes where the history dominates)
        c._journal.sync()
        assert c._m["journal_bytes"].value() == os.path.getsize(
            str(tmp_path / "wal" / "coordinator.journal"))
        # DELTA, not absolute: the counter is process-global (the r17
        # wait-condition lesson, re-applied)
        assert c._m["journal_records"].value(kind="chk") == chk0 + 1.0
        c.close()

    def test_auto_compaction_at_merged_window_boundary(self, tmp_path):
        """The trigger rides _run_merges: once the WAL crosses
        journal_compact_bytes, the next merged-window boundary
        compacts without anyone calling compact_journal()."""
        c = MeshCoordinator([_wagg_spec()], 1,
                            journal=str(tmp_path / "wal"),
                            journal_compact_bytes=1)  # always over
        c.join("m")
        c.sync("m")
        # wm past the barrier: merges (and therefore compacts) NOW
        c.submit("m", codec.encode(_contrib(
            {0: [0, 10]}, wm=900, closed={300: _wagg_win(7, 50)})))
        kinds = [k for k, _, _ in replay_journal(
            str(tmp_path / "wal" / "coordinator.journal"))]
        assert "chk" in kinds
        # recovery still lands on the merged state (nothing re-emits)
        s2 = ListSink()
        c2 = MeshCoordinator([_wagg_spec()], 1, sinks=[s2],
                             journal=str(tmp_path / "wal"))
        assert "flows_5m" not in s2.tables  # merged pre-crash: no re-emit
        assert c2.status()["covered"] == [10]
        c.close()
        c2.close()


class TestBusAndGatewayFaultSeams:
    """r17's other named follow-on: collector-side chaos is now
    expressible — the in-process bus produce/poll paths and the
    flowgate subscription poll consult the fault plan."""

    def test_new_sites_are_known(self):
        sites, _ = parse_plan(
            "bus.produce:p=0.1;bus.poll:p=0.1;gateway.poll:p=0.1")
        assert set(sites) == {"bus.produce", "bus.poll", "gateway.poll"}

    def test_unknown_site_still_rejected(self):
        with pytest.raises(ValueError):
            parse_plan("bus.nope:p=0.1")

    def test_bus_produce_seam_fires(self):
        bus = InProcessBus()
        bus.create_topic("t", 1)
        FAULTS.configure("bus.produce:p=1@seed=3")
        with pytest.raises(OSError):
            bus.produce("t", b"x")
        with pytest.raises(OSError):
            bus.produce_many("t", [b"x", b"y"])
        FAULTS.configure(None)
        bus.produce("t", b"x")
        assert FAULTS.active is False

    def test_bus_poll_seam_fires(self):
        bus = InProcessBus()
        bus.create_topic("t", 1)
        bus.produce("t", b"x")
        FAULTS.configure("bus.poll:p=1@seed=3")
        with pytest.raises(OSError):
            bus.fetch("t", 0, 0)
        with pytest.raises(OSError):
            bus.fetch_span("t", 0, 0)
        FAULTS.configure(None)
        assert len(bus.fetch("t", 0, 0)) == 1

    def test_off_mode_bus_cost_is_one_attribute_read(self):
        bus = InProcessBus()
        bus.create_topic("t", 1)
        FAULTS.configure(None)
        bus.produce("t", b"x")  # no roll consumed
        assert FAULTS.snapshot() == {}

    def test_gateway_poll_seam_drives_the_real_failure_path(self):
        """The injected gateway.poll fault rides the SAME OSError path
        a dead upstream does: the mirror keeps its snapshot and
        recovers when the plan disarms (tests/test_gateway.py has the
        serving-side chaos leg)."""
        from flow_pipeline_tpu.gateway import SnapshotGateway
        from flow_pipeline_tpu.serve import SnapshotStore

        store = SnapshotStore()
        gw = SnapshotGateway([store], poll=60)
        FAULTS.configure("gateway.poll:p=1@seed=1")
        with pytest.raises(OSError):
            gw.sync_once()
        assert FAULTS.snapshot()["gateway.poll"]["injected"] >= 1
        FAULTS.configure(None)
        assert gw.sync_once() == "none"  # empty upstream, healthy poll
