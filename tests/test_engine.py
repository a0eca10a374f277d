"""Engine tests: worker E2E over the bus, offset protocol, checkpoint
save/restore, and the kill-worker-mid-window fault injection from
SURVEY.md §5/§10 (resume without loss or double counting)."""

import numpy as np
import pytest

from flow_pipeline_tpu.engine import (
    StreamWorker,
    WindowedHeavyHitter,
    WorkerConfig,
)
from flow_pipeline_tpu.gen import FlowGenerator, MockerProfile, ZipfProfile
from flow_pipeline_tpu.models import (
    DDoSConfig,
    DDoSDetector,
    HeavyHitterConfig,
    WindowAggConfig,
    WindowAggregator,
)
from flow_pipeline_tpu.models.oracle import flows_5m
from flow_pipeline_tpu.schema.batch import FlowBatch
from flow_pipeline_tpu.sink import MemorySink
from flow_pipeline_tpu.transport import Consumer, InProcessBus, Producer


def fill_bus(n=4000, seed=61, rate=20.0, partitions=2):
    bus = InProcessBus()
    bus.create_topic("flows", partitions)
    gen = FlowGenerator(MockerProfile(), seed=seed, t0=1_699_999_800, rate=rate)
    batches = []
    prod = Producer(bus, fixedlen=True)
    for _ in range(n // 500):
        b = gen.batch(500)
        batches.append(b)
        prod.send_many(b.to_messages())
    return bus, FlowBatch.concat(batches)


def make_worker(bus, checkpoint=None, snapshot_every=3, batch_size=512):
    consumer = Consumer(bus, fixedlen=True)
    models = {
        "flows_5m": WindowAggregator(WindowAggConfig(batch_size=batch_size)),
        "top_talkers": WindowedHeavyHitter(
            HeavyHitterConfig(batch_size=batch_size, width=1 << 12, capacity=64),
            k=10,
        ),
    }
    sink = MemorySink()
    worker = StreamWorker(
        consumer, models, [sink],
        WorkerConfig(poll_max=batch_size, snapshot_every=snapshot_every,
                     checkpoint_path=checkpoint),
    )
    return worker, sink


def flows5m_totals(sink):
    rows = sink.tables.get("flows_5m", [])
    agg = {}
    for r in rows:  # merge partial rows (late-data contract)
        key = (r["timeslot"], r["src_as"], r["dst_as"], r["etype"])
        b, p, c = agg.get(key, (0, 0, 0))
        agg[key] = (b + r["bytes"], p + r["packets"], c + r["count"])
    return agg


def assert_matches_oracle(got, all_flows):
    """Merged (window, key) sink totals must equal the exact oracle."""
    oracle = flows_5m(all_flows)
    assert len(got) == len(oracle["timeslot"])
    for i in range(len(oracle["timeslot"])):
        key = (int(oracle["timeslot"][i]), int(oracle["src_as"][i]),
               int(oracle["dst_as"][i]), int(oracle["etype"][i]))
        assert got[key] == (int(oracle["bytes"][i]),
                            int(oracle["packets"][i]),
                            int(oracle["count"][i]))


class TestWorkerE2E:
    def test_bus_to_sink_parity(self):
        bus, all_flows = fill_bus()
        worker, sink = make_worker(bus)
        worker.run(stop_when_idle=True)
        assert_matches_oracle(flows5m_totals(sink), all_flows)
        # top talkers emitted per closed window
        assert "top_talkers" in sink.tables

    def test_offsets_committed_after_drain(self):
        bus, _ = fill_bus(n=2000)
        worker, _ = make_worker(bus)
        worker.run(stop_when_idle=True)
        assert worker.consumer.lag() == 0

    def test_metrics_incremented(self):
        bus, _ = fill_bus(n=1000)
        worker, _ = make_worker(bus)
        worker.run(stop_when_idle=True)
        assert worker.m_flows.value() >= 1000
        assert worker.m_rows.value() > 0  # insert_count actually increments


class TestCheckpointResume:
    def test_snapshot_roundtrip(self, tmp_path):
        from flow_pipeline_tpu.engine.checkpoint import (
            load_checkpoint,
            save_checkpoint,
        )

        state = {
            "covered": {"0": 17},
            "windows": {1699999800: {(65000, 65001): np.array([1, 2, 3],
                                                              np.uint64)}},
            "scalar": 5,
            "none": None,
        }
        path = str(tmp_path / "ckpt")
        save_checkpoint(path, state)
        save_checkpoint(path, state)  # overwrite must be atomic + idempotent
        got = load_checkpoint(path)
        assert got["covered"] == {"0": 17}
        assert got["scalar"] == 5 and got["none"] is None
        inner = got["windows"][1699999800][(65000, 65001)]
        np.testing.assert_array_equal(inner, [1, 2, 3])

    @pytest.mark.parametrize("case", [
        "roundtrip", "stored", "deflated", "deflated_old"])
    def test_checkpoint_format(self, tmp_path, case):
        """One format is written (ZIP_STORED members), two are read: a
        mixed tree restores bit for bit, and a checkpoint whose
        arrays.npz a pre-PR-30 build deflated loads to the same tree,
        from <path> and from <path>.old."""
        import os
        import zipfile
        from typing import NamedTuple

        import jax.numpy as jnp

        from flow_pipeline_tpu.engine.checkpoint import (
            load_checkpoint,
            save_checkpoint,
        )
        from flow_pipeline_tpu.obs.trace import TRACER

        class Sketch(NamedTuple):
            planes: object
            table: object
            folds: int

        rng = np.random.default_rng(30)
        planes = rng.random((3, 4, 256), np.float32)
        planes[:, :, 100:] = 0.0  # partly filled, as a young window's are
        table = rng.integers(0, 2**63, (64, 5), dtype=np.uint64)
        state = {
            "covered": {"0": 17, "1": 2**40},
            "models": {"hh": Sketch(jnp.asarray(planes), table, 7),
                       "empty": np.zeros((0, 3), np.int32)},
            "parts": [np.float32(1.5), (np.arange(6, dtype=np.int16), None)],
            "rate": 0.25, "name": "w0", "on": True,
        }
        path = str(tmp_path / "ckpt")
        TRACER.configure("always")
        try:
            save_checkpoint(path, state)
            spans = TRACER.snapshot()
        finally:
            TRACER.configure("off")
        npz = os.path.join(path, "arrays.npz")
        if case == "stored":
            with zipfile.ZipFile(npz) as z:
                assert z.namelist() and all(
                    i.compress_type == zipfile.ZIP_STORED
                    for i in z.infolist())
            (ser,) = [s[5] for s in spans if s[0] == "ckpt_serialize"]
            assert ser["npz_bytes"] == os.path.getsize(npz)
            assert ser["npz_bytes"] >= ser["raw_bytes"] > planes.nbytes
        if case.startswith("deflated"):
            # the parent's format: the same members, deflated
            with np.load(npz) as z:
                members = {k: z[k] for k in z.files}
            np.savez_compressed(npz, **members)
            with zipfile.ZipFile(npz) as z:
                assert all(i.compress_type == zipfile.ZIP_DEFLATED
                           for i in z.infolist())
            assert os.path.getsize(npz) < planes.nbytes
        if case == "deflated_old":
            os.rename(path, path + ".old")

        got = load_checkpoint(path)
        assert got["covered"] == state["covered"]
        assert (got["rate"], got["name"], got["on"]) == (0.25, "w0", True)
        hh = got["models"]["hh"]  # a NamedTuple comes back as its fields
        assert set(hh) == {"planes", "table", "folds"} and hh["folds"] == 7
        for want, have in ((planes, hh["planes"]), (table, hh["table"]),
                           (state["models"]["empty"], got["models"]["empty"]),
                           (state["parts"][0], got["parts"][0]),
                           (state["parts"][1][0], got["parts"][1][0])):
            assert have.dtype == want.dtype and have.shape == want.shape
            assert have.tobytes() == want.tobytes()
        assert isinstance(got["parts"], list) and got["parts"][1][1] is None

    def test_kill_mid_window_resume_no_loss_no_double(self, tmp_path):
        """Fault injection: worker dies between snapshots; a fresh worker
        restores and the merged output still matches the oracle exactly."""
        bus, all_flows = fill_bus(n=4000)
        ckpt = str(tmp_path / "ckpt")

        w1, sink1 = make_worker(bus, checkpoint=ckpt, snapshot_every=2)
        for _ in range(3):  # a few batches, at least one snapshot...
            w1.run_once()
        # ... then CRASH (no finalize, no final snapshot/commit)
        del w1

        w2, sink2 = make_worker(bus, checkpoint=ckpt, snapshot_every=2)
        assert w2.restore()
        w2.run(stop_when_idle=True)

        # combine what sink1 flushed before the crash with sink2's output
        combined = MemorySink()
        combined.tables = {
            k: list(v) for k, v in sink1.tables.items()
        }
        for k, v in sink2.tables.items():
            combined.tables.setdefault(k, []).extend(v)
        assert_matches_oracle(flows5m_totals(combined), all_flows)

    def test_flush_triggers_snapshot(self, tmp_path):
        # any flush that emitted rows must immediately snapshot+commit, not
        # wait for the snapshot_every cadence (re-emission exposure)
        import os

        bus, _ = fill_bus(n=4000, rate=10.0)  # 400s -> a window closes mid-run
        ckpt = str(tmp_path / "ckpt")
        worker, sink = make_worker(bus, checkpoint=ckpt, snapshot_every=10**9)
        while worker.run_once():
            if sink.tables.get("flows_5m"):
                break
        assert sink.tables.get("flows_5m"), "test premise: a window must close"
        assert os.path.isdir(ckpt), "snapshot must follow the first emission"
        assert worker._emitted_since_snapshot is False

    def test_old_checkpoint_fallback(self, tmp_path):
        # crash between save_checkpoint's two renames leaves only .old;
        # load/restore must fall back to it
        import os

        from flow_pipeline_tpu.engine.checkpoint import (
            load_checkpoint,
            save_checkpoint,
        )

        path = str(tmp_path / "ckpt")
        save_checkpoint(path, {"v": 1})
        os.rename(path, path + ".old")  # simulate mid-rename crash
        assert load_checkpoint(path)["v"] == 1

    def test_restore_missing_returns_false(self, tmp_path):
        bus, _ = fill_bus(n=500)
        worker, _ = make_worker(bus, checkpoint=str(tmp_path / "nope"))
        assert worker.restore() is False


class TestSupervisedRecovery:
    def test_flaky_sink_supervised_exact_totals(self, tmp_path):
        """Full recovery chain: a sink that dies on its first flush kills
        the worker; the supervisor rebuilds one that restores the
        checkpoint and resumes from committed offsets. The failed flush
        never reached good_sink, so this proves replay-after-crash produces
        the exact oracle totals (cross-restart partial-row merging is
        covered by test_kill_mid_window_resume_no_loss_no_double)."""
        from flow_pipeline_tpu.engine import Supervisor, SupervisorConfig

        bus, all_flows = fill_bus(n=4000, rate=10.0)  # windows close mid-run
        ckpt = str(tmp_path / "ckpt")
        good_sink = MemorySink()
        failures = {"left": 1}

        class FlakySink:
            def write(self, table, rows):
                if failures["left"] > 0:
                    failures["left"] -= 1
                    raise ConnectionError("sink hiccup")
                good_sink.write(table, rows)

        def factory():
            worker, _ = make_worker(bus, checkpoint=ckpt, snapshot_every=2)
            worker.sinks = [FlakySink()]
            worker.restore()
            return worker

        Supervisor(factory, SupervisorConfig(backoff_initial=0.01),
                   stop_when_idle=True).run()
        assert failures["left"] == 0  # the crash actually happened
        assert_matches_oracle(flows5m_totals(good_sink), all_flows)


class TestDDoSInWorker:
    def test_alert_rows_reach_sink(self):
        bus = InProcessBus()
        bus.create_topic("flows", 1)
        gen = FlowGenerator(MockerProfile(), seed=71, t0=1_699_999_800,
                            rate=300.0)
        prod = Producer(bus, fixedlen=True)
        for i in range(9):
            b = gen.batch(3000)
            if i >= 7:
                hot = (b.columns["dst_addr"][:, 3] & 0xFF) == 5
                b.columns["packets"][hot] *= 60
            prod.send_many(b.to_messages())
        consumer = Consumer(bus, fixedlen=True)
        sink = MemorySink()
        worker = StreamWorker(
            consumer,
            {"ddos_alerts": DDoSDetector(DDoSConfig(batch_size=4096,
                                                    n_buckets=1 << 10))},
            [sink],
            WorkerConfig(poll_max=4096, snapshot_every=0),
        )
        worker.run(stop_when_idle=True)
        alerts = sink.tables.get("ddos_alerts", [])
        assert alerts, "attack must produce an alert row"
        assert any(a["dst_addr"].endswith(".0.0.5") or "::5" in a["dst_addr"]
                   or a["dst_addr"].endswith(":5") for a in alerts)


class TestRawArchive:
    """Opt-in flows_raw archiving (ref: compose/clickhouse/create.sh:36-62):
    the worker hands every consumed batch to sinks exposing archive_raw."""

    class ArchivingSink(MemorySink):
        def archive_raw(self, batch):
            from flow_pipeline_tpu.sink.clickhouse import raw_records

            recs = raw_records(batch)
            self.tables.setdefault("flows_raw", []).extend(recs)
            return len(recs)

    def run_worker(self, archive: bool):
        bus, all_flows = fill_bus(n=1000)
        consumer = Consumer(bus, fixedlen=True)
        sink = self.ArchivingSink()
        worker = StreamWorker(
            consumer,
            {"flows_5m": WindowAggregator(WindowAggConfig(batch_size=512))},
            [sink],
            WorkerConfig(poll_max=512, archive_raw=archive),
        )
        worker.run(stop_when_idle=True)
        return worker, sink, all_flows

    def test_disabled_by_default_archives_nothing(self):
        _, sink, _ = self.run_worker(archive=False)
        assert "flows_raw" not in sink.tables

    def test_every_flow_archived_full_fidelity(self):
        worker, sink, all_flows = self.run_worker(archive=True)
        rows = sink.tables["flows_raw"]
        assert len(rows) == len(all_flows)
        assert worker.m_raw.value() == len(all_flows)
        # spot-check full fidelity on the first flow, including exact
        # 16-byte address round-trip through the IPv6 text form
        import ipaddress

        from flow_pipeline_tpu.schema.batch import words_to_addr

        c = all_flows.columns
        r = rows[0]
        assert r["Bytes"] == int(c["bytes"][0])
        assert r["Packets"] == int(c["packets"][0])
        assert r["SrcAS"] == int(c["src_as"][0])
        assert r["TimeReceived"] == int(c["time_received"][0])
        assert (ipaddress.IPv6Address(r["SrcAddr"]).packed
                == words_to_addr(np.asarray(c["src_addr"][0], np.uint32)))
        assert (ipaddress.IPv6Address(r["DstAddr"]).packed
                == words_to_addr(np.asarray(c["dst_addr"][0], np.uint32)))
        # Date is MATERIALIZED server-side from TimeReceived, not shipped
        assert set(r) == {
            "TimeReceived", "TimeFlowStart", "SequenceNum",
            "SamplingRate", "SamplerAddress", "SrcAddr", "DstAddr",
            "SrcAS", "DstAS", "EType", "Proto", "SrcPort", "DstPort",
            "Bytes", "Packets",
        }

    def test_archive_forces_snapshot_commit(self):
        # raw rows have no merge dedup, so every archived batch must be
        # followed by an offset commit (duplicate window = one batch, not
        # snapshot_every batches)
        bus, _ = fill_bus(n=1000)
        consumer = Consumer(bus, fixedlen=True)
        sink = self.ArchivingSink()
        worker = StreamWorker(
            consumer,
            {"flows_5m": WindowAggregator(WindowAggConfig(batch_size=512))},
            [sink],
            # snapshot_every=0: only the archive coupling can trigger commits
            WorkerConfig(poll_max=512, snapshot_every=0, archive_raw=True),
        )
        worker.run_once()
        # the one consumed batch's offsets are committed immediately
        assert worker._covered  # one partition consumed
        for p, next_off in worker._covered.items():
            assert consumer.committed(p) == next_off


class TestRestoreModelMismatch:
    def test_checkpoint_with_extra_model_skipped(self, tmp_path):
        # checkpoint written with a model that is later disabled must not
        # crash restore (e.g. -model.ports flipped off between runs)
        path = str(tmp_path / "ckpt")
        bus, _ = fill_bus(n=1000)
        worker, _ = make_worker(bus, checkpoint=path, snapshot_every=1)
        worker.run(stop_when_idle=True)

        consumer = Consumer(bus, fixedlen=True)
        slim = StreamWorker(
            consumer,
            {"flows_5m": WindowAggregator(WindowAggConfig(batch_size=512))},
            [MemorySink()],
            WorkerConfig(poll_max=512, checkpoint_path=path),
        )
        assert slim.restore()  # top_talkers state present but unconfigured
        assert slim.batches_seen == worker.batches_seen


class TestMultiWorkerPartitionSplit:
    def test_two_workers_disjoint_partitions_sum_to_oracle(self):
        # the sarama consumer-group model (ref: inserter/inserter.go:
        # 238-256): scale-out is more workers on disjoint partition
        # subsets; their merged sink output must equal the exact oracle
        import threading

        bus, all_flows = fill_bus(n=4000, partitions=4)
        # shared sink: both workers append concurrently; MemorySink.write
        # is a single list.extend, atomic under the GIL
        sink = MemorySink()
        workers = []
        for part_set in ([0, 1], [2, 3]):
            consumer = Consumer(bus, fixedlen=True, partitions=part_set)
            workers.append(StreamWorker(
                consumer,
                {"flows_5m": WindowAggregator(WindowAggConfig(batch_size=512))},
                [sink],
                WorkerConfig(poll_max=512),
            ))
        threads = [
            threading.Thread(target=w.run, kwargs={"stop_when_idle": True})
            for w in workers
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
        assert_matches_oracle(flows5m_totals(sink), all_flows)
        # each worker committed exactly its own partitions
        for w, parts in zip(workers, ([0, 1], [2, 3])):
            assert sorted(w._covered) == parts
