"""Ingest runtime: sharded grouping oracle equivalence, pipelined
executor backpressure + drain/stop, async flusher error propagation.

The contracts under test are the ones the dataplane's correctness hangs
on: (1) sharded and native grouping are OUTPUT-IDENTICAL to the serial
numpy groupby (hash-prefix shards concatenate into global hash order);
(2) the executor's bounded queue really bounds (backpressure, no
dropping, order preserved) and its idle protocol never abandons a tail;
(3) a pipelined worker produces byte-identical sink rows to the serial
worker, open windows included (drain-on-stop); (4) a background flush
failure fails the STEP — before its offsets commit — instead of
silently dropping rows.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from flow_pipeline_tpu import native
from flow_pipeline_tpu.engine import StreamWorker, WorkerConfig
from flow_pipeline_tpu.engine.hostfused import HostGroupPipeline
from flow_pipeline_tpu.ingest import (
    AsyncFlusher,
    FlushError,
    PipelinedExecutor,
    ShardPool,
    group_by_key_sharded,
)
from flow_pipeline_tpu.ingest import shard as shard_mod
from flow_pipeline_tpu.ops import hostgroup
from flow_pipeline_tpu.schema import wire
from flow_pipeline_tpu.transport import Consumer, InProcessBus

from test_fused import BS, WINDOW, canon_rows, make_models, make_stream


@pytest.fixture(scope="module")
def pool():
    with ShardPool(workers=4) as p:
        yield p


class TestShardedGrouping:
    def _random(self, rng, n, w=5):
        lanes = rng.integers(0, 40, size=(n, w)).astype(np.uint32)
        planes = [rng.integers(0, 100, size=(n, 3)).astype(np.float32),
                  rng.integers(0, 100, size=n).astype(np.uint64)]
        return lanes, planes

    @pytest.mark.parametrize("exact", [True, False])
    @pytest.mark.parametrize("n", [0, 7, 9000, 20000])
    def test_matches_serial_bitwise(self, rng, pool, exact, n,
                                    monkeypatch):
        """Hash-prefix shards concatenate into exactly the serial result
        — same group order, same sums — for any batch size."""
        monkeypatch.setattr(shard_mod, "MIN_SHARD_ROWS", 4)
        lanes, planes = self._random(rng, n)
        su, ss, sc = hostgroup.group_by_key(lanes, planes, exact)
        pu, ps, pc = group_by_key_sharded(lanes, planes, pool, shards=4,
                                          exact=exact)
        np.testing.assert_array_equal(su, pu)
        np.testing.assert_array_equal(sc, pc)
        for a, b in zip(ss, ps):
            np.testing.assert_array_equal(a, b)

    def test_exact_collision_fallback_survives_sharding(self, rng, pool,
                                                        monkeypatch):
        """A forced full-hash collision lands both keys in the SAME shard
        (identical hashes share every prefix), where the per-shard verify
        regroups lexicographically — sharded stays exact."""
        monkeypatch.setattr(shard_mod, "MIN_SHARD_ROWS", 4)
        monkeypatch.setattr(
            hostgroup, "hash_u64",
            lambda lanes: np.zeros(lanes.shape[0], np.uint64))
        lanes = rng.integers(0, 5, size=(64, 2)).astype(np.uint32)
        vals = [rng.integers(0, 9, size=64).astype(np.uint64)]
        uniq, (s,), counts = group_by_key_sharded(lanes, vals, pool,
                                                  shards=4, exact=True)
        want: dict[tuple, int] = {}
        for i, row in enumerate(map(tuple, lanes)):
            want[row] = want.get(row, 0) + int(vals[0][i])
        assert len(uniq) == len(want)
        for i, row in enumerate(map(tuple, uniq)):
            assert s[i] == want[row]

    @pytest.mark.skipif(not native.group_available(),
                        reason="libflowdecode.so not built with hash_group")
    @pytest.mark.parametrize("exact", [True, False])
    def test_native_matches_numpy(self, rng, exact):
        """The C kernel computes the same hash, so group ORDER (not just
        content) matches the numpy path exactly."""
        lanes = rng.integers(0, 60, size=(5000, 7)).astype(np.uint32)
        planes = [rng.integers(0, 100, size=(5000, 2)).astype(np.float32)]
        nu, ns, nc = hostgroup.group_by_key(lanes, planes, exact)
        gu, gs, gc = hostgroup.group_by_key(lanes, planes, exact,
                                            native=True)
        np.testing.assert_array_equal(nu, gu)
        np.testing.assert_array_equal(nc, gc)
        np.testing.assert_array_equal(ns[0], gs[0])

    @pytest.mark.skipif(not native.group_available(),
                        reason="libflowdecode.so not built with hash_group")
    def test_native_kernel_contract(self, rng):
        lanes = rng.integers(0, 3, size=(257, 2)).astype(np.uint32)
        perm, starts, collided = native.hash_group(lanes)
        assert not collided
        assert sorted(perm.tolist()) == list(range(257))
        h = hostgroup.hash_u64(lanes)
        sh = h[perm]
        assert (np.diff(sh.astype(np.uint64)) >= 0).all()  # hash order
        assert starts[0] == 0 and len(starts) == len(np.unique(h))


class _ListConsumer:
    """Minimal consumer: a fixed batch list, then idle forever."""

    def __init__(self, batches):
        self.batches = list(batches)

    def poll(self, max_messages):
        return self.batches.pop(0) if self.batches else None


class TestPipelinedExecutor:
    def test_backpressure_bound_and_order(self):
        """The prepared queue never exceeds its cap while the consumer
        side lags, nothing is dropped, order is preserved."""
        batches = [[i] * 3 for i in range(20)]  # len() > 0 stands in
        ex = PipelinedExecutor(_ListConsumer(batches), prepare=tuple,
                               depth=2, idle_sleep=0.005)
        got = []
        first = ex.next()
        time.sleep(0.2)  # group thread runs ahead into the bound
        assert ex._out.qsize() <= 2
        got.append(first)
        while True:
            item = ex.next()
            if item is None:
                break
            got.append(item)
            assert ex._out.qsize() <= 2
        assert ex.high_water <= 2
        assert got == [tuple(b) for b in batches]
        assert ex.next() is None  # idle stays idle
        ex.stop()

    def test_prepare_error_propagates(self):
        def boom(batch):
            raise RuntimeError("poison batch")

        ex = PipelinedExecutor(_ListConsumer([[1]]), prepare=boom,
                               idle_sleep=0.005)
        with pytest.raises(RuntimeError, match="poison"):
            ex.next()

    def test_poll_error_propagates(self):
        class Bad:
            def poll(self, n):
                raise OSError("broker gone")

        ex = PipelinedExecutor(Bad(), prepare=tuple, idle_sleep=0.005)
        with pytest.raises(OSError, match="broker gone"):
            ex.next()


class TestAsyncFlusher:
    def test_jobs_run_in_order_and_drain(self):
        f = AsyncFlusher(max_queue=4)
        out = []
        for i in range(10):
            f.submit(lambda i=i: out.append(i))
        f.drain()
        assert out == list(range(10))
        f.stop()

    def test_error_latches_and_fails_drain(self):
        f = AsyncFlusher(max_queue=4)
        f.submit(lambda: 1 / 0)
        with pytest.raises(FlushError):
            f.drain()
        f.submit(lambda: None)  # post-error submits work again
        f.drain()
        f.stop()


def _stream_to_bus(batches):
    bus = InProcessBus()
    bus.create_topic("flows", 1)
    for b in batches:
        for frame in wire.iter_raw_frames(b.to_wire()):
            bus.produce("flows", frame)
    return bus


class CollectSink:
    def __init__(self):
        self.rows: dict[str, list] = {}

    def write(self, table, rows):
        self.rows.setdefault(table, []).append(rows)


def _run_worker(mode, sink, **cfg_kw):
    bus = _stream_to_bus(make_stream())
    worker = StreamWorker(
        Consumer(bus, fixedlen=True),
        make_models(WINDOW, 100),
        [sink],
        WorkerConfig(poll_max=BS, snapshot_every=0, ingest_mode=mode,
                     **cfg_kw),
    )
    worker.run(stop_when_idle=True)
    return worker


class TestPipelinedWorker:
    @pytest.mark.parametrize("kw", [
        {},
        {"ingest_native_group": True},
        {"ingest_shards": 4},
    ])
    def test_sink_rows_match_serial(self, kw):
        """Drain-on-stop oracle: the pipelined worker (in every grouping
        backend) lands the same rows as the serial one for every table,
        open windows included — nothing stuck in a queue at shutdown."""
        serial, pipelined = CollectSink(), CollectSink()
        ws = _run_worker("serial", serial)
        wp = _run_worker("pipelined", pipelined, **kw)
        assert ws.fused is not None and wp.fused is not None
        assert isinstance(wp.fused, HostGroupPipeline)
        assert wp.executor is not None and wp.flusher is not None
        assert set(serial.rows) == set(pipelined.rows)
        f5_s = sorted(sum([canon_rows(r)
                           for r in serial.rows["flows_5m"]], []))
        f5_p = sorted(sum([canon_rows(r)
                           for r in pipelined.rows["flows_5m"]], []))
        assert f5_s == f5_p
        for table in ("top_talkers", "top_src_ips", "top_dst_ips",
                      "top_src_ports"):
            a = serial.rows[table]
            b = pipelined.rows[table]
            assert len(a) == len(b)
            for ra, rb in zip(a, b):
                assert ra.keys() == rb.keys()
                for k in ra:
                    np.testing.assert_array_equal(np.asarray(ra[k]),
                                                  np.asarray(rb[k]))

    def test_flusher_error_fails_step_before_commit(self):
        """A sink failure on the background flusher must surface as a
        FlushError on the worker thread BEFORE offsets commit — rows are
        replayed, not dropped."""
        class FailingSink:
            def write(self, table, rows):
                raise IOError("disk full")

        bus = _stream_to_bus(make_stream())
        consumer = Consumer(bus, fixedlen=True)
        worker = StreamWorker(
            consumer, make_models(WINDOW, 100), [FailingSink()],
            WorkerConfig(poll_max=BS, snapshot_every=0,
                         ingest_mode="pipelined"),
        )
        assert worker.flusher is not None
        with pytest.raises(FlushError):
            worker.run(stop_when_idle=True)
        # nothing was committed past the first flush failure
        assert consumer.committed(0) == 0

    def test_queue_depth_bounded_end_to_end(self):
        sink = CollectSink()
        w = _run_worker("pipelined", sink)
        assert w.executor.high_water <= w.executor.depth == 2
