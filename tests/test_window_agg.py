"""WindowAggregator (device path) vs the numpy flows_5m oracle — the
BASELINE config #1 parity gate, exercised across many batches and window
boundaries, with watermark-driven flushing."""

import numpy as np

from flow_pipeline_tpu.gen import FlowGenerator, MockerProfile, ZipfProfile
from flow_pipeline_tpu.models.oracle import flows_5m
from flow_pipeline_tpu.models.window_agg import WindowAggConfig, WindowAggregator
from flow_pipeline_tpu.schema.batch import FlowBatch


def run_pipeline(batches, config):
    agg = WindowAggregator(config)
    for b in batches:
        agg.update(b)
    return agg


def check_parity(flushed, batch):
    """flushed rows == oracle rows, exactly."""
    oracle = flows_5m(batch)
    assert len(flushed["timeslot"]) == len(oracle["timeslot"])
    got = {
        (int(t), int(s), int(d), int(e)): (int(b), int(p), int(c))
        for t, s, d, e, b, p, c in zip(
            flushed["timeslot"],
            flushed["src_as"],
            flushed["dst_as"],
            flushed["etype"],
            flushed["bytes"],
            flushed["packets"],
            flushed["count"],
        )
    }
    for i in range(len(oracle["timeslot"])):
        key = (
            int(oracle["timeslot"][i]),
            int(oracle["src_as"][i]),
            int(oracle["dst_as"][i]),
            int(oracle["etype"][i]),
        )
        assert got[key] == (
            int(oracle["bytes"][i]),
            int(oracle["packets"][i]),
            int(oracle["count"][i]),
        )


class TestWindowAggParity:
    def test_single_batch_parity(self):
        g = FlowGenerator(MockerProfile(), seed=21, rate=1000.0)
        batch = g.batch(4096)
        agg = run_pipeline([batch], WindowAggConfig(batch_size=4096))
        check_parity(agg.flush(force=True), batch)

    def test_multi_batch_windows_parity(self):
        # 20 batches spanning several 5-minute windows
        g = FlowGenerator(MockerProfile(), seed=22, rate=50.0)
        batches = [g.batch(500) for _ in range(20)]
        agg = run_pipeline(batches, WindowAggConfig(batch_size=512))
        check_parity(agg.flush(force=True), FlowBatch.concat(batches))

    def test_watermark_flushes_only_closed(self):
        g = FlowGenerator(MockerProfile(), seed=23, rate=10.0)  # 50s per batch
        agg = WindowAggregator(WindowAggConfig(batch_size=512))
        for _ in range(20):  # 1000 seconds -> at least 2 closed windows
            agg.update(g.batch(500))
        closed = agg.closed_slots()
        assert len(closed) >= 2
        flushed = agg.flush()
        assert set(int(t) for t in flushed["timeslot"]) == set(closed)
        # open window still buffered
        assert len(agg.windows) >= 1

    def test_flush_then_rest_covers_everything(self):
        g = FlowGenerator(MockerProfile(), seed=24, rate=10.0)
        batches = [g.batch(500) for _ in range(10)]
        agg = run_pipeline(batches, WindowAggConfig(batch_size=512))
        part1 = agg.flush()
        part2 = agg.flush(force=True)
        total = int(part1["count"].sum() + part2["count"].sum())
        assert total == 5000

    def test_zipf_high_cardinality_addr_keys(self):
        config = WindowAggConfig(
            key_cols=("src_addr", "dst_addr"), batch_size=2048
        )
        g = FlowGenerator(ZipfProfile(n_keys=300), seed=25, rate=10000.0)
        batch = g.batch(2048)
        agg = run_pipeline([batch], config)
        flushed = agg.flush(force=True)
        from flow_pipeline_tpu.models.oracle import exact_groupby

        oracle = exact_groupby(batch, ["src_addr", "dst_addr"], timeslot=True)
        assert len(flushed["timeslot"]) == len(oracle["timeslot"])
        assert flushed["bytes"].sum() == oracle["bytes"].sum()
        assert flushed["count"].sum() == 2048

    def test_empty_batch_noop(self):
        agg = WindowAggregator(WindowAggConfig(batch_size=64))
        agg.update(FlowBatch.empty(0))
        out = agg.flush(force=True)
        assert len(out["timeslot"]) == 0


class TestHashCollisionFallback:
    """The hash-grouped fast path must keep flows_5m bit-exact even when
    the 64-bit grouping hash collides: the drain re-runs the chunk
    through the lexicographic path."""

    def test_forced_collision_uses_exact_fallback(self, monkeypatch):
        import jax.numpy as jnp

        from flow_pipeline_tpu.models import window_agg as wa
        from flow_pipeline_tpu.ops import segment

        # A degenerate hash that maps EVERY row to one value guarantees a
        # collision whenever two distinct keys coexist. Unique cache keys
        # (window_seconds=77) keep the stubbed trace out of the shared
        # lru_cache entries other tests use.
        def degenerate(keys):
            n = keys.shape[0]
            one = jnp.ones(n, jnp.uint32)
            return one, one

        monkeypatch.setattr(segment, "hash_lanes", degenerate)
        config = WindowAggConfig(window_seconds=77, batch_size=64)
        gen = FlowGenerator(MockerProfile(), seed=5)
        batch = gen.batch(180)
        agg = WindowAggregator(config)
        agg.update(batch)
        agg._drain()

        # independent exact reference: same config, un-stubbed hash
        monkeypatch.undo()
        wa._cached_update.cache_clear()
        wa._cached_update_exact.cache_clear()
        ref = WindowAggregator(config)
        ref.update(batch)
        ref._drain()
        assert agg.windows.keys() == ref.windows.keys()
        for slot in ref.windows:
            assert set(agg.windows[slot]) == set(ref.windows[slot])
            for k in ref.windows[slot]:
                np.testing.assert_array_equal(
                    agg.windows[slot][k], ref.windows[slot][k])

    def test_fallback_required_when_missing(self):
        import jax.numpy as jnp
        import pytest

        from flow_pipeline_tpu.models.window_agg import WindowAggregator

        agg = WindowAggregator(WindowAggConfig(batch_size=64))
        fake = (jnp.zeros((4, 4), jnp.uint32), jnp.zeros((4, 4), jnp.int32),
                jnp.zeros(4, jnp.int32), jnp.asarray(0),
                jnp.asarray(True))  # collided, no fallback
        agg.add_partial(fake, fallback=None)
        with pytest.raises(RuntimeError, match="no exact"):
            agg._drain()
