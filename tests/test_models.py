"""Model-level tests: heavy-hitter top-K vs exact oracle (the <=1% error
gate from BASELINE.json) and DDoS spike detection on injected attacks."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flow_pipeline_tpu.gen import FlowGenerator, MockerProfile, ZipfProfile
from flow_pipeline_tpu.models import (
    DDoSConfig,
    DDoSDetector,
    HeavyHitterConfig,
    HeavyHitterModel,
)
from flow_pipeline_tpu.models import heavy_hitter as hh
from flow_pipeline_tpu.models.oracle import topk_exact
from flow_pipeline_tpu.ops.segment import hash_lanes
from flow_pipeline_tpu.ops.topk import topk_init
from flow_pipeline_tpu.schema.batch import FlowBatch


def key_tuple(row_keys, i):
    return tuple(int(x) for x in np.atleast_1d(row_keys[i]).ravel())


class TestHeavyHitterParity:
    def run_model(self, config, batches):
        model = HeavyHitterModel(config)
        for b in batches:
            model.update(b)
        return model

    def oracle_top(self, batches, key_cols, k):
        return topk_exact(FlowBatch.concat(batches), list(key_cols), k)

    def test_addr_pair_topk_within_1pct(self):
        config = HeavyHitterConfig(
            key_cols=("src_addr", "dst_addr"), batch_size=4096,
            width=1 << 14, capacity=512,
        )
        g = FlowGenerator(ZipfProfile(n_keys=2000, alpha=1.2), seed=31)
        batches = [g.batch(4096) for _ in range(6)]
        model = self.run_model(config, batches)
        k = 20
        top = model.top(k)
        oracle = self.oracle_top(batches, config.key_cols, k)

        got = {
            (key_tuple(top["src_addr"], i) + key_tuple(top["dst_addr"], i)):
                float(top["bytes"][i])
            for i in range(k)
        }
        errs = []
        for i in range(k):
            key = (key_tuple(oracle["src_addr"], i)
                   + key_tuple(oracle["dst_addr"], i))
            true = float(oracle["bytes"][i])
            assert key in got, f"oracle top-{k} key {i} missing from sketch"
            errs.append(abs(got[key] - true) / true)
        assert max(errs) <= 0.01, f"max top-K bytes error {max(errs):.4f}"

    def test_plain_admission_ab_leg_stays_accurate(self):
        # -sketch.admission=plain (the bench A/B baseline without the
        # CMS-seeded space-saving entry) must still place the oracle
        # top keys — with capacity >= distinct keys nothing is evicted,
        # so table sums are exact even without seeded admission
        config = HeavyHitterConfig(
            key_cols=("src_addr", "dst_addr"), batch_size=2048,
            width=1 << 12, capacity=256, table_admission="plain",
        )
        g = FlowGenerator(ZipfProfile(n_keys=100, alpha=1.3), seed=13)
        batches = [g.batch(2048) for _ in range(4)]
        model = self.run_model(config, batches)
        top = model.top(5)
        oracle = self.oracle_top(batches, config.key_cols, 5)
        for i in range(5):
            assert (top["src_addr"][i] == oracle["src_addr"][i]).all()
            assert float(top["bytes"][i]) == float(oracle["bytes"][i])

    def test_bad_admission_rejected(self):
        config = HeavyHitterConfig(batch_size=256, width=1 << 10,
                                   capacity=32, table_admission="bogus")
        g = FlowGenerator(ZipfProfile(n_keys=10), seed=1)
        with pytest.raises(ValueError, match="table_admission"):
            HeavyHitterModel(config).update(g.batch(256))

    def test_five_tuple_talkers(self):
        config = HeavyHitterConfig(
            key_cols=("src_addr", "dst_addr", "src_port", "dst_port", "proto"),
            batch_size=2048, width=1 << 14, capacity=256,
        )
        g = FlowGenerator(ZipfProfile(n_keys=500, alpha=1.4), seed=32)
        batches = [g.batch(2048) for _ in range(4)]
        model = self.run_model(config, batches)
        top = model.top(10)
        oracle = self.oracle_top(
            batches, config.key_cols, 10
        )
        # rank-0 talker identical, bytes within 1%
        got_key = (key_tuple(top["src_addr"], 0) + key_tuple(top["dst_addr"], 0)
                   + (int(top["src_port"][0]), int(top["dst_port"][0]),
                      int(top["proto"][0])))
        want_key = (key_tuple(oracle["src_addr"], 0)
                    + key_tuple(oracle["dst_addr"], 0)
                    + (int(oracle["src_port"][0]), int(oracle["dst_port"][0]),
                       int(oracle["proto"][0])))
        assert got_key == want_key
        err = abs(float(top["bytes"][0]) - float(oracle["bytes"][0])) / float(
            oracle["bytes"][0]
        )
        assert err <= 0.01

    def test_counts_and_packets_planes(self):
        config = HeavyHitterConfig(batch_size=1024, width=1 << 14, capacity=128)
        g = FlowGenerator(ZipfProfile(n_keys=100, alpha=1.5), seed=33)
        batches = [g.batch(1024) for _ in range(3)]
        model = self.run_model(config, batches)
        top = model.top(5)
        oracle = topk_exact(
            FlowBatch.concat(batches), ["src_addr", "dst_addr"], 5
        )
        # table sums for the hottest key are exact (never evicted)
        assert float(top["bytes"][0]) == float(oracle["bytes"][0])
        assert int(top["count"][0]) > 0
        # CMS estimate plane is an upper bound of the table sum
        assert float(top["bytes_est"][0]) >= float(top["bytes"][0]) - 1e-3

    def test_oversized_and_odd_batches_chunked(self):
        # update() must accept any batch size, not just config.batch_size
        config = HeavyHitterConfig(batch_size=512, width=1 << 12, capacity=64)
        g = FlowGenerator(ZipfProfile(n_keys=50, alpha=1.3), seed=35)
        big = g.batch(1337)  # > batch_size and not a multiple
        whole = HeavyHitterModel(config)
        whole.update(big)
        oracle = topk_exact(big, ["src_addr", "dst_addr"], 3)
        top = whole.top(3)
        assert float(top["bytes"][0]) == float(oracle["bytes"][0])

    def test_saturated_counters_stay_positive(self):
        # bytes >= 2^31 (int32-negative bit patterns) must rank first, not last
        from flow_pipeline_tpu.schema.message import FlowMessage

        msgs = [FlowMessage(bytes=3_000_000_000, packets=1,
                            src_addr=b"\x01" * 16, dst_addr=b"\x02" * 16)]
        msgs += [FlowMessage(bytes=100, packets=1,
                             src_addr=bytes([i]) * 16, dst_addr=b"\x09" * 16)
                 for i in range(3, 20)]
        batch = FlowBatch.from_messages(msgs)
        model = HeavyHitterModel(
            HeavyHitterConfig(batch_size=64, width=1 << 10, capacity=32)
        )
        model.update(batch)
        top = model.top(1)
        assert float(top["bytes"][0]) == 3_000_000_000.0

    def test_reset_clears_state(self):
        model = HeavyHitterModel(HeavyHitterConfig(batch_size=256, width=1 << 10, capacity=32))
        g = FlowGenerator(ZipfProfile(n_keys=50), seed=34)
        model.update(g.batch(256))
        model.reset()
        top = model.top(5)
        assert not top["valid"].any()


class TestDDoS:
    def make_traffic(self, seed, attack_dst=None, attack_mult=50):
        """Baseline mocker traffic; optionally one dst under attack in the
        last sub-windows."""
        g = FlowGenerator(MockerProfile(), seed=seed, t0=1_699_999_800, rate=200.0)
        batches = [g.batch(2000) for _ in range(8)]  # 80s = 8 sub-windows
        if attack_dst is not None:
            # amplify packets toward one dst in the final 2 sub-windows
            for b in batches[-2:]:
                dst = b.columns["dst_addr"]
                hit = (dst[:, 3] & 0xFF) == attack_dst
                b.columns["packets"][hit] = b.columns["packets"][hit] * attack_mult
        return batches

    def run(self, batches, config=None):
        det = DDoSDetector(config or DDoSConfig(batch_size=2048, n_buckets=1 << 10,
                                                sub_window_seconds=10))
        for b in batches:
            det.update(b)
        det.close_sub_window()
        return det

    def test_no_alert_on_steady_traffic(self):
        det = self.run(self.make_traffic(seed=41))
        assert det.alerts == []

    def test_attack_detected(self):
        det = self.run(self.make_traffic(seed=42, attack_dst=7))
        assert len(det.alerts) >= 1
        # alerted address ends with the attacked host byte
        assert any(int(a["dst_addr"][3]) & 0xFF == 7 for a in det.alerts)

    def test_alert_carries_scores(self):
        det = self.run(self.make_traffic(seed=43, attack_dst=9))
        a = det.alerts[0]
        assert a["zscore"] >= 4.0
        assert a["rate"] > a["baseline_quantile"]

    def test_boundary_straddling_batch_split(self):
        # one batch spanning two sub-windows must fold rates separately
        g = FlowGenerator(MockerProfile(), seed=44, t0=1_699_999_800, rate=100.0)
        det = DDoSDetector(DDoSConfig(batch_size=2048, n_buckets=256,
                                      sub_window_seconds=10))
        det.update(g.batch(1500))  # 15 seconds -> straddles one boundary
        assert det.folds == 1  # first sub-window closed by the straddle
        assert det.current_sub == 1_699_999_810

    def test_late_rows_dropped_not_accumulated(self):
        # rows for an already-closed sub-window must be dropped (and
        # counted), never folded into the CURRENT sub-window where they
        # would inflate rates and can fire spurious z-score alerts
        g = FlowGenerator(MockerProfile(), seed=45, t0=1_699_999_800, rate=100.0)
        det = DDoSDetector(DDoSConfig(batch_size=2048, n_buckets=256,
                                      sub_window_seconds=10))
        current = g.batch(1000)  # 10s, fills sub-window 0 exactly
        det.update(current)
        det.update(g.batch(500))  # advances into sub-window 1
        assert det.current_sub == 1_699_999_810
        rates_before = np.asarray(det.state.rates).copy()
        late = FlowBatch(
            {k: v[:200].copy() for k, v in current.columns.items()},
            current.partition,
        )
        late.columns["time_received"][:] = 1_699_999_805  # sub-window 0
        det.update(late)
        assert det.late_flows_dropped == 200
        np.testing.assert_array_equal(np.asarray(det.state.rates), rates_before)
        assert det.current_sub == 1_699_999_810  # no spurious close either

    def test_padding_rows_never_touch_last_bucket(self):
        # regression: -1 "drop" index used to wrap to bucket n_buckets-1
        import jax.numpy as jnp
        from flow_pipeline_tpu.models.ddos import ddos_accumulate, ddos_init
        from flow_pipeline_tpu.ops.quantile import QuantileSketchSpec

        config = DDoSConfig(batch_size=8, n_buckets=16)
        state = ddos_init(config, QuantileSketchSpec())
        state = state._replace(addrs=state.addrs.at[15].set(jnp.uint32(7)))
        cols = {
            "dst_addr": jnp.zeros((8, 4), jnp.int32),
            "packets": jnp.ones(8, jnp.int32),
            "sampling_rate": jnp.ones(8, jnp.int32),
        }
        state = ddos_accumulate(state, cols, jnp.zeros(8, bool), config=config)
        assert np.asarray(state.addrs)[15].tolist() == [7, 7, 7, 7]
        assert float(jnp.sum(state.rates)) == 0.0


class TestTablePrefilter:
    def test_accuracy_within_gate(self):
        # prefilter trades a looser Misra-Gries bound for a 4x smaller
        # merge sort; on a Zipf stream the top-K must still be right
        g = FlowGenerator(ZipfProfile(n_keys=400, alpha=1.3), seed=31)
        batches = [g.batch(2048) for _ in range(4)]
        tops = {}
        for pre in (False, True):
            m = HeavyHitterModel(HeavyHitterConfig(
                batch_size=512, width=1 << 12, capacity=64,
                table_prefilter=pre,
            ))
            for b in batches:
                m.update(b)
            tops[pre] = m.top(10)
        oracle = topk_exact(FlowBatch.concat(batches),
                            ["src_addr", "dst_addr"], 10)
        for pre in (False, True):
            top = tops[pre]
            for i in range(10):
                assert (top["src_addr"][i] == oracle["src_addr"][i]).all(), pre
                assert abs(int(top["bytes"][i]) - int(oracle["bytes"][i])) \
                    <= 0.01 * int(oracle["bytes"][i]) + 1, pre

    def test_selects_everything_when_uniques_fit(self):
        # batch slots (512) exceed 2*capacity (256) so the prefilter
        # branch RUNS, but distinct keys (~30) fit: the top-2C selection
        # must keep every valid group and match the unfiltered path
        g = FlowGenerator(ZipfProfile(n_keys=30, alpha=1.5), seed=32)
        batch = g.batch(512)
        tops = []
        for pre in (False, True):
            m = HeavyHitterModel(HeavyHitterConfig(
                batch_size=512, width=1 << 10, capacity=128,
                table_prefilter=pre,
            ))
            m.update(batch)
            tops.append(m.top(10))
        for k in tops[0]:
            np.testing.assert_array_equal(tops[0][k], tops[1][k])

    @staticmethod
    def _crafted_batch(src_keys: np.ndarray, bytes_: np.ndarray):
        """FlowBatch whose (src_addr, dst_addr) identity is src_keys and
        whose bytes are bytes_; everything else from the generator."""
        n = len(src_keys)
        g = FlowGenerator(ZipfProfile(n_keys=4), seed=0)
        b = g.batch(n)
        addr = np.zeros((n, 4), np.uint32)
        addr[:, 3] = src_keys
        b.columns["src_addr"] = addr
        b.columns["dst_addr"] = addr.copy()
        b.columns["bytes"] = bytes_.astype(np.uint64)
        b.columns["sampling_rate"] = np.ones(n, np.uint64)
        return b

    def test_resident_keys_never_starved(self):
        """The r4 regression (VERDICT #4): with per-batch distinct keys
        >> capacity, table-RESIDENT keys whose rows rank below the batch
        top-candidates lost every later increment (~25x under-count on
        near-uniform streams). The table-aware prefilter must accumulate
        residents exactly, like the unfiltered merge."""
        cap = 64
        rng = np.random.default_rng(34)
        # batch 1: keys 0..63 with heavy rows -> they become residents
        resid = np.repeat(np.arange(cap, dtype=np.uint32), 4)
        b1 = self._crafted_batch(resid, np.full(len(resid), 1000))
        # batches 2..5: residents appear with LOW-ranking rows, buried
        # under 500 fresh distinct keys per batch with big rows
        batches = [b1]
        for r in range(4):
            fresh = 1000 + rng.permutation(2000)[:500].astype(np.uint32)
            keys = np.concatenate([np.arange(cap, dtype=np.uint32), fresh])
            vals = np.concatenate([np.full(cap, 10), np.full(500, 500)])
            batches.append(self._crafted_batch(keys, vals))
        m = HeavyHitterModel(HeavyHitterConfig(
            batch_size=512, width=1 << 12, capacity=cap))
        for b in batches:
            m.update(b)
        top = m.top(cap)
        # every original resident must still be tracked with its EXACT
        # total: 4*1000 from batch 1 + 4 later rows of 10
        got = {int(k): int(v) for k, v in
               zip(top["src_addr"][:, 3], top["bytes"]) if v >= 4000}
        for key in range(cap):
            assert got.get(key) == 4040, (key, got.get(key))

    def test_near_uniform_stream_within_gate(self):
        """BASELINE's <=1% error gate on a near-uniform 64k-key stream
        with DEFAULT flags (prefilter on): the values reported for the
        top-20 keys must be within 1% of those keys' true totals —
        under the r4 prefilter they were ~4% of truth."""
        g = FlowGenerator(ZipfProfile(n_keys=65536, alpha=0.05), seed=33)
        batches = [g.batch(8192) for _ in range(8)]
        m = HeavyHitterModel(HeavyHitterConfig(
            batch_size=8192, width=1 << 16, capacity=1024))
        for b in batches:
            m.update(b)
        top = m.top(20)
        # true totals of the REPORTED keys (identity on a uniform stream
        # is arbitrary — honest VALUES for whatever is reported are not)
        allb = FlowBatch.concat(batches)
        src = allb.columns["src_addr"][:, 3].astype(np.uint64)
        dst = allb.columns["dst_addr"][:, 3].astype(np.uint64)
        flat = src << np.uint64(32) | dst
        want = {}
        for i in range(20):
            k = (np.uint64(top["src_addr"][i, 3]) << np.uint64(32)
                 | np.uint64(top["dst_addr"][i, 3]))
            want[i] = int(allb.columns["bytes"][flat == k].sum())
        for i in range(20):
            got = int(top["bytes"][i])
            assert abs(got - want[i]) <= 0.01 * want[i] + 1, \
                (i, got, want[i])


def drive_admission_rounds(rounds):
    """Assert the space-saving admission bounds over a candidate stream.

    ``rounds``: list of [(key, value), ...] batches. Uses a deliberately
    NARROW CMS (width 64, depth 2 — ~20x more keys than cells) so
    estimates over-state grossly and newcomers enter inflated, competing
    with residents at the eviction boundary. Asserts after every merge:

      (1) upper bound — every resident's table value >= its true total
          (admission seeds the CMS estimate covering pre-entry mass;
          residents take exact increments thereafter);
      (2) Misra-Gries dropped mass — every evicted resident leaves with
          tracked mass <= the minimum SURVIVING table value, so a key
          whose true total dominates the boundary cannot be displaced,
          over-estimated newcomers included (ops.topk.topk_merge_est's
          documented guarantee).

    Returns the number of resident evictions exercised, so callers can
    require the adversarial case actually occurred.
    """
    import jax
    import jax.numpy as jnp

    from flow_pipeline_tpu.ops import cms as cms_ops
    from flow_pipeline_tpu.ops import topk as topk_ops

    C, N, DEPTH, WIDTH = 8, 16, 2, 64
    cms = cms_ops.cms_init(1, DEPTH, WIDTH)
    tk, tv = topk_ops.topk_init(C, 1, 1)
    cms_add = jax.jit(cms_ops.cms_add_conservative)
    cms_query = jax.jit(cms_ops.cms_query)
    merge = jax.jit(topk_ops.topk_merge_est)
    sentinel = int(topk_ops.SENTINEL)

    def as_dict(keys, vals):
        return {int(k[0]): float(v[0]) for k, v in
                zip(np.asarray(keys), np.asarray(vals))
                if k[0] != sentinel}

    true: dict[int, float] = {}
    evictions = 0
    for pairs in rounds:
        sums: dict[int, float] = {}
        for k, v in pairs:
            sums[k] = sums.get(k, 0.0) + v
            true[k] = true.get(k, 0.0) + v
        uniq = np.full((N, 1), topk_ops.SENTINEL, np.uint32)
        vals = np.zeros((N, 1), np.float32)
        valid = np.zeros(N, bool)
        for i, (k, v) in enumerate(list(sums.items())[:N]):
            uniq[i, 0] = k
            vals[i, 0] = v
            valid[i] = True
        cms = cms_add(cms, jnp.asarray(uniq), jnp.asarray(vals),
                      jnp.asarray(valid))
        est = cms_query(cms, jnp.asarray(uniq))
        old = as_dict(tk, tv)
        tk, tv = merge(tk, tv, jnp.asarray(uniq), jnp.asarray(vals), est,
                       jnp.asarray(valid))
        table = as_dict(tk, tv)
        for k, v in table.items():
            assert v >= true[k] - 1e-3 * max(1.0, true[k]), \
                f"table under-counts key {k}: {v} < true {true[k]}"
        if table:
            boundary = min(table.values())
            for k, v in old.items():
                if k not in table:
                    evictions += 1
                    assert v <= boundary + 1e-3 * max(1.0, boundary), (
                        f"evicted resident {k} carried {v} past the "
                        f"rank-C boundary {boundary}")
    return evictions


class TestSpaceSavingAdmissionSeeded:
    """Seeded adversarial admission run (VERDICT r5 #5) — the same
    bounds test_property.py fuzzes with hypothesis, kept runnable in
    environments without it."""

    def test_bounds_hold_and_evictions_occur(self):
        rng = np.random.default_rng(3)
        rounds = []
        for _ in range(50):
            ks = rng.integers(1, 1200, size=rng.integers(1, 17))
            vs = rng.integers(1, 1000, size=len(ks))
            rounds.append([(int(k), float(v)) for k, v in zip(ks, vs)])
        evictions = drive_admission_rounds(rounds)
        # the adversarial case must actually be exercised, not vacuous
        assert evictions > 20


# ---- the prefilter's residency test (PR 32) --------------------------------


def _resident_sorted(th, gh, row_valid):
    """The form `_resident` replaced, kept as the reference: binary search
    of every group hash in the table's sorted hashes (`jnp.searchsorted`
    runs as a loop of dependent gathers), then one gather and a compare."""
    ts = jnp.sort(th)
    pos = jnp.clip(jnp.searchsorted(ts, gh), 0, th.shape[0] - 1)
    return (ts[pos] == gh) & row_valid


def _loop_primitives(jaxpr) -> list:
    """Names of the while / scan primitives anywhere in a jaxpr, the
    jaxprs nested in its equations' parameters included (a `jit` inside
    the traced function, such as `jnp.searchsorted`, hides its loop in
    one)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("while", "scan"):
            found.append(eqn.primitive.name)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else (param,):
                sub = getattr(sub, "jaxpr", sub)  # ClosedJaxpr -> Jaxpr
                if hasattr(sub, "eqns"):
                    found.extend(_loop_primitives(sub))
    return found


def _hashes(rng, n):
    # strictly inside (0, 2^32 - 1), so a case can put hashes outside
    return rng.integers(1, 2**32 - 1, n, dtype=np.uint32)


def _case_empty_table(rng, c, n):
    # every slot the all-sentinel row: one hash, C times; a group that is
    # the sentinel row itself hashes like the slots, as it always did
    keys, _ = topk_init(c, 4, 3)
    th = np.asarray(hash_lanes(keys)[0])
    gh = _hashes(rng, n)
    gh[5] = th[0]
    return th, gh, np.ones(n, bool)


def _case_full_table(rng, c, n):
    th = _hashes(rng, c)
    gh = _hashes(rng, n)
    at = rng.permutation(n)[:c]
    gh[at] = th  # every resident appears in the batch
    return th, gh, rng.random(n) < 0.8


def _case_guard_edge(rng, c, n):
    # N = 2 C + 1: the smallest batch for which the prefilter arms
    n = 2 * c + 1
    th = _hashes(rng, c)
    gh = _hashes(rng, n)
    gh[::3] = th[rng.integers(0, c, len(gh[::3]))]
    return th, gh, rng.random(n) < 0.5


def _case_invalid_residents(rng, c, n):
    # rows whose hash is in the table but which are not valid groups
    th = _hashes(rng, c)
    gh = _hashes(rng, n)
    gh[:c] = th
    valid = np.ones(n, bool)
    valid[:c:2] = False
    return th, gh, valid


def _case_repeated_hash(rng, c, n):
    th = _hashes(rng, c)
    th[c // 2:] = th[: c - c // 2]  # every hash twice
    th[:7] = th[0]
    gh = _hashes(rng, n)
    gh[10:20] = th[0]
    gh[20:30] = th[c // 3]
    return th, gh, np.ones(n, bool)


def _case_outside_the_table(rng, c, n):
    # group hashes below the smallest and above the largest table hash:
    # where the binary search returned 0 and C (clipped to C - 1)
    th = rng.integers(1000, 2**32 - 1000, c, dtype=np.uint32)
    gh = _hashes(rng, n)
    gh[:8] = np.arange(8, dtype=np.uint32)            # below all, 0 too
    gh[8:16] = np.uint32(2**32 - 1) - np.arange(8, dtype=np.uint32)
    gh[16] = th.min()
    gh[17] = th.max()
    return th, gh, np.ones(n, bool)


def _case_extreme_table(rng, c, n):
    # the table holds 0 and 2^32 - 1 themselves
    th = _hashes(rng, c)
    th[0], th[1] = 0, np.uint32(2**32 - 1)
    gh = _hashes(rng, n)
    gh[:4] = (0, 2**32 - 1, 1, 2**32 - 2)
    return th, gh, np.ones(n, bool)


class TestResidencyDense:
    """`models.heavy_hitter._resident` (PR 32): the same mask as the sorted
    search it replaced, bit for bit, and no loop left on the device."""

    FAMILIES = {
        "top_talkers": ("src_addr", "dst_addr", "src_port", "dst_port",
                        "proto"),
        "top_src_ips": ("src_addr",),
        "top_dst_ips": ("dst_addr",),
    }

    @pytest.mark.parametrize("shape", [(64, 512), (128, 2048)],
                             ids=lambda s: f"C{s[0]}xN{s[1]}")
    @pytest.mark.parametrize("case", [
        _case_empty_table, _case_full_table, _case_guard_edge,
        _case_invalid_residents, _case_repeated_hash,
        _case_outside_the_table, _case_extreme_table,
    ], ids=lambda f: f.__name__[6:])
    def test_mask_is_the_sorted_searchs_and_numpys(self, case, shape):
        th, gh, valid = case(np.random.default_rng(41), *shape)
        args = (jnp.asarray(th), jnp.asarray(gh), jnp.asarray(valid))
        got = np.asarray(jax.jit(hh._resident)(*args))
        want = np.isin(gh, th) & valid
        assert want.any() and not want.all()  # the case bites both ways
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(
            got, np.asarray(jax.jit(_resident_sorted)(*args)))

    @pytest.mark.parametrize("family", sorted(FAMILIES))
    def test_state_bit_equal_to_the_sorted_forms_after_every_step(
            self, family, monkeypatch):
        cfg = HeavyHitterConfig(
            key_cols=self.FAMILIES[family], batch_size=2048,
            width=1 << 12, capacity=128)
        g = FlowGenerator(ZipfProfile(n_keys=20000, alpha=1.1), seed=42)
        batches = [g.batch(2048 - 9 * (i % 3)) for i in range(20)]

        def steps(update):
            state = hh.hh_init(cfg)
            for b in batches:
                padded, mask = b.pad_to(cfg.batch_size)
                cols = {k: jnp.asarray(v) for k, v in
                        padded.device_columns(hh.input_cols(cfg)).items()}
                state = update(state, cols, jnp.asarray(mask),
                               config=cfg)
                yield [np.asarray(x) for x in state]

        # the reference: the same update traced with the parent's form in
        # _resident's place (a jit of its own: hh_update's trace is cached)
        monkeypatch.setattr(hh, "_resident", _resident_sorted)
        ref_update = jax.jit(hh.hh_update.__wrapped__,
                             static_argnames=("config",))
        want = list(steps(ref_update))
        monkeypatch.undo()
        residents = 0
        for i, (new, ref) in enumerate(zip(steps(hh.hh_update), want)):
            for name, a, b in zip(hh.HHState._fields, new, ref):
                np.testing.assert_array_equal(a, b, err_msg=f"{name} "
                                              f"after step {i + 1}")
            residents += int((new[1] != 0xFFFFFFFF).any(axis=1).sum())
        assert residents > 20 * cfg.capacity // 2  # the table was in use

    @staticmethod
    def _apply_grouped_jaxpr(key_cols, n=32768):
        """`_apply_grouped` at the default configuration's shapes (the
        processor's defaults at the benchmark's batch of 32,768)."""
        cfg = HeavyHitterConfig(key_cols=key_cols, batch_size=n)
        assert n > 2 * cfg.capacity  # the prefilter arms
        shape = jax.ShapeDtypeStruct
        state = jax.tree_util.tree_map(
            lambda a: shape(a.shape, a.dtype), hh.hh_init(cfg))
        planes = len(cfg.value_cols) + 1
        return jax.make_jaxpr(partial(hh._apply_grouped, config=cfg))(
            state, shape((n, hh.key_width(cfg)), np.uint32),
            shape((n, planes), np.float32), shape((n,), np.bool_)).jaxpr

    @pytest.mark.parametrize("family", sorted(FAMILIES))
    def test_no_loop_left_in_apply_grouped(self, family):
        # the jaxpr, not a platform's HLO: a CPU expands scatters into
        # loops of its own. One loop is there by design since PR 37: the
        # conservative update's estimate over the chunks of live rows
        # (ops.cms.cms_query), whose trips together gather fewer indices
        # than the one gather it stands for; the residency test has none
        jaxpr = self._apply_grouped_jaxpr(self.FAMILIES[family])
        assert _loop_primitives(jaxpr) == ["while"]

    def test_the_walk_finds_the_sorted_forms_loop(self, monkeypatch):
        # what the test above would say of the parent: searchsorted's
        # while sits inside a nested jit, and the walk reaches it
        monkeypatch.setattr(hh, "_resident", _resident_sorted)
        jaxpr = self._apply_grouped_jaxpr(("src_addr",))
        assert len(_loop_primitives(jaxpr)) > 1
