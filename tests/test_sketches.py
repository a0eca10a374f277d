"""Sketch op unit tests against exact numpy counters (SURVEY.md §4:
"unit-test sketch kernels against exact numpy counters")."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flow_pipeline_tpu.models.heavy_hitter import live_rows
from flow_pipeline_tpu.ops import cms as cms_mod
from flow_pipeline_tpu.ops import (
    QuantileSketchSpec,
    cms_add,
    cms_add_conservative,
    cms_init,
    cms_merge,
    cms_query,
    ewma_fold,
    ewma_init,
    rate_accumulate,
    bucket_of,
    topk_extract,
    topk_init,
    topk_merge,
    zscores,
)


def exact_counts(keys, values):
    agg = {}
    for k, v in zip(keys, values):
        agg[tuple(k)] = agg.get(tuple(k), 0) + v
    return agg


class TestCMS:
    def make(self, rng, n=512, n_keys=40, depth=4, width=1 << 12):
        keys = rng.integers(0, 2**32, size=(n_keys, 2), dtype=np.uint32)
        idx = rng.integers(0, n_keys, n)
        vals = rng.integers(1, 100, n)
        # pre-aggregate (the contract: unique keys per call)
        agg = {}
        for i, v in zip(idx, vals):
            agg[i] = agg.get(i, 0) + int(v)
        uk = np.array(sorted(agg))
        ukeys = keys[uk]
        uvals = np.array([[agg[i]] for i in uk], dtype=np.int32)
        return keys, ukeys, uvals, agg, uk

    @pytest.mark.parametrize("add_fn", [cms_add, cms_add_conservative])
    def test_upper_bound_and_accuracy(self, rng, add_fn):
        keys, ukeys, uvals, agg, uk = self.make(rng)
        sk = cms_init(1, 4, 1 << 12)
        sk = add_fn(sk, jnp.asarray(ukeys), jnp.asarray(uvals),
                    jnp.ones(len(ukeys), bool))
        est = np.asarray(cms_query(sk, jnp.asarray(ukeys)))[:, 0]
        true = np.array([agg[i] for i in uk], dtype=np.float64)
        assert (est >= true - 1e-3).all()  # upper bound
        # wide sketch, few keys -> estimates essentially exact
        np.testing.assert_allclose(est, true, rtol=1e-5)

    def test_conservative_tighter_than_linear(self, rng):
        # tiny width forces collisions; CU must never be looser
        keys, ukeys, uvals, agg, uk = self.make(rng, n_keys=300, width=128)
        lin = cms_add(cms_init(1, 2, 128), jnp.asarray(ukeys),
                      jnp.asarray(uvals), jnp.ones(len(ukeys), bool))
        con = cms_add_conservative(cms_init(1, 2, 128), jnp.asarray(ukeys),
                                   jnp.asarray(uvals), jnp.ones(len(ukeys), bool))
        e_lin = np.asarray(cms_query(lin, jnp.asarray(ukeys)))[:, 0]
        e_con = np.asarray(cms_query(con, jnp.asarray(ukeys)))[:, 0]
        true = np.array([agg[i] for i in uk])
        assert (e_con >= true - 1e-3).all()
        assert (e_con <= e_lin + 1e-3).all()
        assert e_con.sum() < e_lin.sum()  # strictly tighter somewhere

    def test_merge_equals_combined_stream(self, rng):
        keys, ukeys, uvals, agg, uk = self.make(rng)
        half = len(ukeys) // 2
        a = cms_add(cms_init(1, 4, 1 << 12), jnp.asarray(ukeys[:half]),
                    jnp.asarray(uvals[:half]), jnp.ones(half, bool))
        b = cms_add(cms_init(1, 4, 1 << 12), jnp.asarray(ukeys[half:]),
                    jnp.asarray(uvals[half:]), jnp.ones(len(ukeys) - half, bool))
        both = cms_add(cms_init(1, 4, 1 << 12), jnp.asarray(ukeys),
                       jnp.asarray(uvals), jnp.ones(len(ukeys), bool))
        np.testing.assert_allclose(
            np.asarray(cms_merge(a, b)), np.asarray(both), rtol=1e-6
        )

    def test_invalid_rows_ignored(self, rng):
        keys, ukeys, uvals, agg, uk = self.make(rng)
        valid = np.zeros(len(ukeys), bool)
        sk = cms_add(cms_init(1, 4, 1 << 12), jnp.asarray(ukeys),
                     jnp.asarray(uvals), jnp.asarray(valid))
        assert float(jnp.sum(sk)) == 0.0


def _conservative_as_the_parent_wrote_it(counts, keys, values, valid=None,
                                         n_live=None):
    """`cms_add_conservative` before the live bound (PR 37's parent),
    kept here as the reference: every slot's estimate gathered and every
    slot in every row's scatter (PR 45 took the padding out of it).
    `n_live` is taken and not used, so that a test can put this in the
    update's place."""
    p, d, w = counts.shape
    buckets = cms_mod.cms_buckets(keys, d, w)
    vals = values.astype(jnp.float32)
    if valid is not None:
        vals = jnp.where(valid[:, None], vals, 0.0)
    est = jnp.min(jnp.stack(
        [counts[:, di, buckets[di]] for di in range(d)]), axis=0).T
    target = est + vals
    for di in range(d):
        counts = counts.at[:, di, buckets[di]].max(target.T)
    return counts


C = cms_mod.LIVE_CHUNK
# N at the chunk (the plain gather stays) and past two chunks by a part
# of one (the last chunk is clamped to end at N)
LIVE_NS = {"N<=C": C, "N>C": 2 * C + 512}


class TestLiveBound:
    """`cms_query` / `cms_add_conservative` under a live bound (PR 37):
    the rows below it as the plain call reads them, and the state after
    the update the parent's, bit for bit."""

    PLANES, DEPTH, WIDTH = 3, 4, 1 << 10  # narrow: most cells collide

    def raised(self, rng, n):
        """A sketch whose cells an earlier batch of the same keys has
        raised: no estimate is 0."""
        keys = jnp.asarray(
            rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32))
        before = cms_add_conservative(
            cms_init(self.PLANES, self.DEPTH, self.WIDTH), keys,
            jnp.asarray(rng.integers(1, 5000, (n, self.PLANES))),
            jnp.ones(n, bool))
        return before, keys

    @staticmethod
    def holed(rng, n, n_live):
        """[n] bool: real rows with holes between them (the fused step's
        shared dst sort can give such), the last of them at n_live - 1."""
        valid = np.zeros(n, bool)
        valid[:n_live] = rng.random(n_live) < 0.7
        if n_live:
            valid[n_live - 1] = True
        return valid

    @pytest.mark.parametrize("n_live", [0, 1, C - 1, C, C + 1, "N"])
    @pytest.mark.parametrize("n", sorted(LIVE_NS))
    def test_query_reads_the_rows_below_the_bound(self, rng, n, n_live):
        n = LIVE_NS[n]
        n_live = n if n_live == "N" else min(n_live, n)
        counts, keys = self.raised(rng, n)
        plain = np.asarray(cms_query(counts, keys))
        assert plain.min() > 0  # a row left out would show
        got = np.asarray(jax.jit(cms_query)(counts, keys,
                                            jnp.int32(n_live)))
        assert got.dtype == plain.dtype and got.shape == plain.shape
        assert got[:n_live].tobytes() == plain[:n_live].tobytes()
        if n > C:  # the bounded form: 0 from the bound on
            assert not got[n_live:].any()

    @pytest.mark.parametrize("n_live", [0, 1, C - 1, C, C + 1, "N"])
    @pytest.mark.parametrize("n", sorted(LIVE_NS))
    def test_update_leaves_the_parents_state(self, rng, n, n_live):
        n = LIVE_NS[n]
        n_live = n if n_live == "N" else min(n_live, n)
        counts, keys = self.raised(rng, n)
        valid = self.holed(rng, n, n_live)
        assert int(live_rows(jnp.asarray(valid))) == n_live
        assert n_live < 2 or not valid[:n_live].all()
        # addends on the padding rows too: the mask is the update's
        values = jnp.asarray(rng.integers(1, 5000, (n, self.PLANES)))
        want = np.asarray(_conservative_as_the_parent_wrote_it(
            counts, keys, values, jnp.asarray(valid)))
        got = np.asarray(jax.jit(cms_add_conservative)(
            counts, keys, values, jnp.asarray(valid), jnp.int32(n_live)))
        assert got.tobytes() == want.tobytes()
        assert (got != np.asarray(counts)).any() == bool(n_live)
        # and without a bound it is the function it was
        assert np.asarray(cms_add_conservative(
            counts, keys, values, jnp.asarray(valid))).tobytes() \
            == want.tobytes()

    # what a batch's slots hold -> [n] bool, or None for `valid=None`
    MASKS = {
        "all_real": lambda rng, n: np.ones(n, bool),
        "all_padding": lambda rng, n: np.zeros(n, bool),
        "prefix": lambda rng, n: np.arange(n) < n // 3,
        "holed": lambda rng, n: TestLiveBound.holed(rng, n, n // 2),
        "padding_shares_a_bucket": lambda rng, n: np.arange(n) % 2 == 0,
        "valid_none": lambda rng, n: None,
    }

    @pytest.mark.parametrize("sketch", ["empty", "holds_mass"])
    @pytest.mark.parametrize("bound", ["bound", "no_bound"])
    @pytest.mark.parametrize("n", sorted(LIVE_NS))
    @pytest.mark.parametrize("mask", sorted(MASKS))
    def test_padding_leaves_the_scatter_and_no_bit_moves(
            self, rng, mask, n, bound, sketch):
        """PR 45: a slot that holds no group is dropped from every row's
        scatter-max, and the state is the one the plain scatter over
        every slot leaves, byte for byte."""
        self.no_bit_moves(rng, mask, n, bound, sketch, self.WIDTH)

    @pytest.mark.parametrize("mask", sorted(MASKS))
    def test_no_bit_moves_at_the_backbones_width(self, rng, mask):
        """The same at 2^18 cells a row (`hh-backbone`): few cells
        collide, so a slot dropped by mistake would leave its own cell
        low."""
        self.no_bit_moves(rng, mask, "N>C", "bound", "holds_mass", 1 << 18)

    def no_bit_moves(self, rng, mask, n, bound, sketch, width):
        n = LIVE_NS[n]
        valid = self.MASKS[mask](rng, n)
        keys = rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32)
        if mask == "padding_shares_a_bucket":
            # each padding slot holds its real neighbour's key: the two
            # share a cell in every row, and the padding one has addends
            keys[1::2] = keys[:-1:2]
        keys = jnp.asarray(keys)
        counts = cms_init(self.PLANES, self.DEPTH, width)
        if sketch == "holds_mass":
            # a window's worth: twenty batches over keys that collide
            # with this one's (the narrow width), so most cells are > 0
            for _ in range(20):
                counts = cms_add_conservative(
                    counts,
                    jnp.asarray(rng.integers(0, 2**32, size=(n, 2),
                                             dtype=np.uint32)),
                    jnp.asarray(rng.integers(1, 5000, (n, self.PLANES))),
                    jnp.ones(n, bool))
            if width == self.WIDTH:
                assert (np.asarray(counts) > 0).mean() > 0.9
        values = jnp.asarray(rng.integers(1, 5000, (n, self.PLANES)))
        if valid is None:
            args = (None, None)
        else:
            valid = jnp.asarray(valid)
            args = (valid, live_rows(valid) if bound == "bound" else None)
        want = np.asarray(_conservative_as_the_parent_wrote_it(
            counts, keys, values, args[0]))
        got = np.asarray(jax.jit(cms_add_conservative)(
            counts, keys, values, *args))
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
        real = n if valid is None else int(valid.sum())
        assert (got != np.asarray(counts)).any() == bool(real)
        # a real slot's cells hold at least what it brought
        if real:
            rows = slice(None) if valid is None else np.asarray(valid)
            est = np.asarray(cms_query(jnp.asarray(got), keys))[rows]
            assert (est >= np.asarray(values, np.float32)[rows]).all()

    @pytest.mark.parametrize("mask", ["all_padding", "prefix", "holed"])
    def test_a_dropped_slot_is_in_no_scatter(self, rng, mask):
        """What the equalities above cannot see (a padding slot is a
        no-op in either form): on a sketch below 0 everywhere, which no
        update makes, a padding slot beyond the bound has the ceiling 0
        and the plain scatter raises its cells to it. Here they stay."""
        n = LIVE_NS["N>C"]
        valid = self.MASKS[mask](rng, n)
        keys = jnp.asarray(
            rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32))
        values = jnp.asarray(rng.integers(1, 5000, (n, self.PLANES)))
        counts = jnp.full((self.PLANES, self.DEPTH, 1 << 16), -1.0)
        got = np.asarray(jax.jit(cms_add_conservative)(
            counts, keys, values, jnp.asarray(valid),
            live_rows(jnp.asarray(valid))))
        plain = np.asarray(_conservative_as_the_parent_wrote_it(
            counts, keys, values, jnp.asarray(valid)))
        buckets = np.asarray(cms_mod.cms_buckets(keys, self.DEPTH, 1 << 16))
        for d in range(self.DEPTH):
            padding_only = np.setdiff1d(buckets[d][~valid],
                                        buckets[d][valid])
            assert len(padding_only) > n // 4
            assert (got[:, d, padding_only] == -1.0).all()
            real = buckets[d][valid]
            assert got[:, d, real].tobytes() == plain[:, d, real].tobytes()

    @staticmethod
    def scatters(jaxpr, inside=()):
        """[(the control flow round it, its mode)] for every scatter-max
        of ``jaxpr``, sub-jaxprs (jit, while, cond) included."""
        out = []
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "scatter-max":
                out.append((inside, eqn.params["mode"]))
            for param in eqn.params.values():
                for sub in (param if isinstance(param, (list, tuple))
                            else [param]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        out += TestLiveBound.scatters(
                            sub, inside if name == "jit" else
                            inside + (name,))
        return out

    @pytest.mark.parametrize("masked", ["mask_and_bound", "mask", "plain"])
    @pytest.mark.parametrize("n", sorted(LIVE_NS))
    @pytest.mark.parametrize("depth", [1, 2, 4, 5])
    def test_the_update_is_depth_scatters_and_none_in_a_loop(
            self, depth, n, masked):
        """The form the records refused (a scatter-max inside a `while`
        costs ~105 ns an index on a v5e; a `lax.switch` ladder is gated:
        PERF.md §6, PRs 37 and 45) must not come back unseen: exactly
        `depth` scatter-max, each at the top level, each dropping what
        is out of range."""
        n = LIVE_NS[n]
        shape = jax.ShapeDtypeStruct
        args = [shape((self.PLANES, depth, self.WIDTH), np.float32),
                shape((n, 2), np.uint32), shape((n, self.PLANES), np.int32)]
        if masked != "plain":
            args.append(shape((n,), bool))
        if masked == "mask_and_bound":
            args.append(shape((), np.int32))
        found = self.scatters(
            jax.make_jaxpr(cms_add_conservative)(*args).jaxpr)
        assert len(found) == depth
        assert {inside for inside, _ in found} == {()}
        assert {mode for _, mode in found} \
            == {jax.lax.GatherScatterMode.FILL_OR_DROP}

    def test_a_scatter_in_a_loop_would_be_seen(self):
        """`scatters` names the control flow round a scatter-max."""
        def chunked(counts, idx, target):
            return jax.lax.fori_loop(
                0, 2, lambda i, c: c.at[idx].max(target), counts)

        shape = jax.ShapeDtypeStruct
        found = self.scatters(jax.make_jaxpr(chunked)(
            shape((8,), np.float32), shape((4,), np.int32),
            shape((4,), np.float32)).jaxpr)
        assert [inside for inside, _ in found] in (
            [("while",)], [("scan",)])

    def test_the_bounded_form_is_a_loop_only_past_one_chunk(self):
        def loops(n):
            shape = jax.ShapeDtypeStruct
            jaxpr = jax.make_jaxpr(cms_query)(
                shape((self.PLANES, self.DEPTH, self.WIDTH), np.float32),
                shape((n, 2), np.uint32), shape((), np.int32))
            return [e.primitive.name for e in jaxpr.eqns
                    if e.primitive.name in ("while", "scan", "cond")]

        assert loops(C) == [] and loops(C + 1) == ["while"]


class TestTopKTable:
    def test_exact_when_capacity_sufficient(self, rng):
        n_keys = 50
        keys = rng.integers(0, 2**31, size=(n_keys, 3), dtype=np.uint32)
        vals = rng.integers(1, 10_000, size=(n_keys, 1)).astype(np.float32)
        tk, tv = topk_init(64, 3, 1)
        # feed in 5 shuffled chunks of 10
        order = rng.permutation(n_keys)
        for c in range(5):
            idx = order[c * 10 : (c + 1) * 10]
            tk, tv = topk_merge(tk, tv, jnp.asarray(keys[idx]),
                                jnp.asarray(vals[idx]), jnp.ones(10, bool))
        out_k, out_v, valid = topk_extract(tk, tv, 64)
        out_k, out_v = np.asarray(out_k), np.asarray(out_v)
        assert np.asarray(valid).sum() == n_keys
        expect = vals[:, 0]
        top_true = keys[np.argsort(-expect)][:10]
        np.testing.assert_array_equal(out_k[:10], top_true)
        assert (np.diff(out_v[: n_keys, 0]) <= 0).all()

    def test_duplicate_keys_summed(self, rng):
        key = np.array([[7, 8]], dtype=np.uint32)
        tk, tv = topk_init(8, 2, 1)
        for v in (5.0, 10.0, 2.5):
            tk, tv = topk_merge(tk, tv, jnp.asarray(key),
                                jnp.asarray([[v]], np.float32), jnp.ones(1, bool))
        assert float(tv[0, 0]) == 17.5
        assert np.asarray(tk[0]).tolist() == [7, 8]

    def test_heavy_key_survives_eviction(self, rng):
        # one dominant key fed early, then floods of one-off keys
        tk, tv = topk_init(16, 1, 1)
        tk, tv = topk_merge(tk, tv, jnp.asarray([[42]], np.uint32),
                            jnp.asarray([[1e6]], np.float32), jnp.ones(1, bool))
        for c in range(8):
            noise_k = (rng.integers(100, 2**30, size=(32, 1))).astype(np.uint32)
            noise_v = rng.integers(1, 50, size=(32, 1)).astype(np.float32)
            tk, tv = topk_merge(tk, tv, jnp.asarray(noise_k),
                                jnp.asarray(noise_v), jnp.ones(32, bool))
        assert int(tk[0, 0]) == 42
        assert float(tv[0, 0]) == 1e6

    def test_empty_candidates_noop(self):
        tk, tv = topk_init(8, 2, 1)
        tk2, tv2 = topk_merge(tk, tv, jnp.zeros((4, 2), jnp.uint32),
                              jnp.ones((4, 1), jnp.float32), jnp.zeros(4, bool))
        np.testing.assert_array_equal(np.asarray(tk), np.asarray(tk2))

    def test_all_sentinel_key_excluded_not_slot_stealing(self):
        # the all-0xFFFFFFFF key tuple is the table's empty-slot marker and
        # therefore unrepresentable: a valid candidate carrying it must be
        # dropped at the merge boundary, never admitted where it would
        # occupy (or win) a capacity slot while being invisible to
        # topk_extract and zeroed on the next merge
        tk, tv = topk_init(2, 2, 1)
        cand_k = np.array(
            [[0xFFFFFFFF, 0xFFFFFFFF], [5, 6], [7, 8]], np.uint32
        )
        cand_v = np.array([[1e9], [10.0], [20.0]], np.float32)
        tk, tv = topk_merge(tk, tv, jnp.asarray(cand_k),
                            jnp.asarray(cand_v), jnp.ones(3, bool))
        out_k, out_v, valid = topk_extract(tk, tv, 2)
        assert np.asarray(valid).all()  # both capacity slots hold real keys
        assert np.asarray(out_k).tolist() == [[7, 8], [5, 6]]
        # a second merge keeps the real rows' mass intact
        tk, tv = topk_merge(tk, tv, jnp.asarray(cand_k),
                            jnp.asarray(cand_v), jnp.ones(3, bool))
        assert np.asarray(tv)[:, 0].tolist() == [40.0, 20.0]


class TestEWMA:
    def test_fold_matches_scalar_recurrence(self, rng):
        m = 8
        state = ewma_init(m)
        series = rng.integers(0, 100, size=(20, m)).astype(np.float32)
        for t in range(20):
            state = ewma_fold(state, jnp.asarray(series[t]), 0.3)
        # scalar reference for bucket 0
        mean = series[0, 0]
        var = 0.0
        for t in range(1, 20):
            d = series[t, 0] - mean
            mean = mean + 0.3 * d
            var = 0.7 * (var + 0.3 * d * d)
        assert abs(float(state[0][0]) - mean) < 1e-3
        assert abs(float(state[1][0]) - var) < 1e-2

    def test_zscore_flags_spike_only(self):
        m = 4
        state = ewma_init(m)
        for _ in range(30):
            state = ewma_fold(state, jnp.full(m, 100.0), 0.2)
        rates = jnp.asarray([100.0, 100.0, 3000.0, 100.0])
        z = np.asarray(zscores(state, rates, min_sigma=1.0))
        assert z[2] > 100
        assert abs(z[0]) < 1 and abs(z[3]) < 1

    def test_rate_accumulate_scatter(self, rng):
        keys = rng.integers(0, 2**32, size=(64, 4), dtype=np.uint32)
        b = np.asarray(bucket_of(jnp.asarray(keys), 128))
        vals = rng.integers(1, 10, 64).astype(np.int32)
        rates = rate_accumulate(jnp.zeros(128, jnp.float32), jnp.asarray(b),
                                jnp.asarray(vals), jnp.ones(64, bool))
        expect = np.zeros(128)
        np.add.at(expect, b, vals)
        np.testing.assert_allclose(np.asarray(rates), expect)


class TestQuantile:
    def test_quantiles_within_relative_error(self, rng):
        spec = QuantileSketchSpec(rel_err=0.01)
        data = rng.lognormal(8, 2, size=5000)
        hist = spec.init()
        hist = spec.add(hist, jnp.asarray(data))
        for q in (0.5, 0.9, 0.99):
            est = spec.quantile(np.asarray(hist), q)
            true = np.quantile(data, q)
            assert abs(est - true) / true < 0.05

    def test_merge_is_sum(self, rng):
        spec = QuantileSketchSpec()
        a = spec.add(spec.init(), jnp.asarray(rng.uniform(1, 1e6, 100)))
        b = spec.add(spec.init(), jnp.asarray(rng.uniform(1, 1e6, 100)))
        assert float(jnp.sum(a + b)) == 200.0

    def test_zeros_bucketed_separately(self):
        spec = QuantileSketchSpec()
        hist = spec.add(spec.init(), jnp.asarray([0.0, 0.0, 5.0]))
        assert float(hist[0]) == 2.0
        assert spec.quantile(np.asarray(hist), 0.5) == 0.0


class TestPerModelPath:
    """`hh_update` (the path a model takes outside the fused step) on
    the conservative update that drops its padding slots (PR 45), against
    the same update with every slot scattered."""

    N = 2 * C + 512

    def cols(self, rng, kind):
        """A batch's columns and row mask: ``zipf`` (few keys, so most
        group slots are padding), ``part_full`` (an eighth of the rows
        there) and ``all_distinct`` (no padding slot)."""
        n = self.N
        table = rng.integers(0, 2**32, size=(400, 2, 4), dtype=np.uint32)
        ranks = (np.arange(n) if kind == "all_distinct"
                 else rng.zipf(1.3, n) % 400)
        addrs = (rng.integers(0, 2**32, size=(n, 2, 4), dtype=np.uint32)
                 if kind == "all_distinct" else table[ranks])
        cols = {
            "src_addr": addrs[:, 0], "dst_addr": addrs[:, 1],
            "bytes": rng.integers(1, 1500, n).astype(np.int32),
            "packets": rng.integers(1, 10, n).astype(np.int32),
            "sampling_rate": np.ones(n, np.int32),
        }
        valid = (np.arange(n) < n // 8 if kind == "part_full"
                 else np.ones(n, bool))
        return ({k: jnp.asarray(v) for k, v in cols.items()},
                jnp.asarray(valid))

    @pytest.mark.parametrize("admission", ["est", "plain"])
    @pytest.mark.parametrize("capacity", [64, 1024])  # prefilter on / off
    @pytest.mark.parametrize("kind", ["zipf", "part_full", "all_distinct"])
    def test_every_state_array_is_the_parents(
            self, monkeypatch, rng, kind, capacity, admission):
        from flow_pipeline_tpu.models import heavy_hitter as hh

        config = hh.HeavyHitterConfig(
            batch_size=self.N, width=1 << 12, capacity=capacity,
            table_admission=admission)
        batches = [self.cols(rng, kind) for _ in range(3)]

        def run(parents_update):
            if parents_update:
                monkeypatch.setattr(cms_mod, "cms_add_conservative",
                                    _conservative_as_the_parent_wrote_it)
            # a fresh jit: hh_update's own would keep the first trace
            step = jax.jit(hh.hh_update.__wrapped__,
                           static_argnames=("config",))
            state, out = hh.hh_init(config), []
            try:
                for cols, valid in batches:
                    state = step(state, cols, valid, config=config)
                    out.append([np.asarray(x) for x in state])
            finally:
                monkeypatch.undo()
            return out

        got, want = run(False), run(True)
        assert got[-1][0].any()
        for i, (new, ref) in enumerate(zip(got, want)):
            for field, a, b in zip(hh.HHState._fields, new, ref):
                assert a.tobytes() == b.tobytes(), f"{field}, batch {i + 1}"
