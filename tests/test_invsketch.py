"""Invertible-sketch family parity suite (`make invertible-parity`).

The contract (docs/ARCHITECTURE.md "invertible sketch"): the three
twins — the pure-numpy reference (hostsketch/engine.py np_inv_*), the
jnp ops kernel (ops/invsketch.py, x64), and the native C kernels
(native/hostsketch.cc hs_inv_*, reached standalone and through
ff_fused_update) — are BIT-EXACT on every plane and decode the same
key set with the same exact values, at any thread count, u64 extremes
included. Downstream: extraction ranks exactly like the table family,
the worker pipelines (staged, fused, per-model fallback) emit
identical rows, checkpoints round-trip, and in the exact regime the
decoded ranking equals table mode bit-for-bit.
"""

from __future__ import annotations

import numpy as np
import pytest

from flow_pipeline_tpu import native
from flow_pipeline_tpu.hostsketch.engine import (
    HostSketchEngine,
    inv_decode_state,
    inv_extract,
    np_inv_decode,
    np_inv_key_hash,
    np_inv_update,
)
from flow_pipeline_tpu.hostsketch.state import (
    HostInvState,
    from_device_state,
    host_inv_init,
    is_inv_state,
)
from flow_pipeline_tpu.models.heavy_hitter import (
    HeavyHitterConfig,
    InvState,
    hh_init,
    inv_init,
)

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

PLANES, DEPTH, WIDTH, KW = 3, 4, 1 << 10, 5


def _state(planes=PLANES, depth=DEPTH, width=WIDTH, kw=KW):
    return HostInvState(
        cms=np.zeros((planes, depth, width), np.uint64),
        keysum=np.zeros((depth, width, kw), np.uint64),
        keycheck=np.zeros((depth, width), np.uint64),
    )


def _groups(n, kw=KW, planes=PLANES, seed=0, key_space=None):
    """(keys [n, kw] u32 unique-ish, vals [n, planes] f32 with the
    count plane last) — the group-table granularity every backend
    consumes."""
    rng = np.random.default_rng(seed)
    if key_space is None:
        keys = rng.integers(0, 2**32, size=(n, kw),
                            dtype=np.uint64).astype(np.uint32)
    else:
        keys = key_space[rng.integers(0, len(key_space), size=n)]
    vals = rng.integers(1, 1500, size=(n, planes)).astype(np.float32)
    vals[:, -1] = rng.integers(1, 64, size=n).astype(np.float32)
    return keys, vals


def _assert_states_equal(a, b):
    assert np.array_equal(a.cms, b.cms)
    assert np.array_equal(a.keysum, b.keysum)
    assert np.array_equal(a.keycheck, b.keycheck)


# ---------------------------------------------------------------------------
# twin parity: numpy vs native vs jnp
# ---------------------------------------------------------------------------


class TestTwinParity:
    def test_native_update_matches_numpy(self):
        if not native.inv_available():
            pytest.skip("native invertible kernels not built")
        keys, vals = _groups(700)
        ref, nat = _state(), _state()
        np_inv_update(ref, keys, vals)
        native.hs_inv_update(nat.cms, nat.keysum, nat.keycheck, keys,
                             vals, None, threads=1)
        _assert_states_equal(ref, nat)

    @pytest.mark.parametrize("threads", [1, 2, 8])
    def test_native_update_thread_count_deterministic(self, threads):
        if not native.inv_available():
            pytest.skip("native invertible kernels not built")
        keys, vals = _groups(5000, seed=3)
        ref, nat = _state(), _state()
        np_inv_update(ref, keys, vals)
        native.hs_inv_update(nat.cms, nat.keysum, nat.keycheck, keys,
                             vals, None, threads=threads)
        _assert_states_equal(ref, nat)

    def test_native_decode_matches_numpy(self):
        if not native.inv_available():
            pytest.skip("native invertible kernels not built")
        keys, vals = _groups(400, seed=5)
        st = _state()
        np_inv_update(st, keys, vals)
        k1, v1 = np_inv_decode(st.cms, st.keysum, st.keycheck)
        k2, v2 = inv_decode_state(st)  # native path + canonical sort
        assert np.array_equal(k1, k2)
        assert np.array_equal(v1, v2)

    def test_jnp_twins_match_numpy(self):
        from jax import enable_x64

        with enable_x64():
            import jax.numpy as jnp

            from flow_pipeline_tpu.ops import invsketch as inv

            keys, vals = _groups(300, seed=7)
            cms, ks, kc = inv.inv_init(PLANES, DEPTH, WIDTH, KW)
            cms, ks, kc = inv.inv_update(cms, ks, kc, jnp.asarray(keys),
                                         jnp.asarray(vals))
            ref = _state()
            np_inv_update(ref, keys, vals)
            assert np.array_equal(np.asarray(cms), ref.cms)
            assert np.array_equal(np.asarray(ks), ref.keysum)
            assert np.array_equal(np.asarray(kc), ref.keycheck)
            k1, v1 = np_inv_decode(ref.cms, ref.keysum, ref.keycheck)
            k2, v2 = inv.inv_decode(cms, ks, kc)
            assert np.array_equal(k1, k2)
            assert np.array_equal(v1, v2)

    def test_jnp_valid_mask_matches_sliced(self):
        from jax import enable_x64

        with enable_x64():
            import jax.numpy as jnp

            from flow_pipeline_tpu.ops import invsketch as inv

            keys, vals = _groups(200, seed=11)
            valid = np.zeros(200, bool)
            valid[:137] = True
            cms, ks, kc = inv.inv_init(PLANES, DEPTH, WIDTH, KW)
            cms, ks, kc = inv.inv_update(cms, ks, kc, jnp.asarray(keys),
                                         jnp.asarray(vals),
                                         jnp.asarray(valid))
            ref = _state()
            np_inv_update(ref, keys[:137], vals[:137])
            assert np.array_equal(np.asarray(cms), ref.cms)
            assert np.array_equal(np.asarray(ks), ref.keysum)
            assert np.array_equal(np.asarray(kc), ref.keycheck)

    def test_jnp_merge_is_element_sum(self):
        from jax import enable_x64

        with enable_x64():
            import jax.numpy as jnp

            from flow_pipeline_tpu.ops import invsketch as inv

            ka, va = _groups(100, seed=1)
            kb, vb = _groups(100, seed=2)
            a = inv.inv_update(*inv.inv_init(PLANES, DEPTH, WIDTH, KW),
                               jnp.asarray(ka), jnp.asarray(va))
            b = inv.inv_update(*inv.inv_init(PLANES, DEPTH, WIDTH, KW),
                               jnp.asarray(kb), jnp.asarray(vb))
            merged = inv.inv_merge(a, b)
            both = inv.inv_update(*inv.inv_update(
                *inv.inv_init(PLANES, DEPTH, WIDTH, KW),
                jnp.asarray(ka), jnp.asarray(va)),
                jnp.asarray(kb), jnp.asarray(vb))
            for m, t in zip(merged, both):
                assert np.array_equal(np.asarray(m), np.asarray(t))

    def test_u64_extremes_clamp_and_wrap_identically(self):
        """Addends at/past the f32->u64 envelope edge (negatives, NaN,
        inf, ~2^64) must clamp identically everywhere, and repeated
        near-cap adds must WRAP identically (mod-2^64 linearity)."""
        keys = np.arange(6 * KW, dtype=np.uint32).reshape(6, KW)
        vals = np.array([
            [1.0, 2.0, 1.0],
            [-5.0, float("nan"), 1.0],
            [float("inf"), 2.0**63, 2.0**40],
            [2.0**64, 1.8446742e19, 1.0],
            [0.0, 1.0, 2.0**52],
            [3.0, 4.0, 2.0**31],
        ], np.float32)
        ref = _state()
        for _ in range(3):  # force u64 wrap in keysum/keycheck
            np_inv_update(ref, keys, vals)
        if native.inv_available():
            nat = _state()
            for _ in range(3):
                native.hs_inv_update(nat.cms, nat.keysum, nat.keycheck,
                                     keys, vals, None, threads=2)
            _assert_states_equal(ref, nat)
        from jax import enable_x64

        with enable_x64():
            import jax.numpy as jnp

            from flow_pipeline_tpu.ops import invsketch as inv

            state = inv.inv_init(PLANES, DEPTH, WIDTH, KW)
            for _ in range(3):
                state = inv.inv_update(*state, jnp.asarray(keys),
                                       jnp.asarray(vals))
            assert np.array_equal(np.asarray(state[0]), ref.cms)
            assert np.array_equal(np.asarray(state[1]), ref.keysum)
            assert np.array_equal(np.asarray(state[2]), ref.keycheck)

    def test_update_linearity_chunk_granularity_irrelevant(self):
        """The whole design premise: folding one big group table equals
        folding its pieces in any order — bit-exactly."""
        keys, vals = _groups(900, seed=13)
        whole = _state()
        np_inv_update(whole, keys, vals)
        parts = _state()
        for lo, hi in ((600, 900), (0, 300), (300, 600)):
            np_inv_update(parts, keys[lo:hi], vals[lo:hi])
        _assert_states_equal(whole, parts)

    def test_degenerate_shapes_rejected(self):
        if not native.inv_available():
            pytest.skip("native invertible kernels not built")
        keys, vals = _groups(4)
        st = _state()
        with pytest.raises(ValueError):
            native.hs_inv_update(
                np.zeros((0, DEPTH, WIDTH), np.uint64), st.keysum,
                st.keycheck, keys, vals, None)

    def test_n_zero_is_noop(self):
        st = _state()
        np_inv_update(st, np.zeros((0, KW), np.uint32),
                      np.zeros((0, PLANES), np.float32))
        assert not st.cms.any() and not st.keysum.any()
        if native.inv_available():
            native.hs_inv_update(st.cms, st.keysum, st.keycheck,
                                 np.zeros((0, KW), np.uint32),
                                 np.zeros((0, PLANES), np.float32), None)
            assert not st.cms.any()

    if HAVE_HYPOTHESIS:
        @given(st.integers(0, 2**32 - 1), st.integers(1, 400),
               st.integers(0, 2**20))
        @settings(max_examples=25, deadline=None)
        def test_property_random_streams_bit_exact(self, seed, n, vmax):
            rng = np.random.default_rng(seed)
            keys = rng.integers(0, 2**32, size=(n, 3),
                                dtype=np.uint64).astype(np.uint32)
            vals = rng.integers(0, max(vmax, 1),
                                size=(n, 2)).astype(np.float32)
            # the count plane (last) is a group's row count, >= 1 for
            # every row a group table can hold: a zero-count row adds
            # value mass the key-recovery planes never see, so a bucket
            # it shares with one real key still looks pure and the
            # decoded value depends on which depth row peels first
            vals[:, -1] = np.maximum(vals[:, -1], 1.0)
            ref = HostInvState(
                cms=np.zeros((2, 2, 128), np.uint64),
                keysum=np.zeros((2, 128, 3), np.uint64),
                keycheck=np.zeros((2, 128), np.uint64))
            np_inv_update(ref, keys, vals)
            if native.inv_available():
                nat = HostInvState(
                    cms=np.zeros((2, 2, 128), np.uint64),
                    keysum=np.zeros((2, 128, 3), np.uint64),
                    keycheck=np.zeros((2, 128), np.uint64))
                native.hs_inv_update(nat.cms, nat.keysum, nat.keycheck,
                                     keys, vals, None, threads=3)
                _assert_states_equal(ref, nat)
                k1, v1 = np_inv_decode(ref.cms, ref.keysum, ref.keycheck)
                k2, v2 = inv_decode_state(nat)
                assert np.array_equal(k1, k2)
                assert np.array_equal(v1, v2)


# ---------------------------------------------------------------------------
# decode semantics
# ---------------------------------------------------------------------------


class TestDecode:
    def test_full_recovery_with_exact_values_in_sparse_regime(self):
        """Keys << buckets: peeling recovers EVERY key with its exact
        u64 per-plane sums (the decode-at-close exactness claim)."""
        rng = np.random.default_rng(21)
        uniq = rng.integers(0, 2**32, size=(250, KW),
                            dtype=np.uint64).astype(np.uint32)
        rows = uniq[rng.integers(0, 250, size=2000)]
        vals = rng.integers(1, 1000, size=(2000, PLANES)).astype(
            np.float32)
        st = _state()
        np_inv_update(st, rows, vals)
        keys, dec = np_inv_decode(st.cms, st.keysum, st.keycheck)
        # exact oracle
        kv = rows.view([("", np.uint32)] * KW).reshape(-1)
        uk, inv_idx = np.unique(kv, return_inverse=True)
        exact = np.zeros((len(uk), PLANES), np.uint64)
        np.add.at(exact, inv_idx, vals.astype(np.uint64))
        assert len(keys) == len(uk)
        got = {keys[i].tobytes(): dec[i] for i in range(len(keys))}
        for i in range(len(uk)):
            assert np.array_equal(got[uk[i].tobytes()], exact[i])

    def test_decode_is_lex_sorted_canonical(self):
        keys, vals = _groups(120, seed=31)
        st = _state()
        np_inv_update(st, keys, vals)
        k, _ = np_inv_decode(st.cms, st.keysum, st.keycheck)
        order = np.lexsort(k.T[::-1])
        assert np.array_equal(order, np.arange(len(k)))

    def test_empty_sketch_decodes_empty(self):
        st = _state()
        k, v = np_inv_decode(st.cms, st.keysum, st.keycheck)
        assert k.shape == (0, KW) and v.shape == (0, PLANES)
        tk, tv = inv_extract(st, 16)
        assert (tk == np.uint32(0xFFFFFFFF)).all() and not tv.any()

    def test_extract_ranks_primary_desc_lex_ties(self):
        """inv_extract reproduces the table family's (primary desc, lex
        key asc) ranking rule, truncated to capacity."""
        st = HostInvState(
            cms=np.zeros((2, DEPTH, WIDTH), np.uint64),
            keysum=np.zeros((DEPTH, WIDTH, 2), np.uint64),
            keycheck=np.zeros((DEPTH, WIDTH), np.uint64))
        keys = np.array([[5, 1], [2, 9], [2, 3], [7, 7]], np.uint32)
        vals = np.array([[30, 1], [10, 1], [10, 1], [40, 1]], np.float32)
        np_inv_update(st, keys, vals)
        tk, tv = inv_extract(st, 3)
        assert np.array_equal(tk, np.array(
            [[7, 7], [5, 1], [2, 3]], np.uint32))
        assert np.array_equal(tv[:, 0],
                              np.array([40, 30, 10], np.float32))

    def test_all_sentinel_key_dropped_at_extract(self):
        st = _state()
        keys = np.vstack([np.full((1, KW), 0xFFFFFFFF, np.uint32),
                          np.arange(KW, dtype=np.uint32)[None, :]])
        vals = np.full((2, PLANES), 9.0, np.float32)
        np_inv_update(st, keys, vals)
        tk, _ = inv_extract(st, 8)
        real = (tk != np.uint32(0xFFFFFFFF)).any(axis=1)
        assert int(real.sum()) == 1

    def test_inv_key_hash_protocol_pinned(self):
        """The checksum hash is a cross-twin protocol constant: pin a
        few words so an accidental reimplementation cannot drift."""
        h = np_inv_key_hash(np.array([[0, 0], [1, 2], [0xFFFFFFFF, 0]],
                                     np.uint32))
        assert h.dtype == np.uint64
        assert len(set(h.tolist())) == 3
        # self-consistency vs native
        if native.inv_available():
            st = HostInvState(
                cms=np.zeros((1, 1, 8), np.uint64),
                keysum=np.zeros((1, 8, 2), np.uint64),
                keycheck=np.zeros((1, 8), np.uint64))
            k = np.array([[1, 2]], np.uint32)
            v = np.array([[1.0]], np.float32)
            native.hs_inv_update(st.cms, st.keysum, st.keycheck, k, v,
                                 None)
            assert st.keycheck.sum() == np_inv_key_hash(k)[0]


# ---------------------------------------------------------------------------
# engine / model / state plumbing
# ---------------------------------------------------------------------------


INV_CFG = HeavyHitterConfig(
    key_cols=("src_addr", "dst_addr"), width=1 << 12, capacity=256,
    batch_size=4096, hh_sketch="invertible")


class TestEngineAndModel:
    def test_engine_update_native_equals_numpy(self):
        keys, vals = _groups(800, kw=8, seed=41)
        engines = [HostSketchEngine([INV_CFG], use_native="numpy")]
        if native.inv_available():
            engines.append(HostSketchEngine([INV_CFG],
                                            use_native="native"))
        states = []
        for eng in engines:
            eng.reset(0)
            eng.update(0, keys, vals, len(keys))
            states.append(eng.states[0])
        for st in states[1:]:
            _assert_states_equal(states[0], st)

    def test_engine_export_import_round_trip(self):
        eng = HostSketchEngine([INV_CFG], use_native="auto")
        keys, vals = _groups(100, kw=8, seed=43)
        eng.update(0, keys, vals, len(keys))
        exported = eng.export_state(0)
        assert isinstance(exported, InvState)
        assert is_inv_state(exported)
        back = from_device_state(exported)
        _assert_states_equal(eng.states[0], back)
        # fresh leaves: mutating the engine must not touch the export
        eng.update(0, keys, vals, len(keys))
        assert not np.array_equal(exported.cms, eng.states[0].cms)

    def test_hh_init_dispatches_on_family(self):
        assert isinstance(hh_init(INV_CFG), InvState)
        assert hh_init(INV_CFG).cms.dtype == np.uint64
        with pytest.raises(ValueError):
            hh_init(HeavyHitterConfig(hh_sketch="wat"))

    def test_model_update_top_exact_regime(self):
        """Per-model fallback path: exact sums, exact ranking."""
        from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
        from flow_pipeline_tpu.models.heavy_hitter import (
            HeavyHitterModel)

        model = HeavyHitterModel(INV_CFG)
        batch = FlowGenerator(ZipfProfile(n_keys=60), seed=3).batch(4000)
        model.update(batch)
        top = model.top(50)
        assert top["valid"].sum() == 50
        primary = top["bytes"][top["valid"]].astype(np.float64)
        assert (np.diff(primary) <= 0).all()  # ranked descending
        # decode values are exact, so est (CMS upper bound) dominates
        assert (top["bytes_est"][top["valid"]]
                >= top["bytes"][top["valid"]]).all()
        lazy = model.top_lazy(50)
        model.update(batch)  # mutates in place — the capture must not move
        top2 = lazy()
        for col in top:
            assert np.array_equal(top[col], top2[col])

    def test_exact_regime_matches_table_mode_bit_for_bit(self):
        """Capacity >= keys, plain update, integer envelope: the
        invertible ranking must equal the table family's rows exactly
        (values AND est columns — same cms planes, same ranking)."""
        from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
        from flow_pipeline_tpu.models.heavy_hitter import (
            HeavyHitterModel)

        common = dict(key_cols=("src_addr", "dst_addr"), width=1 << 12,
                      capacity=512, batch_size=4096,
                      conservative=False)
        batch = FlowGenerator(ZipfProfile(n_keys=300), seed=9).batch(8000)
        m_inv = HeavyHitterModel(HeavyHitterConfig(
            hh_sketch="invertible", **common))
        m_tab = HeavyHitterModel(HeavyHitterConfig(**common))
        m_inv.update(batch)
        m_tab.update(batch)
        t_inv, t_tab = m_inv.top(100), m_tab.top(100)
        assert set(t_inv) == set(t_tab)
        for col in t_tab:
            assert np.array_equal(np.asarray(t_inv[col]),
                                  np.asarray(t_tab[col])), col

    def test_checkpoint_round_trip_and_mismatch_skip(self, tmp_path):
        from flow_pipeline_tpu.engine import StreamWorker, WorkerConfig
        from flow_pipeline_tpu.engine.windowed import WindowedHeavyHitter
        from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile

        path = str(tmp_path / "ckpt")

        def make_worker(cfg):
            return StreamWorker(None, {
                "talkers": WindowedHeavyHitter(cfg, k=16)},
                config=WorkerConfig(checkpoint_path=path, prefetch=0,
                                    sketch_backend="host",
                                    host_assist="on", obs_audit="off"))

        w = make_worker(INV_CFG)
        batch = FlowGenerator(ZipfProfile(n_keys=40), seed=5).batch(2000)
        with w.lock:
            w.models["talkers"].update(batch)
            w.snapshot_and_commit()
        w2 = make_worker(INV_CFG)
        assert w2.restore()
        st1 = w.models["talkers"].model.state
        st2 = w2.models["talkers"].model.state
        assert isinstance(st2, InvState) and st2.cms.dtype == np.uint64
        _assert_states_equal(st1, st2)
        # restoring the invertible checkpoint into a TABLE-config model
        # must skip loudly, not corrupt
        w3 = make_worker(HeavyHitterConfig(
            key_cols=("src_addr", "dst_addr"), width=1 << 12,
            capacity=256, batch_size=4096))
        assert w3.restore()
        st3 = w3.models["talkers"].model.state
        assert not is_inv_state(st3)
        assert not np.asarray(st3.cms).any()  # fresh, not restored


# ---------------------------------------------------------------------------
# pipeline parity: staged vs fused vs per-model fallback
# ---------------------------------------------------------------------------


def _run_worker(hh_sketch, fused, sketch_backend="host", n_flows=30_000,
                audit="off", extra_flags=()):
    from flow_pipeline_tpu.cli import (_batch_frames, _build_models,
                                       _common_flags, _gen_flags,
                                       _make_generator, _processor_flags)
    from flow_pipeline_tpu.engine import StreamWorker, WorkerConfig
    from flow_pipeline_tpu.transport import Consumer, InProcessBus
    from flow_pipeline_tpu.utils.flags import FlagSet

    fs = _processor_flags(_gen_flags(_common_flags(FlagSet("t"))))
    vals = fs.parse(["-produce.profile", "zipf", "-hh.sketch", hh_sketch,
                     "-zipf.keys", "400", "-model.ports=false",
                     "-model.ddos=false", "-sketch.capacity", "512",
                     *extra_flags])
    bus = InProcessBus()
    bus.create_topic("flows", 2)
    gen = _make_generator(vals)
    produced = 0
    while produced < n_flows:
        bus.produce_many("flows", _batch_frames(gen.batch(8192)))
        produced += 8192

    class Sink:
        def __init__(self):
            self.tables = {}

        def write(self, table, rows):
            self.tables.setdefault(table, []).append(rows)

    sink = Sink()
    worker = StreamWorker(
        Consumer(bus, fixedlen=True), _build_models(vals), [sink],
        WorkerConfig(poll_max=8192, snapshot_every=0,
                     sketch_backend=sketch_backend,
                     ingest_native_group=True, ingest_fused=fused,
                     obs_audit=audit))
    worker.run(stop_when_idle=True)
    return sink.tables


def _assert_tables_equal(t1, t2):
    assert set(t1) == set(t2)
    for tab in t1:
        assert len(t1[tab]) == len(t2[tab])
        for r1, r2 in zip(t1[tab], t2[tab]):
            assert set(r1) == set(r2)
            for col in r1:
                assert np.array_equal(np.asarray(r1[col]),
                                      np.asarray(r2[col])), (tab, col)


class TestPipelineParity:
    def test_fused_equals_staged_invertible(self):
        if not (native.fused_available() and native.inv_available()):
            pytest.skip("fused native dataplane not built")
        staged = _run_worker("invertible", "off")
        fused = _run_worker("invertible", "on")
        _assert_tables_equal(staged, fused)

    def test_fallback_equals_host_pipeline_invertible(self):
        """sketch_backend=device routes invertible families to the
        per-model numpy path — same rows as the host engine."""
        host = _run_worker("invertible", "off")
        fallback = _run_worker("invertible", "off",
                               sketch_backend="device")
        _assert_tables_equal(host, fallback)

    def test_audit_is_observational_in_invertible_mode(self):
        if not (native.fused_available() and native.inv_available()):
            pytest.skip("fused native dataplane not built")
        off = _run_worker("invertible", "on", audit="off")
        on = _run_worker("invertible", "on", audit="sample")
        _assert_tables_equal(off, on)

    def test_fused_plan_marks_invertible_families(self):
        from flow_pipeline_tpu.cli import (_build_models, _common_flags,
                                           _gen_flags, _processor_flags)
        from flow_pipeline_tpu.engine import StreamWorker, WorkerConfig
        from flow_pipeline_tpu.utils.flags import FlagSet

        if not (native.fused_available() and native.inv_available()):
            pytest.skip("fused native dataplane not built")
        fs = _processor_flags(_gen_flags(_common_flags(FlagSet("t"))))
        vals = fs.parse(["-hh.sketch", "invertible",
                         "-model.ports=false", "-model.ddos=false"])
        w = StreamWorker(None, _build_models(vals), [],
                         WorkerConfig(sketch_backend="host",
                                      host_assist="on", prefetch=0,
                                      ingest_fused="on",
                                      obs_audit="off"))
        for _, plan in w.fused._fused_trees:
            assert plan.invertible is not None and plan.invertible.all()

    def test_flag_registered_and_validated(self):
        from flow_pipeline_tpu.utils.flags import KNOWN_FLAGS

        assert "hh.sketch" in KNOWN_FLAGS
        with pytest.raises(ValueError):
            HostSketchEngine([HeavyHitterConfig(hh_sketch="bogus")])

    def test_build_info_carries_hh_sketch_label(self):
        from flow_pipeline_tpu.cli import (_build_models, _common_flags,
                                           _gen_flags, _processor_flags)
        from flow_pipeline_tpu.engine import StreamWorker, WorkerConfig
        from flow_pipeline_tpu.obs import REGISTRY
        from flow_pipeline_tpu.utils.flags import FlagSet

        fs = _processor_flags(_gen_flags(_common_flags(FlagSet("t"))))
        vals = fs.parse(["-hh.sketch", "invertible",
                         "-model.ports=false", "-model.ddos=false"])
        StreamWorker(None, _build_models(vals), [],
                     WorkerConfig(sketch_backend="host",
                                  host_assist="on", prefetch=0,
                                  obs_audit="off"))
        g = REGISTRY.gauge("flow_build_info",
                           "build/runtime identity (constant 1; labels "
                           "pin the native capability set, trace mode, "
                           "sketch backend, and mesh role)")
        assert 'hh_sketch="invertible"' in g.render()


# ---------------------------------------------------------------------------
# merge / codec citizenship (unit level; mesh e2e lives in test_mesh.py)
# ---------------------------------------------------------------------------


class TestMergeCodec:
    def test_payload_round_trip_and_plain_sum_merge(self):
        from flow_pipeline_tpu.mesh import codec
        from flow_pipeline_tpu.mesh.merge import merge_hh

        cfg = HeavyHitterConfig(
            key_cols=("src_addr", "dst_addr"), width=1 << 10,
            capacity=64, hh_sketch="invertible")
        shards = []
        whole = host_inv_init(cfg)
        for seed in (1, 2, 3):
            st = host_inv_init(cfg)
            keys, vals = _groups(150, kw=8, seed=seed)
            np_inv_update(st, keys, vals)
            np_inv_update(whole, keys, vals)
            payload = codec.decode(codec.encode(codec.hh_payload(st)))
            assert payload["kind"] == "hh_inv"
            assert payload["cms"].dtype == np.uint64
            shards.append(payload)
        merged = merge_hh(shards, cfg)
        # merge == element-wise u64 sum == the union-stream state
        assert np.array_equal(merged["cms"], whole.cms)
        assert np.array_equal(merged["keysum"], whole.keysum)
        assert np.array_equal(merged["keycheck"], whole.keycheck)
        # and the merged table view is the union decode
        tk, tv = inv_extract(whole, cfg.capacity)
        assert np.array_equal(merged["table_keys"], tk)
        assert np.array_equal(merged["table_vals"], tv)

    def test_mixed_family_payloads_rejected(self):
        from flow_pipeline_tpu.mesh import codec
        from flow_pipeline_tpu.mesh.merge import merge_hh

        cfg = HeavyHitterConfig(key_cols=("src_addr", "dst_addr"),
                                width=1 << 10, capacity=64)
        inv_p = codec.hh_payload(host_inv_init(
            HeavyHitterConfig(key_cols=("src_addr", "dst_addr"),
                              width=1 << 10, capacity=64,
                              hh_sketch="invertible")))
        tab_p = codec.hh_payload(hh_init(cfg))
        with pytest.raises(ValueError):
            merge_hh([inv_p, tab_p], cfg)

    def test_capture_model_ships_inv_payload(self):
        from flow_pipeline_tpu.mesh import codec
        from flow_pipeline_tpu.models.heavy_hitter import (
            HeavyHitterModel)

        model = HeavyHitterModel(INV_CFG)
        payload = codec.capture_model(model)
        assert payload["kind"] == "hh_inv"
        assert set(payload) >= {"cms", "keysum", "keycheck"}

    def test_frozen_cms_preserves_u64_planes(self):
        from flow_pipeline_tpu.hostsketch.state import frozen_cms

        st = host_inv_init(INV_CFG)
        st.cms[0, 0, 0] = np.uint64(2**53 + 1)  # f32-lossy value
        out = frozen_cms(st)
        assert out.dtype == np.uint64
        assert out[0, 0, 0] == np.uint64(2**53 + 1)
        out[0, 0, 0] = 0  # fresh copy, never aliases engine state
        assert st.cms[0, 0, 0] == np.uint64(2**53 + 1)


# ---------------------------------------------------------------------------
# -hh.sketch=auto: the r19 cascade flip (cli._build_models)
# ---------------------------------------------------------------------------


class TestAutoSketchResolution:
    """`auto` (the r19 default) flips CASCADE families — key sets that
    are strict subsets of another enabled hh family's — to the
    invertible sketch when the host sketch dataplane serves; root
    families and every non-host deployment keep the table family, so a
    default worker never lands on the per-model numpy fallback."""

    def _models(self, *flags):
        from flow_pipeline_tpu.cli import (_build_models, _common_flags,
                                           _gen_flags, _processor_flags)
        from flow_pipeline_tpu.utils.flags import FlagSet

        fs = _processor_flags(_gen_flags(_common_flags(FlagSet("t"))))
        return _build_models(fs.parse(list(flags)))

    def _sketch(self, models):
        return {name: m.model.config.hh_sketch
                for name, m in models.items()
                if getattr(getattr(m, "model", None), "snapshot_kind",
                           None) == "windowed_hh"}

    def test_auto_flips_cascade_families_on_host_backend(self):
        got = self._sketch(self._models("-sketch.backend", "host"))
        assert got == {"top_talkers": "table",
                       "top_src_ips": "invertible",
                       "top_dst_ips": "invertible"}

    def test_auto_keeps_table_off_host_backend(self):
        # device backend: the invertible family would fall back to the
        # per-model numpy path — auto must never choose that
        got = self._sketch(self._models())
        assert set(got.values()) == {"table"}

    def test_auto_keeps_table_without_cascade_parent(self):
        # no talkers family -> the IP families are roots, not cascades
        got = self._sketch(self._models("-sketch.backend", "host",
                                        "-model.talkers=false"))
        assert got == {"top_src_ips": "table", "top_dst_ips": "table"}

    def test_explicit_override_beats_auto(self):
        got = self._sketch(self._models("-sketch.backend", "host",
                                        "-hh.sketch", "invertible"))
        assert set(got.values()) == {"invertible"}
        got = self._sketch(self._models("-sketch.backend", "host",
                                        "-hh.sketch", "table"))
        assert set(got.values()) == {"table"}

    @pytest.mark.slow  # two full workers; gated by `make invertible-parity`
    def test_auto_exact_regime_equals_table_bit_for_bit(self):
        """The flip's exactness pin: capacity (512) >= distinct keys
        (400-key zipf), so BOTH families are in their exact regime and
        the auto worker's sink rows — cascade families invertible,
        root table — must be bit-identical to the all-table worker's."""
        if not (native.fused_available() and native.inv_available()):
            pytest.skip("fused native dataplane not built")
        auto = _run_worker("auto", "on",
                           extra_flags=("-sketch.backend", "host"))
        table = _run_worker("table", "on",
                            extra_flags=("-sketch.backend", "host"))
        _assert_tables_equal(auto, table)
