"""Pallas CMS kernel correctness in interpret mode (CPU).

The strongest property: both kernels are exact drop-ins for their XLA
twins on the SAME sketch state — identical bucket scheme (ops.cms), so
linear/conservative updates must match cms_add / cms_add_conservative
cell-for-cell, and ops.cms.cms_query serves either path. Interpret mode
is asked for HERE (interpret=True, or force_tpu_interpret_mode around a
model step); no production selector turns it on. chip_smoke.py's
cms_kernels stage compiles the same kernels on the chip at the
processor's default shapes and checks them bit for bit.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from flow_pipeline_tpu.ops.cms import (
    cms_add,
    cms_add_conservative,
    cms_init,
    cms_query,
)
from flow_pipeline_tpu.ops.cms_pallas import (
    cms_add_conservative_pallas,
    cms_add_pallas,
)


def make_inputs(rng, n, planes, key_lanes=2):
    # random 64-bit-lane keys are unique w.h.p. — the conservative
    # kernels' contract (callers sort_groupby first)
    keys = rng.integers(0, 2**32, size=(n, key_lanes), dtype=np.uint32)
    values = rng.integers(1, 100, size=(n, planes)).astype(np.float32)
    valid = rng.random(n) > 0.2
    return (jnp.asarray(keys.astype(np.int64)), jnp.asarray(values),
            jnp.asarray(valid))


class TestLinearKernel:
    @pytest.mark.parametrize("n,planes,depth,width,tile",
                             [(64, 1, 2, 256, 128), (128, 3, 4, 512, 128)])
    def test_matches_xla_cms_add(self, rng, n, planes, depth, width, tile):
        keys, values, valid = make_inputs(rng, n, planes)
        counts = cms_init(planes, depth, width)
        got = cms_add_pallas(counts, keys, values, valid, tile=tile,
                             interpret=True)
        want = cms_add(counts, keys, values, valid)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)

    def test_accumulates_and_queries_via_shared_scheme(self, rng):
        keys, values, valid = make_inputs(rng, 32, 1)
        values = jnp.ones_like(values)
        counts = cms_init(1, 2, 256)
        counts = cms_add_pallas(counts, keys, values, valid, tile=128,
                                interpret=True)
        counts = cms_add_pallas(counts, keys, values, valid, tile=128,
                                interpret=True)
        est = np.asarray(cms_query(counts, keys))  # the ops.cms query
        assert (est[np.asarray(valid), 0] >= 2).all()

    def test_mixed_xla_pallas_calls_share_state(self, rng):
        # a sketch updated by the XLA path then the Pallas path must equal
        # one updated twice by either — the drop-in claim, end to end
        keys, values, valid = make_inputs(rng, 64, 2)
        counts = cms_init(2, 3, 384)
        mixed = cms_add(counts, keys, values, valid)
        mixed = cms_add_pallas(mixed, keys, values, valid, tile=128,
                               interpret=True)
        pure = cms_add(cms_add(counts, keys, values, valid),
                       keys, values, valid)
        np.testing.assert_allclose(np.asarray(mixed), np.asarray(pure),
                                   rtol=1e-6)

    def test_width_not_multiple_of_tile_rejected(self):
        with pytest.raises(ValueError, match="multiple of tile"):
            cms_add_pallas(cms_init(1, 2, 200), jnp.zeros((8, 1), jnp.int32),
                           jnp.ones((8, 1)), tile=128, interpret=True)


class TestConservativeKernel:
    @pytest.mark.parametrize("n,planes,depth,width,tile,chunk",
                             [(64, 1, 2, 256, 128, 128),
                              (300, 3, 4, 512, 256, 128)])
    def test_matches_xla_conservative(self, rng, n, planes, depth, width,
                                      tile, chunk):
        keys, values, valid = make_inputs(rng, n, planes)
        counts = cms_init(planes, depth, width)
        # several rounds so estimates feed back into ceilings
        got = counts
        want = counts
        for _ in range(3):
            got = cms_add_conservative_pallas(got, keys, values, valid,
                                              tile=tile, chunk=chunk,
                                              interpret=True)
            want = cms_add_conservative(want, keys, values, valid)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)

    def test_tighter_than_linear(self, rng):
        # the whole point of CU: estimates at most the linear path's
        keys, values, valid = make_inputs(rng, 256, 1)
        lin = cms_init(1, 2, 128)  # narrow -> many collisions
        cu = cms_init(1, 2, 128)
        for _ in range(2):
            lin = cms_add_pallas(lin, keys, values, valid, tile=128,
                                 interpret=True)
            cu = cms_add_conservative_pallas(cu, keys, values, valid,
                                             tile=128, chunk=128,
                                             interpret=True)
        e_lin = np.asarray(cms_query(lin, keys))
        e_cu = np.asarray(cms_query(cu, keys))
        v = np.asarray(valid)
        assert (e_cu[v] <= e_lin[v] + 1e-3).all()
        assert e_cu[v].sum() < e_lin[v].sum()  # strictly tighter somewhere

    def test_invalid_rows_raise_nothing(self, rng):
        keys, values, _ = make_inputs(rng, 64, 1)
        counts = cms_add_conservative_pallas(
            cms_init(1, 2, 256), keys, values, jnp.zeros(64, bool),
            tile=128, chunk=128, interpret=True,
        )
        assert float(jnp.sum(counts)) == 0.0

    def test_still_an_upper_bound(self, rng):
        keys, values, valid = make_inputs(rng, 200, 1)
        counts = cms_add_conservative_pallas(
            cms_init(1, 4, 512), keys, values, valid,
            tile=128, chunk=128, interpret=True,
        )
        est = np.asarray(cms_query(counts, keys))[:, 0]
        v = np.asarray(valid)
        assert (est[v] >= np.asarray(values)[v, 0] - 1e-3).all()

    def test_rows_not_multiple_of_chunk_padded(self, rng):
        # the kernel pads the streamed dimension with inert rows, so any
        # batch size works at full chunk width — and matches the XLA path
        keys, values, valid = make_inputs(rng, 50, 1)
        got = cms_add_conservative_pallas(
            cms_init(1, 2, 256), keys, values, valid,
            tile=128, chunk=128, interpret=True,
        )
        want = cms_add_conservative(cms_init(1, 2, 256), keys, values, valid)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)


class TestModelDispatch:
    def test_hh_model_same_topk_under_either_impl(self):
        # the full flagship step (sort_groupby -> CU cms -> topk) must give
        # identical answers whichever CMS impl the config selects
        from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
        from flow_pipeline_tpu.models.heavy_hitter import (
            HeavyHitterConfig,
            HeavyHitterModel,
            hh_estimates,
        )

        batches = [
            FlowGenerator(ZipfProfile(n_keys=60, alpha=1.4), seed=9).batch(1024)
            for _ in range(2)
        ]
        tops, ests = [], []
        for impl in ("xla", "pallas"):
            cfg = HeavyHitterConfig(batch_size=512, width=1 << 10,
                                    capacity=64, cms_impl=impl)
            m = HeavyHitterModel(cfg)
            with pltpu.force_tpu_interpret_mode():
                for b in batches:
                    m.update(b)
            tops.append(m.top(10))
            ests.append(np.asarray(hh_estimates(m.state, config=cfg)))
        for k in tops[0]:
            np.testing.assert_array_equal(tops[0][k], tops[1][k])
        np.testing.assert_allclose(ests[0], ests[1], rtol=1e-6)

    def test_unknown_impl_rejected(self):
        from flow_pipeline_tpu.models.heavy_hitter import (
            HeavyHitterConfig,
            HeavyHitterModel,
        )

        m = HeavyHitterModel(HeavyHitterConfig(batch_size=512,
                                               cms_impl="cuda"))
        from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile

        with pytest.raises(ValueError, match="unknown cms_impl"):
            m.update(FlowGenerator(ZipfProfile(), seed=1).batch(256))

    def test_awkward_batch_and_width_still_work(self):
        # tile/chunk derive from the config: any width%128==0 and any
        # batch size legal for the xla impl must work under pallas too
        from flow_pipeline_tpu.gen import FlowGenerator, ZipfProfile
        from flow_pipeline_tpu.models.heavy_hitter import (
            HeavyHitterConfig,
            HeavyHitterModel,
        )

        cfg = HeavyHitterConfig(batch_size=1000, width=1920, capacity=32,
                                cms_impl="pallas")
        m = HeavyHitterModel(cfg)
        with pltpu.force_tpu_interpret_mode():
            m.update(FlowGenerator(ZipfProfile(n_keys=30),
                                   seed=3).batch(1500))
        top = m.top(5)
        assert top["valid"].any()
