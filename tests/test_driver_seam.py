"""Driver-seam guards: these tests run the REAL driver entry points
(`__graft_entry__.py`) at tiny shapes, so a config-schema change that
breaks the seam fails the suite instead of the driver's compile check.
The benchmark's own seam (`benchmark/` run through `BENCHMARK.json`'s
command) is guarded in tests/test_benchmark_seam.py.
"""

from __future__ import annotations

import jax

import __graft_entry__ as graft


def test_entry_compiles_and_runs():
    """The driver's single-chip compile check, verbatim."""
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    # state pytree comes back with the same structure
    assert type(out) is type(args[0])


def test_dryrun_multichip_small_mesh():
    """The driver's multi-chip dry run on a small virtual mesh (conftest
    forces the 8-device CPU platform)."""
    graft.dryrun_multichip(min(4, len(jax.devices())))
