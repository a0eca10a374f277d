"""The sketch states' merge monoids over a leading axis of stacked states.

Two places hold several states of one model that have to read as one:
the four-chip close (``parallel/sharded.py``: a replica a chip, stacked
by ``all_gather``) and the sliding window's ring (``engine/windowed.py``:
a state a sub-window, stacked by the fold program). Both fold them with
the functions here: planes are a sum monoid, candidate tables fold by
``topk_merge`` in the order of the axis. Under a mesh the plane sum of
the count-min sketch is the ``psum`` itself; the dense tables and the
candidate tables go through these bodies on one chip and on four.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import topk as topk_ops


def named_program(name: str):
    """Name the function a program is jitted from: the compiled module
    is ``jit_<name>`` in a device trace and in the compile log, and its
    ops carry ``<name>`` as their scope (docs/OBSERVABILITY.md: program
    names are a contract with the trace readers)."""

    def rename(fn):
        def scoped(*args):
            with jax.named_scope(name):
                return fn(*args)

        scoped.__name__ = scoped.__qualname__ = name
        return scoped

    return rename


def fold_planes(stacked):
    """[n, ...] -> [...]: count-min planes (float32) and the dense
    tables' (lo, hi) int32 planes are sum monoids. The dense lo planes
    leave their 16 bits here (n * 2^16 is far from int32's end); the
    exact uint64 recombination on the host carries them."""
    return jnp.sum(stacked, axis=0)


def fold_tables(table_keys, table_vals):
    """[n, C, W] keys and [n, C, P] values -> one table of capacity C:
    a static fold of ``topk_merge`` along the leading axis (n is known
    when the program is built)."""
    mk, mv = table_keys[0], table_vals[0]
    for d in range(1, table_keys.shape[0]):
        # topk_merge self-filters sentinel (empty-slot) rows
        cand_valid = jnp.ones(table_keys[d].shape[0], bool)
        mk, mv = topk_ops.topk_merge(mk, mv, table_keys[d], table_vals[d],
                                     cand_valid)
    return mk, mv
