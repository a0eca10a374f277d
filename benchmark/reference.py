"""The plain reference: exact answers over exactly the flows consumed.

Pure numpy on the benchmark's own draws; imports nothing of the program
and reads nothing the program made. A flow is (position, rank, bytes,
packets): every other attribute of it is the stream kind's key table at
its rank and its event time is the kind's function of its position
(``manifest.py`` has the contract), so each slot is first reduced to
per-rank sums (one bincount over ``len(table)`` bins; float64 holds
integers exactly below 2^53 and the sums are checked against it). A
table of a configuration is then a grouping of ranks by the key's
columns; what a table means — exact sums, a ranking by bytes — is its
kind, a module under ``tables/`` named in the configuration's
``checks.tables``.

``precision="bf16"`` is the control, not a mode of the benchmark: every
addend is rounded to bfloat16 before it is summed, which is what a
group-by or a sketch update done as a one-hot matrix product on the MXU
at default precision computes. ``correct`` must come out false for it.
"""

from __future__ import annotations

import numpy as np

_EXACT_F64 = float(2 ** 53)


def _to_bf16(x: np.ndarray) -> np.ndarray:
    """float32 -> nearest bfloat16 (ties to even), returned as float32."""
    u = x.astype(np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


class Reference:
    def __init__(self, spec, table, precision: str = "u64"):
        """``spec`` and ``table``: the stream kind's, of this run."""
        if precision not in ("u64", "bf16"):
            raise ValueError(f"precision must be u64|bf16, got {precision!r}")
        self.spec, self.table, self.precision = spec, table, precision
        self._groups: dict = {}

    def group_of_rank(self, cols: tuple):
        """(group id of every rank, one representative rank per group)."""
        if cols not in self._groups:
            # dense ids column by column: one-dimensional integer sorts
            gid = np.zeros(len(self.table), np.int64)
            for c in cols:
                _u, col = np.unique(getattr(self.table, c),
                                    return_inverse=True)
                _u, gid = np.unique(gid * (int(col.max()) + 1)
                                    + col.reshape(-1), return_inverse=True)
                gid = gid.reshape(-1)
            _u, first = np.unique(gid, return_index=True)
            self._groups[cols] = (gid, first)
        return self._groups[cols]

    def slot_sums(self, idx, rank, nbytes, packets):
        """Per-slot per-rank sums over the flows at positions ``idx``
        (ascending), whose draws are ``rank``, ``nbytes``, ``packets``:
        {timeslot: (bytes[keys], packets[keys], count[keys])} as
        uint64."""
        spec, keys = self.spec, len(self.table)
        ts = spec.event_ts(idx).astype(np.int64)
        slot = ts // spec.slot_seconds * spec.slot_seconds
        out = {}
        for s in np.unique(slot):
            sel = np.flatnonzero(slot == s)
            if not spec.max_disorder_s:
                # event time never runs backwards: a slot is a run
                sel = slice(sel[0], sel[-1] + 1)
            r = rank[sel]
            planes = []
            for v in (nbytes[sel], packets[sel]):
                w = v.astype(np.float64)
                if self.precision == "bf16":
                    w = _to_bf16(w).astype(np.float64)
                tot = np.bincount(r, weights=w, minlength=keys)
                if tot.max(initial=0.0) >= _EXACT_F64:
                    raise OverflowError("per-rank sum left exact float64")
                if self.precision == "bf16":
                    tot = tot.astype(np.float32).astype(np.float64)
                planes.append(tot.astype(np.uint64))
            planes.append(np.bincount(r, minlength=keys).astype(np.uint64))
            out[int(s)] = tuple(planes)
        return out
