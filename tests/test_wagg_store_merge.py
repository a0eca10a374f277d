"""The window store as sorted key rows and a sums array (ISSUE 34): after
any sequence of drains it holds, key for key and sum for sum, what a plain
dict of key tuples holds after the loop the fold used to be, a close emits
the same columns in the same order, and the ``wagg_fold`` span's
``inserted`` counts the keys a drain saw first. Counts only: nothing here
is timed.
"""

import numpy as np
import pytest

from flow_pipeline_tpu.models import WindowAggregator
from flow_pipeline_tpu.models.window_agg import WindowStore, rows_from_stores
from flow_pipeline_tpu.obs.trace import TRACER

T0, SLOT = 1_700_000_100, 300
TOP = 2**32 - 1


def _universe(kind: str, size: int, rng) -> np.ndarray:
    """``size`` distinct key rows (SrcAS, DstAS, EType, rate), shuffled."""
    if kind == "as":  # 256 ASes a side, as estate-as64k draws them
        rows = np.stack([rng.integers(64_000, 64_256, 4 * size),
                         rng.integers(64_000, 64_256, 4 * size),
                         rng.choice([0x0800, 0x86DD], 4 * size),
                         rng.choice([0, 1, 100, 1000], 4 * size)], axis=1)
    elif kind == "last_lane":  # keys that differ only in the last lane
        rows = np.stack([np.full(size, 64_007), np.full(size, 64_009),
                         np.full(size, 0x0800), np.arange(size)], axis=1)
    else:  # "extremes": every lane 0, 1, 2^32-2 or 2^32-1
        rows = np.stack(np.meshgrid(*[[0, 1, TOP - 1, TOP]] * 4),
                        axis=-1).reshape(-1, 4)
    rows = np.unique(rows.astype(np.uint32), axis=0)
    assert len(rows) >= size, (kind, len(rows), size)
    return rows[rng.permutation(len(rows))[:size]]


def _reference_fold(windows: dict, keys, vals) -> None:
    """The fold as it was: a Python step a row into a dict of tuples."""
    for row, val in zip(keys.tolist(), vals.tolist()):
        store = windows.setdefault(row[0], {})
        acc = store.setdefault(tuple(row[1:]), [0] * len(val))
        for j, v in enumerate(val):
            acc[j] = (acc[j] + v) % 2**64


def _reference_rows(windows: dict) -> dict:
    """A close of every window, from the dicts: per (slot, SrcAS, DstAS,
    EType) in key order, the sums over the rates and the scaled sums."""
    out: dict = {}
    for slot in sorted(windows):
        for key, (nbytes, packets, count) in windows[slot].items():
            acc = out.setdefault((slot, *key[:-1]), [0] * 5)
            rate = max(key[-1], 1)
            for j, v in enumerate((nbytes, packets, count,
                                   nbytes * rate, packets * rate)):
                acc[j] = (acc[j] + v) % 2**64
    order = sorted(out)
    cols = {name: np.array([k[i] for k in order], np.uint64)
            for i, name in enumerate(("timeslot", "src_as", "dst_as",
                                      "etype"))}
    for j, name in enumerate(("bytes", "packets", "count", "bytes_scaled",
                              "packets_scaled")):
        cols[name] = np.array([out[k][j] for k in order], np.uint64)
    return cols


def _drain(rng, known, fresh, groups, slots, new_share, partials):
    """One drain's rows: ``groups`` keys a partial, ``new_share`` of them
    from ``fresh`` (the same new keys in every partial, so a key first
    seen here is seen ``partials`` times), each under one of ``slots``."""
    n_new = min(int(round(groups * new_share)), len(fresh))
    new = fresh[:n_new]
    keys = []
    for _ in range(partials):
        old = known[rng.choice(len(known), groups - n_new, replace=False)] \
            if groups > n_new else known[:0]
        part = np.concatenate([new, old])
        slot = rng.choice(slots, len(part)).astype(np.uint32)
        keys.append(np.concatenate([slot[:, None], part], axis=1))
    keys = np.concatenate(keys)
    keys = keys[rng.permutation(len(keys))]
    vals = rng.integers(0, 2**62, (len(keys), 3), dtype=np.uint64)
    return keys, vals, n_new


CASES = {
    # groups a drain, store size, slots a drain, share of new keys,
    # partials a drain, universe
    "256_into_256": (256, 256, 1, 0.0, 1, "as"),
    "9000_into_65000": (9_000, 65_000, 1, 0.001, 1, "as"),
    "a_drain_across_two_slots": (2_000, 5_000, 2, 0.01, 1, "as"),
    "only_new_keys": (500, 1_000, 1, 1.0, 1, "as"),
    "a_key_in_every_folded_partial": (300, 400, 2, 0.1, 4, "as"),
    "keys_apart_in_the_last_lane": (200, 1_000, 1, 0.05, 2, "last_lane"),
    "lanes_of_0_and_2_32_less_1": (64, 128, 2, 0.25, 2, "extremes"),
}


@pytest.fixture(params=CASES, scope="module")
def folded(request):
    """(the aggregator, the reference's windows, drains' (new keys,
    spans' ``inserted``)) after the first drain that fills the store and
    four more."""
    groups, size, n_slots, new_share, partials, kind = CASES[request.param]
    rng = np.random.default_rng(len(request.param))
    slots = np.array([T0 + SLOT * i for i in range(n_slots)], np.uint32)
    univ = _universe(kind, 2 * size, rng)
    known, fresh = univ[:size], univ[size:]
    agg, want, seen, counted = WindowAggregator(), {}, set(), []
    TRACER.configure("always")
    try:
        drains = [_drain(rng, known, fresh[:0], size, slots, 0.0, 1)]
        for _ in range(4):
            keys, vals, n_new = _drain(rng, known, fresh, groups, slots,
                                       new_share, partials)
            known = np.concatenate([known, fresh[:n_new]])
            fresh = fresh[n_new:]
            drains.append((keys, vals, n_new))
        for keys, vals, _ in drains:
            first = {tuple(r) for r in keys.tolist()} - seen
            seen |= first
            # through the device partial's door: 16-bit planes, counts
            planes = np.stack([vals[:, 0] & 0xFFFF, vals[:, 0] >> 16,
                               vals[:, 1] & 0xFFFF, vals[:, 1] >> 16],
                              axis=1)
            agg.add_partial((keys, planes, vals[:, 2], len(keys)))
            agg._drain()
            _reference_fold(want, keys, vals)
            counted.append(len(first))
        spans = [s[5] for s in TRACER.snapshot() if s[0] == "wagg_fold"]
    finally:
        TRACER.configure("off")
    return agg, want, counted, spans


def test_the_store_equals_the_dict_of_tuples_key_for_key(folded):
    agg, want, _counted, _spans = folded
    assert sorted(agg.windows) == sorted(want)
    for slot, ref in want.items():
        store = agg.windows[slot]
        assert isinstance(store, WindowStore) and len(store) == len(ref)
        assert store.key_rows.dtype == np.uint32
        assert store.sums.dtype == np.uint64
        assert list(store) == sorted(ref)  # key order, each key once
        assert store.sums.tolist() == [ref[k] for k in store]
        key = next(iter(ref))
        assert store[key].tolist() == ref[key]
        assert dict((k, v.tolist()) for k, v in store.items()) == ref


def test_a_close_emits_the_same_columns_in_the_same_order(folded):
    agg, want, _counted, _spans = folded
    stores = [(slot, agg.windows[slot]) for slot in sorted(agg.windows)]
    got, ref = rows_from_stores(agg.config, stores), _reference_rows(want)
    assert list(got) == ["timeslot", "src_as", "dst_as", "etype", "bytes",
                         "packets", "count", "bytes_scaled",
                         "packets_scaled"]
    for name, col in ref.items():
        assert got[name].dtype == np.uint64
        assert got[name].tolist() == col.tolist(), name


def test_the_fold_span_counts_the_keys_a_drain_saw_first(folded):
    _agg, want, counted, spans = folded
    assert [s["inserted"] for s in spans] == counted
    assert spans[-1]["store_groups"] == sum(map(len, want.values()))
    assert all(s["inserted"] <= s["groups"] for s in spans)


def test_a_drain_of_known_keys_inserts_nothing():
    agg = WindowAggregator()
    rng = np.random.default_rng(3)
    keys = np.concatenate([np.full((300, 1), T0, np.uint32),
                           _universe("as", 300, rng)], axis=1)
    vals = np.ones((300, 3), np.uint64)
    assert agg._fold_rows(keys, vals) == 300
    before = agg.windows[T0].key_rows
    assert agg._fold_rows(keys[rng.permutation(300)[:120]], vals[:120]) == 0
    assert agg.windows[T0].key_rows is before  # added into, not rebuilt
    assert agg._fold_rows(np.concatenate([keys, keys]),
                          np.concatenate([vals, vals])) == 0
    assert agg.windows[T0].sums[:, 2].sum() == 300 + 120 + 600


def test_the_read_surface_is_a_mapping_of_key_tuples():
    store = WindowStore.from_rows([(3, 4), (1, 2), (3, 4), (1, TOP)],
                                  [(7, 1), (5, 2), (1, 1), (9, 9)])
    assert len(store) == 3
    assert list(store) == [(1, 2), (1, TOP), (3, 4)]
    assert [v.tolist() for _, v in store.items()] == [[5, 2], [9, 9], [8, 2]]
    assert store[(3, 4)].tolist() == [8, 2]
    for missing in [(1, 3), (0, 0), (TOP, TOP), (1,), (1, 2, 3)]:
        with pytest.raises(KeyError):
            store[missing]
    keys, sums = store.snapshot()
    assert store.merge(np.array([[1, 2], [2, 0]], np.uint32),
                       np.array([[1, 1], [4, 4]], np.uint64)) == 1
    assert sums.tolist() == [[5, 2], [9, 9], [8, 2]]  # the copy stands
    assert keys.tolist() == [[1, 2], [1, TOP], [3, 4]]
    assert list(store) == [(1, 2), (1, TOP), (2, 0), (3, 4)]
    assert store[(1, 2)].tolist() == [6, 3]
    empty = WindowStore.from_rows(np.zeros((0, 2), np.uint32),
                                  np.zeros((0, 2), np.uint64))
    assert len(empty) == 0 and list(empty) == []
    with pytest.raises(KeyError):
        empty[(1, 2)]
