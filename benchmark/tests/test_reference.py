"""The plain reference against the program's own oracle on a small
stream, and the lower-precision control against the reference."""

import numpy as np

from benchmark.flowgen import (KeyTable, StreamSpec, chunk_columns,
                               chunk_draws)
from benchmark.reference import Reference
from benchmark.tables import exact_sums, ranked_bytes

FLOWS_5M = {"name": "flows_5m", "kind": "exact_sums",
            "key": ["src_as", "dst_as"], "stream_key": ["etype"]}
SRC_IPS = {"name": "top_src_ips", "kind": "ranked_bytes",
           "key": ["src_host"], "top_n": 20, "limit": 1e-5}

SPEC = StreamSpec(seed=11, n_keys=300, event_rate=20, chunk_flows=2048,
                  first_close_flow=4096)


def _stream(chunks):
    table = KeyTable(SPEC)
    draws = [chunk_draws(SPEC, table, c) for c in range(chunks)]
    cols = [chunk_columns(SPEC, table, c, d) for c, d in enumerate(draws)]
    return table, draws, cols


def test_flows_5m_and_ranked_bytes_equal_the_programs_oracle():
    from flow_pipeline_tpu.models import oracle
    from flow_pipeline_tpu.schema.batch import FlowBatch

    table, draws, cols = _stream(4)
    n = 4 * SPEC.chunk_flows - 100
    rank, nbytes, packets = (np.concatenate([d[i] for d in draws])
                             for i in range(3))
    ref = Reference(SPEC, table)
    sums = ref.slot_sums(np.arange(n), rank[:n], nbytes[:n], packets[:n])
    batch = FlowBatch.concat([FlowBatch(c) for c in cols]).slice(0, n)
    o = oracle.flows_5m(batch)
    want = {tuple(int(o[c][i]) for c in ("timeslot", "src_as", "dst_as",
                                         "etype")):
            tuple(int(o[c][i]) for c in ("bytes", "packets", "count"))
            for i in range(len(o["timeslot"]))}
    assert exact_sums.want(ref, FLOWS_5M, sums) == want
    assert len({k[0] for k in want}) == 2  # the stream crosses a slot
    ex = oracle.exact_groupby(batch, ["src_addr"], ["bytes"], timeslot=True)
    got = ranked_bytes.want(ref, SRC_IPS, sums)  # 300 keys: all are kept
    exact = {(int(t), int(a[3]) & 0xFFFF): int(b) for t, a, b in zip(
        ex["timeslot"], ex["src_addr"], ex["bytes"])}
    assert {(slot, k[0]): b for slot, keys in got.items()
            for k, b in keys.items()} == exact


def test_bf16_control_differs_in_flows_5m_and_little_in_top_bytes():
    table, draws, _cols = _stream(3)
    rank, nbytes, packets = (np.concatenate([d[i] for d in draws])
                             for i in range(3))
    n = len(rank)
    exact, low = Reference(SPEC, table), Reference(SPEC, table, "bf16")
    draws = (np.arange(n), rank, nbytes, packets)
    a = exact_sums.want(exact, FLOWS_5M, exact.slot_sums(*draws))
    b = exact_sums.want(low, FLOWS_5M, low.slot_sums(*draws))
    assert set(a) == set(b)
    assert sum(a[k] != b[k] for k in a) > len(a) // 2
    assert all(a[k][2] == b[k][2] for k in a)  # counts are small integers
    worst = max(abs(a[k][0] - b[k][0]) / max(a[k][0], 1) for k in a)
    assert 0 < worst < 0.01


def test_slot_sums_group_by_slot_where_event_time_runs_backwards():
    """A kind that declares disorder: a slot's flows are no run of
    positions, and the sums are those of a flow-by-flow count over a
    scattered set of positions."""
    import dataclasses

    class Jittered(type(SPEC)):
        max_disorder_s = 40

        def event_ts(self, idx):
            back = (idx.astype(np.int64) * 7919) % (self.max_disorder_s + 1)
            return (type(SPEC).event_ts(self, idx).astype(np.int64)
                    - back).astype(np.uint64)

    spec = Jittered(**dataclasses.asdict(SPEC))
    table = KeyTable(spec)
    rank, nbytes, packets = chunk_draws(spec, table, 2)
    lo = 2 * spec.chunk_flows  # the first flow of the slot at boundary_ts
    idx = lo + np.flatnonzero(np.arange(2048) % 3 != 1)  # two of three
    rank, nbytes, packets = rank[idx - lo], nbytes[idx - lo], \
        packets[idx - lo]
    slot = spec.event_ts(idx).astype(np.int64) // 300 * 300
    assert len(np.unique(slot)) == 2
    assert (np.diff(slot) < 0).any()  # no runs
    got = Reference(spec, table).slot_sums(idx, rank, nbytes, packets)
    want: dict = {}
    for s, r, b, p in zip(slot.tolist(), rank.tolist(), nbytes.tolist(),
                          packets.tolist()):
        tot = want.setdefault(s, np.zeros((3, len(table)), np.uint64))
        tot[:, r] += np.array([b, p, 1], np.uint64)
    assert set(got) == set(want)
    for s in want:
        assert all((g == w).all() for g, w in zip(got[s], want[s]))
