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
    sums = ref.slot_sums(rank, nbytes, packets, 0, n)
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
    a = exact_sums.want(exact, FLOWS_5M,
                        exact.slot_sums(rank, nbytes, packets, 0, n))
    b = exact_sums.want(low, FLOWS_5M,
                        low.slot_sums(rank, nbytes, packets, 0, n))
    assert set(a) == set(b)
    assert sum(a[k] != b[k] for k in a) > len(a) // 2
    assert all(a[k][2] == b[k][2] for k in a)  # counts are small integers
    worst = max(abs(a[k][0] - b[k][0]) / max(a[k][0], 1) for k in a)
    assert 0 < worst < 0.01
