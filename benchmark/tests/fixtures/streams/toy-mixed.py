"""A stream kind added as a file only, and all three things the seam was
cut for (``manifest.py``):

- dual-stack keys: ``v4_share`` of the ranks are IPv4 (``etype`` 0x0800,
  hosts under 10.0.0.0/16 in the trailing four bytes) and the rest IPv6
  (0x86DD, hosts under 2001:db8:0:1::/112), so ``etype`` and the address
  words are columns of the key table; ``src_ip`` / ``dst_ip`` number the
  addresses across both families (bit 16 set: v4);
- an onset: from position ``onset_flow`` on, ``onset_share`` of the flows
  go to the ``onset_keys`` coldest ranks, drawn evenly: keys that the
  Zipf draw all but never picks become the hottest;
- disorder on two partitions: a flow lies up to ``disorder_s`` event
  seconds behind the clock of its position (never one whose clock is the
  first second of a slot, so that slots open at the same positions
  whatever the seed), and its partition is a hash of the seed and its
  position.

Event time's clock and the closes are ``zipf-ranks``' own. Imports numpy
and the standard library only.
"""

from __future__ import annotations

from dataclasses import dataclass, fields

import numpy as np

SFLOW_5 = 1
_V6_WORDS = (0x20010DB8, 0x00000001, 0x00000000, 0x00000000)
_V4_NET = 0x0A000000  # 10.0.0.0/16
_DST_PORTS = (53, 80, 123, 443, 8080)
_PROTOS = (6, 17)
_U64 = np.uint64


def _mix(x: np.ndarray, salt: int) -> np.ndarray:
    """splitmix64's finalizer over ``x + salt``: a hash a position."""
    x = x.astype(_U64) + _U64(salt % 2**64)
    x = (x ^ (x >> _U64(30))) * _U64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> _U64(27))) * _U64(0x94D049BB133111EB)
    return x ^ (x >> _U64(31))


@dataclass(frozen=True)
class ToySpec:
    seed: int
    n_keys: int
    alpha: float
    v4_share: float
    onset_flow: int
    onset_share: float
    onset_keys: int
    disorder_s: int
    as_base: int
    as_count: int
    max_bytes: int
    max_packets: int
    sampling_rate: int
    event_rate: int
    slot_seconds: int
    boundary_ts: int
    chunk_flows: int
    block_flows: int
    first_close_flow: int
    phase_s: int

    @property
    def max_disorder_s(self) -> int:
        return self.disorder_s

    def _clock(self, idx: np.ndarray) -> np.ndarray:
        i = idx.astype(np.int64) - self.first_close_flow
        return (self.boundary_ts + np.where(i >= 0, self.phase_s, 0)
                + i // self.event_rate)

    def event_ts(self, idx: np.ndarray) -> np.ndarray:
        clock = self._clock(idx)
        behind = (_mix(idx, self.seed * 2 + 1)
                  % _U64(self.disorder_s + 1)).astype(np.int64)
        opens_a_slot = clock % self.slot_seconds == 0
        return (clock - np.where(opens_a_slot, 0, behind)).astype(np.uint64)

    def close_flows(self, lo: int, hi: int) -> list[int]:
        k, step = self.first_close_flow, self.slot_seconds * self.event_rate
        second = k + (self.slot_seconds - self.phase_s) * self.event_rate
        out = [k] if lo <= k < hi else []
        first = second + max(0, -(-(lo - second) // step)) * step
        return out + list(range(first, hi, step))

    def partition_of(self, idx: np.ndarray, partitions: int) -> np.ndarray:
        return (_mix(idx, self.seed * 2) % _U64(partitions)).astype(np.int64)


def spec(seed: int, stream: dict, first_close_flow: int,
         phase_s: int) -> ToySpec:
    known = {f.name for f in fields(ToySpec)} - {
        "seed", "first_close_flow", "phase_s"}
    given = set(stream) - {"kind"}
    if given != known:
        raise ValueError(
            f"stream kind toy-mixed: unknown keys {sorted(given - known)}, "
            f"missing keys {sorted(known - given)}")
    return ToySpec(seed=int(seed), first_close_flow=first_close_flow,
                   phase_s=int(phase_s),
                   **{k: stream[k] for k in known})


class KeyTable:
    def __init__(self, spec: ToySpec):
        rng = np.random.default_rng([spec.seed, 0])
        n = spec.n_keys
        self.src_host = rng.integers(0, 2**16, n, dtype=np.uint32)
        self.dst_host = rng.integers(0, 2**16, n, dtype=np.uint32)
        self.src_port = rng.integers(1024, 2**16, n, dtype=np.uint32)
        self.dst_port = rng.choice(np.array(_DST_PORTS, np.uint32), n)
        self.proto = rng.choice(np.array(_PROTOS, np.uint32), n)
        self.src_as = (spec.as_base + rng.integers(
            0, spec.as_count, n)).astype(np.uint32)
        self.dst_as = (spec.as_base + rng.integers(
            0, spec.as_count, n)).astype(np.uint32)
        v4 = rng.random(n) < spec.v4_share
        self.etype = np.where(v4, 0x0800, 0x86DD).astype(np.uint32)
        family = v4.astype(np.uint32) << np.uint32(16)
        self.src_ip, self.dst_ip = (family | self.src_host,
                                    family | self.dst_host)
        self.src_addr = self._words(v4, self.src_host)
        self.dst_addr = self._words(v4, self.dst_host)
        w = np.arange(1, n + 1, dtype=np.float64) ** -spec.alpha
        self.cdf = np.cumsum(w / w.sum())
        self.cdf[-1] = 1.0

    def __len__(self) -> int:
        return len(self.cdf)

    @staticmethod
    def _words(v4: np.ndarray, host: np.ndarray) -> np.ndarray:
        a = np.empty((len(host), 4), np.uint32)
        a[:] = _V6_WORDS
        a[:, 3] |= host
        a[v4, :3] = 0
        a[v4, 3] = np.uint32(_V4_NET) | host[v4]
        return a


key_table = KeyTable


def _block_draws(spec: ToySpec, table: KeyTable, block: int):
    n = spec.block_flows
    rng = np.random.default_rng([spec.seed, 1, block])
    rank = np.minimum(np.searchsorted(table.cdf, rng.random(n),
                                      side="right"), spec.n_keys - 1)
    nbytes = rng.integers(0, spec.max_bytes, n).astype(np.uint16)
    packets = rng.integers(0, spec.max_packets, n).astype(np.uint8)
    struck = (rng.random(n) < spec.onset_share) & (
        block * n + np.arange(n) >= spec.onset_flow)
    cold = spec.n_keys - 1 - rng.integers(0, spec.onset_keys, n)
    return np.where(struck, cold, rank).astype(np.int32), nbytes, packets


def chunk_draws(spec: ToySpec, table: KeyTable, chunk: int):
    lo = chunk * spec.chunk_flows
    hi = lo + spec.chunk_flows
    b = spec.block_flows
    parts = []
    for block in range(lo // b, -(-hi // b)):
        d = _block_draws(spec, table, block)
        a, z = max(lo, block * b) - block * b, min(hi, (block + 1) * b) \
            - block * b
        parts.append(tuple(x[a:z] for x in d))
    return tuple(np.concatenate([p[i] for p in parts]) for i in range(3))


def chunk_columns(spec: ToySpec, table: KeyTable, chunk: int,
                  draws) -> dict:
    rank, nbytes, packets = draws
    n = spec.chunk_flows
    idx = chunk * n + np.arange(n, dtype=np.int64)
    ts = spec.event_ts(idx)
    z32 = np.zeros(n, np.uint32)
    return {
        "type": np.full(n, SFLOW_5, np.uint32),
        "time_received": ts,
        "sampling_rate": np.full(n, spec.sampling_rate, np.uint64),
        "sequence_num": (idx & 0xFFFFFFFF).astype(np.uint32),
        "time_flow_start": ts, "time_flow_end": ts,
        "bytes": nbytes.astype(np.uint64),
        "packets": packets.astype(np.uint64),
        "src_as": table.src_as[rank], "dst_as": table.dst_as[rank],
        "in_if": z32, "out_if": z32,
        "proto": table.proto[rank],
        "src_port": table.src_port[rank], "dst_port": table.dst_port[rank],
        "ip_tos": z32, "forwarding_status": z32, "ip_ttl": z32,
        "tcp_flags": z32,
        "etype": table.etype[rank],
        "icmp_type": z32, "icmp_code": z32, "ipv6_flow_label": z32,
        "flow_direction": z32,
        "src_addr": table.src_addr[rank], "dst_addr": table.dst_addr[rank],
        "sampler_address": np.zeros((n, 4), np.uint32),
    }
