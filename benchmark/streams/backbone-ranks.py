"""Stream kind ``backbone-ranks``: what a processor fed by the routers of a
transit network sees: sampled records of both address families over a key
universe the size of a backbone link's.

The draws, the event clock, the closes and the dealing are
``zipf-ranks``' (a copy: no later PR can change either stream by
changing the other); what differs is the key table:

- ``v4_share`` of the ranks are IPv4 (``etype`` 0x0800, the address in
  the trailing four bytes of the 16, under 10.0.0.0/``32 - host_bits``),
  the rest IPv6 (0x86DD, under 2001:db8:0:1::/``128 - host_bits``), so
  ``etype`` and the address words are columns of the table;
  ``src_ip`` / ``dst_ip`` number an address across both families (the
  host, with bit ``host_bits`` set for v4), as the fixture kind
  ``toy-mixed`` numbers its own at 16 host bits;
- ``sampling_rate`` is a column of the table too: each rank's is drawn
  from ``rates`` with the shares ``rate_shares`` (a prefix seen through
  several exporters, each sampling 1:N at its own N), so one window's
  ranked sums and ``flows_5m``'s ``*_scaled`` columns mix every rate;
- hosts are uniform in 2^``host_bits`` a side a family.

Event time is a function of the position alone and never runs backwards
(``max_disorder_s`` 0); position ``i`` goes to partition ``i mod P``.
Everything else comes from ``--seed``, in blocks of ``block_flows`` with
an RNG each. It has a key for what a configuration sets and no other
(``manifest.py`` has the contract). This module imports numpy and the
standard library only.
"""

from __future__ import annotations

from dataclasses import dataclass, fields

import numpy as np

SFLOW_5 = 1  # schema.message.FlowType.SFLOW_5
ETYPE_V4, ETYPE_V6 = 0x0800, 0x86DD
_V6_WORDS = (0x20010DB8, 0x00000001, 0x00000000, 0x00000000)
_V4_NET = 0x0A000000  # 10.0.0.0
_DST_PORTS = (53, 80, 123, 443, 8080)
_PROTOS = (6, 17)


@dataclass(frozen=True)
class StreamSpec:
    seed: int
    n_keys: int
    alpha: float
    v4_share: float
    host_bits: int
    rates: tuple
    rate_shares: tuple
    as_base: int
    as_count: int
    max_bytes: int
    max_packets: int
    event_rate: int                 # flows per second of EVENT time
    slot_seconds: int
    boundary_ts: int                # a multiple of slot_seconds
    chunk_flows: int                # flows to a chunk (the program's batch)
    block_flows: int                # flows drawn by one RNG
    first_close_flow: int           # first flow of the slot at boundary_ts
    phase_s: int                    # event seconds into that slot it starts

    max_disorder_s = 0              # event time never runs backwards

    @property
    def slot_flows(self) -> int:
        return self.slot_seconds * self.event_rate

    def close_flows(self, lo: int, hi: int) -> list[int]:
        """Positions in [lo, hi) that are the first flow of a slot."""
        k, step = self.first_close_flow, self.slot_flows
        second = k + (self.slot_seconds - self.phase_s) * self.event_rate
        out = [k] if lo <= k < hi else []
        first = second + max(0, -(-(lo - second) // step)) * step
        return out + list(range(first, hi, step))

    def event_ts(self, idx: np.ndarray) -> np.ndarray:
        """Event time (uint64 seconds) of the flows at positions ``idx``."""
        i = idx.astype(np.int64) - self.first_close_flow
        return (self.boundary_ts + np.where(i >= 0, self.phase_s, 0)
                + i // self.event_rate).astype(np.uint64)

    def partition_of(self, idx: np.ndarray, partitions: int) -> np.ndarray:
        return idx.astype(np.int64) % partitions


def spec(seed: int, stream: dict, first_close_flow: int,
         phase_s: int) -> StreamSpec:
    """StreamSpec from a configuration file's whole ``stream`` object:
    every key it has, and no other."""
    known = {f.name for f in fields(StreamSpec)} - {
        "seed", "first_close_flow", "phase_s"}
    given = set(stream) - {"kind"}
    if given != known:
        raise ValueError(
            f"stream kind backbone-ranks: unknown keys "
            f"{sorted(given - known)}, missing keys {sorted(known - given)}")
    if not 0 <= phase_s < int(stream["slot_seconds"]):
        raise ValueError(f"phase_s {phase_s} lies outside a slot")
    rates, shares = tuple(stream["rates"]), tuple(stream["rate_shares"])
    if len(rates) != len(shares) or abs(sum(shares) - 1.0) > 1e-9 \
            or min(rates) < 1:
        raise ValueError(f"rates {rates} and rate_shares {shares} are not "
                         f"one share a rate that sum to 1")
    if not 1 <= int(stream["host_bits"]) <= 24:
        raise ValueError("host_bits must lie in [1, 24]: a v4 host under "
                         "10.0.0.0/8")
    return StreamSpec(seed=int(seed), first_close_flow=first_close_flow,
                      phase_s=int(phase_s),
                      **{**{k: stream[k] for k in known},
                         "rates": rates, "rate_shares": shares})


class KeyTable:
    """The key universe: one 5-tuple, AS pair, family and sampling rate a
    Zipf rank."""

    def __init__(self, spec: StreamSpec):
        rng = np.random.default_rng([spec.seed, 0])
        n, hosts = spec.n_keys, 1 << spec.host_bits
        self.src_host = rng.integers(0, hosts, n, dtype=np.uint32)
        self.dst_host = rng.integers(0, hosts, n, dtype=np.uint32)
        self.src_port = rng.integers(1024, 2**16, n, dtype=np.uint32)
        self.dst_port = rng.choice(np.array(_DST_PORTS, np.uint32), n)
        self.proto = rng.choice(np.array(_PROTOS, np.uint32), n)
        self.src_as = (spec.as_base + rng.integers(
            0, spec.as_count, n)).astype(np.uint32)
        self.dst_as = (spec.as_base + rng.integers(
            0, spec.as_count, n)).astype(np.uint32)
        v4 = rng.random(n) < spec.v4_share
        self.etype = np.where(v4, ETYPE_V4, ETYPE_V6).astype(np.uint32)
        self.sampling_rate = rng.choice(
            np.array(spec.rates, np.uint32), n, p=spec.rate_shares)
        family = v4.astype(np.uint32) << np.uint32(spec.host_bits)
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


def _block_draws(spec: StreamSpec, table: KeyTable, block: int):
    """(rank int32, bytes uint16, packets uint8) of block ``block``."""
    n = spec.block_flows
    rng = np.random.default_rng([spec.seed, 1, block])
    rank = np.searchsorted(table.cdf, rng.random(n), side="right")
    rank = np.minimum(rank, spec.n_keys - 1).astype(np.int32)
    nbytes = rng.integers(0, spec.max_bytes, n).astype(np.uint16)
    packets = rng.integers(0, spec.max_packets, n).astype(np.uint8)
    return rank, nbytes, packets


def chunk_draws(spec: StreamSpec, table: KeyTable, chunk: int):
    """(rank, bytes, packets) of the flows at positions [chunk *
    chunk_flows, (chunk + 1) * chunk_flows): all that is random about
    them."""
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


def chunk_columns(spec: StreamSpec, table: KeyTable, chunk: int,
                  draws) -> dict:
    """The chunk's flows as the program's column layout (names and
    dtypes of ``schema.batch.COLUMNS``; addresses [n, 4] uint32)."""
    rank, nbytes, packets = draws
    n = spec.chunk_flows
    idx = chunk * n + np.arange(n, dtype=np.int64)
    ts = spec.event_ts(idx)
    z32 = np.zeros(n, np.uint32)
    return {
        "type": np.full(n, SFLOW_5, np.uint32),
        "time_received": ts,
        "sampling_rate": table.sampling_rate[rank].astype(np.uint64),
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
