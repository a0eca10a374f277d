"""Stream kind ``zipf-ranks-spreaders``: ``zipf-ranks`` with sources that
talk to many: superspreaders and port scanners among the estate's hosts.

A copy of ``zipf-ranks.py``: the draws (every flow's rank, bytes and
packets), the clock, the closes and the dealing are that file's, byte
for byte, for a ``--seed`` (so a cell on this kind folds
``estate-catchup``'s ranks, bytes and packets, and its ``flows_5m``);
only the key table differs. After ``zipf-ranks``' own table is drawn, a
share ``spread_rank_share`` of the ranks, chosen by the seed uniformly
over ALL ranks (so a spreader's flows are as skewed as anyone's), is
re-homed onto ``spread_sources`` sources:

- source ``s`` (0-based) gets a share ~ 1 / (s + 1) of the re-homed
  ranks (the repo generator's harmonic fan-out,
  ``gen/generator.py::ZipfProfile.spread_*``): at the defaults 50,000
  ranks over 64 sources, 10,540 the first and 165 the last;
- its address is host number ``s`` of the estate's /112. (ISSUE 47 asked
  for a bit above the 16 host bits; ``tables/ranked_bytes.py`` reads an
  address outside the /112 as host -1, and the first spreaders are among
  ``top_src_ips``' first twenty by bytes, so the spreaders live inside
  it. The ~15 ranks that ``zipf-ranks`` gives host ``s`` stay its own
  too: a spreader is also an ordinary host, and the reference counts
  what it sees.) Its ranks keep the source port, protocol and AS pair
  they drew;
- even ``s``: a superspreader. Each of its ranks goes to a destination
  host of its own (consecutive host numbers from a start the seed
  draws), port 443;
- odd ``s``: a scanner of one victim (a host the seed draws). Each of
  its ranks goes to a destination port of its own, 1, 2, 3, ...

A flow is still (position, rank, bytes, packets): a source's distinct
destinations in a window are the distinct ``dst_host`` (or ``dst_port``)
over its ranks that the window saw, which is what
``tables/ranked_spread.py`` counts. A key the kind does not know is an
error that names it. This module imports numpy and the standard library
only.
"""

from __future__ import annotations

from dataclasses import dataclass, fields

import numpy as np

SFLOW_5 = 1  # schema.message.FlowType.SFLOW_5
# 2001:db8:0:1::/112, both sides (the original's prefix)
_PREFIX_WORDS = (0x20010DB8, 0x00000001, 0x00000000, 0x00000000)
_DST_PORTS = (53, 80, 123, 443, 8080)
_PROTOS = (6, 17)


@dataclass(frozen=True)
class StreamSpec:
    seed: int
    n_keys: int = 1_000_000
    alpha: float = 1.1
    as_base: int = 65000
    as_count: int = 16
    max_bytes: int = 1500
    max_packets: int = 100
    etype: int = 0x86DD
    sampling_rate: int = 1
    event_rate: int = 32000         # flows per second of EVENT time
    slot_seconds: int = 300
    boundary_ts: int = 1_700_000_100  # a multiple of slot_seconds
    first_close_flow: int = 65536   # first flow of the slot at boundary_ts
    phase_s: int = 0                # event seconds into that slot it starts
    chunk_flows: int = 32768        # flows to a chunk (the program's batch)
    block_flows: int = 32768        # flows drawn by one RNG
    spread_rank_share: float = 0.05  # of the ranks, re-homed to spreaders
    spread_sources: int = 64        # even: superspreaders, odd: scanners

    max_disorder_s = 0              # event time never runs backwards

    @property
    def slot_flows(self) -> int:
        return self.slot_seconds * self.event_rate

    def close_flows(self, lo: int, hi: int) -> list[int]:
        """Positions in [lo, hi) that are the first flow of a slot: the
        flows whose arrival closes the slot before."""
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
        """The partition each position goes to, of ``partitions``."""
        return idx.astype(np.int64) % partitions


def spec(seed: int, stream: dict, first_close_flow: int,
         phase_s: int) -> StreamSpec:
    """StreamSpec from a configuration file's whole ``stream`` object."""
    known = {f.name for f in fields(StreamSpec)} - {
        "seed", "first_close_flow", "phase_s"}
    unknown = sorted(set(stream) - known - {"kind"})
    if unknown:
        raise ValueError(
            f"stream kind zipf-ranks-spreaders has no key {unknown}; it has "
            f"{sorted(known)}")
    if not 0 <= phase_s < int(stream["slot_seconds"]):
        raise ValueError(f"phase_s {phase_s} lies outside a slot")
    if not (0.0 < float(stream.get("spread_rank_share", 0.05)) < 1.0
            and 1 <= int(stream.get("spread_sources", 64)) <= 2**16):
        raise ValueError("spread_rank_share lies in (0, 1) and "
                         "spread_sources in [1, 2^16]")
    return StreamSpec(seed=int(seed), first_close_flow=first_close_flow,
                      phase_s=int(phase_s),
                      **{k: v for k, v in stream.items() if k != "kind"})


FAN_PORT = 443  # where every superspreader's flows go


def spreader_ranks(spec: StreamSpec):
    """(ranks re-homed, the source of each): ``spread_rank_share`` of the
    ranks in the seed's own order, dealt to the sources in runs whose
    lengths fall as 1 / (s + 1) (each at least one rank)."""
    rng = np.random.default_rng([spec.seed, 2])
    total = max(spec.spread_sources,
                int(round(spec.spread_rank_share * spec.n_keys)))
    ranks = rng.permutation(spec.n_keys)[:total]
    w = 1.0 / np.arange(1, spec.spread_sources + 1)
    counts = np.maximum(1, np.floor(total * w / w.sum()).astype(np.int64))
    counts[0] += total - counts.sum()
    return ranks, np.repeat(np.arange(spec.spread_sources), counts), rng


class KeyTable:
    """The key universe: one 5-tuple + AS pair per Zipf rank, the
    spreaders' ranks re-homed (the module's docstring)."""

    def __init__(self, spec: StreamSpec):
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
        w = np.arange(1, n + 1, dtype=np.float64) ** -spec.alpha
        self.cdf = np.cumsum(w / w.sum())
        self.cdf[-1] = 1.0
        ranks, source, rng = spreader_ranks(spec)
        # the place of a rank among its source's ranks: 0, 1, 2, ...
        nth = np.arange(len(ranks)) - np.searchsorted(source, source)
        start = rng.integers(0, 2**16, spec.spread_sources, dtype=np.uint32)
        fans = source % 2 == 0
        self.src_host[ranks] = source.astype(np.uint32)
        self.dst_host[ranks] = np.where(
            fans, (start[source] + nth) % 2**16, start[source]).astype(
            np.uint32)
        self.dst_port[ranks] = np.where(fans, FAN_PORT, 1 + nth).astype(
            np.uint32)

    def __len__(self) -> int:
        return len(self.cdf)

    def addr_words(self, host: np.ndarray) -> np.ndarray:
        a = np.empty((len(host), 4), np.uint32)
        a[:] = _PREFIX_WORDS
        a[:, 3] = (a[:, 3] & np.uint32(0xFFFF0000)) | host
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
    cols = {
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
        "etype": np.full(n, spec.etype, np.uint32),
        "icmp_type": z32, "icmp_code": z32, "ipv6_flow_label": z32,
        "flow_direction": z32,
        "src_addr": table.addr_words(table.src_host[rank]),
        "dst_addr": table.addr_words(table.dst_host[rank]),
        "sampler_address": np.zeros((n, 4), np.uint32),
    }
    return cols
