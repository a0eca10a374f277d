"""Table kind ``ranked_bytes_sampled``: ``ranked_bytes`` for a stream of
both address families whose sampling rate is a column of the key table.

Per timeslot, the keys ranked by the sampling-corrected sum(bytes *
max(sampling_rate, 1)), from a sketch: the sink holds ``rank``, the key's
columns and ``bytes`` (the corrected sum: what a ranked family with
``scale_col`` estimates). The reference's is exact: each rank's exact
bytes times its rate, summed over the key's ranks in integers. Compared
both ways over every slot as ``ranked_bytes`` compares (its ``compare``):
the sink's top ``top_n`` rows against their exact sums, and the
reference's top ``top_n`` keys against the sink's rows.

Addresses: the key columns ``src_ip`` / ``dst_ip`` are the stream kind's
own numbering across both families (``backbone-ranks``: the host under
2001:db8:0:1::/``128 - host_bits``, or under 10.0.0.0/``32 - host_bits``
with bit ``host_bits`` set); the sink prints a v4 address as a dotted
quad. An address outside both nets reads -1 and matches no key.

An entry of ``checks.tables``: ``name``, ``key``, ``top_n``, ``limit``
(set from readings, PERF.md 2).

numbers (limit):
  topk_bytes_max_rel_err (``limit``)  the worst relative error of the
      corrected bytes over the slots, the largest over the tables
"""

import ipaddress

import numpy as np

from benchmark.tables import ranked_bytes

_SINK_COLS = {"src_ip": "src_addr", "dst_ip": "dst_addr"}
_V6 = 0x20010DB8_00000001_00000000_00000000
_V4 = 0x0A000000
_EXACT_F64 = float(2 ** 53)

compare = ranked_bytes.compare


def want(ref, entry: dict, sums: dict) -> dict:
    """{timeslot: {key tuple: exact corrected bytes}}, the
    ``ranked_bytes.KEEP`` largest keys under ``ref``'s precision:
    ``ranked_bytes.want`` over each rank's bytes times its rate (uint64;
    a slot's whole corrected mass stays under 2^53, so every key's sum is
    exact in the float64 it is grouped in)."""
    rate = np.maximum(ref.table.sampling_rate.astype(np.uint64), 1)
    scaled = {slot: (nbytes * rate, packets, count)
              for slot, (nbytes, packets, count) in sums.items()}
    if any(float(planes[0].sum()) >= _EXACT_F64
           for planes in scaled.values()):
        raise OverflowError("a slot's corrected bytes left exact float64")
    return ranked_bytes.want(ref, entry, scaled)


def _ip(addr: str, host_bits: int) -> int:
    a = ipaddress.ip_address(addr)
    v, net = int(a), (_V4 if a.version == 4 else _V6)
    if v >> host_bits << host_bits != net:
        return -1
    return (v & ((1 << host_bits) - 1)) | ((a.version == 4) << host_bits)


def read_sink(con, entry: dict, run) -> dict:
    """{timeslot: [(key tuple, bytes)] in rank order}."""
    key = entry["key"]
    bits = int(run.cell.config["stream"]["host_bits"])
    cols = ", ".join(_SINK_COLS.get(c, c) for c in key)
    out: dict = {}
    for row in con.execute(
            f"SELECT timeslot, {cols}, bytes FROM {entry['name']} "
            f"ORDER BY timeslot, rank"):
        k = tuple(_ip(v, bits) if c in _SINK_COLS else int(v)
                  for c, v in zip(key, row[1:-1]))
        out.setdefault(int(row[0]), []).append((k, int(row[-1])))
    return out


def control(ref, entry: dict, sums: dict, run) -> dict:
    """What ``ref`` would have put in the sink."""
    depth = int(run.cell.config["sink_rows_per_window"])
    return {slot: list(keys.items())[:depth]
            for slot, keys in want(ref, entry, sums).items()}
