"""A table kind added as a file only: ``ranked_bytes`` over addresses of
both families, as the stream kind ``toy-mixed`` numbers them: the key
columns ``src_ip`` / ``dst_ip`` are the host under 2001:db8:0:1::/112, or
under 10.0.0.0/16 with bit 16 set. The sink prints a v4 address as a
dotted quad."""

import ipaddress

from benchmark.tables import ranked_bytes

_SINK_COLS = {"src_ip": "src_addr", "dst_ip": "dst_addr"}
_V6 = 0x20010DB8_00000001_00000000_0000 << 16
_V4 = 0x0A00 << 16

want, control, compare = (ranked_bytes.want, ranked_bytes.control,
                          ranked_bytes.compare)


def _ip(addr: str) -> int:
    a = ipaddress.ip_address(addr)
    v, net = int(a), (_V4 if a.version == 4 else _V6)
    if v >> 16 << 16 != net:
        return -1
    return (v & 0xFFFF) | ((a.version == 4) << 16)


def read_sink(con, entry: dict, run) -> dict:
    key = entry["key"]
    cols = ", ".join(_SINK_COLS.get(c, c) for c in key)
    out: dict = {}
    for row in con.execute(
            f"SELECT timeslot, {cols}, bytes FROM {entry['name']} "
            f"ORDER BY timeslot, rank"):
        k = tuple(_ip(v) if c in _SINK_COLS else int(v)
                  for c, v in zip(key, row[1:-1]))
        out.setdefault(int(row[0]), []).append((k, int(row[-1])))
    return out
