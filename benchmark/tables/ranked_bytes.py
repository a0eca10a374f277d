"""Table kind ``ranked_bytes``: per timeslot, the keys ranked by
sum(bytes), from a sketch: the sink holds ``rank``, the key's columns and
``bytes``. Compared both ways over every slot: the sink's top ``top_n``
rows against their exact bytes, and the reference's top ``top_n`` keys
against the sink's rows (a key the other side lacks counts 1.0).

An entry of ``checks.tables``: ``name`` (the sink's table), ``key`` (the
key table's columns), ``top_n``, ``limit`` (set from readings, PERF.md;
the configuration's ``guarantees`` only state what users are promised).

numbers (limit):
  topk_bytes_max_rel_err (``limit``)  the worst relative error of bytes
      over the slots, the largest over the tables of this kind
"""

import ipaddress

import numpy as np

_PREFIX = 0x20010DB8_00000001_00000000_0000 << 16  # zipf-ranks' prefix
_SINK_COLS = {"src_host": "src_addr", "dst_host": "dst_addr"}
KEEP = 4000  # exact keys kept a slot: far past any sink's depth


def _host(addr: str) -> int:
    v = int(ipaddress.IPv6Address(addr))
    return v & 0xFFFF if v >> 16 << 16 == _PREFIX else -1


def want(ref, entry: dict, sums: dict) -> dict:
    """{timeslot: {key tuple: exact bytes}}, the ``KEEP`` largest keys by
    bytes under ``ref``'s precision (ties by key order)."""
    cols = tuple(entry["key"])
    gid, first = ref.group_of_rank(cols)
    keys = np.stack([getattr(ref.table, c)[first] for c in cols], axis=1)
    out = {}
    for slot, planes in sums.items():
        tot = np.bincount(gid, weights=planes[0].astype(np.float64),
                          minlength=len(first))
        cnt = np.bincount(gid, weights=planes[2].astype(np.float64),
                          minlength=len(first))
        live = np.flatnonzero(cnt)
        top = live[np.argsort(-tot[live], kind="stable")[:KEEP]]
        out[slot] = {tuple(int(x) for x in keys[g]): int(tot[g])
                     for g in top}
    return out


def read_sink(con, entry: dict, run) -> dict:
    """{timeslot: [(key tuple, bytes)] in rank order}."""
    key = entry["key"]
    cols = ", ".join(_SINK_COLS.get(c, c) for c in key)
    out: dict = {}
    for row in con.execute(
            f"SELECT timeslot, {cols}, bytes FROM {entry['name']} "
            f"ORDER BY timeslot, rank"):
        k = tuple(_host(v) if c in _SINK_COLS else int(v)
                  for c, v in zip(key, row[1:-1]))
        out.setdefault(int(row[0]), []).append((k, int(row[-1])))
    return out


def control(ref, entry: dict, sums: dict, run) -> dict:
    """What ``ref`` would have put in the sink."""
    depth = int(run.cell.config["sink_rows_per_window"])
    return {slot: list(keys.items())[:depth]
            for slot, keys in want(ref, entry, sums).items()}


def compare(entry: dict, wanted: dict, got: dict, n_flows: int) -> dict:
    top_n, worst = int(entry["top_n"]), 0.0
    for slot, keys in wanted.items():
        rows = got.get(slot, [])
        have = dict(rows)
        errs = [abs(b - keys[k]) / max(keys[k], 1) if k in keys else 1.0
                for k, b in rows[:top_n]]
        errs += [abs(have[k] - b) / max(b, 1) if k in have else 1.0
                 for k, b in list(keys.items())[:min(top_n, len(rows))]]
        if not rows:
            errs.append(1.0)
        worst = max([worst, *errs])
    return {"topk_bytes_max_rel_err": (worst, float(entry["limit"]))}
