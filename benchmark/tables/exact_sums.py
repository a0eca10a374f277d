"""Table kind ``exact_sums``: GROUP BY (timeslot, key columns, stream
constants) -> sum(bytes), sum(packets), count(), in unsigned 64-bit
integers, compared exactly (upstream's ``flows_5m``:
compose/clickhouse/create.sh:92-110). The sink's rows are summed by key
first: late partials merge.

An entry of ``checks.tables``: ``name`` (the sink's table), ``key`` (the
key table's columns, under the same names in the sink), ``stream_key``
(columns that hold a constant of the stream), ``numbers`` (the prefix of
this table's numbers).

numbers (limit):
  <numbers>_mismatched_groups (0)  groups that differ from the reference in
      bytes, packets or count, or are missing, or are extra
  <numbers>_scaled_mismatches (0)  groups whose *_scaled sums differ from
      the sums times the stream's sampling rate
  unaccounted_flows (0)            |SUM(count) - flows consumed|
"""

import numpy as np


def _cols(entry: dict) -> list:
    return list(entry["key"]) + list(entry.get("stream_key", []))


def want(ref, entry: dict, sums: dict) -> dict:
    """{(timeslot, *key): (bytes, packets, count)}."""
    gid, first = ref.group_of_rank(tuple(entry["key"]))
    const = tuple(int(getattr(ref.spec, c))
                  for c in entry.get("stream_key", []))
    out = {}
    for slot, planes in sums.items():
        tots = [np.bincount(gid, weights=p.astype(np.float64),
                            minlength=len(first)).astype(np.uint64)
                for p in planes]
        for g in np.flatnonzero(tots[2]):
            r = first[g]
            key = tuple(int(getattr(ref.table, c)[r]) for c in entry["key"])
            out[(slot, *key, *const)] = (
                int(tots[0][g]), int(tots[1][g]), int(tots[2][g]))
    return out


def read_sink(con, entry: dict, run) -> tuple:
    """(rows by key, groups whose scaled sums are off, SUM(count))."""
    cols = _cols(entry)
    by = ", ".join(str(i + 1) for i in range(len(cols) + 1))
    rate = max(int(run.cell.config["stream"].get("sampling_rate", 1)), 1)
    rows, scaled_bad, count = {}, 0, 0
    nk = len(cols) + 1
    for r in con.execute(
            f"SELECT timeslot, {', '.join(cols)}, SUM(bytes), SUM(packets), "
            f"SUM(count), SUM(bytes_scaled), SUM(packets_scaled) "
            f"FROM {entry['name']} GROUP BY {by}"):
        rows[tuple(int(x) for x in r[:nk])] = tuple(
            int(x) for x in r[nk:nk + 3])
        scaled_bad += (int(r[nk + 3]) != int(r[nk]) * rate
                       or int(r[nk + 4]) != int(r[nk + 1]) * rate)
        count += int(r[nk + 2])
    return rows, scaled_bad, count


def control(ref, entry: dict, sums: dict, run) -> tuple:
    """What ``ref`` would have put in the sink."""
    rows = want(ref, entry, sums)
    return rows, 0, sum(v[2] for v in rows.values())


def compare(entry: dict, wanted: dict, got: tuple, n_flows: int) -> dict:
    rows, scaled_bad, count = got
    bad = sum(1 for k in set(wanted) | set(rows)
              if wanted.get(k) != rows.get(k))
    p = entry.get("numbers", entry["name"])
    return {f"{p}_mismatched_groups": (bad, 0),
            f"{p}_scaled_mismatches": (scaled_bad, 0),
            "unaccounted_flows": (abs(count - n_flows), 0)}
