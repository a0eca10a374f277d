"""Table kind ``exact_sums_sampled``: ``exact_sums`` for a stream whose
sampling rate is a column of the key table (a rate a rank), with every key
column taken from the table (``etype`` too: no stream constant).

GROUP BY (timeslot, key columns) -> sum(bytes), sum(packets), count() and
the sampling-corrected ``bytes_scaled`` / ``packets_scaled`` = the sum
over the group's ranks of the rank's sums times max(its rate, 1)
(upstream's query-time ``sum(bytes*sampling_rate)``,
compose/grafana/dashboards/viz.json:62), all in unsigned 64-bit integers,
compared exactly. The sink's rows are summed by key first: late partials
merge.

An entry of ``checks.tables``: ``name`` (the sink's table), ``key`` (the
key table's columns, under the same names in the sink), ``numbers`` (the
prefix of this table's numbers).

numbers (limit):
  <numbers>_mismatched_groups (0)  groups that differ from the reference in
      bytes, packets or count, or are missing, or are extra
  <numbers>_scaled_mismatches (0)  groups whose *_scaled sums differ from
      the reference's (or that one side lacks)
  unaccounted_flows (0)            |SUM(count) - flows consumed|
"""

import numpy as np

_EXACT_F64 = float(2 ** 53)


def _group_sums(gid, groups: int, plane: np.ndarray) -> np.ndarray:
    tot = np.bincount(gid, weights=plane.astype(np.float64),
                      minlength=groups)
    if tot.max(initial=0.0) >= _EXACT_F64:
        raise OverflowError("a group's sum left exact float64")
    return tot.astype(np.uint64)


def want(ref, entry: dict, sums: dict) -> dict:
    """{(timeslot, *key): (bytes, packets, count, bytes_scaled,
    packets_scaled)}."""
    cols = tuple(entry["key"])
    gid, first = ref.group_of_rank(cols)
    rate = np.maximum(ref.table.sampling_rate.astype(np.uint64), 1)
    keys = [getattr(ref.table, c)[first] for c in cols]
    out = {}
    for slot, (nbytes, packets, count) in sums.items():
        tots = [_group_sums(gid, len(first), p)
                for p in (nbytes, packets, count, nbytes * rate,
                          packets * rate)]
        for g in np.flatnonzero(tots[2]):
            out[(slot, *(int(k[g]) for k in keys))] = tuple(
                int(t[g]) for t in tots)
    return out


def read_sink(con, entry: dict, run) -> dict:
    """The sink's rows under the same shape."""
    cols = list(entry["key"])
    nk = len(cols) + 1
    by = ", ".join(str(i + 1) for i in range(nk))
    return {tuple(int(x) for x in r[:nk]): tuple(int(x) for x in r[nk:])
            for r in con.execute(
                f"SELECT timeslot, {', '.join(cols)}, SUM(bytes), "
                f"SUM(packets), SUM(count), SUM(bytes_scaled), "
                f"SUM(packets_scaled) FROM {entry['name']} GROUP BY {by}")}


def control(ref, entry: dict, sums: dict, run) -> dict:
    """What ``ref`` would have put in the sink."""
    return want(ref, entry, sums)


def compare(entry: dict, wanted: dict, got: dict, n_flows: int) -> dict:
    keys = set(wanted) | set(got)
    none = (None,) * 5
    bad = sum(1 for k in keys
              if wanted.get(k, none)[:3] != got.get(k, none)[:3])
    scaled_bad = sum(1 for k in keys
                     if wanted.get(k, none)[3:] != got.get(k, none)[3:])
    count = sum(v[2] for v in got.values())
    p = entry.get("numbers", entry["name"])
    return {f"{p}_mismatched_groups": (bad, 0),
            f"{p}_scaled_mismatches": (scaled_bad, 0),
            "unaccounted_flows": (abs(count - n_flows), 0)}
