"""Table kind ``ranked_bytes_sliding``: at every slide end e, the keys
ranked by sum(bytes) over the last ``window_seconds``, from a ring of
sub-window sketches (``-window.slide``): the sink holds ``rank``, the
key's columns and ``bytes`` under ``timeslot`` e - ``window_seconds``.

What the reference computes (the configuration's ``stands_for``): W =
``window_seconds``, S = ``slide_seconds``, K = W / S. Sub-window of a flow:
j = time_received // S. For every sub-window j from the first consumed
flow's to the last one's, slide end e = (j + 1) * S: the exact sums by key
over the flows with e - W <= time_received < e, ranked. A window that
reaches back before the stream's first flow is the sum of what there is;
one that holds no flow has no rows; the last, which the stream's end cut
short, is the sum of what was consumed (the worker's forced flush).

The sums come from the benchmark's own draws, a sub-window at a time,
through ``Reference(spec with slot_seconds = S).slot_sums`` (the spec is
a dataclass whose event time does not depend on ``slot_seconds``), and K
consecutive ones are added for each slide end; then ranked as
``ranked_bytes.want`` ranks. A sub-window is found as a range of flows, by
bisection: for a stream whose event time never runs backwards, consumed
from one partition (anything else is refused). ``want`` is
handed no draws, so ``read_sink`` and ``control``, which are handed the
run and are called first, leave the sub-window sums of their precision on
the run's key table, which every ``Reference`` of the run shares.

An entry of ``checks.tables``: ``name``, ``key``, ``top_n``, ``limit`` as
``ranked_bytes``; ``window_seconds``, ``slide_seconds``.

numbers (limit):
  topk_bytes_max_rel_err (``limit``)  the worst relative error of bytes
      over the top ``top_n`` both ways of every slide end, the largest
      over the tables of this kind
  slide_windows_missing (0)  slide ends the consumed flows reached that
      have no rows in the sink, and timeslots in the sink that are no
      such slide end's
"""

import dataclasses

import numpy as np

from benchmark import check
from benchmark.reference import Reference
from benchmark.tables import ranked_bytes

KEEP = ranked_bytes.KEEP


def _ts(spec, flow: int) -> int:
    return int(spec.event_ts(np.array([flow], np.int64))[0])


def _first_flow_at(spec, ts: int, lo: int, hi: int) -> int:
    """The first flow of [lo, hi) whose event time is ``ts`` or later
    (``hi`` if none is)."""
    while lo < hi:
        mid = (lo + hi) // 2
        if _ts(spec, mid) < ts:
            lo = mid + 1
        else:
            hi = mid
    return lo


def _sub_sums(table, spec, precision: str, slide: int, run=None) -> dict:
    """{sub-window start: (bytes[keys], count[keys]) uint64} under
    ``precision``, kept on the run's key table once made."""
    kept = table.__dict__.setdefault("_sub_window_sums", {})
    if (precision, slide) not in kept:
        if run is None:
            raise RuntimeError(
                "ranked_bytes_sliding.want before read_sink or control "
                "made the sub-window sums")
        idx, rank, nbytes, packets = check.consumed_draws(run)
        n = len(idx)
        if spec.max_disorder_s or idx[-1] != n - 1:
            raise ValueError(
                "ranked_bytes_sliding finds a sub-window as a range of "
                "flows: event time must never run backwards and the flows "
                "consumed be every flow up to the last")
        ref = Reference(dataclasses.replace(spec, slot_seconds=slide),
                        table, precision)
        # event time is monotone in the flow index: a sub-window is a
        # range of flows, found by bisection and summed on its own (where
        # slot_sums over the whole stream would sort every flow's slot)
        edges = [0]
        while edges[-1] < n:
            edges.append(_first_flow_at(
                spec, (_ts(spec, edges[-1]) // slide + 1) * slide,
                edges[-1], n))
        sums: dict = {}
        for lo, hi in zip(edges, edges[1:]):
            for start, planes in ref.slot_sums(
                    idx[lo:hi], rank[lo:hi], nbytes[lo:hi],
                    packets[lo:hi]).items():
                sums[start] = (planes[0], planes[2])
        kept[precision, slide] = sums
    return kept[precision, slide]


def _windows(subs: dict, window: int, slide: int):
    """(timeslot, bytes, count) of every slide end from the first
    sub-window to the last, by a running sum over K sub-windows."""
    first, last = min(subs), max(subs)
    n = len(next(iter(subs.values()))[0])
    nbytes, count = np.zeros(n, np.uint64), np.zeros(n, np.uint64)
    for sub in range(first, last + slide, slide):
        for sign, at in ((1, sub), (-1, sub - window)):
            if at in subs:
                b, c = subs[at]
                nbytes = nbytes + b if sign > 0 else nbytes - b
                count = count + c if sign > 0 else count - c
        if count.any():
            yield sub + slide - window, nbytes, count


def _top(tot: np.ndarray, live: np.ndarray) -> np.ndarray:
    """The ``KEEP`` largest of ``live`` by ``tot``, ties by index: what a
    stable sort of all of them keeps, without sorting all of them."""
    if len(live) > KEEP:
        t = tot[live]
        live = live[t >= np.partition(t, len(t) - KEEP)[len(t) - KEEP]]
    return live[np.argsort(-tot[live], kind="stable")[:KEEP]]


def want(ref, entry: dict, sums: dict) -> dict:
    """{timeslot: {key tuple: exact bytes}} for every slide end, the
    ``KEEP`` largest keys by bytes under ``ref``'s precision (ties by key
    order). Made once for each precision and table."""
    window, slide = int(entry["window_seconds"]), int(entry["slide_seconds"])
    kept = ref.table.__dict__.setdefault("_sliding_wanted", {})
    at = (ref.precision, entry["name"], window, slide)
    if at in kept:
        return kept[at]
    cols = tuple(entry["key"])
    gid, first = ref.group_of_rank(cols)
    keys = np.stack([getattr(ref.table, c)[first] for c in cols], axis=1)
    out = {}
    for slot, nbytes, count in _windows(
            _sub_sums(ref.table, ref.spec, ref.precision, slide),
            window, slide):
        tot = np.bincount(gid, weights=nbytes.astype(np.float64),
                          minlength=len(first))
        cnt = np.bincount(gid, weights=count.astype(np.float64),
                          minlength=len(first))
        top = _top(tot, np.flatnonzero(cnt))
        out[slot] = dict(zip(map(tuple, keys[top].tolist()),
                             tot[top].astype(np.int64).tolist()))
    kept[at] = out
    return out


def read_sink(con, entry: dict, run) -> dict:
    """{timeslot: [(key tuple, bytes)] in rank order}."""
    _sub_sums(run.key_table, run.spec, "u64", int(entry["slide_seconds"]),
              run)
    return ranked_bytes.read_sink(con, entry, run)


def control(ref, entry: dict, sums: dict, run) -> dict:
    """What ``ref`` would have put in the sink."""
    _sub_sums(ref.table, ref.spec, ref.precision,
              int(entry["slide_seconds"]), run)
    depth = int(run.cell.config["sink_rows_per_window"])
    return {slot: list(keys.items())[:depth]
            for slot, keys in want(ref, entry, sums).items()}


def compare(entry: dict, wanted: dict, got: dict, n_flows: int) -> dict:
    found = ranked_bytes.compare(entry, wanted, got, n_flows)
    missing = sum(1 for slot in wanted if not got.get(slot)) \
        + sum(1 for slot in got if slot not in wanted)
    return {**found, "slide_windows_missing": (missing, 0)}
