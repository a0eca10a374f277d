"""The comparison that decides ``correct``.

Made after the window has closed, the stream has ended and the worker has
finalized, over exactly the flows the worker consumed: on every partition
the flows below the offset it had folded up to (``final.folded``, which
sum to ``flows_seen``), at the positions the stream's kind dealt there
(``drive.Deal``). What the timed path wrote to its sink, its commits and
its query surface, against the plain reference (``reference.py``). A
configuration's file lists its tables (``checks.tables``: each names its
kind, a module under ``tables/`` that reads the sink, asks the reference
and compares) and its query checks (``checks.queries``: each names its
kind, a module under ``queries/``). Each number compared has a limit of
its own; where several tables give the same number, the largest stands
against the least limit.

numbers of this module (limit); those of the tables are in their kinds:
  commit_offset_gap (0)       |committed offset - offset folded up to|,
      summed over the partitions
  commits_ahead_of_flush (0)  commits made in the run whose offset
      covers a window close on their partition that had not reached the
      sink by then
  query_mismatches (0)        answers of the query surface at the
      final version that differ from the sink's rows
"""

from __future__ import annotations

import os
import sqlite3

import numpy as np

from .reference import Reference


def consumed_draws(run) -> tuple:
    """(positions, rank, bytes, packets) of the flows consumed, by
    ascending position."""
    idx = run.deal.consumed(run.final["folded"])
    if not len(idx):
        raise ValueError("the worker consumed no flow")
    chunks = int(idx[-1]) // run.spec.chunk_flows + 1
    draws = [np.concatenate([d[i] for d in run.draws[:chunks]])
             for i in range(3)]
    if idx[-1] == len(idx) - 1:  # every flow up to the last: views
        return (idx, *(d[:len(idx)] for d in draws))
    return (idx, *(d[idx] for d in draws))


def _numbers(run, ref: Reference, sums: dict, outputs: dict,
             n_flows: int) -> list:
    """Every table's numbers for ``outputs`` (what each entry's kind's
    read_sink or control returned, in the entries' order) against ``ref``
    over ``sums``."""
    merged: dict = {}
    for entry, got in zip(run.cell.config["checks"]["tables"], outputs):
        kind = run.cell.table_kinds[entry["kind"]]
        found = kind.compare(entry, kind.want(ref, entry, sums), got,
                             n_flows)
        for name, (value, limit) in found.items():
            v, lim = merged.get(name, (value, limit))
            merged[name] = (max(v, value), min(lim, limit))
    return [{"name": n, "value": v, "limit": lim, "ok": bool(v <= lim)}
            for n, (v, lim) in merged.items()]


def _closing_offset(run, partition: int, close: int, ts: int):
    """The first offset of ``partition`` at or past position ``close``
    whose flow's event time is ``ts`` or later, below what the worker
    folded; None if there is none."""
    at, _ = run.deal.offsets(partition, close, close)
    end = run.final["folded"][partition]
    while at < end:
        block = run.deal.positions(partition, at, min(end - at, 65536))
        hit = np.flatnonzero(run.spec.event_ts(block).astype(np.int64) >= ts)
        if len(hit):
            return at + int(hit[0])
        at += len(block)
    return None


def _commits_ahead_of_flush(run, last: int) -> int:
    """Commit-after-flush: when a commit covers a close, that close's rows
    reached the sink before the commit began (``last``: the highest
    position consumed). A slot closes at the first
    flow to arrive, on any partition, whose event time lies the
    configuration's ``close_lateness_s`` (0 where it has none: the first
    flow of the next slot) or more past the slot's end; a commit covers
    the close when it takes in the first such flow of its own partition.
    A close's rows have reached the sink when the first write of the
    configuration's ``close_table`` that began after the fetch of the
    closing flow has returned (two closes in one batch share it)."""
    spans, spec = run.spans.spans, run.spec
    table = run.cell.config["close_table"]
    lateness = int(run.cell.config.get("close_lateness_s", 0))
    writes = sorted((s[1], s[2]) for s in spans
                    if s[0] == "sink_write" and s[4] == table)
    fetches = run.fetches()
    parts = range(run.deal.partitions)
    closes = []   # (offset that closes it a partition, when written)
    for c in spec.close_flows(0, last + 2):
        slot = int(spec.event_ts(np.array([c]))[0]) \
            // spec.slot_seconds * spec.slot_seconds
        at = [_closing_offset(run, p, c, slot + lateness) for p in parts]
        taken = min((t for t, p, first, n, _pos in fetches
                     if at[p] is not None and first <= at[p] < first + n),
                    default=None)
        closes.append((at, next((t1 for t0, t1 in writes
                                 if taken is not None and t0 >= taken),
                                float("inf"))))
    return sum(1 for s in spans
               if s[0] == "bus_commit"
               and any(at[s[4][0]] is not None and at[s[4][0]] < s[4][1]
                       and written > s[1] for at, written in closes))


def late_flows(run) -> int:
    """The reference's own count of the flows that arrive after their
    slot has rolled, which a sketch family drops (a closed sketch cannot
    reopen): batches in the order they were fetched, a batch's slots in
    ascending order, a slot older than the newest one seen is late. 0
    without looking where event time never runs backwards on one
    partition."""
    spec = run.spec
    if run.deal.partitions == 1 and not spec.max_disorder_s:
        return 0
    folded, newest, late = run.final["folded"], None, 0
    for _t, p, first, n, _pos in run.fetches():
        n = min(n, folded[p] - first)
        if n <= 0:
            continue  # fetched ahead, never folded
        ts = spec.event_ts(run.deal.positions(p, first, n)).astype(np.int64)
        slots, counts = np.unique(ts // spec.slot_seconds, return_counts=True)
        for slot, count in zip(slots.tolist(), counts.tolist()):
            if newest is None or slot > newest:
                newest = slot
            elif slot < newest:
                late += count
    return late


def run_checks(run) -> list:
    cfg = run.cell.config
    con = sqlite3.connect(os.path.join(run.rundir, "sink.db"))
    try:
        draws = consumed_draws(run)
        n = len(draws[0])
        run.key_table = run.cell.stream.kind.key_table(run.spec)
        ref = Reference(run.spec, run.key_table)
        run.ref_sums = ref.slot_sums(*draws)
        sink = [run.cell.table_kinds[e["kind"]].read_sink(con, e, run)
                for e in cfg["checks"]["tables"]]
        out = _numbers(run, ref, run.ref_sums, sink, n)

        def num(name, value):
            out.append({"name": name, "value": value, "limit": 0,
                        "ok": value == 0})

        num("commit_offset_gap", sum(
            abs(c - f) for c, f in zip(run.final["committed"],
                                       run.final["folded"])))
        num("commits_ahead_of_flush",
            _commits_ahead_of_flush(run, int(draws[0][-1])))
        if cfg["checks"].get("queries"):
            num("query_mismatches", sum(
                run.cell.query_kinds[q["kind"]].mismatches(run, con, q)
                for q in cfg["checks"]["queries"]))
        return out
    finally:
        con.close()


def run_control(run, control: str) -> dict:
    """The control: the reference in a lower precision, put in the
    program's place, under the same comparison. It has to come out not
    correct. ``control`` is ``<precision>`` (every table) or
    ``<precision>:<kind>`` (the tables of that kind alone; the others
    come from the exact reference, as a sound program's would)."""
    precision, _, only = control.partition(":")
    exact = Reference(run.spec, run.key_table)
    low = Reference(run.spec, run.key_table, precision)
    draws = consumed_draws(run)
    n = len(draws[0])
    low_sums = low.slot_sums(*draws)
    outputs = []
    for e in run.cell.config["checks"]["tables"]:
        kind = run.cell.table_kinds[e["kind"]]
        lowered = not only or e["kind"] == only
        outputs.append(kind.control(
            low if lowered else exact,
            e, low_sums if lowered else run.ref_sums, run))
    checks = _numbers(run, exact, run.ref_sums, outputs, n)
    return {"control": control, "checks": checks,
            "correct": all(c["ok"] for c in checks)}
