"""The comparison that decides ``correct``.

Made after the window has closed, the stream has ended and the worker has
finalized, over exactly the flows the worker consumed (``flows_seen``):
what the timed path wrote to its sink, its commits and its query surface,
against the plain reference (``reference.py``). A configuration's file
lists its tables (``checks.tables``: each names its kind, a module under
``tables/`` that reads the sink, asks the reference and compares) and its
query checks (``checks.queries``: each names its kind, a module under
``queries/``). Each number compared has a limit of its own; where several
tables give the same number, the largest stands against the least limit.

numbers of this module (limit); those of the tables are in their kinds:
  commit_offset_gap (0)       |committed offset - flows consumed|
  commits_ahead_of_flush (0)  commits made in the run whose offset
      covers a window close that had not reached the sink by then
  query_mismatches (0)        answers of the query surface at the
      final version that differ from the sink's rows
"""

from __future__ import annotations

import os
import sqlite3

import numpy as np

from .flowgen import KeyTable
from .reference import Reference


def _draw_arrays(run):
    n = run.final["flows_seen"]
    c = run.spec.chunk_flows
    k = -(-n // c)
    rank, nbytes, packets = (np.concatenate([d[i] for d in run.draws[:k]])
                             for i in range(3))
    return rank, nbytes, packets, n


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


def _commits_ahead_of_flush(run) -> int:
    """Commit-after-flush: when a commit covers a close (the first flow of
    a new slot), that close's rows reached the sink before the commit
    began. A close's rows have reached the sink when the first write of
    the configuration's ``close_table`` that began after the fetch of the
    closing flow has returned (two closes in one batch share it)."""
    spans = run.spans.spans
    table = run.cell.config["close_table"]
    writes = sorted((s[1], s[2]) for s in spans
                    if s[0] == "sink_write" and s[4] == table)
    fetches = run.fetches()
    written_at = {}
    for c in run.spec.close_flows(0, run.final["flows_seen"] + 1):
        taken = next((t for t, first, n in fetches
                      if first <= c < first + n), None)
        written_at[c] = next((t1 for t0, t1 in writes
                              if taken is not None and t0 >= taken),
                             float("inf"))
    return sum(1 for s in spans
               if s[0] == "bus_commit" and s[4] is not None
               and any(c < s[4] and t > s[1]
                       for c, t in written_at.items()))


def run_checks(run) -> list:
    cfg = run.cell.config
    con = sqlite3.connect(os.path.join(run.rundir, "sink.db"))
    try:
        rank, nbytes, packets, n = _draw_arrays(run)
        run.key_table = KeyTable(run.spec)
        ref = Reference(run.spec, run.key_table)
        run.ref_sums = ref.slot_sums(rank, nbytes, packets, 0, n)
        sink = [run.cell.table_kinds[e["kind"]].read_sink(con, e, run)
                for e in cfg["checks"]["tables"]]
        out = _numbers(run, ref, run.ref_sums, sink, n)

        def num(name, value):
            out.append({"name": name, "value": value, "limit": 0,
                        "ok": value == 0})

        num("commit_offset_gap", abs(run.final["committed"] - n))
        num("commits_ahead_of_flush", _commits_ahead_of_flush(run))
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
    rank, nbytes, packets, n = _draw_arrays(run)
    low_sums = low.slot_sums(rank, nbytes, packets, 0, n)
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
