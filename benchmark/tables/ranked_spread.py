"""Table kind ``ranked_spread``: per timeslot, the sources ranked by how
many distinct elements they touched (destination hosts, destination
ports), from a sketch: the sink holds ``rank``, the source's address and
``spread``, a register-decoded estimate. Compared both ways over every
slot, as ``ranked_bytes`` compares: the sink's first ``top_n`` rows
against their exact distinct counts, and the reference's ``top_n``
sources against the sink's rows.

The exact count is this file's own (the plain reference of a distinct
count; ``flow_pipeline_tpu/models/oracle.py::distinct_exact`` is the
program's copy of the same idea and neither imports the other): a flow is
(position, rank, bytes, packets), so a rank was seen in a slot iff its
count there (``Reference.slot_sums``) is above 0, and a source's distinct
elements are the distinct values of the element column over its seen
ranks. Sets by sort and unique, in numpy; no hash of the program's.

An entry of ``checks.tables``: ``name`` (the sink's table), ``key`` (one
column of the key table: the source, a host number), ``element`` (the
column counted), ``top_n``, ``limit`` and ``heavy_limit`` (set from
readings, PERF.md 2), ``heavy`` and ``floor``. Every one of the
reference's ``top_n`` sources has to be among the sink's rows of the
slot. ``floor`` is for the slot the warm-up leaves, 65,536 flows whose
``top_n``-th source has 3 elements and shares its place with hundreds:
below ``floor`` elements a place among the ``top_n`` is a tie, so such a
source need not be among the rows, and an error is taken relative to
``max(exact, floor)`` (5 decoded for 1 is four foreign elements in a
bucket, not 400 %). In every other slot the ``top_n``-th source lies
above it (PERF.md 2).

numbers (limit):
  spread_max_rel_err (``limit``)  the worst |spread - exact| /
      max(exact, floor) over the slots, both ways, the largest over the
      tables of this kind
  spread_heavy_rms_rel_err (``heavy_limit``)  over every slot's reference
      sources of ``heavy`` elements or more, the root mean square of
      (spread - exact) / exact, a missing one counting as 1: what the
      configuration's ``spread_rel_err_max`` promises. The mean and not
      the worst: a sketch's error has a tail, and with 256 registers one
      such source in a few hundred decodes more than a quarter off
      (PERF.md 2 has the count), so the worst of a run is held by
      ``limit`` and the guarantee by this
  spread_missing_keys (0)  reference sources of ``floor`` elements or
      more among a slot's first ``top_n`` that the sink's rows of the
      slot lack

``control`` puts a source's *flow count* where its distinct count
belongs: what a sum in the place of the max would report, whatever the
precision (``--control bf16:ranked_spread``). It has to come out not
correct.

What the kind needs of the program, asked as the module loads
(``require_typed_tables``): the sink's typed tables of the family, whose
columns ``read_sink`` selects. Of the program this file imports
``sink/ddl.py``'s list of columns and nothing else: no sketch, no hash,
no model.
"""

import ipaddress

import numpy as np

from benchmark.drive import Abort

_PREFIX = 0x20010DB8_00000001_00000000_0000 << 16  # zipf-ranks' /112
TABLES = ("superspreaders", "portscan")
COLUMNS = ("timeslot", "rank", "src_addr", "spread")


def require_typed_tables() -> None:
    """A cell that lists this kind ends here, as its files load and before
    a stream is made, on a program whose sink has no typed table of the
    family (``sink/ddl.py::TABLE_COLUMNS``, PR 47). Such a program keeps a
    detector's rows in the sink's untyped journal, and it is also the one
    whose candidate table admits by a batch's count of pairs and so loses
    a slow spreader: read from the journal it ran this kind's cell at its
    own pace and came out not correct, ``spread_missing_keys`` 1-2 a run
    (my chip runs, PR 47; the driver's, seed 585587331: PERF.md 6). It
    cannot hold what this kind compares, so it is given no result to
    fail with."""
    from flow_pipeline_tpu.sink.ddl import TABLE_COLUMNS

    lacks = [t for t in TABLES
             if not set(COLUMNS) <= set(TABLE_COLUMNS.get(t, ()))]
    if lacks:
        raise Abort(
            f"table kind ranked_spread reads the sink's typed tables "
            f"{list(TABLES)} ({', '.join(COLUMNS)}); this program's "
            f"sink/ddl.py has no such {lacks}: it is from before the "
            f"spread family's typed tables and cannot run the cell")


require_typed_tables()


def _host(addr: str) -> int:
    v = int(ipaddress.IPv6Address(addr))
    return v & 0xFFFF if v >> 16 << 16 == _PREFIX else -1


def _per_source(ref, entry: dict, counts: np.ndarray):
    """(sources, distinct elements of each, flows of each) over the ranks
    whose ``counts`` are above 0."""
    seen = np.flatnonzero(counts)
    src = getattr(ref.table, entry["key"][0])[seen].astype(np.int64)
    elem = getattr(ref.table, entry["element"])[seen].astype(np.int64)
    # the distinct (source, element) pairs, then how many a source
    span = int(elem.max(initial=0)) + 1
    sources, distinct = np.unique(np.unique(src * span + elem) // span,
                                  return_counts=True)
    flows = np.zeros(int(src.max(initial=0)) + 1, np.float64)
    np.add.at(flows, src, counts[seen].astype(np.float64))
    return sources, distinct, flows[sources]


def want(ref, entry: dict, sums: dict) -> dict:
    """{timeslot: {source: exact distinct elements}}, every source the
    slot saw, largest first (ties by source)."""
    out = {}
    for slot, planes in sums.items():
        sources, distinct, _flows = _per_source(ref, entry, planes[2])
        order = np.argsort(-distinct, kind="stable")
        out[slot] = {int(sources[i]): int(distinct[i]) for i in order}
    return out


def read_sink(con, entry: dict, run) -> dict:
    """{timeslot: [(source, spread)] in rank order}, from the sink's
    typed table."""
    out = {}
    for slot, addr, spread in con.execute(
            f"SELECT timeslot, src_addr, spread FROM {entry['name']} "
            f"ORDER BY timeslot, rank"):
        out.setdefault(int(slot), []).append((_host(addr), float(spread)))
    return out


def control(ref, entry: dict, sums: dict, run) -> dict:
    """What a program that SUMS where it should take the max would put
    in the sink: the sources by their flow counts."""
    depth = int(run.cell.config["sink_rows_per_window"])
    out = {}
    for slot, planes in sums.items():
        sources, _distinct, flows = _per_source(ref, entry, planes[2])
        order = np.argsort(-flows, kind="stable")[:depth]
        out[slot] = [(int(sources[i]), float(flows[i])) for i in order]
    return out


def compare(entry: dict, wanted: dict, got: dict, n_flows: int) -> dict:
    top_n, floor = int(entry["top_n"]), float(entry["floor"])
    heavy = int(entry["heavy"])
    worst, missing, squares = 0.0, 0, []
    for slot, exact in wanted.items():
        rows = got.get(slot, [])
        have = dict(rows)
        errs = [abs(s - exact.get(k, 0)) / max(exact.get(k, 0), floor)
                for k, s in rows[:top_n]]
        for k, n in list(exact.items())[:top_n]:
            if k in have:
                errs.append(abs(have[k] - n) / max(n, floor))
            elif n >= floor:
                missing += 1
        worst = max([worst, *errs])
        # largest first: the heavy sources open the slot's dict
        for k, n in exact.items():
            if n < heavy:
                break
            squares.append(((have[k] - n) / n) ** 2 if k in have else 1.0)
    rms = float(np.sqrt(np.mean(squares))) if squares else 0.0
    return {"spread_max_rel_err": (worst, float(entry["limit"])),
            "spread_heavy_rms_rel_err": (rms, float(entry["heavy_limit"])),
            "spread_missing_keys": (missing, 0)}
