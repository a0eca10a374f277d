"""Find a cell's files by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name. A workload entry names a configuration (whose ``file`` is in the
manifest's ``configs``) and a traffic mix (``<dir>/traffic/<name>.json``);
a per-layer metric ``m`` is read by ``<dir>/layer_metrics/<m>.py`` and an
end-to-end metric by ``<dir>/end_to_end/<m>.py``, each a module with
``read(run) -> float | None``. The traffic file's ``mode`` is the module
``<dir>/modes/<mode>.py`` (``plan``, ``control``, ``window_flows``,
``describe``); each kind named in the configuration's ``checks.tables``
is ``<dir>/tables/<kind>.py`` (``want``, ``read_sink``, ``control``,
``compare``) and each in ``checks.queries`` is ``<dir>/queries/<kind>.py``
(``mismatches``). The configuration's ``stream.kind`` is the module
``<dir>/streams/<kind>.py`` (below); without the key it is
``zipf-ranks``. ``<dir>`` is each of the manifest's ``paths`` in turn.
A later PR adds entries and files and edits none.

A stream kind (PR 38), the contract on one page. The invariant, which
``reference.py`` has always lived by: *a flow is (position, rank, bytes,
packets); everything else about it is the kind's key table at that rank,
its event time is the kind's function of position and seed, and its
partition is the kind's function of position, seed and the partition
count.* A kind's file exports (``STREAM_API``):

  spec(seed, stream, first_close_flow, phase_s)  from ``--seed``, the
      configuration's whole ``stream`` object and the plan's phase lock
      (``schedule.py``); a key of ``stream`` that the kind does not know
      is an error that names it, never dropped (``kind`` it may ignore).
      What it returns is frozen, a dataclass, and has (``SPEC_API``):
      ``seed``, ``chunk_flows``, ``slot_seconds``; ``max_disorder_s``, the
      most event seconds a flow may lie behind one at an earlier position
      (0: event time never runs backwards; above 0 ``reference.slot_sums``
      groups by slot without assuming runs); ``event_ts(idx)``, uint64
      seconds of the flows at positions ``idx``; ``close_flows(lo, hi)``,
      the positions in [lo, hi) that are the first flow of a slot, the
      same whatever the seed; ``partition_of(idx, partitions)``, the
      partition of each position. ``stream`` itself tells the plan
      ``chunk_flows``, ``event_rate`` and ``slot_seconds``: event time
      advances a second every ``event_rate`` flows, give or take the
      disorder.
  key_table(spec)   the key universe: any columns, numpy arrays of
      ``len(table)`` rows, one a rank; a table kind names the columns it
      groups by
  chunk_draws(spec, table, chunk)   (rank, bytes, packets) of a chunk's
      positions: all that is random about its flows, and all the
      reference is handed
  chunk_columns(spec, table, chunk, draws)   the chunk in the program's
      column layout; ``etype``, ``sampling_rate``, the address words may
      come from the table

The ring, the cut format, the encoder and the worker pool are
``flowgen.py``'s and load the kind by its path. Cut for three consumers:
dual-stack keys (a per-rank ``etype`` and address words), an onset (a rank
distribution that changes at a stated position) and disorder on two
partitions; the fixture kind ``tests/fixtures/streams/toy-mixed.py`` is
all three. Left out on purpose: a per-flow attribute outside the key
table (a flow's rank is all the reference knows of it); a key in
``zipf-ranks`` that no configuration sets; a second way to name a stream.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list = field(default_factory=list)   # (entry, reader module)
    per_layer: list = field(default_factory=list)    # (entry, reader module)
    mode: object = None                              # modes/<mode>.py
    stream: object = None                            # Stream, below
    table_kinds: dict = field(default_factory=dict)  # kind -> module
    query_kinds: dict = field(default_factory=dict)  # kind -> module


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _find(root: str, paths: list, *parts: str) -> str:
    for p in paths:
        cand = os.path.join(root, p, *parts)
        if os.path.isfile(cand):
            return cand
    raise FileNotFoundError(
        f"{os.path.join(*parts)} not found under any of {paths}")


def _load_module(path: str, needs: tuple = ("read",)):
    """The module at ``path``, executed once a process: in ``sys.modules``
    under a name made from its absolute path (a dataclass looks its own
    module up there), so that every loader of a file shares its classes."""
    path = os.path.abspath(path)
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in path[:-3])
    mod = sys.modules.get(name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    for attr in needs:
        if not callable(getattr(mod, attr, None)):
            raise TypeError(f"{path} defines no {attr}()")
    return mod


def _load_reader(path: str):
    return _load_module(path)


MODE_API = ("plan", "control", "window_flows", "describe")
TABLE_API = ("want", "read_sink", "control", "compare")
QUERY_API = ("mismatches",)
STREAM_API = ("spec", "key_table", "chunk_draws", "chunk_columns")
SPEC_API = ("seed", "chunk_flows", "slot_seconds", "max_disorder_s",
            "event_ts", "close_flows", "partition_of")
DEFAULT_STREAM = "zipf-ranks"


class Stream(dict):
    """A configuration's ``stream`` object with its kind beside it: the
    module ``kind`` and the file ``path`` it was loaded from (the worker
    processes load it again by that path)."""

    def __init__(self, params: dict, kind, path: str):
        super().__init__(params)
        self.kind, self.path = kind, path

    def with_params(self, **changed) -> "Stream":
        return Stream({**self, **changed}, self.kind, self.path)

    def spec(self, seed: int, first_close_flow: int, phase_s: int):
        spec = self.kind.spec(int(seed), dict(self), int(first_close_flow),
                              int(phase_s))
        lacks = [a for a in SPEC_API if not hasattr(spec, a)]
        if lacks:
            raise TypeError(f"{self.path}: what spec() returns has no "
                            f"{lacks}")
        return spec


def load_stream_kind(path: str):
    return _load_module(path, STREAM_API)


def load_stream(root: str, paths: list, params: dict) -> Stream:
    """``params`` (a configuration's ``stream``) with the kind it names,
    found under each of ``paths`` in turn."""
    path = _find(root, paths, "streams",
                 params.get("kind", DEFAULT_STREAM) + ".py")
    return Stream(params, load_stream_kind(path), path)


def load_cell(root: str, manifest_path: str, workload: str) -> Cell:
    man = _load_json(manifest_path)
    paths = man["paths"]
    entry = next((w for w in man["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(
            f"no workload {workload!r} in {manifest_path}; it has "
            f"{[w['name'] for w in man['workloads']]}")
    cfg_entry = next(c for c in man["configs"]
                     if c["name"] == entry["config"])
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    traffic = _load_json(
        _find(root, paths, "traffic", entry["traffic"] + ".json"))
    e2e = [(m, _load_reader(_find(root, paths, "end_to_end",
                                  m["name"] + ".py")))
           for m in man["end_to_end"] if _applies(m, workload)]
    names = {m["name"] for m, _r in e2e}
    layer = []
    for m in man["per_layer"]:
        if not _applies(m, workload):
            continue
        if m["moves"] not in names:
            raise ValueError(
                f"per-layer metric {m['name']} moves {m['moves']}, which "
                f"cell {workload} does not report")
        layer.append((m, _load_reader(
            _find(root, paths, "layer_metrics", m["name"] + ".py"))))
    checks = config.get("checks", {})
    return Cell(
        workload, int(entry["chips"]), entry["config"], entry["traffic"],
        config, traffic, e2e, layer,
        stream=load_stream(root, paths, config["stream"]),
        mode=_load_module(_find(root, paths, "modes",
                                traffic["mode"] + ".py"), MODE_API),
        table_kinds={k: _load_module(_find(root, paths, "tables", k + ".py"),
                                     TABLE_API)
                     for k in {e["kind"] for e in checks.get("tables", [])}},
        query_kinds={k: _load_module(_find(root, paths, "queries",
                                           k + ".py"), QUERY_API)
                     for k in {q["kind"]
                               for q in checks.get("queries", [])}})
