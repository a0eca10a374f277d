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
(``mismatches``). ``<dir>`` is each of the manifest's ``paths`` in turn.
A later PR adds entries and files and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
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
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path)[:-3])
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for attr in needs:
        if not callable(getattr(mod, attr, None)):
            raise TypeError(f"{path} defines no {attr}()")
    return mod


def _load_reader(path: str):
    return _load_module(path)


MODE_API = ("plan", "control", "window_flows", "describe")
TABLE_API = ("want", "read_sink", "control", "compare")
QUERY_API = ("mismatches",)


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
        mode=_load_module(_find(root, paths, "modes",
                                traffic["mode"] + ".py"), MODE_API),
        table_kinds={k: _load_module(_find(root, paths, "tables", k + ".py"),
                                     TABLE_API)
                     for k in {e["kind"] for e in checks.get("tables", [])}},
        query_kinds={k: _load_module(_find(root, paths, "queries",
                                           k + ".py"), QUERY_API)
                     for k in {q["kind"]
                               for q in checks.get("queries", [])}})
