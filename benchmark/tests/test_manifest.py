"""The harness is data: a cell, a traffic mix and a per-layer metric are
found by name, and each can be added as files and entries only."""

import json
import os
import re

import pytest

from benchmark import manifest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REAL = os.path.join(ROOT, "BENCHMARK.json")
TINY = os.path.join(HERE, "fixtures", "BENCHMARK.tiny.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


# a configuration that names no stream kind gets zipf-ranks, in order, on
# the one partition it states; one names its own (PR 39): the kind, the
# most seconds a flow lies behind an earlier one, the partitions
STREAMS = {"estate-2part": ("zipf-ranks-delayed", 3, 2)}


def _man(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in _man(REAL)["workloads"]])
def test_every_cell_of_the_benchmark_loads(cell):
    c = manifest.load_cell(ROOT, REAL, cell)
    assert c.config["name"] == c.config_name
    assert c.traffic["name"] == c.traffic_name
    assert "setup_s" in {m["name"] for m, _ in c.end_to_end}
    assert len(c.end_to_end) >= 2 and len(c.per_layer) >= 1
    assert all(callable(r.read) for _, r in c.end_to_end + c.per_layer)
    kind, disorder_s, partitions = STREAMS.get(
        c.config_name, (manifest.DEFAULT_STREAM, 0, 1))
    assert c.config["stream"].get("kind", manifest.DEFAULT_STREAM) == kind
    assert os.path.basename(c.stream.path) == kind + ".py"
    assert all(callable(getattr(c.stream.kind, a))
               for a in manifest.STREAM_API)
    spec = c.stream.spec(1, 65536, 0)
    assert all(hasattr(spec, a) for a in manifest.SPEC_API)
    assert spec.max_disorder_s == disorder_s
    assert c.config["bus_partitions"] == partitions


def test_cells_added_as_files_only():
    """The fixture manifest adds configurations, traffic mixes, a
    per-layer metric, a traffic mode and a table kind under a directory
    of its own; no file under benchmark/ outside that directory names any
    of them."""
    c = manifest.load_cell(ROOT, TINY, "tiny-catchup")
    assert c.traffic["mode"] == "backlog"
    assert c.mode.__file__.endswith(os.path.join("modes", "backlog.py"))
    assert set(c.table_kinds) == {"exact_sums", "ranked_bytes"}
    assert set(c.query_kinds) == {"range_flows5m"}
    assert "batches_in_window.fixture" in {m["name"] for m, _ in c.per_layer}
    live = manifest.load_cell(ROOT, TINY, "tiny-live")
    assert "batches_in_window.fixture" not in {
        m["name"] for m, _ in live.per_layer}
    added = manifest.load_cell(ROOT, TINY, "tiny-totals-catchup")
    fixtures = os.path.join(HERE, "fixtures")
    assert added.mode.__file__.startswith(fixtures)
    assert added.table_kinds["slot_totals.fixture"].__file__.startswith(
        fixtures)
    new = ("tiny-estate", "tiny-backlog", "tiny-live", "tiny-catchup",
           "batches_in_window", "tiny-totals", "tiny-close-late",
           "backlog_close_late", "slot_totals")
    for d, _sub, files in os.walk(os.path.join(ROOT, "benchmark")):
        if os.sep + "tests" in d or "__pycache__" in d:
            continue
        for fn in files:
            with open(os.path.join(d, fn), errors="ignore") as f:
                text = f.read()
            assert not any(n in text for n in new), (d, fn)


def test_the_harness_names_no_cell():
    names = {x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in _man(REAL)[k]}
    names |= {w["traffic"] for w in _man(REAL)["workloads"]}
    names |= {json.load(open(os.path.join(ROOT, c["file"])))["name"]
              for c in _man(REAL)["configs"]}
    for kind in ("modes", "tables", "queries"):
        names |= {fn[:-3] for fn in os.listdir(
            os.path.join(ROOT, "benchmark", kind)) if fn.endswith(".py")}
    for fn in ("run.py", "drive.py", "sut.py", "manifest.py", "schedule.py",
               "check.py", "reference.py", "trace_reduce.py", "spans.py",
               "reduce.py", "flowgen.py"):
        with open(os.path.join(ROOT, "benchmark", fn)) as f:
            code = re.sub(r'""".*?"""', "", f.read(), flags=re.S)
        hits = [n for n in names if re.search(
            r"['\"]%s['\"]" % re.escape(n), code)]
        assert not hits, (fn, hits)


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        manifest.load_cell(ROOT, REAL, "no-such-cell")


def test_an_unknown_mode_or_kind_is_an_error_before_anything_runs(tmp_path):
    man = _man(TINY)
    for sub, key, val in (("traffic", "mode", "no_such_mode"),):
        t = json.load(open(os.path.join(HERE, "fixtures", sub,
                                        "tiny-backlog.json")))
        t[key] = val
        os.makedirs(tmp_path / sub, exist_ok=True)
        with open(tmp_path / sub / "tiny-backlog.json", "w") as f:
            json.dump(t, f)
    man["paths"] = [os.path.relpath(tmp_path, ROOT)] + man["paths"]
    with open(tmp_path / "m.json", "w") as f:
        json.dump(man, f)
    with pytest.raises(FileNotFoundError, match="no_such_mode"):
        manifest.load_cell(ROOT, str(tmp_path / "m.json"), "tiny-catchup")


def test_contract_shape():
    man = _man(REAL)
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= man["run_seconds"] <= 51
    cells = {w["name"]: w for w in man["workloads"]}
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in man["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in man["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            assert w in e2e[m["moves"]].get("workloads", cells), m["name"]
    for x in (man["configs"] + man["workloads"] + man["end_to_end"]
              + man["per_layer"]):
        assert NAME.match(x["name"]), x["name"]
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert four <= max(1, len(cells) // 2)
    used = {w["config"] for w in man["workloads"]}
    assert used == {c["name"] for c in man["configs"]}
    for c in man["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert set(c["reduced"]) <= set(_man(os.path.join(
            ROOT, c["file"]))["reduced"])


# the twins that tests/ names, each with the file and line that names it
# (at PR 41): a PR that may edit tests/ retires them into their bases'
# lists, as the other seventeen were
KEPT_TWINS = {
    "device_steps_per_batch.2part": "tests/test_benchmark_seam.py:424",
    "batch_fill_share.2part": "tests/test_benchmark_seam.py:425",
    "step_device_ms_p50.2part": "tests/test_bench_stream_seam.py:474",
    "fused_step_roofline.2part": "tests/test_bench_stream_seam.py:474",
    "batch_period_ms_p50.2part": "tests/test_bench_stream_seam.py:475",
    "checkpoint_raw_mb_p50.2part": "tests/test_bench_stream_seam.py:475",
    "checkpoint_raw_mb_p50.sliding": "tests/test_benchmark_seam.py:382",
}


def test_a_new_cell_joins_a_metrics_list_and_adds_no_twin_of_it():
    """An entry ``<base>.<suffix>`` that differs from the entry ``<base>``
    in its name and cells alone is the same reader under a second name:
    the cell belongs in the base's ``workloads``, and an entry of its own
    is for a span, a counter or a kernel that is new."""
    entries = {m["name"]: m for m in _man(REAL)["per_layer"]}

    def rest(m):
        return {k: v for k, v in m.items() if k not in ("name", "workloads")}

    twins = [name for name, m in entries.items()
             if rest(entries.get(name.rpartition(".")[0], {})) == rest(m)]
    assert sorted(twins) == sorted(KEPT_TWINS)
    for name, where in KEPT_TWINS.items():
        with open(os.path.join(ROOT, where.split(":")[0])) as f:
            assert f'"{name}"' in f.read(), where
