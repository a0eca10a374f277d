"""The stream seam (PR 38): a stream kind is a file the manifest finds,
``zipf-ranks`` behind it makes the bytes ``flowgen.py`` made, and a
configuration's partitions are what the harness drives: the arithmetic of
what was consumed on several of them, and the fixture cell that runs a
kind added as a file on two."""

import hashlib
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import check, drive, flowgen, manifest, schedule
from benchmark.modes import backlog

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REAL = os.path.join(ROOT, "BENCHMARK.json")
TINY_STREAM = "benchmark/tests/fixtures/BENCHMARK.tiny-stream.json"
PATHS = ["benchmark", "benchmark/tests/fixtures"]
with open(os.path.join(HERE, "fixtures", "zipf_ranks_digests.json")) as f:
    # chunk_blob of chunks 0, 1 and the last of every cell at 51 s, two
    # seeds, by the parent of PR 38 (2a8a86d) before the first edit
    DIGESTS = json.load(f)


# ---- a kind is a file, found by name ---------------------------------------


def _stream(**changed):
    with open(os.path.join(ROOT, "benchmark/configs/default-estate.json")) \
            as f:
        return manifest.load_stream(ROOT, PATHS,
                                    {**json.load(f)["stream"], **changed})


def test_a_configuration_without_the_key_gets_zipf_ranks():
    stream = _stream()
    assert "kind" not in stream
    assert stream.path == os.path.join(ROOT, "benchmark", "streams",
                                       "zipf-ranks.py")
    assert stream.kind.StreamSpec is flowgen.StreamSpec  # loaded once


def test_an_unknown_stream_key_is_an_error_that_names_it():
    with pytest.raises(ValueError, match=r"no key \['attack_share'\]"):
        _stream(attack_share=0.1).spec(1, 4096, 0)
    toy = manifest.load_cell(ROOT, os.path.join(ROOT, TINY_STREAM),
                             "tiny-stream-2part-catchup").stream
    with pytest.raises(ValueError, match=r"unknown keys \['alpha_2'\]"):
        toy.with_params(alpha_2=1.0).spec(1, 4096, 0)


def test_a_missing_kind_file_names_the_paths_searched():
    with pytest.raises(FileNotFoundError) as e:
        _stream(kind="no-such-kind")
    assert "no-such-kind.py" in str(e.value)
    assert all(p in str(e.value) for p in PATHS)


def test_a_kind_that_lacks_part_of_the_api_is_refused(tmp_path):
    os.makedirs(tmp_path / "streams")
    with open(tmp_path / "streams" / "half.py", "w") as f:
        f.write("def spec(*a): return None\n"
                "def key_table(s): return None\n")
    with pytest.raises(TypeError, match="defines no chunk_draws"):
        manifest.load_stream(str(tmp_path), ["."], {"kind": "half"})
    with open(tmp_path / "streams" / "thin.py", "w") as f:
        f.write("import types\n"
                "def spec(*a): return types.SimpleNamespace(seed=1)\n"
                "key_table = chunk_draws = chunk_columns = spec\n")
    thin = manifest.load_stream(str(tmp_path), ["."], {"kind": "thin"})
    with pytest.raises(TypeError, match="max_disorder_s"):
        thin.spec(1, 0, 0)


def test_a_kind_under_a_fixture_path_is_found_after_the_benchmarks_own():
    cell = manifest.load_cell(ROOT, os.path.join(ROOT, TINY_STREAM),
                              "tiny-stream-2part-catchup")
    assert cell.stream.path.endswith(os.path.join(
        "tests", "fixtures", "streams", "toy-mixed.py"))
    assert cell.stream["kind"] == "toy-mixed"
    spec = cell.stream.spec(7, 4096, 0)
    assert spec.max_disorder_s == 2 == cell.config["close_lateness_s"]
    table = cell.stream.kind.key_table(spec)
    assert set(np.unique(table.etype)) == {0x0800, 0x86DD}
    assert len(table) == 2000 == len(table.src_addr)


# ---- zipf-ranks makes what flowgen.py made ---------------------------------


@pytest.mark.parametrize("at", sorted(DIGESTS))
def test_zipf_ranks_makes_the_parents_bytes(at):
    name, seed = at.split(":")
    cell = manifest.load_cell(ROOT, REAL, name)
    plan = cell.mode.plan(cell.traffic, cell.stream, 51.0)
    spec = schedule.spec_for(int(seed), cell.stream, plan)
    table = cell.stream.kind.key_table(spec)
    want = DIGESTS[at]
    assert want["chunks"][-1] == plan.total_flows // spec.chunk_flows - 1
    frames, draws = hashlib.sha256(), hashlib.sha256()
    for c in want["chunks"]:
        blob, drawn = flowgen.chunk_blob(cell.stream.kind, spec, table, c)
        frames.update(blob)
        for d in drawn:
            draws.update(d.dtype.str.encode())
            draws.update(d.tobytes())
    assert frames.hexdigest() == want["frames"]
    assert draws.hexdigest() == want["draws"]


def test_zipf_ranks_in_order_on_one_partition_keeps_nothing():
    deal = drive.Deal(_stream().spec(3, 4096, 0), 1, 10**9)
    assert deal._positions is None  # a position is its own offset
    assert deal.offsets(0, 17, 99) == (17, 99)
    assert deal.consumed([12]).tolist() == list(range(12))
    assert deal.beyond([40], 30, 50) == 10


# ---- what was consumed, on three partitions ---------------------------------


class _ByThree:
    """A spec that deals position i to partition (i * i) mod 3: uneven
    (partition 2 gets nothing)."""
    chunk_flows, slot_seconds, max_disorder_s = 8, 300, 0

    def partition_of(self, idx, partitions):
        return (idx * idx) % partitions

    def event_ts(self, idx):
        return (1_700_000_100 + idx // 4).astype(np.uint64)

    def close_flows(self, lo, hi):
        return []


def test_consumed_set_arithmetic_on_three_partitions_with_uneven_offsets():
    deal = drive.Deal(_ByThree(), 3, 30)
    mine = [[i for i in range(30) if (i * i) % 3 == p] for p in range(3)]
    assert [deal.positions(p, 0, 30).tolist() for p in range(3)] == mine
    assert mine[2] == [] and len(mine[0]) == 10 and len(mine[1]) == 20
    # offsets of the flows at positions [7, 19): by search, not by count
    assert [deal.offsets(p, 7, 19) for p in range(3)] == [(3, 7), (4, 12),
                                                          (0, 0)]
    assert [deal.offsets(p, 7, 19) for p in range(3)] == [
        tuple(sum(i < edge for i in mine[p]) for edge in (7, 19))
        for p in range(3)]
    assert [x.tolist() for x in deal.split(7, 19)] == [
        [2, 5, 8, 11], [0, 1, 3, 4, 6, 7, 9, 10], []]
    # folded up to offsets 4, 9 and 0: the union of three prefixes
    got = deal.consumed([4, 9, 0])
    assert got.tolist() == sorted(mine[0][:4] + mine[1][:9])
    # of the flows [7, 19): those at or past each partition's offset
    assert deal.beyond([4, 9, 0], 7, 19) == len(
        [i for i in range(7, 19) if i not in set(got.tolist())])
    run = types.SimpleNamespace(
        deal=deal, spec=_ByThree(), final={"folded": [4, 9, 0]},
        draws=[tuple(np.arange(8 * c, 8 * c + 8) * k for k in (1, 2, 3))
               for c in range(4)])
    idx, rank, nbytes, packets = check.consumed_draws(run)
    assert idx.tolist() == got.tolist()
    assert (rank == idx).all() and (nbytes == 2 * idx).all() \
        and (packets == 3 * idx).all()


def test_a_stream_that_deals_past_the_bus_is_refused():
    class Wide(_ByThree):
        def partition_of(self, idx, partitions):
            return idx % (partitions + 1)

    with pytest.raises(drive.Abort, match="the bus has 3"):
        drive.Deal(Wide(), 3, 30)


def test_produce_deals_each_frame_to_its_partition_in_offset_order():
    class Bus:
        def __init__(self):
            self.logs = {p: [] for p in range(3)}

        def produce_many(self, topic, values, partition=None):
            self.logs[partition].extend(values)

    bus = Bus()
    run = types.SimpleNamespace(
        spec=_ByThree(), deal=drive.Deal(_ByThree(), 3, 32),
        frames=[tuple(b"%d" % i for i in range(8 * c, 8 * c + 8))
                for c in range(4)],
        sut=types.SimpleNamespace(bus=bus, topic="t"))
    for lo, hi in ((0, 5), (5, 6), (6, 21), (21, 32)):
        drive.produce(run, lo, hi)
    assert run.frames == [None] * 4
    for p in range(3):
        assert bus.logs[p] == [b"%d" % i for i in
                               run.deal.positions(p, 0, 32).tolist()]


def _spans(*fetches):
    return types.SimpleNamespace(spans=types.SimpleNamespace(spans=[
        ("bus_fetch", t - 0.001, t, 0, meta) for t, meta in fetches]))


def test_the_fetch_position_is_the_count_over_all_partitions():
    scan = drive.FetchScan(_spans(
        (1.0, (0, 0, 10)), (2.0, None), (3.0, (1, 0, 4)), (4.0, (0, 10, 5)),
        (5.0, (1, 4, 6))))
    assert scan.new() == [(1.0, 0, 0, 10, 10), (3.0, 1, 0, 4, 14),
                          (4.0, 0, 10, 5, 19), (5.0, 1, 4, 6, 25)]
    assert scan.new() == []


def test_backlog_aborts_at_the_summed_position(monkeypatch):
    """Two partitions of 50 flows each: the run is dry when the fetches
    of both have taken 100 between them, not when one has reached its
    own end."""
    plan = types.SimpleNamespace(window_start_flow=20, total_flows=100,
                                 seconds=60.0)
    run = _spans()
    coming = _spans((1.0, (0, 0, 20)), (2.0, (1, 0, 30)), (3.0, (0, 20, 30)),
                    (4.0, (1, 30, 20))).spans.spans
    run.__dict__.update(
        plan=plan, spec=types.SimpleNamespace(chunk_flows=10), error=None,
        traced=False, cell=types.SimpleNamespace(traffic={"run_in_chunks": 1}),
        sut=types.SimpleNamespace(worker=types.SimpleNamespace(
            flows_seen=10**9)))

    def wait(run, cond, what, poll=0.0):
        """A fetch returns between two looks of the mode."""
        while not cond():
            if not coming:
                raise AssertionError(f"still waiting for {what}")
            run.spans.spans.append(coming.pop(0))

    monkeypatch.setattr(drive, "wait", wait)
    monkeypatch.setattr(drive, "generate", lambda *a: None)
    monkeypatch.setattr(drive, "produce", lambda *a: None)
    with pytest.raises(drive.Abort, match=r"ran dry 2\.00 s into the "
                                          r"window, at 25 flows/s"):
        backlog.control(run, None)
    # the window opened at the fetch that began at or past 20 flows taken
    assert (run.t_a, run.pos_a) == (2.0, 50)
    assert not coming  # partition 0 reached its own end at 3.0: not dry


def test_an_open_loop_on_two_partitions_is_refused_at_plan_time():
    p = _cell("tiny-stream-2part-catchup", seed=5, manifest_rel=_live_on_two())
    assert p.returncode == 3 and not p.stdout.strip()
    assert "holds on one partition only" in p.stderr
    assert "toy-2part has 2" in p.stderr


# ---- the fixture cell: a kind added as a file, on two partitions ------------


def _cell(workload, seed, trace=0, manifest_rel=TINY_STREAM):
    with open(REAL) as f:
        command = json.load(f)["command"]
    return subprocess.run(
        [sys.executable, *command[1:], "--manifest", manifest_rel,
         "--workload", workload, "--seed", str(seed), "--seconds", "3",
         "--trace", str(trace)], cwd=ROOT, text=True, capture_output=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)


def _live_on_two() -> str:
    """The fixture manifest with the two-partition cell on the open-loop
    traffic file, under the run directory git ignores."""
    with open(os.path.join(ROOT, TINY_STREAM)) as f:
        man = json.load(f)
    man["workloads"][0]["traffic"] = "tiny-live"
    man["end_to_end"] = [m for m in man["end_to_end"]
                         if "workloads" not in m]
    man["per_layer"] = man["per_layer"][:1]
    out = os.path.join(ROOT, ".bench_run", "tiny-stream-live.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(man, f)
    return os.path.relpath(out, ROOT)


def _line(p):
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("seed", [2**31 + 11, 3700001001])
def test_two_partitions_with_disorder_fail_by_the_ranked_tables_alone(seed):
    p = _cell("tiny-stream-2part-catchup", seed, trace=1)
    line = _line(p)
    checks = {c["name"]: c for c in line["checks"]}
    assert line["correct"] is False
    assert [n for n, c in checks.items() if not c["ok"]] == [
        "topk_bytes_max_rel_err"]
    for exact in ("flows5m_mismatched_groups", "unaccounted_flows",
                  "commit_offset_gap", "commits_ahead_of_flush",
                  "query_mismatches"):
        assert checks[exact]["value"] == 0, exact
    # the value beside its limit, in the result's line and as the last
    # lines of standard error
    assert checks["topk_bytes_max_rel_err"]["limit"] == 1e-5
    assert checks["topk_bytes_max_rel_err"]["value"] > 1e-3
    assert "check topk_bytes_max_rel_err: " in p.stderr[-2000:]
    w = line["window"]
    # both partitions were driven, folded and committed to where they
    # were folded, and neither is the whole stream
    assert len(w["folded"]) == 2 and min(w["folded"]) > 0
    assert w["folded"] == w["committed"]
    assert sum(w["folded"]) == w["flows_consumed"] == w["committed_total"]
    assert all(f < e for f, e in zip(w["folded"], w["bus_end"]))
    assert w["bus_end_total"] == sum(w["bus_end"])
    # B-mech 1: a sketch family drops what arrives after its slot rolled,
    # and the reference's own count of those flows is each family's
    sketches = {m: n for m, n in w["late_by_model"].items()
                if m.startswith("top_")}
    assert len(sketches) == 5 and w["late_by_model"]["flows_5m"] == 0
    assert set(sketches.values()) == {w["late_expected"]}
    assert w["late_expected"] > 0
    assert w["late_dropped"] == sum(w["late_by_model"].values())
    assert line["failed"] >= w["late_dropped"]
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["metrics"]["window_closes_in_window"]["value"] >= 3


@pytest.mark.parametrize("seed", [2**31 + 11, 3700001001])
def test_the_same_stream_in_order_on_one_partition_is_correct(seed):
    line = _line(_cell("tiny-stream-inorder-catchup", seed))
    assert line["correct"] is True and line["failed"] == 0
    w = line["window"]
    assert w["folded"] == w["committed"] == [w["flows_consumed"]]
    assert w["late_expected"] == 0 == w["late_dropped"]
    # v4 and v6 keys and the onset's keys all pass the exact table and
    # the five ranked ones
    assert {c["name"] for c in line["checks"]} >= {
        "flows5m_mismatched_groups", "topk_bytes_max_rel_err",
        "query_mismatches"}
