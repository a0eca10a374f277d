"""The seam the two-partition cell stands on, in tier-1 (PR 38's cases that
need no subprocess, copied from ``benchmark/tests/test_stream_seam.py``,
``test_manifest.py`` and ``test_reference.py``, which tier-1 does not
collect: PERF.md 7), and ISSUE 39's own: the stream kind
``zipf-ranks-delayed`` is ``zipf-ranks`` byte for byte but for a flow's
event time and partition, and ``estate-2part`` is ``default-estate`` but
for the keys its file lists."""

import hashlib
import json
import os
import types

import numpy as np
import pytest

from benchmark import check, drive, flowgen, manifest, schedule
from benchmark.modes import backlog

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(ROOT, "benchmark", "tests", "fixtures")
REAL = os.path.join(ROOT, "BENCHMARK.json")
TINY_STREAM = "benchmark/tests/fixtures/BENCHMARK.tiny-stream.json"
PATHS = ["benchmark", "benchmark/tests/fixtures"]
with open(os.path.join(FIXTURES, "zipf_ranks_digests.json")) as f:
    # chunk_blob of chunks 0, 1 and the last of every cell at 51 s, two
    # seeds, by the parent of PR 38 (2a8a86d) before the first edit
    DIGESTS = json.load(f)
with open(os.path.join(ROOT, "tests", "data",
                       "backbone_ranks_digests.json")) as f:
    # the same of hh-backbone-catchup, as PR 42 left the kind
    # backbone-ranks: a later PR that changes a stream's bytes changes
    # what every ledger line of its cells measured
    DIGESTS.update(json.load(f))
with open(os.path.join(ROOT, "tests", "data",
                       "spreaders_ranks_digests.json")) as f:
    # and of estate-spread-catchup, as PR 47 left zipf-ranks-spreaders
    DIGESTS.update(json.load(f))


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
def test_a_stream_kind_makes_the_bytes_it_was_recorded_making(at):
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


# ---- every cell's stream is a kind with the whole API (test_manifest.py) ----


def _cells() -> list:
    with open(REAL) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("name", _cells())
def test_every_cell_loads_a_stream_kind_with_the_whole_api(name):
    c = manifest.load_cell(ROOT, REAL, name)
    assert all(callable(getattr(c.stream.kind, a))
               for a in manifest.STREAM_API)
    spec = c.stream.spec(1, 65536, 0)
    assert all(hasattr(spec, a) for a in manifest.SPEC_API)
    if name == CELL_2PART:
        assert os.path.basename(c.stream.path) == "zipf-ranks-delayed.py"
        assert spec.max_disorder_s == 3 and c.config["bus_partitions"] == 2
    elif name == CELL_BACKBONE:
        assert os.path.basename(c.stream.path) == "backbone-ranks.py"
        assert spec.max_disorder_s == 0 and c.config["bus_partitions"] == 1
    elif name == CELL_SPREAD:
        assert os.path.basename(c.stream.path) == "zipf-ranks-spreaders.py"
        assert spec.max_disorder_s == 0 and c.config["bus_partitions"] == 1
    else:
        # no other configuration names a stream kind: each gets
        # zipf-ranks, and drives the one partition it states
        assert "kind" not in c.config["stream"]
        assert os.path.basename(c.stream.path) == "zipf-ranks.py"
        assert spec.max_disorder_s == 0 and c.config["bus_partitions"] == 1


# ---- the reference groups by slot under disorder (test_reference.py) ---------


def test_slot_sums_group_by_slot_where_event_time_runs_backwards():
    """A kind that declares disorder: a slot's flows are no run of
    positions, and the sums are those of a flow-by-flow count over a
    scattered set of positions."""
    import dataclasses

    from benchmark.reference import Reference

    kind = _stream().kind
    base = kind.StreamSpec(seed=11, n_keys=300, event_rate=20,
                           chunk_flows=2048, first_close_flow=4096)

    class Jittered(type(base)):
        max_disorder_s = 40

        def event_ts(self, idx):
            back = (idx.astype(np.int64) * 7919) % (self.max_disorder_s + 1)
            return (type(base).event_ts(self, idx).astype(np.int64)
                    - back).astype(np.uint64)

    spec = Jittered(**dataclasses.asdict(base))
    table = kind.KeyTable(spec)
    rank, nbytes, packets = kind.chunk_draws(spec, table, 2)
    lo = 2 * spec.chunk_flows  # the first flow of the slot at boundary_ts
    idx = lo + np.flatnonzero(np.arange(2048) % 3 != 1)  # two of three
    rank, nbytes, packets = rank[idx - lo], nbytes[idx - lo], \
        packets[idx - lo]
    slot = spec.event_ts(idx).astype(np.int64) // 300 * 300
    assert len(np.unique(slot)) == 2
    assert (np.diff(slot) < 0).any()  # no runs
    got = Reference(spec, table).slot_sums(idx, rank, nbytes, packets)
    want: dict = {}
    for s, r, b, p in zip(slot.tolist(), rank.tolist(), nbytes.tolist(),
                          packets.tolist()):
        tot = want.setdefault(s, np.zeros((3, len(table)), np.uint64))
        tot[:, r] += np.array([b, p, 1], np.uint64)
    assert set(got) == set(want)
    for s in want:
        assert all((g == w).all() for g, w in zip(got[s], want[s]))


# ---- ISSUE 39: zipf-ranks-delayed and estate-2part ---------------------------

CELL_2PART = "estate-2part-catchup"
CELL_BACKBONE = "hh-backbone-catchup"
CELL_SPREAD = "estate-spread-catchup"
SEEDS = [2**31 + 11, 3700001001]


def _both(seed: int):
    """(zipf-ranks' spec, the delayed kind's spec, each with its kind) for
    the two cells' plans at 51 s."""
    out = []
    for name in ("estate-catchup", CELL_2PART):
        cell = manifest.load_cell(ROOT, REAL, name)
        plan = cell.mode.plan(cell.traffic, cell.stream, 51.0)
        out.append((cell.stream.kind,
                    schedule.spec_for(seed, cell.stream, plan), plan))
    return out


@pytest.mark.parametrize("seed", SEEDS)
def test_the_delayed_kind_is_zipf_ranks_but_for_time_and_partition(seed):
    (plain, spec_a, plan_a), (delayed, spec_b, plan_b) = _both(seed)
    assert plan_a == plan_b  # the same traffic file lays the same closes
    ta, tb = plain.key_table(spec_a), delayed.key_table(spec_b)
    for col in ("src_host", "dst_host", "src_port", "dst_port", "proto",
                "src_as", "dst_as", "cdf"):
        assert (getattr(ta, col) == getattr(tb, col)).all(), col
    last = plan_a.total_flows // spec_a.chunk_flows - 1
    for chunk in (0, 1, 107, last):
        da = plain.chunk_draws(spec_a, ta, chunk)
        db = delayed.chunk_draws(spec_b, tb, chunk)
        for x, y in zip(da, db):
            assert x.dtype == y.dtype and (x == y).all()
        ca = plain.chunk_columns(spec_a, ta, chunk, da)
        cb = delayed.chunk_columns(spec_b, tb, chunk, db)
        assert list(ca) == list(cb)
        timed = {"time_received", "time_flow_start", "time_flow_end"}
        for name in ca:
            assert ca[name].dtype == cb[name].dtype
            if name not in timed:
                assert (ca[name] == cb[name]).all(), name
        behind = ca["time_received"].astype(np.int64) \
            - cb["time_received"].astype(np.int64)
        assert set(np.unique(behind)) <= {0, 1, 2, 3}
        assert (cb["time_flow_start"] == cb["time_received"]).all()
    assert spec_a.close_flows(0, plan_a.total_flows) \
        == spec_b.close_flows(0, plan_b.total_flows)


@pytest.mark.parametrize("seed", SEEDS)
def test_a_tenth_of_the_flows_lie_one_to_three_seconds_behind(seed):
    _, (kind, spec, plan) = _both(seed)
    idx = np.arange(262144, 262144 + 2_000_000)
    clock = spec._clock(idx)
    behind = clock - spec.event_ts(idx).astype(np.int64)
    share = (behind > 0).mean()
    assert 0.097 < share < 0.103
    counts = np.bincount(behind, minlength=4)[1:]
    assert len(counts) == 3 and counts.min() > 0.3 * counts.sum()
    # never a flow whose clock is the first second of a slot: the slot
    # opens at the same position whatever the seed
    closes = spec.close_flows(0, plan.total_flows)
    assert closes[0] == spec.first_close_flow and len(closes) >= 4
    for close in closes[1:]:
        second = np.arange(close, close + spec.event_rate)
        assert (spec.event_ts(second) == spec._clock(second)).all()
        assert spec._clock(second[:1])[0] % spec.slot_seconds == 0
    # the warm-up's close opens its slot phase_s seconds in: a delay of
    # 3 s stays inside the slot
    assert spec.phase_s >= spec.delay_s_max
    for close in closes:
        slot = spec.event_ts(np.arange(close - 200_000, close + 200_000)
                             ).astype(np.int64) // spec.slot_seconds
        assert (slot[:200_000] < slot[200_000]).all()
        # and some that follow it lie back in the slot before: what the
        # lateness is for
        late = np.flatnonzero(slot[200_000:] < slot[200_000])
        if close == closes[0]:
            assert not len(late)
        else:
            assert 0 < len(late) < 0.1 * 200_000
            assert late.max() < 3 * spec.event_rate
    # dealt round-robin, as upstream's keyless producer deals
    assert (spec.partition_of(idx, 2) == idx % 2).all()
    # another seed delays other flows
    other = kind.spec(seed + 1, {k: v for k, v in _stream_2part().items()},
                      spec.first_close_flow, spec.phase_s)
    assert (other.event_ts(idx) != spec.event_ts(idx)).mean() > 0.15


def _stream_2part() -> dict:
    with open(os.path.join(ROOT, "benchmark/configs/estate-2part.json")) as f:
        return json.load(f)["stream"]


def test_the_delayed_kind_names_a_key_it_does_not_know():
    stream = manifest.load_stream(ROOT, PATHS, _stream_2part())
    with pytest.raises(ValueError, match=r"zipf-ranks-delayed has no key "
                                         r"\['delay_ms'\]"):
        stream.with_params(delay_ms=5).spec(1, 4096, 0)
    with pytest.raises(ValueError, match="delayed_share"):
        stream.with_params(delayed_share=1.5).spec(1, 4096, 0)


def test_estate_2part_is_default_estate_but_for_what_its_file_lists():
    with open(os.path.join(ROOT, "benchmark/configs/default-estate.json")) \
            as f:
        base = json.load(f)
    with open(os.path.join(ROOT, "benchmark/configs/estate-2part.json")) as f:
        cfg = json.load(f)
    with open(REAL) as f:
        (entry,) = [c for c in json.load(f)["configs"]
                    if c["name"] == "estate-2part"]
    assert entry["reduced"] == ["scale"] == list(cfg["reduced"])
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert cfg["bus_partitions"] == 2 and cfg["close_lateness_s"] == 7
    flags = dict(zip(cfg["processor_flags"][::2], cfg["processor_flags"][1::2]))
    was = dict(zip(base["processor_flags"][::2], base["processor_flags"][1::2]))
    assert flags == {**was, "-window.lateness": "7"}
    assert cfg["stream"] == {"kind": "zipf-ranks-delayed", **base["stream"],
                             "delayed_share": 0.1, "delay_s_max": 3}
    # the shapes, the checks and their limits are default-estate's
    for same in ("chips", "topic", "checks", "sink_rows_per_window",
                 "close_table", "flags_added_by_the_harness"):
        assert cfg[same] == base[same], same
    assert cfg["reduced"]["scale"] == base["reduced"]["scale"]
    assert {k: v for k, v in cfg["guarantees"].items()
            if k != "late_rows"} == base["guarantees"]
    assert "late_flows_dropped 0" in cfg["guarantees"]["late_rows"]
    changed = set(cfg["changed_from_default_estate"])
    assert {"bus_partitions", "stream.kind", "close_lateness_s"} <= changed


def test_the_benchmark_stays_inside_its_limits():
    """128 per-layer metrics at most, names of 64 characters, a `why` of
    200: a file outside them is refused before a run."""
    with open(REAL) as f:
        man = json.load(f)
    assert len(man["per_layer"]) <= 128 and len(man["workloads"]) <= 24
    assert os.path.getsize(REAL) <= 64 * 1024
    for group in ("configs", "workloads", "per_layer", "end_to_end"):
        names = [e["name"] for e in man[group]]
        assert len(names) == len(set(names))
        assert all(len(n) <= 64 for n in names)
    for e in man["configs"] + man["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    (cell,) = [w for w in man["workloads"] if w["name"] == CELL_2PART]
    assert cell == {"name": CELL_2PART, "config": "estate-2part",
                    "traffic": "backlog-drain", "chips": 1,
                    "why": cell["why"]}
    listed = [e["name"] for e in man["per_layer"]
              if CELL_2PART in e.get("workloads", [])]
    assert listed == [
        "backlog_left_share", "split_parts_ms_p50",
        "device_steps_per_batch.2part", "batch_fill_share.2part",
        "step_device_ms_p50.2part", "fused_step_roofline.2part",
        "batch_period_ms_p50.2part", "checkpoint_raw_mb_p50.2part",
        "late_rows_folded_share", "late_rows_dropped",
        "held_close_delay_ms_p50", "held_units_at_checkpoint_p50",
        "partition_skew_s_p50", "detector_dispatch_per_batch"]


# ---- the stream kind backbone-ranks (ISSUE 42) --------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_backbone_ranks_has_the_spec_api_and_the_table_its_file_states(seed):
    at = str(seed)
    cell = manifest.load_cell(ROOT, REAL, CELL_BACKBONE)
    plan = cell.mode.plan(cell.traffic, cell.stream, 51.0)
    spec = schedule.spec_for(seed, cell.stream, plan)
    table = cell.stream.kind.key_table(spec)  # the real size: 4x10^6 ranks
    stream = cell.config["stream"]
    assert all(hasattr(spec, a) for a in manifest.SPEC_API)
    assert spec.seed == int(at) and spec.max_disorder_s == 0
    assert len(table) == stream["n_keys"] == 4_000_000
    # both families, the v4 address in the trailing four bytes
    v4 = table.etype == 0x0800
    assert set(np.unique(table.etype)) == {0x0800, 0x86DD}
    assert abs(v4.mean() - stream["v4_share"]) < 0.002
    for words in (table.src_addr, table.dst_addr):
        assert not words[v4, :3].any()
        assert (words[v4, 3] >> 20 == 0x0A000000 >> 20).all()
        assert (words[~v4, 0] == 0x20010DB8).all()
    assert (table.src_ip >> stream["host_bits"] == v4).all()
    # a rate a rank, from the file's list in the file's shares
    rates, counts = np.unique(table.sampling_rate, return_counts=True)
    assert rates.tolist() == stream["rates"]
    assert np.allclose(counts / len(table), stream["rate_shares"],
                       atol=0.002)
    # the event clock, the closes and the dealing are zipf-ranks' own
    other = _stream().spec(int(at), plan.first_close_flow, plan.phase_s)
    idx = np.array([0, 65535, 65536, 10**6, 5 * 10**7])
    assert np.array_equal(spec.event_ts(idx), other.event_ts(idx))
    assert spec.close_flows(0, 10**8) == other.close_flows(0, 10**8)
    assert np.array_equal(spec.partition_of(idx, 3), idx % 3)
    # the columns carry the table's family and rate at each flow's rank
    draws = cell.stream.kind.chunk_draws(spec, table, 5)
    cols = cell.stream.kind.chunk_columns(spec, table, 5, draws)
    assert np.array_equal(cols["etype"], table.etype[draws[0]])
    assert np.array_equal(cols["sampling_rate"],
                          table.sampling_rate[draws[0]].astype(np.uint64))
    assert np.array_equal(cols["src_addr"], table.src_addr[draws[0]])


def test_backbone_ranks_names_the_keys_it_does_not_know_or_lacks():
    with open(os.path.join(ROOT, "benchmark/configs/hh-backbone.json")) as f:
        params = json.load(f)["stream"]
    stream = manifest.load_stream(ROOT, PATHS, {**params, "etype": 34525})
    with pytest.raises(ValueError, match="etype"):
        stream.spec(1, 65536, 0)
    lacking = {k: v for k, v in params.items() if k != "host_bits"}
    with pytest.raises(ValueError, match="host_bits"):
        manifest.load_stream(ROOT, PATHS, lacking).spec(1, 65536, 0)
    with pytest.raises(ValueError, match="rate_shares"):
        manifest.load_stream(ROOT, PATHS, {
            **params, "rate_shares": [0.5, 0.3]}).spec(1, 65536, 0)


def test_the_sampled_table_kinds_sum_in_integers_flow_by_flow():
    """``exact_sums_sampled`` and ``ranked_bytes_sampled`` against a sum
    made one flow at a time in Python integers: each flow's bytes times
    its own rank's rate."""
    from benchmark.reference import Reference

    cell = manifest.load_cell(
        ROOT, os.path.join(FIXTURES, "BENCHMARK.tiny-backbone.json"),
        "tiny-backbone-catchup")
    spec = cell.stream.spec(2**31 + 11, 4096, 0)
    kind = cell.stream.kind
    table = kind.key_table(spec)
    draws = [kind.chunk_draws(spec, table, c) for c in range(4)]
    rank, nbytes, packets = (np.concatenate([d[i] for d in draws])
                             for i in range(3))
    idx = np.arange(len(rank))
    ref = Reference(spec, table)
    sums = ref.slot_sums(idx, rank, nbytes, packets)
    slot = (spec.event_ts(idx).astype(np.int64) // spec.slot_seconds
            * spec.slot_seconds).tolist()
    exact, pairs = {}, {}
    for s, r, b, p in zip(slot, rank.tolist(), nbytes.tolist(),
                          packets.tolist()):
        rate = int(table.sampling_rate[r])
        k = (s, int(table.src_as[r]), int(table.dst_as[r]),
             int(table.etype[r]))
        e = exact.setdefault(k, [0, 0, 0, 0, 0])
        for i, v in enumerate((b, p, 1, b * rate, p * rate)):
            e[i] += v
        k = (int(table.src_ip[r]), int(table.dst_ip[r]))
        pairs.setdefault(s, {}).setdefault(k, 0)
        pairs[s][k] += b * rate
    entries = {e["name"]: e for e in cell.config["checks"]["tables"]}
    sampled = cell.table_kinds["exact_sums_sampled"]
    want = sampled.want(ref, entries["flows_5m"], sums)
    assert want == {k: tuple(v) for k, v in exact.items()}
    assert len({k[3] for k in want}) == 2  # both families
    ranked = cell.table_kinds["ranked_bytes_sampled"]
    got = ranked.want(ref, entries["top_pairs"], sums)
    for s, keys in got.items():
        best = sorted(pairs[s].items(), key=lambda kv: -kv[1])
        assert list(keys.values())[:50] == [v for _k, v in best[:50]]
        assert all(pairs[s][k] == v for k, v in keys.items())
    # a wrong scaled sum and a missing group are each seen
    good = dict(want)
    numbers = sampled.compare(entries["flows_5m"], want, good, len(rank))
    assert [v for v, _lim in numbers.values()] == [0, 0, 0]
    k = next(iter(good))
    off = {**good, k: (*good[k][:3], good[k][3] + 1, good[k][4])}
    assert sampled.compare(entries["flows_5m"], want, off, len(rank))[
        "flows5m_scaled_mismatches"] == (1, 0)
    less = {q: v for q, v in good.items() if q != k}
    found = sampled.compare(entries["flows_5m"], want, less, len(rank))
    assert found["flows5m_mismatched_groups"][0] == 1
    assert found["unaccounted_flows"][0] == good[k][2]
    # addresses of both families come back to the kind's own numbering
    bits = cell.config["stream"]["host_bits"]
    assert ranked._ip("10.0.0.5", bits) == 5 | 1 << bits
    assert ranked._ip("2001:db8:0:1::9", bits) == 9
    assert ranked._ip("10.16.0.5", bits) == -1  # outside 10.0.0.0/12
    assert ranked._ip("2001:db8:0:2::9", bits) == -1


def test_the_backbone_roofline_counts_four_families_at_the_files_width():
    from benchmark import backbone_roofline as br

    with open(os.path.join(ROOT, "benchmark/configs/hh-backbone.json")) as f:
        cfg = json.load(f)
    fams = br.families(cfg)
    assert fams == [br.FIVE, ("src_addr", "dst_addr"), ("src_addr",),
                    ("dst_addr",)]
    assert br._chains(fams) == (3, 0)  # one sort for three, none alone
    whole = br.hh_step_bytes(cfg)

    def with_flags(*changed):
        return br.hh_step_bytes({"processor_flags": [
            f for f in cfg["processor_flags"]
            if f.split("=")[0] not in {c.split("=")[0] for c in changed}]
            + list(changed)})

    rows, cap = 32768, 1024
    pair = (2 * rows * 4 * 3 * 4            # its count-min cells
            + 2 * cap * (8 + 3) * 4         # its table
            + 2 * rows * 2 * 4)             # its two hash lanes on the sort
    assert whole - with_flags("-model.pairs=false") == pair
    # a batch touches at most as many cells of a row as it has rows
    flags = cfg["processor_flags"]
    at = flags.index("-sketch.width")
    narrow = dict(cfg, processor_flags=[*flags[:at + 1], "1024",
                                        *flags[at + 2:]])
    assert whole - br.hh_step_bytes(narrow) == 4 * 2 * (
        rows - 1024) * 4 * 3 * 4
    least, bound = br.hh_step_least_seconds(cfg, "TPU v5 lite")
    assert bound == "hbm_bytes" and least == whole / 819e9
    with pytest.raises(KeyError):
        br.hh_step_least_seconds(cfg, "cpu")


def test_a_familys_merge_scope_is_told_from_the_others_by_its_index():
    from benchmark import family_scopes

    text = """
%fused_computation.7 (p.1: f32[8], p.2: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %t.8 = f32[8]{0} transpose(%p.1), metadata={op_name="jit(step)/hh_table_merge_2/transpose"}
  ROOT %s.9 = f32[8]{0} scatter(%p.1, %t.8)
}

ENTRY %main (a.1: f32[8]) -> f32[8] {
  %a.1 = f32[8]{0} parameter(0), metadata={op_name="jit(step)/hh_chain_sort/sort"}
  %f.7 = f32[8]{0} fusion(%c.3, %a.1), kind=kLoop, calls=%fused_computation.7
  %f.2 = f32[8]{0} fusion(%a.1), kind=kLoop, metadata={op_name="jit(step)/hh_table_merge_1/scatter"}
  %c.3 = f32[8]{0} copy(%f.2)
  %f.4 = f32[8]{0} fusion(%a.1), kind=kLoop, metadata={op_name="jit(step)/hh_table_merge_10/while/body/gather"}
  %f.5 = f32[8]{0} fusion(%a.1), kind=kLoop, metadata={op_name="jit(step)/hh_table_merge_0/top_k"}
  %c.6 = f32[8]{0} copy(%a.1)
}
"""
    # f.7 has no metadata of its own: what is fused into it says whose it
    # is, before its first operand (another family's) does
    assert family_scopes.merge_index_map(text) == {
        "t.8": 2, "s.9": 2, "f.7": 2,
        "f.2": 1, "c.3": 1, "f.4": 10, "f.5": 0}


# ---- ISSUE 47: zipf-ranks-spreaders, estate-spread and its checks ------------


@pytest.mark.parametrize("seed", SEEDS)
def test_the_spreaders_kind_is_zipf_ranks_but_for_the_re_homed_ranks(seed):
    """Ranks, bytes and packets of a seed are ``zipf-ranks``' byte for
    byte; of the key table only the re-homed ranks' source, destination
    host and destination port differ, so ``flows_5m`` (the AS pairs) is
    ``estate-catchup``'s too."""
    plain = manifest.load_cell(ROOT, REAL, "estate-catchup").stream
    cell = manifest.load_cell(ROOT, REAL, CELL_SPREAD)
    spread = cell.stream
    assert cell.config["stream"]["kind"] == "zipf-ranks-spreaders"
    sa, sb = plain.spec(seed, 65536, 17), spread.spec(seed, 65536, 17)
    ta, tb = plain.kind.key_table(sa), spread.kind.key_table(sb)
    for chunk in (0, 1, 107):
        da = plain.kind.chunk_draws(sa, ta, chunk)
        db = spread.kind.chunk_draws(sb, tb, chunk)
        for x, y in zip(da, db):
            assert x.dtype == y.dtype and (x == y).all()
        ca = plain.kind.chunk_columns(sa, ta, chunk, da)
        cb = spread.kind.chunk_columns(sb, tb, chunk, db)
        assert list(ca) == list(cb)
        for name in ca:
            assert ca[name].dtype == cb[name].dtype
            if name not in ("src_addr", "dst_addr", "dst_port"):
                assert (ca[name] == cb[name]).all(), name
    assert sa.close_flows(0, 10**8) == sb.close_flows(0, 10**8)
    for col in ("src_port", "proto", "src_as", "dst_as", "cdf"):
        assert (getattr(ta, col) == getattr(tb, col)).all(), col
    ranks, source, _rng = spread.kind.spreader_ranks(sb)
    assert len(ranks) == len(set(ranks.tolist())) == 50_000
    moved = np.zeros(len(tb), bool)
    moved[ranks] = True
    for col in ("src_host", "dst_host", "dst_port"):
        assert (getattr(ta, col)[~moved] == getattr(tb, col)[~moved]).all()
    # 64 sources whose shares fall as 1 / (s + 1): ~10,500 the first,
    # ~165 the last; even ones fan out to hosts, odd ones scan ports
    held = np.bincount(source, minlength=64)
    assert len(held) == 64 and 10_000 < held[0] < 11_000
    assert 150 < held[63] < 180 and (np.diff(held) <= 0).all()
    assert (tb.src_host[ranks] == source).all()
    for s in (0, 1, 62, 63):
        mine = ranks[source == s]
        hosts = len(np.unique(tb.dst_host[mine]))
        ports = len(np.unique(tb.dst_port[mine]))
        assert (hosts, ports) == ((len(mine), 1) if s % 2 == 0
                                  else (1, len(mine)))
    assert (tb.dst_port[ranks[source % 2 == 0]]
            == spread.kind.FAN_PORT).all()
    assert tb.src_host.max() < 2**16 and tb.dst_host.max() < 2**16


def test_the_spreaders_kind_names_the_keys_it_does_not_know():
    stream = manifest.load_cell(ROOT, REAL, CELL_SPREAD).stream
    with pytest.raises(ValueError,
                       match=r"zipf-ranks-spreaders has no key \['attack"):
        stream.with_params(attack=1).spec(1, 4096, 0)
    with pytest.raises(ValueError, match="spread_rank_share"):
        stream.with_params(spread_rank_share=1.5).spec(1, 4096, 0)


def test_estate_spread_is_default_estate_but_for_what_its_file_lists():
    with open(os.path.join(ROOT, "benchmark/configs/default-estate.json")) \
            as f:
        base = json.load(f)
    with open(os.path.join(ROOT, "benchmark/configs/estate-spread.json")) \
            as f:
        cfg = json.load(f)
    with open(REAL) as f:
        man = json.load(f)
    (entry,) = [c for c in man["configs"] if c["name"] == "estate-spread"]
    assert entry["reduced"] == ["scale", "bus_partitions"] == list(
        cfg["reduced"])
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert cfg["processor_flags"] == [
        *base["processor_flags"], "-spread.enabled=true",
        "-spread.width", "32768", "-spread.regs", "256"]
    assert cfg["stream"] == {"kind": "zipf-ranks-spreaders",
                             **base["stream"], "spread_rank_share": 0.05,
                             "spread_sources": 64}
    for same in ("chips", "topic", "bus_partitions", "reduced",
                 "sink_rows_per_window", "close_table",
                 "flags_added_by_the_harness"):
        assert cfg[same] == base[same], same
    # the estate's own tables, checks and limits stay; two tables and
    # the query checks of the detectors come after them
    n = len(base["checks"]["tables"])
    assert cfg["checks"]["tables"][:n] == base["checks"]["tables"]
    assert [(t["name"], t["kind"], t["key"], t["element"], t["top_n"])
            for t in cfg["checks"]["tables"][n:]] == [
        ("superspreaders", "ranked_spread", ["src_host"], "dst_host", 32),
        ("portscan", "ranked_spread", ["src_host"], "dst_port", 32)]
    # every one of the 32 has to be there (the floor is top_n: the
    # warm-up slot's ties), the worst error under 1 (a lost source), the
    # sources of 1,000 targets held to the guarantee itself
    assert all(0.25 < t["limit"] < 1.0 and t["floor"] == t["top_n"]
               and t["heavy"] == 1000 and t["heavy_limit"]
               == cfg["guarantees"]["spread_rel_err_max"]
               for t in cfg["checks"]["tables"][n:])
    q = len(base["checks"]["queries"])
    assert cfg["checks"]["queries"][:q] == base["checks"]["queries"]
    # one reset check a detector (benchmark/queries/spread_keys.py)
    assert [(x["kind"], x["model"], "key=" in x["path"])
            for x in cfg["checks"]["queries"][q:]] == [
        ("spread_keys", "superspreaders", False),
        ("spread_keys", "portscan", False)]
    assert {k: v for k, v in cfg["guarantees"].items()
            if not k.startswith("spread_")} == base["guarantees"]
    assert cfg["guarantees"]["spread_rel_err_max"] == 0.25
    assert {k: v for k, v in cfg["assumed"].items()
            if not k.startswith("spread_")} == base["assumed"]
    assert {"spread_rank_share", "spread_sources", "spread_width",
            "spread_regs", "spread_floor"} <= set(cfg["assumed"])
    (cell,) = [w for w in man["workloads"] if w["name"] == CELL_SPREAD]
    assert cell == {"name": CELL_SPREAD, "config": "estate-spread",
                    "traffic": "backlog-drain-spread", "chips": 1,
                    "why": cell["why"]}
    with open(os.path.join(
            ROOT, "benchmark/traffic/backlog-drain-spread.json")) as f:
        traffic = json.load(f)
    assert traffic["mode"] == "backlog"
    assert traffic["provision_flows_per_s"] == 2_240_000
    assert traffic["reader"] == {"poll_interval_s": 0.02}


def _toy_reference():
    """Six ranks of three sources on a hand-made key table, counts a
    slot: source 7 touches hosts {1, 2, 3} (rank 3 unseen: host 4 does
    not count) with ports {80}; source 8 one host, ports {1, 2}."""
    table = types.SimpleNamespace(
        src_host=np.array([7, 7, 7, 7, 8, 8], np.uint32),
        dst_host=np.array([1, 2, 3, 4, 9, 9], np.uint32),
        dst_port=np.array([80, 80, 80, 80, 1, 2], np.uint32))
    counts = np.array([5, 1, 2, 0, 40, 2], np.uint64)
    ref = types.SimpleNamespace(table=table)
    return ref, {300: (counts * 100, counts, counts)}


def test_the_spread_tables_reference_counts_distinct_elements_of_seen_ranks():
    kind = manifest._load_module(os.path.join(
        ROOT, "benchmark", "tables", "ranked_spread.py"), manifest.TABLE_API)
    ref, sums = _toy_reference()
    hosts = {"name": "superspreaders", "key": ["src_host"],
             "element": "dst_host", "top_n": 2, "floor": 2, "limit": 0.25,
             "heavy": 3, "heavy_limit": 0.1}
    ports = dict(hosts, name="portscan", element="dst_port")
    assert kind.want(ref, hosts, sums) == {300: {7: 3, 8: 1}}
    assert kind.want(ref, ports, sums) == {300: {8: 2, 7: 1}}
    run = types.SimpleNamespace(cell=types.SimpleNamespace(
        config={"sink_rows_per_window": 100}))
    # the control ranks by flows and reports them: 42 where 1 belongs
    assert kind.control(ref, hosts, sums, run) == {
        300: [(8, 42.0), (7, 8.0)]}
    exact = kind.want(ref, hosts, sums)
    ok = kind.compare(hosts, exact, {300: [(7, 3.2), (8, 1.0)]}, 50)
    assert ok["spread_max_rel_err"][0] == pytest.approx(0.2 / 3)
    assert ok["spread_missing_keys"] == (0, 0)
    # the heavy sources (3 elements or more: source 7 alone), by the
    # root mean square over the slots; a lost one counts as 1
    assert ok["spread_heavy_rms_rel_err"] == (pytest.approx(0.2 / 3), 0.1)
    two = kind.compare(hosts, {300: exact[300], 600: exact[300]},
                       {300: [(7, 3.3), (8, 1.0)], 600: [(8, 1.0)]}, 50)
    assert two["spread_heavy_rms_rel_err"][0] == pytest.approx(
        ((0.1 ** 2 + 1.0) / 2) ** 0.5)
    assert kind.compare(dict(hosts, heavy=4), exact, {300: [(7, 9.0)]},
                        50)["spread_heavy_rms_rel_err"][0] == 0.0
    # a missing source counts only at or above the floor; an error is
    # relative to max(exact, floor)
    lost = kind.compare(hosts, exact, {300: [(8, 1.5)]}, 50)
    assert lost["spread_missing_keys"] == (1, 0)
    assert lost["spread_max_rel_err"][0] == pytest.approx(0.25)
    summed = kind.compare(hosts, exact,
                          kind.control(ref, hosts, sums, run), 50)
    assert summed["spread_max_rel_err"][0] == pytest.approx(41 / 2)
    # of the program the file imports the sink's list of columns alone:
    # no sketch, no hash, no model
    with open(kind.__file__) as f:
        imported = [ln.split()[1] for ln in f.read().splitlines()
                    if ln.split()[:1] in (["from"], ["import"])
                    and "flow_pipeline_tpu" in ln]
    assert imported == ["flow_pipeline_tpu.sink.ddl"]


def test_the_spread_table_kind_ends_a_run_on_a_sink_without_its_tables(
        monkeypatch):
    """What the parent of PR 47 is: a sink whose ``TABLE_COLUMNS`` has
    neither typed table. The kind's cell ends as its files load, by
    ``Abort`` (exit 3, one line), and gives no result."""
    from benchmark.drive import Abort
    from flow_pipeline_tpu.sink import ddl

    kind = manifest._load_module(os.path.join(
        ROOT, "benchmark", "tables", "ranked_spread.py"), manifest.TABLE_API)
    kind.require_typed_tables()  # this tree's sink has both
    monkeypatch.setattr(ddl, "TABLE_COLUMNS", {
        k: v for k, v in ddl.TABLE_COLUMNS.items() if k != "portscan"})
    with pytest.raises(Abort, match="no such \\['portscan'\\]"):
        kind.require_typed_tables()


def test_the_spread_roofline_counts_four_words_an_index():
    from benchmark import spread_roofline as sr

    with open(os.path.join(ROOT, "benchmark/configs/estate-spread.json")) \
            as f:
        cfg = json.load(f)
    # two detectors x depth 2 x 32,768 rows: a cell read and written,
    # the index and the value, a word each
    assert sr.scatter_bytes(cfg) == 2 * 2 * 32768 * 4 * 4
    assert sr.scatter_least_seconds(cfg, "TPU v5 lite") \
        == sr.scatter_bytes(cfg) / 819e9
    assert sr.scatter_least_seconds(cfg, "cpu") is None
    with open(os.path.join(ROOT, "benchmark/configs/default-estate.json")) \
            as f:
        assert sr.scatter_bytes(json.load(f)) == 0


def test_a_detectors_scopes_are_told_apart_by_its_name():
    from benchmark import spread_scopes

    text = """
%fused_computation.7 (p.1: s32[8], p.2: s32[8]) -> s32[8] {
  %p.1 = s32[8]{0} parameter(0)
  %t.8 = s32[8]{0} maximum(%p.1, %p.1), metadata={op_name="jit(step)/spread_regs_portscan/scatter-max"}
  ROOT %s.9 = s32[8]{0} scatter(%p.1, %t.8)
}

ENTRY %main (a.1: s32[8]) -> s32[8] {
  %a.1 = s32[8]{0} parameter(0), metadata={op_name="jit(step)/hh_chain_sort/sort"}
  %f.7 = s32[8]{0} fusion(%c.3, %a.1), kind=kLoop, calls=%fused_computation.7
  %f.2 = s32[8]{0} fusion(%a.1), kind=kLoop, metadata={op_name="jit(step)/spread_table_superspreaders/top_k"}
  %c.3 = s32[8]{0} copy(%f.2)
  %f.4 = s32[8]{0} fusion(%a.1), kind=kLoop, metadata={op_name="jit(step)/spread_regs_superspreaders/scatter-max"}
  %c.6 = s32[8]{0} copy(%a.1)
}
"""
    assert spread_scopes.scope_names(text) == {
        "t.8": "spread_regs_portscan", "s.9": "spread_regs_portscan",
        "f.7": "spread_regs_portscan",
        "f.2": "spread_table_superspreaders",
        "c.3": "spread_table_superspreaders",
        "f.4": "spread_regs_superspreaders"}
