"""flowtorn: crash-point model checking for every durable surface.

Each scenario here drives REAL production code (the coordinator
journal, the dead-letter spill, the history archive, the sketch
checkpoint) under ``fsutil.observed``, then hands the recorded op log
to ``utils/crashsim.explore`` — which materializes every legal crash
state (durable-effects-only, torn publishes, dropped directory
entries, torn/reordered unsynced writes) and runs the REAL recovery
code over each, asserting the docs/FAULT_TOLERANCE.md invariants:

- journal: every acked submission survives recovery bit-exact (or is
  subsumed by an acked compaction checkpoint);
- dead-letter: every acked spill replays to row equality;
- archive: every committed version reconstructs bit-equal, and a
  missing version is an honest HistoryGapError, never damaged data;
- checkpoint: an acked save restores exactly; mid-save crashes restore
  the complete predecessor; a sliding window's ring (members written
  once beside the checkpoint that names them) comes back whole from
  every crash state.

The ``TestBarrierMutations`` half is the dynamic prong of the
``make lint-mutation`` durability gate: ``fsutil.suppressed(kind)``
deletes one barrier kind (fsync / dir-fsync / atomic replace) from the
protocol the way a bad refactor would, and every (surface, barrier)
pair must produce at least one crash-state invariant violation —
proof that each barrier in each surface is load-bearing, not
cargo-culted. The static prong (tests/test_flowlint.py) proves the
lint rule catches the same deletions in source form.
"""

import os

import numpy as np
import pytest

from flow_pipeline_tpu.engine import checkpoint as ckpt
from flow_pipeline_tpu.engine.checkpoint import (Member,
                                                 checkpoint_exists,
                                                 load_checkpoint,
                                                 save_checkpoint)
from flow_pipeline_tpu.engine.worker import (restore_wagg_state,
                                             save_wagg_state)
from flow_pipeline_tpu.gateway.delta import encode_full
from flow_pipeline_tpu.history.archive import (ArchiveReader,
                                               ArchiveWriter,
                                               HistoryGapError)
from flow_pipeline_tpu.mesh.journal import (JOURNAL_FILE,
                                            CoordinatorJournal,
                                            replay_journal)
from flow_pipeline_tpu.models import WindowAggregator
from flow_pipeline_tpu.sink.resilient import ResilientSink, replay_deadletter
from flow_pipeline_tpu.utils import crashsim, fsutil

T0 = 1_699_999_800


# ---- scenario: coordinator journal -----------------------------------------

_BLOBS = {"a": b"envelope-a" * 3, "b": b"envelope-b" * 5,
          "c": b"envelope-c" * 7}
_CHK_BLOB = b"compacted-coordinator-state"


def _run_journal(root: str, rec: fsutil.OpRecorder) -> None:
    """Append+ack three submissions with a compaction in the middle —
    the full journal lifecycle (init, group commit, atomic compact)."""
    with fsutil.observed(rec):
        j = CoordinatorJournal(os.path.join(root, "mesh"))
        j.append("sub", {"member": "a"}, _BLOBS["a"])
        j.sync()
        rec.mark("a")
        j.append("sub", {"member": "b"}, _BLOBS["b"])
        j.sync()
        rec.mark("b")
        j.compact({"epoch": 2}, _CHK_BLOB)
        rec.mark("chk")
        j.append("sub", {"member": "c"}, _BLOBS["c"])
        j.sync()
        rec.mark("c")
        j.close()


def _check_journal(croot: str, acked: list) -> None:
    recs = list(replay_journal(os.path.join(croot, "mesh", JOURNAL_FILE)))
    chk = next((blob for kind, _m, blob in recs if kind == "chk"), None)
    subs = {m["member"]: blob for kind, m, blob in recs if kind == "sub"}
    for label in acked:
        if label == "chk":
            assert chk is not None, "acked compaction checkpoint lost"
            assert chk == _CHK_BLOB, "checkpoint blob not bit-exact"
        elif label in ("a", "b") and chk is not None:
            continue  # folded into the (also durable) checkpoint
        else:
            assert label in subs, f"acked submission {label!r} lost"
            assert subs[label] == _BLOBS[label], \
                f"submission {label!r} not bit-exact"


# ---- scenario: dead-letter spill -------------------------------------------

_BATCHES = {
    "batch1": [{"src_addr": "10.0.0.1", "bytes": 100, "flows": 2}],
    "batch2": [{"src_addr": "10.0.0.2", "bytes": 7, "flows": 1},
               {"src_addr": "10.0.0.3", "bytes": 9, "flows": 4}],
}


class _DownSink:
    def write(self, table, rows):
        raise OSError("sink is down")


class _CollectSink:
    def __init__(self):
        self.rows = set()

    def write(self, table, records):
        for r in records:
            self.rows.add((table, tuple(sorted(r.items()))))


def _run_dlq(root: str, rec: fsutil.OpRecorder) -> None:
    sink = ResilientSink(_DownSink(), retries=2, backoff=0.0, jitter=0.0,
                         deadletter_dir=os.path.join(root, "sink"),
                         sleep=lambda _s: None)
    with fsutil.observed(rec):
        for label, rows in _BATCHES.items():
            sink.write("flows", rows)  # exhausts retries, spills
            rec.mark(label)


def _check_dlq(croot: str, acked: list) -> None:
    col = _CollectSink()
    # a torn acked spill raises here — that IS the invariant violation
    replay_deadletter(os.path.join(croot, "sink"), [col], delete=False)
    for label in acked:
        for r in _BATCHES[label]:
            key = ("flows", tuple(sorted(r.items())))
            assert key in col.rows, f"acked spill {label!r} lost {r}"


# ---- scenario: history archive ---------------------------------------------


def _mk_state(version: int, *, bump: int = 0) -> dict:
    """A compact canonical gateway state (one hh family, one range
    table) — the delta-codec shape the archive persists."""
    rng = np.random.default_rng(7)
    cms = rng.integers(0, 1000, size=(2, 2, 8)).astype(np.uint64)
    if bump:
        cms[0, 1, bump % 8] += np.uint64(bump)
    return {
        "version": int(version), "created": 100.0 + version,
        "watermark": float(T0 + 300 * version),
        "flows_seen": 10 * version, "source": "worker",
        "families": {
            "hh": {"kind": "hh", "window_start": T0, "depth": 4,
                   "key_lanes": 2, "value_cols": ["bytes"],
                   "rows": {
                       "src_addr": np.arange(4, dtype=np.uint32)
                       + np.uint32(bump),
                       "bytes": np.asarray([9.0, 5.0, 3.0, 1.0],
                                           np.float32),
                       "valid": np.asarray([True, True, True, False]),
                   },
                   "cms": cms, "regs": None},
        },
        "ranges": {"flows_5m": [
            [T0, {"timeslot": np.asarray([T0, T0], np.int64),
                  "bytes": np.asarray([1, 2 + bump], np.uint64)}],
        ]},
        "audit": {"hh": {"cms_err": 0.0, "windows": version}},
    }


_STATES = {v: _mk_state(v, bump=v - 1) for v in (1, 2, 3, 4, 5)}


def _run_archive(root: str, rec: fsutil.OpRecorder) -> None:
    """Five versions at keyframe_every=2: two rotations, commits that
    cover records in BOTH the rotated-away and the live segment."""
    with fsutil.observed(rec):
        w = ArchiveWriter(os.path.join(root, "hist"), keyframe_every=2)
        prev = None
        committed = []
        for v in sorted(_STATES):
            w.record(prev, _STATES[v])
            prev = _STATES[v]
            committed.append(v)
            if v % 2 == 0 or v == max(_STATES):
                w.commit()
                for c in committed:
                    rec.mark(f"v{c}")
                committed = []
        w.close()


def _check_archive(croot: str, acked: list) -> None:
    rd = ArchiveReader(os.path.join(croot, "hist"))
    versions = set(rd.versions())
    for label in acked:
        v = int(label[1:])
        assert v in versions, f"archived v{v} lost"
        state = rd.reconstruct(v)
        assert encode_full(state) == encode_full(_STATES[v]), \
            f"v{v} did not reconstruct bit-equal"
    # honesty: everything listed reconstructs, everything else is a
    # loud gap — never a damaged snapshot
    for v in versions:
        rd.reconstruct(v)
    with pytest.raises(HistoryGapError):
        rd.reconstruct(max(versions, default=0) + 1)


# ---- scenario: sketch checkpoint -------------------------------------------

_CKPT_1 = {"step": 1, "hh": np.arange(6, dtype=np.uint64)}
_CKPT_2 = {"step": 2, "hh": np.arange(6, dtype=np.uint64) * 3}


def _run_checkpoint(root: str, rec: fsutil.OpRecorder) -> None:
    path = os.path.join(root, "ckpt", "snap")
    with fsutil.observed(rec):
        save_checkpoint(path, _CKPT_1)
        rec.mark("s1")
        save_checkpoint(path, _CKPT_2)  # exercises the .old dance
        rec.mark("s2")


def _ckpt_equal(got: dict, want: dict) -> bool:
    return got["step"] == want["step"] and \
        np.array_equal(got["hh"], want["hh"])


def _check_checkpoint(croot: str, acked: list) -> None:
    path = os.path.join(croot, "ckpt", "snap")
    if not acked:
        if not checkpoint_exists(path):
            return  # crashed before anything was published: fine
        got = load_checkpoint(path)  # must load completely or raise
        assert _ckpt_equal(got, _CKPT_1) or _ckpt_equal(got, _CKPT_2), \
            "checkpoint on disk matches neither saved state"
        return
    got = load_checkpoint(path)
    if "s2" in acked:
        assert _ckpt_equal(got, _CKPT_2), \
            "acked checkpoint s2 did not restore"
    else:
        # s1 acked, s2 mid-save: the complete predecessor or the
        # complete successor — never a torn mix
        assert _ckpt_equal(got, _CKPT_1) or _ckpt_equal(got, _CKPT_2), \
            "acked checkpoint restored a torn state"


# ---- scenario: a checkpoint of several arrays --------------------------------
#
# arrays.npz is streamed into its staging file one write an array
# (engine/checkpoint.py::_write_npz), so the crash points of a save fall
# between two arrays' writes and, by the torn tails and the dropped
# writes, inside one. Whatever is left, a reader sees one whole state.


def _multi_state(step: int) -> dict:
    return {"step": step,
            "cms": np.full((3, 512), step, np.float32),
            "keys": np.arange(96, dtype=np.uint64) * step,
            "valid": np.arange(40) % (step + 1) == 0,
            "sums": np.arange(60, dtype=np.int64).reshape(12, 5)[:, ::2]
            - step}


_MULTI_ARRAYS = ("cms", "keys", "valid", "sums")


def _run_checkpoint_multi(root: str, rec: fsutil.OpRecorder,
                          whole: bool = False) -> None:
    path = os.path.join(root, "ckpt", "snap")
    with fsutil.observed(rec):
        for step in (1, 2):
            save_checkpoint(path, _multi_state(step), whole=whole)
            rec.mark(f"m{step}")


def _run_checkpoint_whole(root: str, rec: fsutil.OpRecorder) -> None:
    """The mesh processor's form (ShardedPipeline.checkpoint_whole): the
    archive built in memory and written in one piece."""
    _run_checkpoint_multi(root, rec, whole=True)


def _check_checkpoint_multi(croot: str, acked: list) -> None:
    path = os.path.join(croot, "ckpt", "snap")
    if not acked and not checkpoint_exists(path):
        return  # crashed before anything was published: fine
    got = load_checkpoint(path)  # loads completely or raises
    want = _multi_state(got["step"])
    for name in _MULTI_ARRAYS:  # every array of ONE step: no torn tree
        assert got[name].dtype == want[name].dtype
        assert np.array_equal(got[name], want[name]), \
            f"{name} is not step {got['step']}'s"
    if "m2" in acked:
        assert got["step"] == 2, "acked checkpoint m2 did not restore"


# ---- scenario: the window store's array form in a checkpoint ----------------


def _wagg(step: int) -> WindowAggregator:
    """An aggregator two batches apart: step 2 has added to every group
    of step 1's open window and opened 40 groups more."""
    agg = WindowAggregator()
    lanes = agg.store_key_lanes
    for i in range(200 + 40 * (step - 1)):
        agg._fold_rows(
            np.array([[T0, *[i + 7 * j for j in range(lanes)]]], np.uint32),
            np.array([[1500 * step, 3 * step, step]], np.uint64))
    agg.watermark = T0 + 60 * step
    return agg


def _run_checkpoint_wagg(root: str, rec: fsutil.OpRecorder) -> None:
    path = os.path.join(root, "ckpt", "snap")
    with fsutil.observed(rec):
        for step in (1, 2):
            save_checkpoint(path, {"models": {
                "flows_5m": save_wagg_state(_wagg(step))}})
            rec.mark(f"w{step}")


def _restored_wagg_step(path: str):
    """The step whose store the checkpoint restores to, group for group;
    None if it is neither's."""
    got = WindowAggregator()
    restore_wagg_state(got, load_checkpoint(path)["models"]["flows_5m"],
                       "flows_5m")
    for step in (1, 2):
        want = _wagg(step)
        if (got.watermark == want.watermark
                and got.windows.keys() == want.windows.keys()
                and all(set(got.windows[s]) == set(st)
                        and all(np.array_equal(got.windows[s][k], v)
                                for k, v in st.items())
                        for s, st in want.windows.items())):
            return step
    return None


def _check_checkpoint_wagg(croot: str, acked: list) -> None:
    path = os.path.join(croot, "ckpt", "snap")
    if not acked and not checkpoint_exists(path):
        return  # crashed before anything was published: fine
    step = _restored_wagg_step(path)  # loads completely or raises
    assert step is not None, "restored store matches neither saved state"
    if "w2" in acked:
        assert step == 2, "acked checkpoint w2 did not restore"


# ---- scenario: a sliding window's ring in a checkpoint ----------------------
#
# The closed sub-window states are members, each written once, before
# the first checkpoint that names it, and removed after the first one
# that no longer does (engine/checkpoint.py::Member).


def _sub_state(sub: int) -> dict:
    return {"cms": np.full((2, 8), sub, np.float32),
            "table_keys": np.arange(sub, sub + 6, dtype=np.uint32)}


def _ring_state(step: int, members: dict) -> dict:
    """Step ``step``'s checkpoint: the open state and a ring of the two
    sub-windows before it. ``members`` is the ring's own record, as
    SubWindowRing keeps it from one checkpoint to the next."""
    subs = [s for s in (step - 1, step) if s >= 1]
    for sub in subs:
        members.setdefault(sub, Member(f"top.{sub}", sub, _sub_state(sub)))
    return {"step": step, "open": np.arange(4, dtype=np.uint64) * step,
            "ring": {"subs": subs, "members": [members[s] for s in subs]}}


def _run_checkpoint_ring(root: str, rec: fsutil.OpRecorder) -> None:
    path = os.path.join(root, "ckpt", "snap")
    members: dict = {}
    with fsutil.observed(rec):
        for step in (1, 2, 3):  # 3 drops sub-window 1's member
            save_checkpoint(path, _ring_state(step, members))
            rec.mark(f"r{step}")


def _restored_ring_step(path: str):
    """The step the checkpoint at ``path`` restores to, its ring read
    back whole and bit for bit; raises where a member it names is not
    there or is torn."""
    got = load_checkpoint(path)
    step = got["step"]
    want = _ring_state(step, {})
    assert np.array_equal(got["open"], want["open"]), "open state torn"
    assert got["ring"]["subs"] == want["ring"]["subs"]
    for sub, arrays in zip(got["ring"]["subs"], got["ring"]["members"]):
        for name, value in _sub_state(sub).items():
            assert np.array_equal(arrays[name], value), \
                f"member of sub-window {sub} not bit-exact"
    return step


def _check_checkpoint_ring(croot: str, acked: list) -> None:
    path = os.path.join(croot, "ckpt", "snap")
    if not acked and not checkpoint_exists(path):
        return  # crashed before anything was published: fine
    step = _restored_ring_step(path)
    newest = max((int(label[1:]) for label in acked), default=0)
    assert step in (newest, newest + 1), \
        f"acked checkpoint r{newest} restored step {step}"


_SCENARIOS = {
    "checkpoint_ring": (_run_checkpoint_ring, _check_checkpoint_ring),
    "journal": (_run_journal, _check_journal),
    "deadletter": (_run_dlq, _check_dlq),
    "archive": (_run_archive, _check_archive),
    "checkpoint": (_run_checkpoint, _check_checkpoint),
    "checkpoint_multi": (_run_checkpoint_multi, _check_checkpoint_multi),
    "checkpoint_whole": (_run_checkpoint_whole, _check_checkpoint_multi),
    "checkpoint_wagg": (_run_checkpoint_wagg, _check_checkpoint_wagg),
}


def _explore(tmp_path, surface: str, **kw) -> crashsim.CrashReport:
    run, check = _SCENARIOS[surface]
    root = str(tmp_path)
    rec = fsutil.OpRecorder()
    run(root, rec)
    assert rec.ops, "scenario recorded no durable ops"
    return crashsim.explore(rec, root, check, **kw)


# ---- the gate: every crash window of every surface -------------------------


class TestCrashPoints:

    @pytest.mark.parametrize("surface", sorted(_SCENARIOS))
    def test_every_crash_state_recovers(self, tmp_path, surface):
        report = _explore(tmp_path, surface)
        assert report.crash_points > 10, report.render()
        assert report.states_explored > 10, report.render()
        assert report.ok, report.render()

    def test_final_state_is_complete(self, tmp_path):
        """The no-crash run itself satisfies every invariant (sanity:
        the checkers are not vacuous)."""
        for surface in sorted(_SCENARIOS):
            run, check = _SCENARIOS[surface]
            root = str(tmp_path / surface)
            rec = fsutil.OpRecorder()
            run(root, rec)
            check(root, [m[1] for m in rec.ops if m[0] == "mark"])


# ---- the dynamic mutation gate ---------------------------------------------


class TestBarrierMutations:
    """Delete one barrier kind from one surface's protocol; the model
    checker must find a crash state that violates an invariant. A
    mutation that nothing catches means the barrier was decorative."""

    CASES = [
        ("journal", "fsync"), ("journal", "fsync_dir"),
        ("journal", "replace"),
        ("deadletter", "fsync"), ("deadletter", "fsync_dir"),
        ("deadletter", "replace"),
        ("checkpoint", "fsync"), ("checkpoint", "fsync_dir"),
        ("checkpoint", "replace"),
        ("checkpoint_multi", "fsync"), ("checkpoint_multi", "fsync_dir"),
        ("checkpoint_multi", "replace"),
        ("checkpoint_whole", "fsync"), ("checkpoint_whole", "fsync_dir"),
        ("checkpoint_whole", "replace"),
        ("checkpoint_wagg", "fsync"), ("checkpoint_wagg", "fsync_dir"),
        ("checkpoint_wagg", "replace"),
        ("checkpoint_ring", "fsync"), ("checkpoint_ring", "fsync_dir"),
        ("checkpoint_ring", "replace"),
        # the archive publishes by append+rotate, never by replace
        ("archive", "fsync"), ("archive", "fsync_dir"),
    ]

    @pytest.mark.parametrize("surface,barrier",
                             CASES, ids=[f"{s}-{b}" for s, b in CASES])
    def test_dropped_barrier_is_caught(self, tmp_path, surface, barrier):
        run, check = _SCENARIOS[surface]
        root = str(tmp_path)
        rec = fsutil.OpRecorder()
        with fsutil.suppressed(barrier):
            run(root, rec)
        report = crashsim.explore(rec, root, check, fail_fast=True)
        assert not report.ok, (
            f"deleting every {barrier!r} barrier from the {surface} "
            f"protocol produced no crash-state violation — the model "
            f"checker lost its teeth\n{report.render()}")

    def test_unknown_barrier_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown suppressible"):
            with fsutil.suppressed("flush"):
                pass


# ---- satellite: checkpoint crash-mid-save specifics ------------------------


class TestCheckpointMidSave:

    def test_crash_between_renames_restores_predecessor(self, tmp_path):
        """Simulate the exact mid-dance crash: the old checkpoint moved
        to .old, the new one never renamed in. Load must fall back to
        the complete predecessor."""
        path = str(tmp_path / "snap")
        save_checkpoint(path, _CKPT_1)
        os.rename(path, path + ".old")  # crash window between renames
        assert checkpoint_exists(path)
        assert _ckpt_equal(load_checkpoint(path), _CKPT_1)
        # and the next save self-heals the stale .old
        save_checkpoint(path, _CKPT_2)
        assert not os.path.isdir(path + ".old")
        assert _ckpt_equal(load_checkpoint(path), _CKPT_2)

    def test_one_write_an_array_in_the_op_log(self, tmp_path):
        """The recorder sees every byte of the streamed arrays.npz at
        the offset it landed on, offsets rising, and each array's bytes
        as ONE write from the array's buffer: the crash points between
        two of them and the torn tails inside one are what
        test_every_crash_state_recovers[checkpoint_multi] replays."""
        path = str(tmp_path / "snap")
        state = _multi_state(1)
        rec = fsutil.OpRecorder()
        with fsutil.observed(rec):
            save_checkpoint(path, state)
        writes = [op for op in rec.ops if op[0] == "write"
                  and op[1].endswith("arrays.npz.tmp")]
        offsets = [op[2] for op in writes]
        assert offsets == sorted(offsets)
        assert [off + len(data) for _, _, off, data in writes][:-1] == \
            offsets[1:]  # no hole, no byte written twice
        for name in _MULTI_ARRAYS:
            raw = np.ascontiguousarray(state[name]).tobytes()
            assert sum(data == raw for *_, data in writes) == 1, name
        with open(os.path.join(path, "arrays.npz"), "rb") as f:
            assert f.read() == b"".join(op[3] for op in writes)
        # the fsync of what was staged comes before the name it gets
        kinds = [(op[0], os.path.basename(op[1])) for op in rec.ops]
        assert kinds.index(("fsync", "arrays.npz.tmp")) < \
            kinds.index(("replace", "arrays.npz.tmp"))

    def test_torn_payload_rejects_loudly(self, tmp_path):
        """A damaged arrays.npz must raise, never silently decode."""
        path = str(tmp_path / "snap")
        save_checkpoint(path, _CKPT_1)
        with open(os.path.join(path, "arrays.npz"), "wb") as f:
            f.write(b"\0\0\0\0")
        with pytest.raises(Exception):
            load_checkpoint(path)

    def test_failed_save_keeps_previous(self, tmp_path, monkeypatch):
        path = str(tmp_path / "snap")
        save_checkpoint(path, _CKPT_1)
        real = fsutil.write_bytes_durable

        def boom(p, data):
            if p.endswith("meta.json"):
                raise OSError("disk full")
            real(p, data)

        monkeypatch.setattr(fsutil, "write_bytes_durable", boom)
        with pytest.raises(OSError, match="disk full"):
            save_checkpoint(path, _CKPT_2)
        monkeypatch.setattr(fsutil, "write_bytes_durable", real)
        assert _ckpt_equal(load_checkpoint(path), _CKPT_1)
        # no staging litter left behind
        litter = [n for n in os.listdir(tmp_path)
                  if n.startswith(".ckpt-")]
        assert litter == []


# ---- satellite: the ring's members, the two windows round a checkpoint -----


class TestCheckpointRingMembers:

    def test_crash_between_member_write_and_its_checkpoint(self, tmp_path):
        """A slide's member is on disk and the process dies before the
        checkpoint that would name it: the restart reads the
        predecessor, whose ring does not hold it; the replay closes the
        sub-window again, writes the member again over the orphan, and
        the next checkpoint names it."""
        path = str(tmp_path / "snap")
        members: dict = {}
        save_checkpoint(path, _ring_state(1, members))
        orphan = Member("top.2", 2, {"cms": np.zeros((2, 8), np.float32),
                                     "table_keys": np.zeros(6, np.uint32)})
        ckpt._write_member(path, orphan)  # ...and the crash
        assert sorted(os.listdir(path + ".members")) == \
            ["top.1.npz", "top.2.npz"]
        assert _restored_ring_step(path) == 1
        # the restart: members it restored are written, the rest are new
        restarted = {1: Member("top.1", 1, written=True)}
        save_checkpoint(path, _ring_state(2, restarted))
        assert restarted[2].written
        assert _restored_ring_step(path) == 2  # the replay's, bit-exact

    def test_crash_between_checkpoint_and_removal(self, tmp_path,
                                                  monkeypatch):
        """The checkpoint that drops a member is in place and the process
        dies before the removal: the stale file is named by nothing,
        harms nothing, and the next save removes it."""
        path = str(tmp_path / "snap")
        members: dict = {}
        for step in (1, 2):
            save_checkpoint(path, _ring_state(step, members))
        real = ckpt._prune_members
        monkeypatch.setattr(ckpt, "_prune_members", lambda *a: None)
        save_checkpoint(path, _ring_state(3, members))  # drops top.1
        monkeypatch.setattr(ckpt, "_prune_members", real)
        assert sorted(os.listdir(path + ".members")) == \
            ["top.1.npz", "top.2.npz", "top.3.npz"]
        assert _restored_ring_step(path) == 3
        save_checkpoint(path, _ring_state(4, members))
        assert sorted(os.listdir(path + ".members")) == \
            ["top.3.npz", "top.4.npz"]
        assert _restored_ring_step(path) == 4

    def test_a_member_is_written_once(self, tmp_path):
        path = str(tmp_path / "snap")
        members: dict = {}
        rec = fsutil.OpRecorder()
        with fsutil.observed(rec):
            for step in (1, 2, 3):
                save_checkpoint(path, _ring_state(step, members))
        writes = [op[1] for op in rec.ops
                  if op[0] == "replace" and ".members" in op[2]]
        assert [os.path.basename(w) for w in writes] == \
            ["top.1.npz.tmp", "top.2.npz.tmp", "top.3.npz.tmp"]
