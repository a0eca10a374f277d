"""A checkpoint's arrays cross the host once (ISSUE 43).

``save_checkpoint`` streams every array from its own buffer into the
staging ``arrays.npz`` (``engine/checkpoint.py::_write_npz`` under
``fsutil.staged_durable``): no ``BytesIO``, no ``getvalue()``, no
``tobytes()``. What these tests hold:

- the file is what ``np.savez`` would have made as far as a reader can
  tell: plain ``np.load`` opens it and every leaf comes back equal, dtype
  and shape, over a zoo of leaves;
- ``load_checkpoint`` restores from it what it restores from the parent's
  writer's file, and still reads the files of older writers;
- the span's ``npz_bytes`` is the file's size;
- saving allocates next to nothing (the parent's writer held all the
  bytes a second time), and ``DurableFile.write`` copies only for a
  recorder, whose op log is what it was;
- ``whole=True`` is the parent's writer, byte for byte, and only the
  mesh processor's pipeline asks the worker for it.
"""

import io
import json
import os
import tracemalloc
import zipfile

import numpy as np
import pytest

from flow_pipeline_tpu.engine import checkpoint as ckpt
from flow_pipeline_tpu.engine.checkpoint import (Member, load_checkpoint,
                                                 save_checkpoint)
from flow_pipeline_tpu.obs.trace import TRACER
from flow_pipeline_tpu.utils import fsutil

RNG = np.random.default_rng(43)

ZOO = {
    "float32_planes": {"cms": RNG.random((3, 1 << 12), dtype=np.float32),
                       "regs": RNG.random((4, 3, 257), dtype=np.float32)},
    "int32": {"a": RNG.integers(-2**31, 2**31, (7, 5), dtype=np.int32)},
    "int64": {"a": RNG.integers(-2**62, 2**62, 33, dtype=np.int64)},
    "uint64": {"a": RNG.integers(0, 2**64, (5, 9), dtype=np.uint64)},
    "bool": {"a": RNG.random(77) < 0.5},
    "zero_d": {"a": np.asarray(np.float32(2.5)), "b": np.asarray(7)},
    "empty": {"a": np.zeros((0, 3), np.uint32), "b": np.zeros(0)},
    "fortran_ordered": {"a": np.asfortranarray(
        RNG.integers(0, 99, (6, 11), dtype=np.int64))},
    "strided_view": {"a": np.arange(120, dtype=np.uint32)
                     .reshape(10, 12)[1::2, ::3],
                     "b": np.arange(9.0)[::-1]},
    "big_endian": {"a": np.arange(12, dtype=">u4")},
    # the window store's older form: a member a group
    "small_members_4096": {f"g{i}": np.array([i, 3 * i, 1], np.uint64)
                           for i in range(1 << 12)},
}


def _same(got, want) -> bool:
    want = np.asarray(want)
    return (got.dtype == want.dtype and got.shape == want.shape
            and np.array_equal(got, want))


# ---- what the file is ---------------------------------------------------------


@pytest.mark.parametrize("case", sorted(ZOO))
def test_plain_np_load_reads_every_leaf_equal(tmp_path, case):
    arrays = ZOO[case]
    path = str(tmp_path / "snap")
    save_checkpoint(path, arrays)
    with np.load(os.path.join(path, "arrays.npz")) as got:
        assert len(got.files) == len(arrays)
        with open(os.path.join(path, "meta.json")) as f:
            refs = {k: v["ref"] for k, v in json.load(f)["items"]}
        for name, want in arrays.items():
            assert _same(got[refs[name]], want), name
    restored = load_checkpoint(path)
    assert restored.keys() == arrays.keys()
    assert all(_same(restored[k], v) for k, v in arrays.items())


def test_the_archive_is_a_stored_zip_with_crcs(tmp_path):
    path = str(tmp_path / "snap")
    save_checkpoint(path, ZOO["float32_planes"])
    with zipfile.ZipFile(os.path.join(path, "arrays.npz")) as archive:
        assert archive.testzip() is None  # every member's CRC holds
        for info in archive.infolist():
            assert info.compress_type == zipfile.ZIP_STORED
            assert info.filename.endswith(".npy")
            assert info.CRC and info.file_size == info.compress_size > 0


def test_a_members_file_is_the_same_archive(tmp_path):
    path = str(tmp_path / "snap")
    arrays = {"cms": RNG.random((2, 64), dtype=np.float32),
              "table_keys": np.arange(9, dtype=np.uint32)[::2]}
    member = Member("top.3", 3, dict(arrays))
    save_checkpoint(path, {"ring": [member]})
    assert member.written and member.arrays is None
    with np.load(os.path.join(path + ".members", "top.3.npz")) as got:
        assert sorted(got.files) == sorted(arrays)
        assert all(_same(got[k], v) for k, v in arrays.items())
    (restored,) = load_checkpoint(path)["ring"]
    assert all(_same(restored[k], v) for k, v in arrays.items())


def test_an_object_array_is_refused(tmp_path):
    """np.savez would have pickled it, and load_checkpoint (no pickle)
    could never read it back."""
    path = str(tmp_path / "snap")
    with pytest.raises(ValueError, match="object"):
        save_checkpoint(path, {"a": np.array([{}, None], dtype=object)})
    assert not os.path.exists(path)
    assert [n for n in os.listdir(tmp_path) if n.startswith(".ckpt-")] == []


# ---- against the parent's writer, and older ones --------------------------------


def _state():
    return {"offsets": {0: 1234, 1: 99}, "watermark": 1_699_999_800,
            "models": {"hh": {"cms": RNG.random((3, 256), dtype=np.float32),
                              "keys": RNG.integers(0, 2**32, (16, 4),
                                                   dtype=np.uint32),
                              "valid": RNG.random(16) < 0.5},
                       "flows_5m": [np.arange(30, dtype=np.uint64)
                                    .reshape(10, 3), (1, 2.5, "x", None)]}}


def _write_as(path: str, state, savez) -> None:
    """The checkpoint an older writer left: ``savez`` of the arrays in
    memory, then both files written whole (the parent's save_checkpoint
    less its staging directory)."""
    arrays: dict = {}
    meta = ckpt._encode(state, arrays, "r", [])
    buf = io.BytesIO()
    savez(buf, **arrays)
    os.makedirs(path)
    fsutil.write_bytes_durable(os.path.join(path, "arrays.npz"),
                               buf.getvalue())
    fsutil.write_bytes_durable(os.path.join(path, "meta.json"),
                               json.dumps(meta).encode("utf-8"))


def _flat(tree, prefix=""):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _flat(v, f"{prefix}.{k}")
    elif isinstance(tree, (list, tuple)):
        yield prefix + ".type", type(tree).__name__
        for i, v in enumerate(tree):
            yield from _flat(v, f"{prefix}.{i}")
    else:
        yield prefix, tree


def _equal_trees(a, b) -> bool:
    fa, fb = list(_flat(a)), list(_flat(b))
    return [k for k, _ in fa] == [k for k, _ in fb] and all(
        _same(x, y) if isinstance(x, np.ndarray) else x == y
        for (_, x), (_, y) in zip(fa, fb))


@pytest.mark.parametrize("writer", ["stored_before_pr43",
                                    "deflated_before_pr30"])
def test_restores_what_an_older_writers_file_restores(tmp_path, writer):
    state = _state()
    old, new = str(tmp_path / "old"), str(tmp_path / "new")
    _write_as(old, state, np.savez if writer.startswith("stored")
              else np.savez_compressed)
    save_checkpoint(new, state)
    want, got = load_checkpoint(old), load_checkpoint(new)
    assert _equal_trees(got, want)
    assert _equal_trees(got, state)
    with zipfile.ZipFile(os.path.join(old, "arrays.npz")) as a, \
            zipfile.ZipFile(os.path.join(new, "arrays.npz")) as b:
        assert a.namelist() == b.namelist()  # the refs, in _encode's order


# ---- the parent's writer, kept for the mesh processor ---------------------------


_STREAMED = 0x08  # a member's CRC and sizes follow its bytes


@pytest.mark.parametrize("case", ["float32_planes", "strided_view",
                                  "small_members_4096"])
def test_whole_writes_the_parents_file(tmp_path, case):
    state = ZOO[case]
    whole, streamed = str(tmp_path / "whole"), str(tmp_path / "streamed")
    save_checkpoint(whole, state, whole=True)
    save_checkpoint(streamed, state)
    arrays: dict = {}
    ckpt._encode(state, arrays, "r", [])
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    with open(os.path.join(whole, "arrays.npz"), "rb") as f:
        assert f.read() == buf.getvalue()
    assert _equal_trees(load_checkpoint(whole), load_checkpoint(streamed))
    for path, flag in ((whole, 0), (streamed, _STREAMED)):
        with zipfile.ZipFile(os.path.join(path, "arrays.npz")) as archive:
            assert all(i.flag_bits & _STREAMED == flag
                       for i in archive.infolist())


def test_whole_tiles_the_same_spans(tmp_path):
    path = str(tmp_path / "snap")
    TRACER.configure("always")
    try:
        save_checkpoint(path, _state(), whole=True)
        spans = [s for s in TRACER.snapshot() if s[0].startswith("ckpt_")]
    finally:
        TRACER.configure("off")
    assert [s[0] for s in sorted(spans, key=lambda s: s[1])] == [
        "ckpt_d2h", "ckpt_serialize", "ckpt_write"]
    (args,) = [s[5] for s in spans if s[0] == "ckpt_serialize"]
    assert args["npz_bytes"] == os.path.getsize(
        os.path.join(path, "arrays.npz")) >= args["raw_bytes"] > 0


def test_only_the_mesh_pipeline_asks_for_whole(tmp_path, monkeypatch):
    """ShardedPipeline.checkpoint_whole holds estate-mesh4-catchup
    under its traffic file until that is re-provisioned (ROADMAP
    B-bench 0); every other dataplane streams."""
    from flow_pipeline_tpu.engine import worker as worker_mod
    from flow_pipeline_tpu.engine.fused import FusedPipeline
    from flow_pipeline_tpu.engine.hostfused import HostGroupPipeline
    from flow_pipeline_tpu.models import WindowAggConfig, WindowAggregator
    from flow_pipeline_tpu.parallel.pipeline import ShardedPipeline
    from flow_pipeline_tpu.sink import MemorySink
    from flow_pipeline_tpu.transport import Consumer, InProcessBus

    assert ShardedPipeline.checkpoint_whole is True
    assert not hasattr(FusedPipeline, "checkpoint_whole")
    assert not hasattr(HostGroupPipeline, "checkpoint_whole")
    asked = []
    monkeypatch.setattr(
        worker_mod, "save_checkpoint",
        lambda path, state, **kw: asked.append(kw))
    bus = InProcessBus()
    bus.create_topic("flows")
    worker = worker_mod.StreamWorker(
        Consumer(bus, fixedlen=True),
        {"flows_5m": WindowAggregator(WindowAggConfig(batch_size=64))},
        [MemorySink()],
        worker_mod.WorkerConfig(checkpoint_path=str(tmp_path / "ckpt")))
    worker.snapshot_and_commit()
    monkeypatch.setattr(worker, "fused", ShardedPipeline.__new__(
        ShardedPipeline))
    worker.snapshot_and_commit()
    assert asked == [{"whole": False}, {"whole": True}]


# ---- the span, and what saving costs ------------------------------------------


def test_npz_bytes_is_the_files_size(tmp_path):
    path = str(tmp_path / "snap")
    TRACER.configure("always")
    try:
        save_checkpoint(path, _state())
        (span,) = [s for s in TRACER.snapshot() if s[0] == "ckpt_serialize"]
    finally:
        TRACER.configure("off")
    args = span[5]
    assert args["members"] == 4
    assert args["npz_bytes"] == os.path.getsize(
        os.path.join(path, "arrays.npz"))
    assert args["npz_bytes"] >= args["raw_bytes"] == sum(
        np.asarray(v).nbytes for k, v in _flat(_state())
        if isinstance(v, np.ndarray))


def _traced_peak(fn) -> int:
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        fn()
        return tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()


def test_saving_holds_no_second_copy_of_the_arrays(tmp_path):
    """32 MB of planes. The parent's writer had them all a second time
    in memory (the BytesIO, and a chunk's tobytes beside it: 1.25 x the
    raw bytes by this count); the streamed one allocates headers. A
    single plane copied (tobytes, bytes(data) for a recorder that is not
    there) would read 0.25."""
    state = {f"f{i}": np.full((2, 1 << 20), i, np.float32) for i in range(4)}
    raw = sum(a.nbytes for a in state.values())
    assert raw == 32 << 20

    def parent():
        _write_as(str(tmp_path / "parent"), state, np.savez)

    assert _traced_peak(parent) > 1.0 * raw
    peak = _traced_peak(lambda: save_checkpoint(str(tmp_path / "snap"),
                                                state))
    assert peak < 0.05 * raw, peak  # ISSUE 43 asks for under 0.5
    assert _equal_trees(load_checkpoint(str(tmp_path / "snap")), state)


class _Probe(bytearray):
    """A buffer that says when something makes ``bytes`` of it."""

    copies = 0

    def __bytes__(self):
        type(self).copies += 1
        return bytes(bytearray(self))


@pytest.mark.parametrize("recorded", [False, True])
def test_durable_file_copies_only_for_a_recorder(tmp_path, recorded):
    path = str(tmp_path / "f.bin")
    rec = fsutil.OpRecorder()
    _Probe.copies = 0

    def write():
        with fsutil.open_durable(path, "wb") as f:
            assert f.write(_Probe(b"abc")) == 3
            assert f.write(memoryview(np.arange(2, dtype=np.uint8))) == 2
            assert f.tell() == 5
            fsutil.fsync_file(f)

    if recorded:
        with fsutil.observed(rec):
            write()
    else:
        write()
    with open(path, "rb") as f:
        assert f.read() == b"abc\x00\x01"
    assert _Probe.copies == (1 if recorded else 0)
    # every byte at the offset it landed on, in order: what
    # utils/crashsim.py replays
    assert rec.ops == ([("open", path, "w"), ("write", path, 0, b"abc"),
                        ("write", path, 3, b"\x00\x01"), ("fsync", path)]
                       if recorded else [])


def test_write_bytes_durable_logs_what_it_did_before(tmp_path):
    path = str(tmp_path / "meta.json")
    rec = fsutil.OpRecorder()
    with fsutil.observed(rec):
        fsutil.write_bytes_durable(path, b"{}")
    assert rec.ops == [("open", path + ".tmp", "w"),
                       ("write", path + ".tmp", 0, b"{}"),
                       ("fsync", path + ".tmp"),
                       ("replace", path + ".tmp", path),
                       ("fsync_dir", str(tmp_path))]
