"""Sketch-state snapshot / restore.

Kafka offsets are the reference's only checkpoint ("it fetches from the
current offset", ref: README.md:115); a sketch worker additionally needs
the open-window device state so a restart resumes without double counting
(SURVEY.md §5). A checkpoint is a directory with:

- ``arrays.npz``   every device/host array leaf (numpy, members stored:
  the planes are float32 counters, and deflating them was all but the
  whole cost of a checkpoint for half the bytes; ``load_checkpoint``
  reads the deflated members of older checkpoints too)
- ``meta.json``    consumer positions, window dicts, scalars, tree layout

A state may hold ``Member`` leaves: parts that never change once made (a
sliding window's closed sub-window states, ``engine/windowed.py``). Each
is written once, as ``<path>.members/<file>.npz`` under the same durable
idiom, before the first checkpoint that names it; the checkpoint carries
its name alone, ``load_checkpoint`` reads it back in the leaf's place,
and a member no checkpoint names any more is removed after the
checkpoint that dropped it has been published durably. A crash between
a member's write and its checkpoint leaves a file nothing names (the
replay writes it again); one between a checkpoint and the removal
leaves files the next save removes.

Writes follow the full durable-publish protocol via ``utils/fsutil``
(this was the one durable surface with ZERO fsyncs before flowtorn):
each payload is written with write→fsync→replace→dir-fsync inside a
staging directory, the staging directory is atomically renamed over
the target, and the containing directory is fsynced — so a crash at
ANY point leaves the complete old checkpoint (possibly under ``.old``)
or the complete new one, never a torn or silently-empty mix. The
arrays go through that idiom streamed, not whole: ``_write_npz`` writes
the archive member by member into the staging file that
``fsutil.staged_durable`` holds open, each array's bytes in one write
from the array's own buffer, and the fsync, the replace and the
directory fsync follow when that block ends; a 50 MB checkpoint is
never a second time in memory. The crash-point model checker
(``make crash-parity``) enumerates every window of the save and pins
exactly that. Only numpy/json are used — no pickle, so a checkpoint
directory is safe to share between trust domains.
"""

from __future__ import annotations

# flowlint: durable-checked

import contextlib
import io
import json
import os
import shutil
import tempfile
import zipfile
from typing import Any

import numpy as np

from ..obs.trace import TRACER
from ..utils import fsutil


class Member:
    """A leaf of a checkpoint's state that is a file of its own: ``file``
    names it, ``sub`` is the sub-window it holds (for the span),
    ``arrays`` its content ({name: array}) until it has been written."""

    __slots__ = ("file", "sub", "arrays", "written")

    def __init__(self, file: str, sub: int, arrays: dict | None = None,
                 written: bool = False):
        self.file, self.sub = file, sub
        self.arrays, self.written = arrays, written


def _members_dir(path: str) -> str:
    return path + ".members"


def _write_member(path: str, member: Member) -> None:
    """One closed state, durable before any checkpoint names it."""
    parent = _members_dir(path)
    if not os.path.isdir(parent):
        os.makedirs(parent, exist_ok=True)
        fsutil.fsync_dir(os.path.dirname(parent))
    with TRACER.span("ckpt_member", sub=member.sub) as span:
        with fsutil.staged_durable(
                os.path.join(parent, member.file + ".npz")) as f:
            _write_npz(f, member.arrays)
            span["bytes"] = f.tell()
    member.arrays, member.written = None, True


def _write_npz(f, arrays: dict) -> None:
    """Stream ``arrays`` into ``f`` as the archive ``np.savez`` makes
    and ``np.load`` opens: a zip of stored ``<name>.npy`` members with
    their CRCs, zip64 where it takes one. Each array goes from its own
    buffer to the file in one write (one copy only where a leaf is not
    C-contiguous). A :class:`fsutil.DurableFile` has no ``seek``, so
    ``zipfile`` writes its streaming form: a member's CRC and size
    follow its bytes and stand in the central directory, which is what
    a reader goes by."""
    with zipfile.ZipFile(f, "w", zipfile.ZIP_STORED) as archive:
        for name, arr in arrays.items():
            arr = np.asarray(arr, order="C")  # copies a strided leaf
            if arr.dtype.hasobject:
                raise ValueError(f"{name}: a checkpoint holds no "
                                 f"object arrays (no pickle)")
            with archive.open(name + ".npy", "w",
                              force_zip64=True) as member:
                np.lib.format.write_array_header_1_0(
                    member, np.lib.format.header_data_from_array_1_0(arr))
                member.write(memoryview(arr.reshape(-1).view(np.uint8)))


def _prune_members(path: str, named: list) -> None:
    """Remove the members the checkpoint just published does not name."""
    parent = _members_dir(path)
    if not os.path.isdir(parent):
        return
    keep = {m.file + ".npz" for m in named}
    stale = [n for n in os.listdir(parent) if n not in keep]
    for name in stale:
        fsutil.remove(os.path.join(parent, name))
    if stale:
        fsutil.fsync_dir(parent)


def _to_host(obj: Any, span: dict) -> Any:
    """The same tree with every device leaf brought to the host, in the
    order ``_encode`` walks it; counts what it copied into ``span``."""
    if isinstance(obj, dict):
        return {k: _to_host(v, span) for k, v in obj.items()}
    if isinstance(obj, tuple) and hasattr(obj, "_fields"):  # NamedTuple
        return type(obj)(*(_to_host(getattr(obj, f), span)
                           for f in obj._fields))
    if isinstance(obj, (list, tuple)):
        return type(obj)(_to_host(v, span) for v in obj)
    if isinstance(obj, (str, int, float, bool, np.ndarray, Member)) \
            or obj is None:
        return obj
    arr = np.asarray(obj)
    span["bytes"] += arr.nbytes
    span["leaves"] += 1
    return arr


def _encode(obj: Any, arrays: dict[str, np.ndarray], path: str,
            members: list | None = None) -> Any:
    """Recursively split a state object into JSON-able structure + arrays
    + the ``Member`` leaves it names (``members``: a checkpoint's alone;
    the mesh codec's payloads hold none)."""
    if isinstance(obj, Member):
        members.append(obj)
        return {"__kind__": "member", "file": obj.file}
    if isinstance(obj, dict):
        return {
            "__kind__": "dict",
            "items": [
                [_encode(k, arrays, f"{path}.k{i}", members),
                 _encode(v, arrays, f"{path}.v{i}", members)]
                for i, (k, v) in enumerate(obj.items())
            ],
        }
    if isinstance(obj, tuple) and hasattr(obj, "_fields"):  # NamedTuple
        return {
            "__kind__": "namedtuple",
            "name": type(obj).__name__,
            "fields": {
                f: _encode(getattr(obj, f), arrays, f"{path}.{f}", members)
                for f in obj._fields
            },
        }
    if isinstance(obj, (list, tuple)):
        return {
            "__kind__": "list" if isinstance(obj, list) else "tuple",
            "items": [_encode(v, arrays, f"{path}.{i}", members)
                      for i, v in enumerate(obj)],
        }
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    # array-like (jax or numpy): materialize to host
    arr = np.asarray(obj)
    arrays[path] = arr
    return {"__kind__": "array", "ref": path}


def _decode(spec: Any, arrays, members_dir: str | None = None) -> Any:
    if isinstance(spec, dict) and "__kind__" in spec:
        kind = spec["__kind__"]
        if kind == "dict":
            return {
                _freeze(_decode(k, arrays, members_dir)):
                _decode(v, arrays, members_dir)
                for k, v in spec["items"]
            }
        if kind == "namedtuple":
            return {f: _decode(v, arrays, members_dir)
                    for f, v in spec["fields"].items()}
        if kind in ("list", "tuple"):
            items = [_decode(v, arrays, members_dir)
                     for v in spec["items"]]
            return items if kind == "list" else tuple(items)
        if kind == "array":
            return arrays[spec["ref"]]
        if kind == "member":
            return dict(np.load(os.path.join(members_dir,
                                             spec["file"] + ".npz")))
        raise ValueError(f"unknown kind {kind}")
    return spec


def _freeze(key):
    return tuple(key) if isinstance(key, list) else key


def save_checkpoint(path: str, state: Any, *, whole: bool = False) -> None:
    """Atomically and DURABLY write ``state`` (nested dicts/lists/
    NamedTuples/arrays). The payloads are staged (and individually
    fsynced) in a sibling temp directory, the directory is renamed over
    the target, and the parent directory entry is fsynced — only then
    is the superseded ``.old`` tree deleted, so every crash window
    leaves a complete old or complete new checkpoint on disk.

    ``whole`` builds ``arrays.npz`` in memory and writes it in one
    piece, as every checkpoint was written before the arrays were
    streamed: the same file to a reader behind the same barriers, with
    three more passes over the bytes. Only the mesh processor asks for
    it, and only until its benchmark cell has room for the faster form
    (``parallel/pipeline.py::ShardedPipeline.checkpoint_whole``)."""
    parent = os.path.dirname(os.path.abspath(path)) or "."
    os.makedirs(parent, exist_ok=True)
    with TRACER.span("ckpt_d2h", bytes=0, leaves=0) as span:
        state = _to_host(state, span)
    tmp = tempfile.mkdtemp(prefix=".ckpt-", dir=parent)
    try:
        # publish through the one durable-write idiom (write tmp ->
        # fsync -> replace -> dir fsync; numpy's own savez path never
        # fsyncs): the arrays are streamed into their staging file
        # under ckpt_serialize, and ``staging`` says the rest of the
        # sentence under ckpt_write
        with contextlib.ExitStack() as staging:
            with TRACER.span("ckpt_serialize") as span:
                arrays: dict[str, np.ndarray] = {}
                members: list[Member] = []
                meta = _encode(state, arrays, "r", members)
                meta_json = json.dumps(meta).encode("utf-8")
                npz = os.path.join(tmp, "arrays.npz")
                if whole:
                    buf = io.BytesIO()
                    np.savez(buf, **arrays)
                    data = buf.getvalue()
                else:
                    f = staging.enter_context(fsutil.staged_durable(npz))
                    _write_npz(f, arrays)
                span["raw_bytes"] = sum(a.nbytes for a in arrays.values())
                span["members"] = len(arrays)
                span["npz_bytes"] = len(data) if whole else f.tell()
            for member in members:  # durable before the checkpoint names it
                if not member.written:
                    _write_member(path, member)
            with TRACER.span("ckpt_write"):
                if whole:
                    fsutil.write_bytes_durable(npz, data)
                staging.close()  # the arrays' fsync, replace and dir fsync
                fsutil.write_bytes_durable(os.path.join(tmp, "meta.json"),
                                           meta_json)
                if os.path.isdir(path):
                    old = path + ".old"
                    # a crash between the renames below can leave a stale .old;
                    # clear it or every future snapshot fails with ENOTEMPTY
                    if os.path.isdir(old):
                        fsutil.rmtree(old)
                    fsutil.rename(path, old)
                    fsutil.rename(tmp, path)
                    fsutil.rmtree(old)
                else:
                    fsutil.rename(tmp, path)
                    # a crash between the two renames of a PREVIOUS save leaves
                    # the predecessor under .old with no primary; now that a
                    # complete new checkpoint is published (rename above), the
                    # stale .old is superseded — clear it AFTER publishing so
                    # no crash window is ever left with neither tree
                    if os.path.isdir(path + ".old"):
                        fsutil.rmtree(path + ".old")
                # directory-entry barrier: the renames above (and the .old
                # cleanup) are durable only once the parent directory is —
                # without this a power loss after the ack could silently revert
                # an acked checkpoint to its predecessor
                fsutil.fsync_dir(parent)
                _prune_members(path, members)
    except BaseException:
        # flowlint: disable=durability-protocol -- best-effort cleanup of the unpublished staging dir on a failed save; no ack references it, resurrection after a crash is harmless garbage
        shutil.rmtree(tmp, ignore_errors=True)
        raise


def checkpoint_exists(path: str) -> bool:
    return os.path.isdir(path) or os.path.isdir(path + ".old")


def load_checkpoint(path: str) -> Any:
    """Load a checkpoint. NamedTuples come back as field dicts — callers
    rebuild their concrete state types (see StreamWorker.restore).

    Falls back to ``<path>.old`` when the primary is missing: a crash
    between save_checkpoint's two renames leaves only the previous
    checkpoint under .old, which is still a consistent snapshot."""
    primary = path  # members stay beside it whichever tree is read
    if not os.path.isdir(path) and os.path.isdir(path + ".old"):
        path = path + ".old"
    with TRACER.span("ckpt_load") as span:
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        # one zip member read an array: a checkpoint of before the window
        # store's array form has a member a group, and is slow to read
        arrays = np.load(os.path.join(path, "arrays.npz"))
        state = _decode(meta, arrays, _members_dir(primary))
        span["members"] = len(arrays.files)
        span["bytes"] = sum(os.path.getsize(os.path.join(path, name))
                            for name in ("arrays.npz", "meta.json"))
    return state
