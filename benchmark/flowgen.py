"""How a stream gets made: a chunk encoded to the wire in a worker process,
handed over through a ring of shared memory, cut into frames.

What a stream *is* (its keys, its draws, its event time, its partitions) is
its kind's, a file under ``streams/`` that ``manifest.py`` finds by the name
in a configuration's ``stream.kind``; the worker processes load it by its
path. Encoding to the wire goes through the program's producer-side
encoder (``FlowBatch.to_wire``), in ``chunk_blob``; the reference never
reads what that returns. ``StreamSpec``, ``KeyTable``, ``chunk_draws`` and
``chunk_columns`` are ``streams/zipf-ranks.py``'s, under the names tests
have imported from here since PR 23.
"""

from __future__ import annotations

import os
import time

import numpy as np

from . import manifest

_ZIPF = manifest.load_stream_kind(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "streams",
    manifest.DEFAULT_STREAM + ".py"))
StreamSpec, KeyTable = _ZIPF.StreamSpec, _ZIPF.KeyTable
chunk_draws, chunk_columns = _ZIPF.chunk_draws, _ZIPF.chunk_columns


def frame_offsets(blob: bytes) -> np.ndarray:
    """Start of every length-prefixed frame in ``blob``, and its end."""
    offs, pos, n = [0], 0, len(blob)
    while pos < n:
        length, shift = 0, 0
        while True:
            b = blob[pos]
            pos += 1
            length |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        pos += length
        offs.append(pos)
    if pos != n:
        raise ValueError("truncated frame stream")
    return np.asarray(offs, np.int64)


# ---- the ring: how a chunk's blob gets from a worker to the cutting thread ---


class Ring:
    """Shared memory through which the worker processes hand over their
    chunks' blobs: a count of the chunks cut so far, then ``slots`` slots
    of ``slot_bytes``, chunk ``c`` in slot ``c mod slots``. A worker
    writes chunk ``c`` (``put``) once the count has passed ``c - slots``:
    the chunk that had the slot before is cut. The thread that cuts
    (``cut``) alone moves the count, and cuts in order, so the lowest
    chunk not yet cut always finds its slot free and nothing can wait for
    ever. (A semaphore a slot does: it lets chunk ``c + slots`` take the
    turn that was chunk ``c``'s.) The process that makes the ring
    (``name`` None) unlinks it; a worker attaches by name."""

    HEADER = 64
    WAIT_S = 300.0  # a run is over long before

    def __init__(self, slots: int, slot_bytes: int, name=None):
        import ctypes
        from multiprocessing import shared_memory

        self.owner = name is None
        # attaching registers the name once more with the resource
        # tracker, which a worker shares with the process that made the
        # segment and unlinks it: a set, so nothing to undo there
        self.shm = shared_memory.SharedMemory(
            name=name, create=self.owner,
            size=self.HEADER + slots * slot_bytes)
        self.slots, self.slot_bytes = slots, slot_bytes
        self.count = ctypes.c_int64.from_buffer(self.shm.buf)
        if self.owner:
            self.count.value = 0

    def for_workers(self) -> tuple:
        return self.slots, self.slot_bytes, self.shm.name

    def _at(self, chunk: int) -> int:
        return self.HEADER + (chunk % self.slots) * self.slot_bytes

    def put(self, chunk: int, blob: bytes) -> int:
        """Worker side: the blob into the chunk's slot, once that is
        free; returns its length."""
        if len(blob) > self.slot_bytes:
            raise ValueError(f"chunk {chunk} encodes to {len(blob)} bytes, "
                             f"a slot of the ring holds {self.slot_bytes}")
        deadline = time.monotonic() + self.WAIT_S
        while self.count.value <= chunk - self.slots:
            if time.monotonic() > deadline:
                raise TimeoutError(f"chunk {chunk} waited {self.WAIT_S} s "
                                   f"for its slot of the ring")
            time.sleep(0.0005)
        at = self._at(chunk)
        self.shm.buf[at:at + len(blob)] = blob
        return len(blob)

    def cut(self, chunk: int, nbytes: int, cutter) -> tuple:
        """Owner side: the chunk's frames by ``cutter`` (a
        ``struct.Struct``), and the slot given back."""
        if cutter.size != nbytes:
            raise ValueError(f"chunk {chunk}: a format of {cutter.size} "
                             f"bytes for a blob of {nbytes}")
        frames = cutter.unpack_from(self.shm.buf, self._at(chunk))
        self.count.value = chunk + 1
        return frames

    def close(self) -> None:
        self.count = None  # its view of the buffer, or close() refuses
        self.shm.close()
        if self.owner:
            self.shm.unlink()


# ---- worker-process side (multiprocessing, spawn; never imports jax) -------

_W: dict = {}


def _init_worker(stream_path: str, spec_args: tuple, root: str,
                 ring: tuple) -> None:
    """``spec_args``: what the kind's ``spec()`` takes, as plain data (a
    spec is of a class that only the kind's file, loaded by path, has)."""
    import sys

    if root not in sys.path:
        sys.path.insert(0, root)
    kind = _W["kind"] = manifest.load_stream_kind(stream_path)
    _W["spec"] = kind.spec(*spec_args)
    _W["table"] = kind.key_table(_W["spec"])
    _W["ring"] = Ring(*ring)


def cut_format(offs: np.ndarray) -> bytes:
    """The ``struct`` format that cuts a blob at ``offs`` into its frames,
    length prefix and all: ``=93s92s...``, one code a frame."""
    lens = np.diff(offs)
    code = {int(n): b"%ds" % n for n in np.unique(lens)}
    return b"=" + b"".join(map(code.__getitem__, lens.tolist()))


def chunk_blob(kind, spec, table, chunk: int) -> tuple:
    """One chunk's wire blob, the program's own producer-side encoding of
    its columns as its kind makes them, and its draws."""
    from flow_pipeline_tpu.schema.batch import FlowBatch

    draws = kind.chunk_draws(spec, table, chunk)
    return FlowBatch(kind.chunk_columns(spec, table, chunk,
                                        draws)).to_wire(), draws


def encode_chunk(chunk: int):
    """One chunk: (chunk, bytes of its blob, cut format, draws, seconds it
    took). The blob goes into the ring, 3 MB a chunk that pass through no
    pipe and no pickle; the format is how the process that owns the bus
    cuts it into frames in one call."""
    t0 = time.monotonic()
    blob, draws = chunk_blob(_W["kind"], _W["spec"], _W["table"],
                             chunk)
    fmt = cut_format(frame_offsets(blob))
    seconds = time.monotonic() - t0  # without the wait for a slot
    return chunk, _W["ring"].put(chunk, blob), fmt, draws, seconds
