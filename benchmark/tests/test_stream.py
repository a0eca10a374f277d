"""The stream's way from the worker processes to the bus (PR 29): the
cut format makes the frames a slice a frame made, the ring of shared
memory hands over the same frames in the same order with more workers
than slots, and a backlog's one large ``produce`` puts the same frames on
the bus as an open loop's small ones."""

import json
import multiprocessing
import os
import struct
import threading
import types

import pytest

from benchmark import drive, flowgen, manifest, schedule
from benchmark.modes import backlog

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CHUNKS = 120


SEED = 2**31 + 29


def _stream():
    """(the stream, what its kind's spec() takes) at a small size."""
    with open(os.path.join(ROOT, "benchmark/configs/default-estate.json")) \
            as f:
        stream = manifest.load_stream(ROOT, ["benchmark"], dict(
            json.load(f)["stream"], n_keys=500, chunk_flows=256,
            block_flows=256))
    with open(os.path.join(ROOT, "benchmark/traffic/backlog-drain.json")) \
            as f:
        plan = backlog.plan(json.load(f), stream, 1)
    return stream, (SEED, dict(stream), plan.first_close_flow, plan.phase_s)


def _spec():
    stream, args = _stream()
    return stream.kind.spec(*args)


@pytest.fixture(scope="module")
def by_slices():
    """Every chunk's frames, cut a slice a frame from the whole blob (the
    way of PRs 23-28), and its draws."""
    spec = _spec()
    table = flowgen.KeyTable(spec)
    out = []
    for c in range(CHUNKS):
        blob, draws = flowgen.chunk_blob(flowgen._ZIPF, spec, table, c)
        offs = flowgen.frame_offsets(blob)
        o = offs.tolist()
        out.append(([blob[a:b] for a, b in zip(o[:-1], o[1:])],
                    flowgen.cut_format(offs), blob, draws))
    return out


def test_the_cut_format_cuts_what_a_slice_a_frame_cut(by_slices):
    for frames, fmt, blob, _draws in by_slices:
        assert len(frames) == 256 and b"".join(frames) == blob
        assert list(struct.Struct(fmt).unpack(blob)) == frames
        assert struct.calcsize(fmt) == len(blob)


@pytest.mark.parametrize("procs,slots", [(4, 3), (3, 8)])
def test_the_ring_hands_over_the_same_frames_in_order(by_slices, procs,
                                                      slots):
    """More workers than slots: every worker waits for its slot most of
    the time, and a chunk may not take the turn of the chunk ``slots``
    before it (a semaphore a slot let it, and the run hung)."""
    stream, spec_args = _stream()
    spec = stream.kind.spec(*spec_args)
    ctx = multiprocessing.get_context("spawn")
    ring = flowgen.Ring(slots, spec.chunk_flows * 128)
    pool = ctx.Pool(procs, initializer=flowgen._init_worker,
                    initargs=(stream.path, spec_args, ROOT,
                              ring.for_workers()))
    run = types.SimpleNamespace(
        spec=spec, frames=[], draws=[],
        plan=types.SimpleNamespace(total_flows=CHUNKS * spec.chunk_flows))
    try:
        stream = drive.Stream(
            run, pool.imap(flowgen.encode_chunk, range(CHUNKS)), ring)
        stream.thread.join(timeout=120)
        assert not stream.thread.is_alive(), "the hand-over hung"
        assert stream.error is None and stream.done
        assert stream.made == CHUNKS * spec.chunk_flows
    finally:
        pool.terminate()
        pool.join()
        ring.close()
    assert run.frames == [tuple(frames) for frames, *_ in by_slices]
    for (_f, _fmt, _b, draws), got in zip(by_slices, run.draws):
        assert all((a == b).all() for a, b in zip(draws, got))


def test_a_run_aborts_where_shared_memory_cannot_hold_the_ring(monkeypatch):
    from benchmark import run as bench

    room = types.SimpleNamespace(f_bavail=8192, f_frsize=4096)  # 32 MB
    monkeypatch.setattr(bench.os, "statvfs", lambda path: room)
    with pytest.raises(drive.Abort, match="33554432 bytes free.*37748800"):
        bench._ring(8, 32768)
    ring = bench._ring(2, 2048)
    try:
        assert (ring.slots, ring.slot_bytes) == (3, 2048 * 128)
    finally:
        ring.close()


def test_stopping_the_stream_ends_the_cut_before_the_ring_is_closed():
    """An aborted run closes the ring while chunks still come: the
    thread that cuts has ended by then, at a chunk's edge."""
    ring = flowgen.Ring(2, 8)
    served = []

    def chunks():
        for c in range(10_000):
            served.append(ring.put(c, bytes(8)))
            yield c, 8, b"=4s4s", (c,), 0.0

    run = types.SimpleNamespace(
        spec=types.SimpleNamespace(chunk_flows=2), frames=[], draws=[],
        plan=types.SimpleNamespace(total_flows=20_000))
    stream = drive.Stream(run, chunks(), ring)
    stream.stop()
    assert not stream.thread.is_alive() and stream.done
    assert stream.error is None and stream.made == 2 * len(run.frames)
    assert len(run.frames) <= len(served) < 10_000  # cut short
    ring.close()  # no view of it is held


def test_a_blob_the_format_does_not_fit_is_refused():
    ring = flowgen.Ring(2, 64)
    try:
        with pytest.raises(ValueError):
            ring.cut(0, 9, struct.Struct("=4s4s"))
    finally:
        ring.close()


class _Bus:
    def __init__(self):
        self.log, self.calls = [], 0

    def produce_many(self, topic, values, partition=None):
        values = list(values)
        self.calls += 1
        self.log.extend(values)
        return len(values)


def test_one_large_produce_and_many_small_put_the_same_frames_on_the_bus():
    frames = [bytes([i % 251]) * 3 for i in range(5000)]
    buses = []
    for cuts in ([0, 40, 5000], list(range(0, 5001, 70)) + [5000]):
        bus = _Bus()
        run = types.SimpleNamespace(
            spec=types.SimpleNamespace(chunk_flows=100),
            frames=[tuple(frames[i:i + 100]) for i in range(0, 5000, 100)],
            deal=drive.Deal(None, 1, 5000),
            sut=types.SimpleNamespace(bus=bus, topic="t"))
        for lo, hi in zip(cuts[:-1], cuts[1:]):
            drive.produce(run, lo, hi)
        assert bus.calls == len(cuts) - 1
        assert run.frames == [None] * 50  # the bus holds them now
        buses.append(bus.log)
    assert buses[0] == buses[1] == frames


def test_generate_sends_the_early_flows_and_waits_for_the_rest():
    """Flows below ``early_hi`` reach the bus while the stream is still
    being made; ``generate`` returns when all of it is."""
    spec = types.SimpleNamespace(chunk_flows=4)
    gate = threading.Event()

    ring = flowgen.Ring(2, 8)

    def chunks():
        for c in range(6):
            if c == 3:
                gate.wait(timeout=30)
            yield c, ring.put(c, bytes(range(c * 8, c * 8 + 8))), \
                b"=2s2s2s2s", (c,), 0.0

    bus = _Bus()
    ready = threading.Event()
    ready.set()
    run = types.SimpleNamespace(
        spec=spec, frames=[], draws=[], error=None,
        plan=types.SimpleNamespace(total_flows=24),
        deal=drive.Deal(None, 1, 24),
        sut=types.SimpleNamespace(bus=bus, topic="t", bus_ready=ready))
    stream = drive.Stream(run, chunks(), ring)
    done = threading.Thread(target=drive.generate, args=(run, stream, 10))
    done.start()
    deadline = threading.Event()
    for _ in range(200):
        if len(bus.log) == 10:
            break
        deadline.wait(0.02)
    assert len(bus.log) == 10 and done.is_alive()  # chunk 3 is held back
    gate.set()
    done.join(timeout=30)
    assert not done.is_alive()
    assert len(run.frames) == 6 and len(bus.log) == 10
    assert run.frames[:2] == [None, None]  # sent whole
    assert bus.log[8:] == list(run.frames[2][:2])
    assert all(len(f) == 4 for f in run.frames[2:])
    ring.close()
