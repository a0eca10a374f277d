"""flowmesh in-process runtime: N members + coordinator on one box.

The harness behind ``cli.py pipeline -mesh.workers N`` and ``make
mesh-parity``: flows are sharded by KEY-HASH across
bus partitions (every row of a flow key lands on the same partition, so
per-shard sketches see each key's complete substream), N MeshMember
threads consume their assigned partitions, and the coordinator merges
window state network-wide at close. The same member/coordinator objects
run across real processes through mesh/server.py — this module only
supplies the single-process wiring.
"""

from __future__ import annotations

# flowlint: lock-checked
# (the runtime mutates its attributes from the driver thread only;
# member threads touch members, which carry their own contract)

import threading
import time
from typing import Any, Callable, Optional, Sequence

import numpy as np

from ..engine.hostfused import _key_lanes_np
from ..engine.worker import WorkerConfig
from ..obs import get_logger
from ..schema import wire
from ..schema.batch import FlowBatch
from ..schema.keys import hash_words_np
from ..transport import Consumer, InProcessBus
from .coordinator import MeshCoordinator, spec_from_models
from .member import MeshMember

log = get_logger("mesh")

# The canonical shard key: the finest key family (5-tuple). Families
# whose key tuple CONTAINS the shard key (the top-talkers family) get
# the strongest guarantee: each of their keys lands wholly on one shard,
# so merged candidate tables are a disjoint union with exact per-key
# sums. Subset families (per-IP, per-port) necessarily spread one key
# across shards — no single shard key can colocate every projection —
# and merge as standard sketch monoids instead: the CMS element-sum is
# still a true union-stream sketch (count-min is linear), and the table
# fold sums per-shard resident values — exact whenever a key is
# resident in every shard that saw it (always, while distinct keys <=
# capacity: the regime `make mesh-parity` pins bit-exact), and
# otherwise upper-bounded by the est columns with per-shard
# Misra-Gries admission bounds (the HashPipe per-shard trade).
SHARD_KEY_COLS = ("src_addr", "dst_addr", "src_port", "dst_port", "proto")


def shard_ids(batch: FlowBatch, n_partitions: int,
              key_cols: Sequence[str] = SHARD_KEY_COLS):
    """Per-row partition ids: murmur3 over the uint32 key lanes mod P —
    deterministic, so every leg of an A/B (and a replay) shards the
    stream identically."""
    lanes = _key_lanes_np(batch.columns, tuple(key_cols))
    return hash_words_np(lanes) % np.uint32(n_partitions)


def produce_sharded(bus: InProcessBus, topic: str, batch: FlowBatch,
                    n_partitions: int,
                    key_cols: Sequence[str] = SHARD_KEY_COLS) -> int:
    """Append one generated batch to the bus, key-hash sharded. Row
    order within each partition preserves the batch's time order."""
    pids = shard_ids(batch, n_partitions, key_cols)
    for p in range(n_partitions):
        idx = np.flatnonzero(pids == p)
        if not len(idx):
            continue
        part = FlowBatch({k: v[idx] for k, v in batch.columns.items()})
        bus.produce_many(topic, wire.iter_raw_frames(part.to_wire()),
                         partition=p)
    return len(batch)


class InProcessMesh:
    """Coordinator + N member threads over one in-process bus."""

    def __init__(self, bus: InProcessBus, topic: str, n_workers: int,
                 model_factory: Callable[[], dict],
                 config: WorkerConfig = WorkerConfig(),
                 sinks: Sequence[Any] = (),
                 member_sinks: Sequence[Any] = (),
                 heartbeat_timeout: float = 30.0,
                 submit_every: int = 0,
                 sync_interval: float = 0.05,
                 journal: Optional[str] = None):
        self.bus = bus
        self.topic = topic
        # one throwaway model set derives the merge specs — members
        # build their own fresh sets per assignment epoch
        self.coordinator = MeshCoordinator(
            spec_from_models(model_factory()), bus.partitions(topic),
            sinks=sinks, heartbeat_timeout=heartbeat_timeout,
            journal=journal)
        self.members = []
        for i in range(n_workers):
            mid = f"w{i}"
            self.members.append(MeshMember(
                mid, self.coordinator,
                consumer_factory=self._consumer_factory(mid),
                model_factory=model_factory, config=config,
                sinks=list(member_sinks), submit_every=submit_every,
                sync_interval=sync_interval))
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []

    def _consumer_factory(self, member_id: str):
        def factory(partitions):
            return Consumer(self.bus, self.topic,
                            group=f"mesh-{member_id}", fixedlen=True,
                            partitions=list(partitions))
        return factory

    # ---- lifecycle --------------------------------------------------------

    def start(self) -> "InProcessMesh":
        # pre-join every member before any thread consumes: the first
        # assignment is computed once over the FULL membership, instead
        # of member 0 grabbing all partitions and resyncing immediately
        for m in self.members:
            self.coordinator.join(m.member_id, provider=m._query_state)
            m._joined = True
        for m in self.members:
            t = threading.Thread(target=m.run, args=(self._stop,),
                                 name=f"mesh-{m.member_id}", daemon=True)
            self._threads.append(t)
            t.start()
        return self

    def kill_member(self, i: int, fence: bool = True) -> str:
        """Abrupt member death (churn): stop it WITHOUT submission, then
        (by default) fence it at the coordinator immediately — the
        deterministic stand-in for the heartbeat timeout."""
        m = self.members[i]
        m.kill()
        if fence:
            self.coordinator.fence(m.member_id)
        return m.member_id

    def wait_idle(self, idle_rounds: int = 20, timeout: float = 300.0,
                  poll: float = 0.02) -> None:
        """Block until every live member has been idle for
        ``idle_rounds`` consecutive steps AND every partition is owned
        (pre-produced streams: everything consumed and every rebalance
        settled — members idling mid-handoff, with partitions released
        but not yet re-acquired, do NOT count as quiescence)."""
        deadline = time.monotonic() + timeout
        streak = 0
        while time.monotonic() < deadline:
            live = [m for m in self.members if not m._dead]
            ok = live and all(m.idle_streak >= idle_rounds for m in live)
            if ok:
                st = self.coordinator.status()
                owned = sum(len(v["owned"])
                            for v in st["members"].values())
                ok = owned == st["partitions"]
            # two consecutive successful polls: closes the sliver where
            # a member was just granted ownership but has not yet reset
            # its (stale) idle streak from the waiting phase
            streak = streak + 1 if ok else 0
            if streak >= 2:
                return
            time.sleep(poll)
        raise TimeoutError("mesh did not quiesce within timeout")

    def finalize(self) -> None:
        """Stop member threads, final-submit every live member, merge
        everything outstanding, release the coordinator's journal."""
        self._stop.set()
        for t in self._threads:
            t.join(timeout=60)
        for m in self.members:
            m.finalize()
        self.coordinator.close()

    def run(self, idle_rounds: int = 20, timeout: float = 300.0) -> float:
        """start() -> wait_idle() -> finalize(); returns the wall-clock
        seconds between start and quiescence (the bench number)."""
        t0 = time.perf_counter()
        self.start()
        try:
            self.wait_idle(idle_rounds=idle_rounds, timeout=timeout)
            elapsed = time.perf_counter() - t0
        finally:
            self.finalize()
        return elapsed
