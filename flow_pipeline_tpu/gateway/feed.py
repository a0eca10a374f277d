"""flowgate subscription feed: the publisher side of delta shipping.

One :class:`SnapshotFeed` sits next to a :class:`~..serve.SnapshotStore`
and answers ``/sub/snapshot?since=V`` polls (serve/server.py routes
them here). It is lazy the same way ``FrozenCms`` is: nothing is
encoded until a subscriber asks, and the encode runs on the
SUBSCRIBER-FACING reader thread — the dataplane publish path never pays
a byte of it (``store.publish`` stays one pointer swap).

Per observed version the feed keeps ONE full frame plus a bounded chain
of delta frames between consecutively OBSERVED versions (a feed that is
polled slower than the publisher publishes simply produces coarser
deltas — the chain is over what the feed saw, and a subscriber's
``since`` either matches a chain link or gets the full frame). History
eviction, a subscriber older than the chain, or a brand-new subscriber
(``since=0``) all resolve to a full-snapshot ship — the resync path is
the bootstrap path, not a special case.
"""

from __future__ import annotations

# flowlint: lock-checked
# (polled from N subscriber HTTP threads; one lock guards the memoized
# state/frames. The store pointer read inside is the RCU-lock-free read
# every serve reader does.)

import threading
from collections import deque
from typing import Optional

from .delta import encode_delta, encode_full, encode_none, snapshot_state

# Delta-chain retention (observed version transitions). A subscriber
# further behind than this gets a full snapshot — at production poll
# cadences (sub-second) 64 transitions is tens of seconds of outage
# ridden on deltas.
FEED_HISTORY = 64

# ...and a cumulative BYTE budget on the same chain: under saturated
# ingest every CMS tile is dirty and a delta is ~full-snapshot sized
# (megabytes), so a count-only bound
# could hold 64 snapshots' worth of encoded bytes resident (the r17
# journal lesson, on RAM instead of disk). Evicting the oldest links
# past the budget just widens the full-resync window — the fallback
# every evicted subscriber already takes.
FEED_HISTORY_BYTES = 128 << 20


class SnapshotFeed:
    """Delta/full frame source for one snapshot store."""

    def __init__(self, store, history: int = FEED_HISTORY,
                 history_bytes: int = FEED_HISTORY_BYTES):
        self.store = store
        self.history_bytes = history_bytes
        # flowlint: unguarded -- the lock itself; bound once
        self._lock = threading.Lock()
        self._state: Optional[dict] = None  # guarded-by: _lock
        self._full: Optional[bytes] = None  # guarded-by: _lock
        # (from_version, to_version, frame bytes), consecutive by
        # construction: each append chains from the previous _state
        self._deltas: deque = deque(maxlen=history)  # guarded-by: _lock
        self._delta_bytes_held = 0  # guarded-by: _lock
        # shipping-cost ledger: per-transition encoded sizes — the
        # honest bytes-per-publish evidence for delta vs full shipping
        self._stats = {"publishes": 0, "full_bytes": 0,  # guarded-by: _lock
                       "delta_bytes": 0, "deltas": 0}

    def _refresh_locked(self) -> None:
        snap = self.store.current
        if snap is None:
            return
        if self._state is not None and \
                snap.version <= self._state["version"]:
            return
        state = snapshot_state(snap)
        full = encode_full(state)
        self._stats["publishes"] += 1  # flowlint: disable=lock-discipline -- *_locked helper: every caller holds _lock (the checker is per-write-site)
        self._stats["full_bytes"] += len(full)  # flowlint: disable=lock-discipline -- *_locked helper: every caller holds _lock (the checker is per-write-site)
        if self._state is not None:
            frame = encode_delta(self._state, state)
            if len(self._deltas) == self._deltas.maxlen:
                # the append below will silently drop the oldest link
                self._delta_bytes_held -= len(self._deltas[0][2])  # flowlint: disable=lock-discipline -- *_locked helper: every caller holds _lock (the checker is per-write-site)
            self._deltas.append(
                (self._state["version"], state["version"], frame))
            self._delta_bytes_held += len(frame)  # flowlint: disable=lock-discipline -- *_locked helper: every caller holds _lock (the checker is per-write-site)
            while self._delta_bytes_held > self.history_bytes \
                    and self._deltas:
                self._delta_bytes_held -= len(self._deltas.popleft()[2])  # flowlint: disable=lock-discipline -- *_locked helper: every caller holds _lock (the checker is per-write-site)
            self._stats["deltas"] += 1  # flowlint: disable=lock-discipline -- *_locked helper: every caller holds _lock (the checker is per-write-site)
            self._stats["delta_bytes"] += len(frame)  # flowlint: disable=lock-discipline -- *_locked helper: every caller holds _lock (the checker is per-write-site)
        self._state, self._full = state, full  # flowlint: disable=lock-discipline -- *_locked helper: every caller holds _lock (the checker is per-write-site)

    def frame_since(self, since: int) -> tuple[str, int, bytes]:
        """(kind, current_version, frames) for one subscriber poll.
        ``kind``: "none" (already current), "delta" (a chain of >= 1
        delta frames), or "full" (bootstrap / gap / evicted history)."""
        with self._lock:
            self._refresh_locked()
            if self._state is None:
                return "none", 0, encode_none(0)
            cur = self._state["version"]
            if since == cur:
                return "none", cur, encode_none(cur)
            if since:
                frms = [frm for frm, _, _ in self._deltas]
                if since in frms:
                    # the deque links consecutively, so everything from
                    # the `since` link onward IS the exact chain to cur
                    chain = list(self._deltas)[frms.index(since):]
                    return "delta", cur, b"".join(f for _, _, f in chain)
            return "full", cur, self._full

    def stats(self) -> dict:
        """Copy of the shipping-cost ledger (+ per-publish averages)."""
        with self._lock:
            out = dict(self._stats)
        if out["publishes"]:
            out["full_bytes_per_publish"] = round(
                out["full_bytes"] / out["publishes"], 1)
        if out["deltas"]:
            out["delta_bytes_per_publish"] = round(
                out["delta_bytes"] / out["deltas"], 1)
        return out
