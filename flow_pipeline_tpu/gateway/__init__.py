"""flowgate: a replicated, delta-fed serve gateway.

The reference pipeline's read surface is Grafana hitting ClickHouse — a
dedicated read tier decoupled from ingest. flowserve (r14) still serves
every snapshot from the dataplane's own cores: readers and the worker
time-slice the same CPUs. flowgate moves the read tier OFF the
dataplane by construction:

- the publisher side (worker or mesh coordinator) grows a
  **subscription feed** (:mod:`.feed`): between versions it ships
  **deltas** — only changed top-K rows, dirty CMS plane tiles and new
  range slots travel (:mod:`.delta`); a version gap or CRC mismatch
  falls back to a full-snapshot resync;
- each **gateway replica** (:mod:`.subscriber`) mirrors the upstream's
  versioned snapshot stream into its OWN :class:`~..serve.SnapshotStore`
  and serves it through the unchanged ``ServeServer`` — so every
  ``/query/*`` answer is bit-exact against the direct snapshot path at
  the same version *by construction* (same immutable arrays, same
  handler code);
- **K stateless replicas** sit behind client-side consistent hashing
  over the query key (:mod:`.ring`): reads scale horizontally, and a
  replica kill is invisible — the client re-rings onto the survivors;
- **tail latency**: the hot query set (top-K at default k per family)
  is pre-rendered into the response cache the moment a snapshot lands,
  so the p99 path is one dict lookup + one ``sendall``.

The mergeability that makes the tier cheap is the same linearity story
as the mesh (PAPERS.md 1910.10441 / 1902.06993): every family's
snapshot is a monoid fold, so the coordinator's published snapshot IS
the network-wide merged view, and a gateway holding that immutable
object can answer for the whole mesh.
"""

from .delta import (DeltaError, DeltaGapError, apply_delta, decode_frames,
                    diff_states, encode_delta, encode_full, snapshot_state,
                    state_to_snapshot)
from .feed import SnapshotFeed
from .ring import GatewayClient, HashRing
from .subscriber import GATEWAY_METRICS, SnapshotGateway

__all__ = [
    "DeltaError",
    "DeltaGapError",
    "GATEWAY_METRICS",
    "GatewayClient",
    "HashRing",
    "SnapshotFeed",
    "SnapshotGateway",
    "apply_delta",
    "decode_frames",
    "diff_states",
    "encode_delta",
    "encode_full",
    "snapshot_state",
    "state_to_snapshot",
]
