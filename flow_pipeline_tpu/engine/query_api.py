"""Live query API: serve aggregates straight off the worker's models.

The reference answers "top talkers right now?" by scanning raw rows in the
database at query time (ref: compose/grafana/dashboards/viz.json queries,
SURVEY.md §3.5) — O(rows). Here the device already holds ranked sketch
state, so the worker can answer in O(K) without touching storage, including
for the WINDOW STILL OPEN (storage only sees closed windows):

    GET /healthz            liveness + progress counters
    GET /topk?model=X&k=N   current open-window top-K from the sketch
    GET /windows?model=X    open exact-window slots + row counts
    GET /alerts?limit=N     recent DDoS alerts

Handlers acquire the worker's lock (held across each run_once step), so
queries see consistent model state and never race a concurrent flush.
"""

from __future__ import annotations

import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

from ..models.ddos import DDoSDetector
from ..models.window_agg import WindowAggregator
from ..obs import get_logger
from ..obs.server import reply_json
from ..sink.base import rows_to_records
from .windowed import WindowedHeavyHitter

log = get_logger("query")


class QueryServer:
    """HTTP query endpoint over a StreamWorker's models.

    ``mesh`` (a mesh.MeshCoordinator) makes /topk mesh-aware: instead of
    reading one worker's sketch, the coordinator fans the query to every
    live member's state provider and answers from the network-wide
    MERGED open-window view — the same monoid fold the window-close
    merge runs, so the answer equals a single worker seeing the whole
    stream (tests/test_mesh.py pins the equality).

    ``serve`` (a serve.SnapshotStore) lets /topk answer from the
    flowserve snapshot WITHOUT the worker lock whenever the snapshot is
    fresh — covers the exact consumed point (``flows_seen`` matches), so
    the answer is bit-identical to the locked read
    (tests/test_serve.py pins the parity); anything staler falls back to
    the locked path."""

    def __init__(self, worker, port: int = 8082, host: str = "127.0.0.1",
                 mesh=None, serve=None):
        self.worker = worker
        self.mesh = mesh
        self.serve = serve
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802
                url = urlparse(self.path)
                q = {k: v[0] for k, v in parse_qs(url.query).items()}
                try:
                    handler = {
                        "/healthz": outer._healthz,
                        "/topk": outer._topk,
                        "/windows": outer._windows,
                        "/alerts": outer._alerts,
                    }.get(url.path)
                    if handler is None:
                        reply_json(self, {"error":
                                          f"unknown path {url.path}"}, 404)
                        return
                    if url.path == "/topk" and outer.mesh is None \
                            and outer.worker is not None:
                        # flowserve fast path FIRST, outside the lock: a
                        # fresh snapshot answers without stalling (or
                        # being stalled by) the dataplane
                        result = outer._topk_from_snapshot(q)
                        if result is not None:
                            reply_json(self, result, default=str)
                            return
                    if outer.mesh is not None and url.path in (
                            "/topk", "/healthz"):
                        # mesh fan-out acquires MEMBER locks; it must
                        # not run under a co-resident worker's lock
                        result = handler(q)
                    elif outer.worker is None:
                        reply_json(self, {"error":
                                          "no worker behind this path"},
                                   400)
                        return
                    else:
                        with outer.worker.lock:  # consistent view
                            result = handler(q)
                    reply_json(self, result, default=str)
                except (KeyError, ValueError) as e:
                    # malformed query params (/topk?k=abc) and unknown
                    # models answer 400, never a handler traceback
                    reply_json(self, {"error": str(e)}, 400)

            def log_message(self, *args):
                pass

        self._server = ThreadingHTTPServer((host, port), Handler)
        self.host = host
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="query-http", daemon=True
        )

    # ---- endpoints --------------------------------------------------------

    def _healthz(self, q) -> dict:
        if self.worker is None:
            st = self.mesh.status()
            return {"ok": True, "mesh_epoch": st["epoch"],
                    "mesh_members": len(st["members"]),
                    "models": [s.name for s in self.mesh.specs]}
        return {
            "ok": True,
            "flows_seen": self.worker.flows_seen,
            "batches_seen": self.worker.batches_seen,
            "models": list(self.worker.models),
        }

    def _model(self, q, want_type):
        name = q.get("model")
        if name:
            model = self.worker.models.get(name)
            if model is None:
                raise KeyError(f"no model named {name!r}")
            return name, model
        for name, model in self.worker.models.items():
            if isinstance(model, want_type):
                return name, model
        raise KeyError(f"no model of kind {want_type.__name__} configured")

    def _topk_from_snapshot(self, q):
        """Lock-free /topk off the flowserve snapshot, or None when the
        snapshot cannot answer VERBATIM what the locked path would:
        it must cover the exact consumed point (``flows_seen`` — reading
        the worker's counter is one atomic attribute load), know the
        requested model, and hold at least k extracted rows. The
        returned dict is shaped exactly like the locked ``_topk`` (the
        parity test compares them field-for-field)."""
        if self.serve is None:
            return None
        snap = self.serve.current
        if snap is None or snap.source != "worker" or \
                snap.flows_seen != self.worker.flows_seen:
            return None
        name = q.get("model")
        if name:
            fam = snap.families.get(name)
        else:
            fam = next(iter(snap.families.values()), None)
        k = int(q.get("k", 10))
        if fam is None or k < 0 or k > fam.depth:
            # the locked path serves (or errors) instead — a negative k
            # would slice from the END here but not there, and the fast
            # path must answer VERBATIM or not at all
            return None
        rows = {col: arr[:k] for col, arr in fam.rows.items()}
        return {
            "model": fam.name,
            "window_start": fam.window_start,
            "rows": rows_to_records(rows),
        }

    def _topk(self, q) -> dict:
        if self.mesh is not None:
            # the coordinator merges every live member's open-window
            # state (mesh.MeshCoordinator.query_topk) — O(K) per member
            return self.mesh.query_topk(
                q.get("model"), int(q["k"]) if "k" in q else None)
        name, model = self._model(q, WindowedHeavyHitter)
        if not isinstance(model, WindowedHeavyHitter):
            raise ValueError(f"model {name!r} has no top-K surface")
        # host sketch backend: model state is engine-resident between
        # syncs; pull it current before reading (we hold worker.lock)
        self.worker.sync_sketch_states()
        k = int(q.get("k", 10))
        top = model.top(k)
        return {
            "model": name,
            "window_start": model.window_start,
            "rows": rows_to_records(top),
        }

    def _windows(self, q) -> dict:
        name, model = self._model(q, WindowAggregator)
        if not isinstance(model, WindowAggregator):
            raise ValueError(f"model {name!r} is not a window aggregator")
        model._drain()
        return {
            "model": name,
            "watermark": model.watermark,
            "open_windows": [
                {"timeslot": slot, "groups": len(store)}
                for slot, store in sorted(model.windows.items())
            ],
        }

    def _alerts(self, q) -> dict:
        limit = int(q.get("limit", 50))
        out = []
        for name, model in self.worker.models.items():
            if isinstance(model, DDoSDetector):
                # `recent` is retained for queries; `alerts` drains to sinks
                out.extend(
                    {**a, "model": name} for a in list(model.recent)[-limit:]
                )
        return {"alerts": rows_to_records(out)}

    # ---- lifecycle --------------------------------------------------------

    def start(self) -> "QueryServer":
        self._thread.start()
        log.info("query api on http://%s:%d", self.host, self.port)
        return self

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()
