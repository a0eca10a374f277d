"""flowserve closed-loop query load generator.

N threads, each with one keep-alive HTTP connection, issue queries
back-to-back (closed loop: the next request waits for the previous
response — the honest client model for "how many concurrent readers can
this sustain"). ``make serve-load`` (the CI smoke leg) drives it.
"""

from __future__ import annotations

# flowlint: lock-checked
# (each worker thread owns its private _Worker stats; aggregation reads
# them only after join() — no shared mutable state while running)
# flowlint: net-checked
# (a load generator with an unbounded read wedges the whole run when
# the server under test hangs — exactly the condition being measured)

import http.client
import threading
import time

DEFAULT_ENDPOINTS = (
    "/query/topk?k=10",
    "/query/version",
    "/query/topk?k=50",
    "/query/range",
)


class _Worker:
    """Per-thread private stats."""

    def __init__(self):
        self.latencies: list = []
        self.codes: dict = {}
        self.errors = 0


def _quantile(sorted_vals: list, q: float) -> float:
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(len(sorted_vals) - 1,
                           int(q * len(sorted_vals)))]


def wait_ready(host: str, port: int, timeout: float = 30.0) -> bool:
    """Block until /query/version answers 200 (first snapshot
    published) — load measured before that would count bootstrap 503s
    against the serving path."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            conn = http.client.HTTPConnection(host, port, timeout=2)
            conn.request("GET", "/query/version")
            code = conn.getresponse().status
            conn.close()
            if code == 200:
                return True
        except OSError:
            pass
        time.sleep(0.05)
    return False


def sample_ages(host: str, port: int, stop: threading.Event,
                interval: float = 0.1) -> tuple[threading.Thread, list]:
    """Started snapshot-age sampler: polls /query/version every
    ``interval`` until ``stop`` and appends ``age_seconds`` to the
    returned list — the freshness evidence `make serve-load` asserts
    over. join() the thread after setting ``stop``."""
    ages: list = []

    def drive() -> None:
        import json as _json
        import urllib.request as _rq

        while not stop.is_set():
            try:
                doc = _json.loads(_rq.urlopen(
                    f"http://{host}:{port}/query/version",
                    timeout=5).read())
                ages.append(doc["age_seconds"])
            except OSError:
                pass
            stop.wait(interval)

    t = threading.Thread(target=drive, name="serve-age-sampler",
                         daemon=True)
    t.start()
    return t, ages


def run_load(host: str, port: int, threads: int = 8,
             duration: float = 2.0,
             endpoints=DEFAULT_ENDPOINTS,
             stop: threading.Event | None = None) -> dict:
    """Closed-loop load for ``duration`` seconds (or until ``stop``).

    Returns {qps, p50_ms, p99_ms, requests, errors, codes, threads,
    duration_s}. ``errors`` counts transport failures; ``codes`` the
    HTTP status distribution (a 5xx in there fails the CI smoke)."""
    stop = stop or threading.Event()
    workers = [_Worker() for _ in range(threads)]
    t_end = time.monotonic() + duration

    def drive(w: _Worker, idx: int) -> None:
        conn = http.client.HTTPConnection(host, port, timeout=10)
        i = idx  # offset so threads don't hit one endpoint in lockstep
        while time.monotonic() < t_end and not stop.is_set():
            path = endpoints[i % len(endpoints)]
            i += 1
            t0 = time.perf_counter()
            try:
                conn.request("GET", path)
                resp = conn.getresponse()
                resp.read()  # drain: keep-alive needs the body consumed
                code = resp.status
            except OSError:
                w.errors += 1
                conn.close()
                conn = http.client.HTTPConnection(host, port, timeout=10)
                continue
            w.latencies.append(time.perf_counter() - t0)
            w.codes[code] = w.codes.get(code, 0) + 1
        conn.close()

    t0 = time.monotonic()
    ts = [threading.Thread(target=drive, args=(w, i), daemon=True)
          for i, w in enumerate(workers)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.monotonic() - t0
    lats = sorted(x for w in workers for x in w.latencies)
    codes: dict[int, int] = {}
    for w in workers:
        for c, n in w.codes.items():
            codes[c] = codes.get(c, 0) + n
    n = len(lats)
    return {
        "qps": round(n / wall, 1) if wall else 0.0,
        "p50_ms": round(_quantile(lats, 0.5) * 1e3, 3),
        "p99_ms": round(_quantile(lats, 0.99) * 1e3, 3),
        "requests": n,
        "errors": sum(w.errors for w in workers),
        "codes": {str(c): n for c, n in sorted(codes.items())},
        "threads": threads,
        "duration_s": round(wall, 3),
    }


def main(argv=None) -> int:
    """Command-line entry: HOST PORT [THREADS] [DURATION] [ENDPOINTS] ->
    one JSON summary line on stdout."""
    import json as _json
    import sys as _sys

    args = list(argv if argv is not None else _sys.argv[1:])
    host, port = args[0], int(args[1])
    threads = int(args[2]) if len(args) > 2 else 8
    duration = float(args[3]) if len(args) > 3 else 2.0
    endpoints = tuple(args[4].split(",")) if len(args) > 4 \
        else DEFAULT_ENDPOINTS
    print(_json.dumps(run_load(host, port, threads=threads,
                               duration=duration, endpoints=endpoints)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
