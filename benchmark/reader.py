"""The reader: a process of its own that polls ``/query/version``.

    python3 benchmark/reader.py --port P --interval 0.02 --out FILE

It stands for an operator's dashboard. It speaks HTTP over one
keep-alive connection, never imports jax or the program, and polls on a
fixed schedule (tick k at ``t0 + k * interval``; a late tick is not made
up, the next one keeps the schedule). It writes one line to ``--out`` for
every CHANGE of version, ``t_seen version flows_seen``, with ``t_seen``
on ``time.monotonic()`` — CLOCK_MONOTONIC, which every process on the
host shares — taken when the answer arrived. It ends when its standard
input closes, and then appends one ``# polls ...`` line of its counts.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import select
import sys
import time


def _stdin_closed() -> bool:
    r, _, _ = select.select([sys.stdin], [], [], 0)
    return bool(r) and os.read(sys.stdin.fileno(), 4096) == b""


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--interval", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    conn = None
    last_version = None
    polls = errors = not_ready = 0
    worst_late = 0.0
    t0 = time.monotonic()
    k = 0
    with open(args.out, "w", buffering=1) as out:
        while not _stdin_closed():
            k += 1
            due = t0 + k * args.interval
            now = time.monotonic()
            if now < due:
                time.sleep(due - now)
            else:
                worst_late = max(worst_late, now - due)
                k = int((now - t0) / args.interval)  # keep the schedule
            try:
                if conn is None:
                    conn = http.client.HTTPConnection(
                        "127.0.0.1", args.port, timeout=5)
                conn.request("GET", "/query/version")
                resp = conn.getresponse()
                body = resp.read()
                t_seen = time.monotonic()
                polls += 1
                if resp.status != 200:
                    not_ready += 1  # 503: no snapshot yet
                    continue
                doc = json.loads(body)
            except (OSError, http.client.HTTPException, ValueError):
                errors += last_version is not None  # not while it starts
                if conn is not None:
                    conn.close()
                conn = None
                continue
            if doc["version"] != last_version:
                last_version = doc["version"]
                out.write(f"{t_seen!r} {doc['version']} "
                          f"{doc['flows_seen']}\n")
        out.write(f"# polls {polls} errors {errors} not_ready {not_ready} "
                  f"worst_late_s {worst_late!r}\n")
    if conn is not None:
        conn.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
