"""SQLite sink: a real queryable store from the stdlib.

Plays the Postgres role in zero-dependency deployments and tests; the table
shapes mirror the reference's Postgres schema (see sink.ddl.SQLITE_TABLES).
Known table names map to typed tables; unknown tables land in a generic
key-value journal so new models don't need schema changes to be observable.
"""

from __future__ import annotations

import json
import sqlite3
import threading

from ..obs.trace import TRACER
from . import ddl
from .base import sink_batch


class SQLiteSink:
    def __init__(self, path: str = ":memory:"):
        # one connection guarded by a lock: sinks may be called from the
        # worker thread while tests query from the main thread
        self._conn = sqlite3.connect(path, check_same_thread=False)
        self._lock = threading.Lock()
        with self._lock:
            for stmt in ddl.SQLITE_TABLES.values():
                self._conn.executescript(stmt)
            self._conn.execute(
                "CREATE TABLE IF NOT EXISTS journal "
                "(table_name TEXT, record TEXT)"
            )
            self._migrate()
            self._conn.commit()

    def _migrate(self) -> None:
        """Upgrade pre-r4 files to the current flows_5m shape.

        CREATE TABLE IF NOT EXISTS is a no-op on an existing .db, so a
        file created before the sampling-scaled columns landed keeps the
        old schema and the first insert dies with "no column named
        bytes_scaled" — the crash-loop the Postgres/ClickHouse DDL
        already guards against (sink/ddl.py migrations). SQLite has no
        ADD COLUMN IF NOT EXISTS, so probe PRAGMA table_info first.
        Call under self._lock."""
        have = {row[1] for row in
                self._conn.execute("PRAGMA table_info(flows_5m)")}
        for col in ("bytes_scaled", "packets_scaled"):
            if have and col not in have:
                self._conn.execute(
                    f'ALTER TABLE flows_5m ADD COLUMN "{col}" INTEGER')

    def write(self, table: str, rows) -> None:
        # "sink_records" + "sink_execute" tile this sink's "sink_put"
        # (engine/worker.py::_write_rows); the span is here and not in
        # rows_to_records, which the query threads call too
        with TRACER.span("sink_records") as span:
            batch, n = sink_batch(table, rows)
            span["rows"] = n
        if not n:
            return
        with TRACER.span("sink_execute", rows=n), self._lock:
            cols = ddl.TABLE_COLUMNS.get(table)
            if cols is None:
                self._conn.executemany(
                    "INSERT INTO journal (table_name, record) VALUES (?, ?)",
                    [(table, json.dumps(r, default=str)) for r in batch],
                )
            else:
                placeholders = ",".join("?" for _ in cols)
                collist = ",".join(f'"{c}"' for c in cols)
                self._conn.executemany(
                    f'INSERT INTO "{table}" ({collist}) VALUES ({placeholders})',
                    ddl.statement_rows(table, batch),
                )
            self._conn.commit()

    def query(self, sql: str, params=()) -> list[tuple]:
        with self._lock:
            return list(self._conn.execute(sql, params))

    def close(self) -> None:
        with self._lock:
            self._conn.close()
