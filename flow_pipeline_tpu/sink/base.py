"""Sink plumbing: row normalization + trivial sinks."""

from __future__ import annotations

import itertools
import sys
from typing import Any

import numpy as np

from ..schema.batch import words_to_addr
from . import ddl


def _addr_str(words) -> str:
    """[4] uint32 words -> printable address. IPv4-in-trailing-4-bytes
    renders dotted quad (the convention Grafana queries decode,
    ref: viz-ch.json IPv4NumToString(...substring(reverse(SrcAddr),13,4))."""
    raw = words_to_addr(np.asarray(words, dtype=np.uint32))
    if raw[:12] == b"\x00" * 12:
        return ".".join(str(b) for b in raw[12:])
    import ipaddress

    return str(ipaddress.IPv6Address(raw))


def _value(v):
    if isinstance(v, np.ndarray):  # [4] address words
        return _addr_str(v)
    return v.item() if isinstance(v, np.generic) else v


def rows_to_columns(rows: dict) -> dict[str, list]:
    """Columnar flush output (dict of arrays, an optional ``valid`` mask)
    -> the valid rows as one Python list a column, with printable
    addresses: the form a SQL sink's statement is zipped from. The mask
    is applied once a column and a numeric column converts in one
    ``tolist()`` (the ``int`` / ``float`` that ``.item()`` gives), so a
    close of 6x10^4 rows costs a handful of array calls, not a Python
    step a row and column."""
    keep = None
    if "valid" in rows:
        keep = np.asarray(rows["valid"], dtype=bool)
    columns = {}
    for name, col in rows.items():
        if name == "valid":
            continue
        if isinstance(col, np.ndarray):
            if keep is not None:
                col = col[keep]
            if col.ndim == 1 and col.dtype != object:
                columns[name] = col.tolist()
                continue
        elif keep is not None:
            col = itertools.compress(col, keep.tolist())
        # [n, 4] address words (the ranked tables' few hundred rows),
        # objects, plain sequences: a value at a time
        columns[name] = [_value(v) for v in col]
    return columns


def column_records(columns: dict[str, list]):
    """``rows_to_columns``' columns, a dict a row."""
    names = list(columns)
    return (dict(zip(names, row)) for row in zip(*columns.values()))


def rows_to_records(rows: Any) -> list[dict]:
    """Columnar flush output (dict of arrays) or a list of dicts -> list of
    flat records with printable addresses."""
    if isinstance(rows, list):  # e.g. DDoS alerts
        out = []
        for r in rows:
            r = dict(r)
            for k, v in list(r.items()):
                if isinstance(v, np.ndarray) and v.shape == (4,):
                    r[k] = _addr_str(v)
                elif isinstance(v, np.generic):
                    r[k] = v.item()
            out.append(r)
        return out
    return list(column_records(rows_to_columns(rows)))


def sink_batch(table: str, rows: Any) -> tuple[Any, int]:
    """What a SQL sink builds its statement from, and how many rows: a
    close's columns (``rows_to_columns``) where dict-of-arrays rows go to
    a typed table; records otherwise (list input: alerts, a dead-letter
    replay; a table the DDL lacks: sqlite's journal)."""
    if isinstance(rows, list) or table not in ddl.TABLE_COLUMNS:
        records = rows_to_records(rows)
        return records, len(records)
    columns = rows_to_columns(rows)
    return columns, ddl.column_rows(columns)


class MemorySink:
    """Accumulates records per table (tests)."""

    def __init__(self):
        self.tables: dict[str, list[dict]] = {}

    def write(self, table: str, rows) -> None:
        self.tables.setdefault(table, []).extend(rows_to_records(rows))


class StdoutSink:
    """Prints one line per record (demos)."""

    def __init__(self, stream=None, limit_per_flush: int = 20):
        self.stream = stream or sys.stdout
        self.limit = limit_per_flush

    def write(self, table: str, rows) -> None:
        records = rows_to_records(rows)
        for rec in records[: self.limit]:
            print(f"{table} {rec}", file=self.stream)
        if len(records) > self.limit:
            print(f"{table} ... {len(records) - self.limit} more rows",
                  file=self.stream)
