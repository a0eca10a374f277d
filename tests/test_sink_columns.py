"""A close reaches the SQL statement as columns (sink/base.py::
rows_to_columns), and what the sinks store is what the row-wise path
stored.

The reference is the row-wise code as it stood before the columnar
normaliser, frozen here: ``rows_to_records`` a row and column at a time,
``assign_ranks`` on the records, ``r.get(c)`` a value into the statement.
Every table of ``ddl.TABLE_COLUMNS`` is held to it with rows shaped as the
models emit them, through every SQL sink and the dead-letter frame; and a
counting ``ndarray`` shows that a column is indexed a constant number of
times whatever the close's size.
"""

import json
import sqlite3

import numpy as np
import pytest

from flow_pipeline_tpu.schema.batch import words_to_addr
from flow_pipeline_tpu.sink import (ClickHouseSink, MemorySink,
                                    ResilientSink, SQLiteSink, ddl,
                                    rows_to_records)
from flow_pipeline_tpu.sink import resilient
from flow_pipeline_tpu.sink.base import rows_to_columns, sink_batch
from flow_pipeline_tpu.sink.postgres import insert_sql

# ---- the reference: the row-wise path, frozen ------------------------------


def _ref_addr_str(words) -> str:
    raw = words_to_addr(np.asarray(words, dtype=np.uint32))
    if raw[:12] == b"\x00" * 12:
        return ".".join(str(b) for b in raw[12:])
    import ipaddress

    return str(ipaddress.IPv6Address(raw))


def _ref_records(rows) -> list[dict]:
    if isinstance(rows, list):
        out = []
        for r in rows:
            r = dict(r)
            for k, v in list(r.items()):
                if isinstance(v, np.ndarray) and v.shape == (4,):
                    r[k] = _ref_addr_str(v)
                elif isinstance(v, np.generic):
                    r[k] = v.item()
            out.append(r)
        return out
    names = list(rows.keys())
    n = len(rows[names[0]]) if names else 0
    records = []
    for i in range(n):
        if "valid" in rows and not rows["valid"][i]:
            continue
        rec = {}
        for name in names:
            if name == "valid":
                continue
            v = rows[name][i]
            if isinstance(v, np.ndarray):
                rec[name] = _ref_addr_str(v)
            else:
                rec[name] = v.item() if isinstance(v, np.generic) else v
        records.append(rec)
    return records


def _ref_ranked(table: str, rows) -> list[dict]:
    records = _ref_records(rows)
    if table in ddl.RANKED_TABLES:
        for rank, r in enumerate(records):
            r.setdefault("rank", rank)
    return records


def _ref_sqlite(table: str, rows) -> list[tuple]:
    """What the row-wise ``SQLiteSink.write`` stored, value and sqlite
    type of every column, in row order."""
    conn = sqlite3.connect(":memory:")
    conn.executescript(ddl.SQLITE_TABLES[table])
    records = _ref_ranked(table, rows)
    cols = ddl.TABLE_COLUMNS[table]
    if records:
        placeholders = ",".join("?" for _ in cols)
        collist = ",".join(f'"{c}"' for c in cols)
        conn.executemany(
            f'INSERT INTO "{table}" ({collist}) VALUES ({placeholders})',
            [tuple(r.get(c) for c in cols) for r in records])
        conn.commit()
    return _stored(conn.execute, table)


def _ref_insert_sql(table: str, rows) -> tuple[str, list]:
    records = _ref_ranked(table, rows)
    cols = ddl.TABLE_COLUMNS[table]
    collist = ", ".join(f'"{c}"' for c in cols)
    row_ph = "(" + ", ".join(["%s"] * len(cols)) + ")"
    placeholders = ", ".join([row_ph] * len(records))
    sql = f'INSERT INTO "{table}" ({collist}) VALUES {placeholders}'
    return sql, [r.get(c) for r in records for c in cols]


def _ref_clickhouse_body(table: str, rows) -> bytes:
    records = _ref_ranked(table, rows)
    cols = ddl.TABLE_COLUMNS[table]
    records = [{c: r.get(c) for c in cols if c in r} for r in records]
    if table == "flows_5m":
        records = [{ClickHouseSink._FLOWS_5M_COLS.get(k, k): v
                    for k, v in r.items()} for r in records]
        for r in records:
            r.setdefault("Date", int(r.get("Timeslot", 0)) // 86400)
    return "\n".join(json.dumps(r, default=str) for r in records).encode()


def _stored(execute, table: str) -> list[tuple]:
    cols = ddl.TABLE_COLUMNS[table]
    select = ", ".join(f'"{c}", typeof("{c}")' for c in cols)
    return list(execute(f'SELECT {select} FROM "{table}" ORDER BY rowid'))


def _typed(value):
    """A value with the type of every part, so 1 != 1.0 != True."""
    if isinstance(value, dict):
        return [(k, _typed(v)) for k, v in value.items()]
    if isinstance(value, (list, tuple)):
        return (type(value), [_typed(v) for v in value])
    return (type(value), value)


# ---- rows shaped as the models emit them -----------------------------------

_ADDRS = ("src_addr", "dst_addr")
_LANES = ("src_port", "dst_port", "proto")
_SKETCH = ("top_talkers", "top_pairs", "top_src_ips", "top_dst_ips")
_DENSE = ("top_src_ports", "top_dst_ports")
_SPREAD = ("superspreaders", "portscan")
COLUMNAR = ("flows_5m",) + _SKETCH + _DENSE + _SPREAD

# name -> (rows, valid mask or None, the metrics' float dtype, drop a DDL column)
VARIANTS = {
    "no_mask": (7, None, np.float32, False),
    "mixed_mask": (7, "mixed", np.float32, False),
    "mixed_mask_f64": (7, "mixed", np.float64, False),
    "all_false": (7, "none", np.float32, False),
    "all_true_lacking": (7, "all", np.float32, True),
    "empty": (0, "mixed", np.float32, False),
}


def _addresses(rng, n: int) -> np.ndarray:
    """[n, 4] uint32 words: IPv4 in the trailing four bytes on the even
    rows, IPv6 on the odd ones."""
    words = rng.integers(0, 2**32, (n, 4), dtype=np.uint64).astype(np.uint32)
    words[::2, :3] = 0
    return words


def _columnar_rows(table: str, variant: str) -> dict:
    n, mask, fdt, lacking = VARIANTS[variant]
    rng = np.random.default_rng(len(table) * 1000 + n)
    cols = ddl.TABLE_COLUMNS[table]
    rows: dict = {}
    if table == "flows_5m":  # models/window_agg.py::rows_from_stores
        for c in cols:
            rows[c] = rng.integers(0, 2**62, n, dtype=np.uint64)
        rows["timeslot"] = np.full(n, 1_700_000_100, np.uint64)
    else:
        for c in cols:
            if c in _ADDRS:
                rows[c] = _addresses(rng, n)
            elif c in _LANES:
                rows[c] = rng.integers(0, 65536, n).astype(
                    np.int32 if table in _DENSE else np.uint32)
        if table in _SKETCH:  # models/heavy_hitter.py::_top_from_state
            for c in ("bytes", "packets"):
                rows[c] = (rng.random(n) * 1e9).astype(fdt)
                rows[f"{c}_est"] = (rng.random(n) * 1e9).astype(fdt)
            rows["count"] = np.floor(rng.random(n) * 1e6).astype(fdt)
            rows["count_est"] = np.floor(rng.random(n) * 1e6).astype(fdt)
        elif table in _DENSE:  # models/dense_top.py::_top_from_totals
            for c in ("bytes", "packets", "count"):
                rows[c] = rng.integers(0, 2**62, n, dtype=np.uint64)
        else:  # models/spread.py
            rows["spread"] = (rng.random(n) * 1e4).astype(fdt)
            rows["pairs"] = np.floor(rng.random(n) * 1e4).astype(fdt)
    if mask is not None:
        rows["valid"] = {"mixed": np.arange(n) % 3 != 1,
                         "none": np.zeros(n, bool),
                         "all": np.ones(n, bool)}[mask]
    if table != "flows_5m":  # engine/windowed.py: stamped after the mask
        rows["timeslot"] = np.full(n, 1_700_000_100, np.uint64)
    if lacking:
        del rows["packets_scaled" if table == "flows_5m" else
                 "pairs" if table in _SPREAD else "packets"]
    return rows


def _alert_rows() -> list[dict]:
    """models/ddos.py's alerts, and one with numpy scalars as a merged
    or replayed alert may carry them."""
    return [
        {"sub_window": 170000010, "bucket": 5,
         "dst_addr": np.array([0, 0, 0, 0x0A000007], np.uint32),
         "rate": 1.5e6, "zscore": 9.25, "baseline_quantile": 100.0},
        {"sub_window": np.uint64(170000020), "bucket": np.int64(9),
         "dst_addr": np.array([0x20010DB8, 0, 0, 0x1234], np.uint32),
         "rate": np.float32(2.5), "zscore": np.float64(7.5)},
    ]


def _raw_flow_rows() -> list[dict]:
    """cli.py::_raw_rows: the inserter's records, plain Python values."""
    return [{"time_flow": "2023-11-14 22:13:20", "type": 1,
             "sampling_rate": 1024, "src_as": 65000 + i, "dst_as": 65001,
             "src_ip": f"10.0.0.{i}", "dst_ip": "2001:db8::1",
             "bytes": 1500 * i, "packets": i, "etype": 0x0800, "proto": 6,
             "src_port": 443, "dst_port": 50000 + i} for i in range(3)]


def _cases():
    for table in ddl.TABLE_COLUMNS:
        if table in COLUMNAR:
            for variant in VARIANTS:
                yield pytest.param(table, variant, id=f"{table}-{variant}")
        else:
            yield pytest.param(table, "records", id=f"{table}-records")


def _rows(table: str, variant: str):
    if table == "ddos_alerts":
        return _alert_rows()
    if table == "flows":
        return _raw_flow_rows()
    return _columnar_rows(table, variant)


def test_every_table_has_its_rows():
    assert set(COLUMNAR) | {"ddos_alerts", "flows"} == set(ddl.TABLE_COLUMNS)


# ---- parity ----------------------------------------------------------------


@pytest.mark.parametrize("table, variant", _cases())
def test_records_equal_the_row_wise_ones(table, variant):
    rows = _rows(table, variant)
    want = _ref_records(rows)
    got = rows_to_records(rows)
    assert _typed(got) == _typed(want)
    sink = MemorySink()
    sink.write(table, rows)
    assert _typed(sink.tables[table]) == _typed(want)
    if isinstance(rows, dict):
        columns = rows_to_columns(rows)
        assert "valid" not in columns
        assert list(columns) == [k for k in rows if k != "valid"]
        assert all(type(c) is list for c in columns.values())
        valid = rows.get("valid")
        assert len(want) == (int(valid.sum()) if valid is not None
                             else len(rows["timeslot"]))
        if want:
            assert type(want[0]["timeslot"]) is int


@pytest.mark.parametrize("table, variant", _cases())
def test_sqlite_stores_what_the_row_wise_path_stored(table, variant):
    rows = _rows(table, variant)
    sink = SQLiteSink()
    try:
        sink.write(table, rows)
        got = _stored(sink.query, table)
        assert sink.query("SELECT COUNT(*) FROM journal") == [(0,)]
    finally:
        sink.close()
    want = _ref_sqlite(table, rows)
    assert _typed(got) == _typed(want)
    if table in ddl.RANKED_TABLES and want:
        rank = 2 * ddl.TABLE_COLUMNS[table].index("rank")
        assert [r[rank] for r in got] == list(range(len(got)))
    if variant == "all_true_lacking":
        assert any(v is None for v in got[0])


@pytest.mark.parametrize("table, variant", _cases())
def test_postgres_statement_equals_the_row_wise_one(table, variant):
    rows = _rows(table, variant)
    batch, n = sink_batch(table, rows)
    sql, args = insert_sql(table, batch)
    want_sql, want_args = _ref_insert_sql(table, rows)
    assert sql == want_sql
    assert _typed(args) == _typed(want_args)
    assert n == len(want_args) // len(ddl.TABLE_COLUMNS[table])


@pytest.mark.parametrize("table, variant", _cases())
def test_clickhouse_body_equals_the_row_wise_one(table, variant):
    rows = _rows(table, variant)
    sink = ClickHouseSink(create_tables=False)
    posts = []
    sink._post = lambda query, body=b"": posts.append((query, body))
    batch, _ = sink_batch(table, rows)
    sink._insert(table, batch)
    assert posts == [(f"INSERT INTO {table} FORMAT JSONEachRow",
                      _ref_clickhouse_body(table, rows))]
    # through write(): the same post, or none for a close with no row
    posts.clear()
    sink.write(table, rows)
    assert posts == ([(f"INSERT INTO {table} FORMAT JSONEachRow",
                       _ref_clickhouse_body(table, rows))]
                     if _ref_records(rows) else [])


@pytest.mark.parametrize("table, variant", _cases())
def test_dead_letter_frame_equals_the_row_wise_one(table, variant, tmp_path,
                                                   monkeypatch):
    rows = _rows(table, variant)
    monkeypatch.setattr(resilient.time, "time", lambda: 1_700_000_000.5)
    sink = ResilientSink(MemorySink(), deadletter_dir=str(tmp_path))
    exc = OSError("sink down")
    sink._spill(table, rows, exc)
    (path,) = resilient.deadletter_files(str(tmp_path))
    want = {"table": table, "records": _ref_records(rows),
            "spilled_at": 1_700_000_000.5, "error": repr(exc), "version": 1}
    with open(path, "rb") as f:
        assert f.read() == json.dumps(want, default=str).encode("utf-8")


def test_unknown_table_still_goes_to_the_journal():
    rows = {"k": np.array([1, 2], np.uint32), "v": np.array([.5, 1.5])}
    sink = SQLiteSink()
    try:
        sink.write("new_model", rows)
        got = sink.query("SELECT table_name, record FROM journal "
                         "ORDER BY rowid")
    finally:
        sink.close()
    assert got == [("new_model", json.dumps(r, default=str))
                   for r in _ref_records(rows)]


def test_rows_that_carry_a_rank_keep_it():
    rows = _columnar_rows("top_src_ports", "mixed_mask")
    rows["rank"] = np.arange(7, dtype=np.int32)[::-1].copy()
    sink = SQLiteSink()
    try:
        sink.write("top_src_ports", rows)
        got = _stored(sink.query, "top_src_ports")
    finally:
        sink.close()
    assert _typed(got) == _typed(_ref_sqlite("top_src_ports", rows))
    assert [r[2] for r in got] == [6, 4, 3, 1, 0]


def test_object_and_sequence_columns_go_a_value_at_a_time():
    words = np.empty(3, object)
    words[:] = [np.array([0, 0, 0, 0x0A000001], np.uint32), "text",
                np.float32(2.5)]
    rows = {"timeslot": [300, np.uint64(600), 900], "mixed": words,
            "valid": [True, False, True]}
    assert _typed(rows_to_records(rows)) == _typed(_ref_records(rows))
    assert rows_to_columns(rows) == {"timeslot": [300, 900],
                                     "mixed": ["10.0.0.1", 2.5]}


# ---- the mechanism: a column is not indexed a row at a time ----------------


class _Counting(np.ndarray):
    """An ndarray that counts its ``__getitem__`` calls by column."""

    calls: dict = {}
    column = ""

    def __array_finalize__(self, obj):
        self.column = getattr(obj, "column", "")

    def __getitem__(self, key):
        _Counting.calls[self.column] = _Counting.calls.get(self.column, 0) + 1
        return super().__getitem__(key)


def _counted_write(n: int) -> dict:
    rng = np.random.default_rng(n)
    rows = {}
    for c in ddl.TABLE_COLUMNS["flows_5m"]:
        col = rng.integers(0, 2**40, n, dtype=np.uint64).view(_Counting)
        col.column = c
        rows[c] = col
    rows["valid"] = np.arange(n) % 5 != 0
    _Counting.calls = {}
    sink = SQLiteSink()
    try:
        sink.write("flows_5m", rows)
        (stored,) = sink.query("SELECT COUNT(*), SUM(count) FROM flows_5m")
    finally:
        sink.close()
    keep = rows["valid"]
    assert stored == (int(keep.sum()),
                      int(np.asarray(rows["count"])[keep].sum()))
    return dict(_Counting.calls)


def test_a_close_indexes_each_column_a_constant_number_of_times():
    small, large = _counted_write(4), _counted_write(65_536)
    assert small == large
    assert set(large) <= set(ddl.TABLE_COLUMNS["flows_5m"])
    assert all(calls <= 2 for calls in large.values())
