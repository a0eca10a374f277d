"""Sink + CLI tests: record normalization, SQLite storage, Postgres SQL
generation, and the CLI surface (pipeline demo, mocker -out / processor -in
file roundtrip, flag errors)."""

import sqlite3

import numpy as np
import pytest

from flow_pipeline_tpu.cli import main
from flow_pipeline_tpu.sink import MemorySink, SQLiteSink, rows_to_records
from flow_pipeline_tpu.sink.postgres import insert_sql


class TestRecords:
    def test_columnar_rows(self):
        rows = {
            "timeslot": np.array([300, 300], np.uint64),
            "src_as": np.array([65000, 65001], np.uint64),
            "bytes": np.array([10, 20], np.uint64),
        }
        recs = rows_to_records(rows)
        assert recs == [
            {"timeslot": 300, "src_as": 65000, "bytes": 10},
            {"timeslot": 300, "src_as": 65001, "bytes": 20},
        ]

    def test_valid_mask_filters(self):
        rows = {
            "bytes": np.array([1, 2], np.uint64),
            "valid": np.array([True, False]),
        }
        assert len(rows_to_records(rows)) == 1

    def test_ipv4_and_ipv6_render(self):
        v4 = np.array([0, 0, 0, (10 << 24) | (0 << 16) | (0 << 8) | 7], np.uint32)
        v6 = np.array([0x20010DB8, 0, 0, 0x1234], np.uint32)
        rows = {"dst_addr": np.stack([v4, v6]), "bytes": np.array([1, 2], np.uint64)}
        recs = rows_to_records(rows)
        assert recs[0]["dst_addr"] == "10.0.0.7"
        assert recs[1]["dst_addr"] == "2001:db8::1234"


class TestSQLite:
    def test_known_tables(self):
        sink = SQLiteSink()
        sink.write("flows_5m", {
            "timeslot": np.array([300], np.uint64),
            "src_as": np.array([65000], np.uint64),
            "dst_as": np.array([65001], np.uint64),
            "etype": np.array([0x86DD], np.uint64),
            "bytes": np.array([99], np.uint64),
            "packets": np.array([3], np.uint64),
            "count": np.array([1], np.uint64),
        })
        assert sink.query("SELECT bytes FROM flows_5m") == [(99,)]

    def test_migrates_pre_r4_file_missing_scaled_columns(self, tmp_path):
        """A .db created before the sampling-scaled columns landed must
        be ALTERed at sink init, not crash-loop on the first insert
        ('no column named bytes_scaled') — CREATE TABLE IF NOT EXISTS is
        a no-op on existing files (ADVICE r5)."""
        path = str(tmp_path / "pre_r4.db")
        conn = sqlite3.connect(path)
        conn.execute(
            "CREATE TABLE flows_5m (timeslot INTEGER, src_as INTEGER, "
            "dst_as INTEGER, etype INTEGER, bytes INTEGER, "
            "packets INTEGER, count INTEGER)")
        conn.execute(
            "INSERT INTO flows_5m VALUES (0, 1, 2, 3, 10, 1, 1)")
        conn.commit()
        conn.close()
        sink = SQLiteSink(path)
        sink.write("flows_5m", {
            "timeslot": np.array([300], np.uint64),
            "src_as": np.array([65000], np.uint64),
            "dst_as": np.array([65001], np.uint64),
            "etype": np.array([0x86DD], np.uint64),
            "bytes": np.array([99], np.uint64),
            "packets": np.array([3], np.uint64),
            "count": np.array([1], np.uint64),
            "bytes_scaled": np.array([990], np.uint64),
            "packets_scaled": np.array([30], np.uint64),
        })
        assert sink.query(
            "SELECT bytes, bytes_scaled FROM flows_5m "
            "WHERE timeslot = 300") == [(99, 990)]
        # pre-migration rows survive with NULL scaled columns
        assert sink.query(
            "SELECT bytes_scaled FROM flows_5m WHERE timeslot = 0"
        ) == [(None,)]
        sink.close()

    def test_unknown_table_journaled(self):
        sink = SQLiteSink()
        sink.write("mystery", [{"a": 1}])
        rows = sink.query("SELECT table_name, record FROM journal")
        assert rows[0][0] == "mystery"

    def test_topk_rank_assigned(self):
        sink = SQLiteSink()
        sink.write("top_talkers", {
            "timeslot": np.array([0, 0], np.uint64),
            "bytes": np.array([100, 50], np.uint64),
            "valid": np.array([True, True]),
        })
        assert sink.query("SELECT rank, bytes FROM top_talkers ORDER BY rank") == [
            (0, 100), (1, 50),
        ]


    @pytest.mark.parametrize("table", ["superspreaders", "portscan"])
    def test_spread_rows_land_in_a_typed_table_in_rank_order(self, table):
        """The detectors' rows (models/spread.py: a source, its decoded
        spread, the admission metric) have tables of their own since
        PR 47, ranked like the top-K tables, not the journal's JSON."""
        sink = SQLiteSink()
        sink.write(table, {
            "timeslot": np.array([300, 300, 300], np.uint64),
            "src_addr": np.array([[0x20010DB8, 1, 0, 7],
                                  [0x20010DB8, 1, 0, 9],
                                  [0, 0, 0, 0]], np.uint32),
            "spread": np.array([812.5, 40.25, 0.0], np.float32),
            "pairs": np.array([1200.0, 64.0, 0.0], np.float32),
            "valid": np.array([True, True, False]),
        })
        assert sink.query(f"SELECT timeslot, rank, src_addr, spread, pairs "
                          f"FROM {table} ORDER BY rank") == [
            (300, 0, "2001:db8:0:1::7", 812.5, 1200.0),
            (300, 1, "2001:db8:0:1::9", 40.25, 64.0)]
        assert sink.query("SELECT COUNT(*) FROM journal") == [(0,)]


class TestDialects:
    """One column list a table, shared by the three SQL sinks
    (sink/ddl.py::TABLE_COLUMNS): the spread detectors' tables beside
    ``top_pairs`` (PR 42)."""

    @pytest.mark.parametrize("table, cols", [
        ("top_pairs", ["timeslot", "rank", "src_addr", "dst_addr", "bytes",
                       "packets", "count"]),
        ("superspreaders", ["timeslot", "rank", "src_addr", "spread",
                            "pairs"]),
        ("portscan", ["timeslot", "rank", "src_addr", "spread", "pairs"]),
    ])
    def test_a_ranked_table_has_its_columns_in_every_dialect(self, table,
                                                             cols):
        from flow_pipeline_tpu.sink import clickhouse, ddl, postgres

        assert ddl.TABLE_COLUMNS[table] == cols
        assert table in ddl.RANKED_TABLES
        clickhouse_ddl = getattr(ddl, f"CLICKHOUSE_{table.upper()}")
        for text in (ddl.SQLITE_TABLES[table], postgres.DDL[table],
                     clickhouse_ddl):
            assert f"CREATE TABLE IF NOT EXISTS {table} (" in text
            body = text.split("(", 1)[1]
            assert [ln.split()[0] for ln in body.replace(",", "\n")
                    .splitlines() if ln.split()
                    and ln.split()[0] in cols] == cols
        assert "ORDER BY (timeslot, rank)" in clickhouse_ddl
        # the ClickHouse sink creates it with the others at start-up
        import inspect

        assert f"ddl.CLICKHOUSE_{table.upper()}" in inspect.getsource(
            clickhouse)
        sql, args = insert_sql(table, [dict.fromkeys(cols, 1)])
        assert sql.startswith(f'INSERT INTO "{table}"')
        assert args == [1] * len(cols)

    def test_a_spread_row_without_a_rank_gets_one(self):
        from flow_pipeline_tpu.sink import ddl

        rows = ddl.assign_ranks("portscan", [{"spread": 9.0},
                                             {"spread": 4.0}])
        assert [r["rank"] for r in rows] == [0, 1]


class TestPostgresSQL:
    def test_insert_sql_multirow_single_statement(self):
        sql, args = insert_sql("flows_5m", [
            {"timeslot": 300, "src_as": 1, "dst_as": 2, "etype": 3,
             "bytes": 4, "packets": 5, "count": 6,
             "bytes_scaled": 40, "packets_scaled": 50},
            {"timeslot": 600, "src_as": 7, "dst_as": 8, "etype": 9,
             "bytes": 10, "packets": 11, "count": 12,
             "bytes_scaled": 100, "packets_scaled": 110},
        ])
        assert sql.startswith('INSERT INTO "flows_5m"')
        assert sql.count("(%s") == 2  # one VALUES group per record
        assert args == [300, 1, 2, 3, 4, 5, 6, 40, 50,
                        600, 7, 8, 9, 10, 11, 12, 100, 110]

    def test_missing_fields_become_none(self):
        _, args = insert_sql("ddos_alerts", [{"rate": 1.5}])
        assert args.count(None) == 5


class TestCLI:
    def test_usage(self, capsys):
        assert main([]) == 2
        assert main(["-h"]) == 0
        assert "mocker" in capsys.readouterr().out

    def test_unknown_command(self, capsys):
        assert main(["fnord"]) == 2

    def test_unknown_flag(self, capsys):
        # flowlint: disable=flag-registry -- deliberately unregistered: this IS the unknown-flag rejection test
        assert main(["pipeline", "-not.a.flag", "x"]) == 2
        assert "not.a.flag" in capsys.readouterr().err

    def test_pipeline_to_sqlite(self, tmp_path):
        db = str(tmp_path / "flows.db")
        rc = main([
            "pipeline", "-produce.count", "2000", "-produce.rate", "50",
            "-processor.batch", "512", "-sink", f"sqlite:{db}",
            "-metrics.addr", "", "-model.ddos=false",
        ])
        assert rc == 0
        conn = sqlite3.connect(db)
        total = conn.execute("SELECT SUM(count) FROM flows_5m").fetchone()[0]
        assert total == 2000

    def test_mocker_file_then_processor(self, tmp_path):
        frames = str(tmp_path / "frames.bin")
        db = str(tmp_path / "flows.db")
        assert main(["mocker", "-out", frames, "-produce.count", "1500",
                     "-produce.rate", "50"]) == 0
        assert main(["processor", "-in", frames, "-processor.batch", "512",
                     "-sink", f"sqlite:{db}", "-metrics.addr", "",
                     "-model.ddos=false", "-model.talkers=false"]) == 0
        conn = sqlite3.connect(db)
        assert conn.execute("SELECT SUM(count) FROM flows_5m").fetchone()[0] == 1500

    def test_pipeline_with_mesh(self, tmp_path):
        # -processor.mesh 8 runs the sharded models over the CPU mesh
        db = str(tmp_path / "mesh.db")
        rc = main([
            "pipeline", "-produce.count", "4000", "-produce.rate", "40",
            "-processor.batch", "128", "-processor.mesh", "8",
            "-sink", f"sqlite:{db}", "-metrics.addr", "",
            "-model.ddos=false", "-sketch.width", str(1 << 12),
            "-sketch.capacity", "64",
        ])
        assert rc == 0
        conn = sqlite3.connect(db)
        assert conn.execute("SELECT SUM(count) FROM flows_5m").fetchone()[0] == 4000
        assert conn.execute("SELECT COUNT(*) FROM top_talkers").fetchone()[0] > 0

    def test_mocker_then_inserter_raw_rows(self, tmp_path):
        frames = str(tmp_path / "frames.bin")
        db = str(tmp_path / "raw.db")
        assert main(["mocker", "-out", frames, "-produce.count", "300"]) == 0
        assert main(["inserter", "-in", frames, "-sqlite", db]) == 0
        conn = sqlite3.connect(db)
        n, su = conn.execute("SELECT COUNT(*), SUM(bytes) FROM flows").fetchone()
        assert n == 300 and su > 0
        ip = conn.execute("SELECT src_ip FROM flows LIMIT 1").fetchone()[0]
        assert ip.startswith("2001:db8:0:1::")
