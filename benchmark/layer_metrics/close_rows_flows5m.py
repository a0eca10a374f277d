"""Rows of flows_5m a window close writes to the sinks: median over the
window's closes. Source: the program's flush span [table, rows]."""

import statistics

from benchmark import program_spans


def read(run):
    w = program_spans.window(run)
    rows = [s[5]["rows"] for s in (w.named("flush") if w else [])
            if s[5].get("table") == "flows_5m" and s[5].get("rows")]
    return statistics.median(rows) if rows else None
