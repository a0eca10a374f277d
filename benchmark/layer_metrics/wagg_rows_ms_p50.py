"""Popped window stores turned into the close's columnar rows
(rows_from_stores): median over the window's closes. Source: the program's
wagg_rows span; a program without it reads nothing."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "wagg_rows")
