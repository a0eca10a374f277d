"""Rows of a drain that were new to their window's store (the rest were
added into rows that were there): median. Source: wagg_fold's inserted."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_arg(run, "wagg_fold", "inserted")
