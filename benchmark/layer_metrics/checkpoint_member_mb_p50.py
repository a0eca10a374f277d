"""Bytes of one checkpoint member (a table's closed sub-window state), in MB:
median. Source: ckpt_member [bytes]."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_arg(run, "ckpt_member", "bytes", 1e-6)
