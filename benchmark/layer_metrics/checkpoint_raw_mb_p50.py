"""Bytes of the arrays one checkpoint serializes, in MB (what the file
holds too: nothing is compressed since PR 30): median. The as64k cell's
holds the window store among them, the mesh cell's four stacked replicas
of each sketch. Source: ckpt_serialize's raw_bytes."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_arg(run, "ckpt_serialize", "raw_bytes", 1e-6)
