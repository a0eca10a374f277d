"""Bytes of the arrays one checkpoint serializes under a mesh (four stacked
replicas of each sketch), in MB (what the file holds too: nothing is
compressed since PR 30): median. Source: ckpt_serialize's raw_bytes."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_arg(run, "ckpt_serialize", "raw_bytes", 1e-6)
