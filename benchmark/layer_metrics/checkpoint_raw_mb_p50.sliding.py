"""Bytes of the arrays one checkpoint serializes, in MB: median. Under a slide
the closed sub-window states are members written once (checkpoint_member_*),
so this stays a single window's. Source: ckpt_serialize's raw_bytes."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_arg(run, "ckpt_serialize", "raw_bytes", 1e-6)
