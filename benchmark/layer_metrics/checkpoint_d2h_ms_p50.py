"""Bringing the device leaves of the state tree to the host: median. Source:
the program's ckpt_d2h span."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "ckpt_d2h")
