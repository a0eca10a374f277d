"""The jnp.asarray of every column and the mask of one device step (host ->
device copies, as far as they block the loop): median. Source: the
program's h2d span."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "h2d")
