"""Age of a batch's first flow when the dispatch loop picks the batch up:
wall clock less the stamp the bus gave it at produce; median. Bus, fetch,
decode and the prefetch queue. Source: the program's apply span [age_ms]."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_arg(run, "apply", "age_ms")
