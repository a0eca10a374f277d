"""The first host read of a flows_5m drain, which blocks until the device step
that made the partial has finished: median. Source: the program's wagg_wait
span, as drain_wait_ms_p50 reads it."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "wagg_wait")
