"""The first host read of a flows_5m drain, which blocks until the device step
that made the partial has finished: median. Against step_device_ms_p50 it
says whether the loop is serial with the device step. Source: the program's
wagg_wait span."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "wagg_wait")
