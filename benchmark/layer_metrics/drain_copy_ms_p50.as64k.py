"""The remaining device -> host reads of a flows_5m drain: median. Source: the
program's wagg_d2h span, as drain_copy_ms_p50 reads it."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "wagg_d2h")
