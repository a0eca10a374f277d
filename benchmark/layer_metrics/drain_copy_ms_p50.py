"""The remaining device -> host reads of a flows_5m drain (keys, sums, counts
of the pending partials): median. Source: the program's wagg_d2h span."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "wagg_d2h")
