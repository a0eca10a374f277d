"""Distinct sampling rates among the rows of one flows_5m drain (the store
is keyed by key lanes and rate, and folds its per-rate subgroups into the
scaled columns at the flush): median over the window's drains. Source:
wagg_fold's rates; a program whose wagg_fold does not say reads
nothing."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_arg(run, "wagg_fold", "rates")
