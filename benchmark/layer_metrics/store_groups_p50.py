"""Entries of the window store (every open window) after a fold: median over
the window's folds. Source: wagg_fold's store_groups; a program whose
wagg_fold does not say reads nothing."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_arg(run, "wagg_fold", "store_groups")
