"""Rows a drain hands to the host fold (the device's groups of the partials it
folds, before they merge into the store): median. Source: wagg_fold's
groups."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_arg(run, "wagg_fold", "groups")
