"""The host fold of a drain's partials into the window store (_merge_partials
-> _fold_rows): median. Source: the program's wagg_fold span."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "wagg_fold")
