"""Device time of one fused step under the host-pair family's table merge
(the scope hh_table_merge_<i> of the family top_pairs: its conservative
count-min update, prefilter and admission merge): median over the step's
executions in the traced window. Source: profiler trace, XLA Ops self
times by scope; the family's index from the program
(family_scopes.py). A program without the family reads nothing."""

from benchmark import family_scopes


def read(run):
    return family_scopes.merge_ms_p50(run, "top_pairs")
