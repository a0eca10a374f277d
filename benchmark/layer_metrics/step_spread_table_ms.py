"""Device time of one fused step under the spread detectors' candidate
tables (the scopes spread_table_<detector>, both summed: the groups a
source from the families' shared sort, the admission metric, the
prefilter and the table's merge): median over the step's executions in
the traced window. Source: profiler trace, XLA Ops self times by scope
(spread_scopes.py). A program whose step holds no spread detector reads
nothing."""

from benchmark import spread_scopes


def read(run):
    return spread_scopes.scope_ms_p50(run, "spread_table_")
