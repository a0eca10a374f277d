"""The host fold of a drain's partials into a window store of ~6x10^4 groups
(_merge_partials -> _fold_rows -> WindowStore.merge: since PR 34 a binary
search, one indexed add and an insert of the few new rows, numpy alone):
median. Source: the program's wagg_fold span, as fold_host_ms_p50 reads it."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "wagg_fold")
