"""The host fold of a drain's partials into the window store (_merge_partials
-> _fold_rows -> WindowStore.merge: since PR 34 a binary search, one
indexed add and an insert of the few new rows, numpy alone): median. In the
as64k cell the store holds ~6x10^4 groups, in the others 256. Source: the
program's wagg_fold span."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "wagg_fold")
