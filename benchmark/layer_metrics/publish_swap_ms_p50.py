"""The end of a publish: the range ledger's freeze and the snapshot swap
(SnapshotStore.publish): median. Source: the program's publish_swap span
[ranges]."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "publish_swap")
