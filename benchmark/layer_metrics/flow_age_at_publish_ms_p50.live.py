"""Age, at the snapshot swap, of the first flow of the newest batch the
snapshot holds: wall clock at the swap less the stamp the bus gave it at
produce; median over the window's publishes. Source: the program's
snapshot_publish span [age_ms]."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_arg(run, "snapshot_publish", "age_ms")
