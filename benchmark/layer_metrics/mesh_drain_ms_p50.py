"""One flows_5m drain under a mesh (the wait for the program just
dispatched, the per-shard reads and the host fold): median. Source: the
program's mesh_drain span."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "mesh_drain")
