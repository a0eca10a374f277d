"""Host time a polled batch spends padding, building and placing the
models' columns row-sharded on the mesh (every model shards its own): sum
of the mesh_shard spans inside one apply span, median over the window's
batches that held any. Source: the program's spans."""

from benchmark import mesh_spans, reduce


def read(run):
    sums = mesh_spans.ms_per_apply(run, "mesh_shard")
    return reduce.p50([ms for ms in sums or () if ms > 0.0])
