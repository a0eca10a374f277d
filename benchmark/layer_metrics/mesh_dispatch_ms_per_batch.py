"""Host time a polled batch spends dispatching the models' sharded update
programs: the mesh_update spans inside one apply span (one a model) less
the mesh_shard spans nested in them, median over the window's batches that
held any. Source: the program's spans."""

from benchmark import mesh_spans, reduce


def read(run):
    update = mesh_spans.ms_per_apply(run, "mesh_update")
    shard = mesh_spans.ms_per_apply(run, "mesh_shard")
    if not update:
        return None
    return reduce.p50([u - s for u, s in zip(update, shard) if u > 0.0])
