"""Device ms of one window close's merge programs on a chip: the sum over
mesh_hh_merge_<model> and mesh_dense_merge_<model> of one execution (the
median over its executions and the chips; a publish runs the same
programs). Source: profiler trace, XLA Modules by program name."""

from benchmark import mesh_trace


def read(run):
    return mesh_trace.execution_ms(run, mesh_trace.is_merge)
