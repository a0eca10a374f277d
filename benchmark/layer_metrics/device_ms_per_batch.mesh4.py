"""Under a mesh one batch is several sharded programs, not one step:
device-busy time (averaged over the chips) per batch step of the traced
part of the window. Source: profiler trace + spans."""


def read(run):
    if run.trace is None or run.trace_span is None:
        return None
    batches = len(run.spans.named("process", *run.trace_span))
    return None if not batches else run.trace.busy_s * 1e3 / batches
