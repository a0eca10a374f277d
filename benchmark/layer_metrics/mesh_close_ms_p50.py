"""Four-chip merge: ShardedHeavyHitter.merged_state (psum + all_gather of
the per-chip sketches) calls in the window, median. Source: span."""

from benchmark import reduce


def read(run):
    return reduce.p50(reduce.window_ms(run, "mesh_merge"))
