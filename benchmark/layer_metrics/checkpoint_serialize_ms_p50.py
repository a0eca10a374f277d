"""np.savez of every array (stored, not compressed, since PR 30) and
json.dumps of the tree, in memory: median. The arrays are the sketch
planes and, in the as64k cell, one key array and one sums array of ~6x10^4
rows a window; under a mesh four stacked replicas of each sketch; under a
slide the open state's alone (the tree names the ring's members). Source:
the program's ckpt_serialize span."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "ckpt_serialize")
