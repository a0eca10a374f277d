"""np.savez of every array (stored, not compressed, since PR 30) and
json.dumps of the tree, in memory: median. Source: the program's
ckpt_serialize span."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "ckpt_serialize")
