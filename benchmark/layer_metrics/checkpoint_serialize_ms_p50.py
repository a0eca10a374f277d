"""np.savez_compressed of every array and json.dumps of the tree, in memory:
median. Source: the program's ckpt_serialize span."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "ckpt_serialize")
