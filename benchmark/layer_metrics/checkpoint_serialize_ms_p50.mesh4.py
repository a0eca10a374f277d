"""np.savez of every array (four stacked replicas of each sketch; stored,
not compressed, since PR 30) and json.dumps of the tree, in memory: median.
Source: the program's ckpt_serialize span, as checkpoint_serialize_ms_p50
reads it on one chip."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "ckpt_serialize")
