"""np.savez of every array (the sketch planes and, a window, one key array and
one sums array of ~6x10^4 rows) and json.dumps of the tree, in memory:
median. Source: the program's ckpt_serialize span, as
checkpoint_serialize_ms_p50 reads it."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "ckpt_serialize")
