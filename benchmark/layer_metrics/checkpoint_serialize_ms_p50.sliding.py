"""np.savez of the open state's arrays and json.dumps of the tree (the ring's
members by name), in memory: median. Source: the program's ckpt_serialize
span, as checkpoint_serialize_ms_p50 reads it."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "ckpt_serialize")
