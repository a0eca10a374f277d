"""One closed sub-window state written as a checkpoint member (np.savez,
fsynced write, replace, directory fsync), once, by the checkpoint after its
slide: median. Source: the program's ckpt_member span."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "ckpt_member")
