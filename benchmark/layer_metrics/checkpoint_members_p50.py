"""Members of a checkpoint's arrays.npz: median. It must not grow with the
window store (store_groups_p50): an array a group made a checkpoint of
6x10^4 groups cost seconds to write and a minute to read. Source:
ckpt_serialize's members; a program whose span does not say reads nothing."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_arg(run, "ckpt_serialize", "members")
