"""The durable publish of a checkpoint: two fsynced writes, the renames, the
rmtree, the directory fsync: median. Source: the program's ckpt_write span."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "ckpt_write")
