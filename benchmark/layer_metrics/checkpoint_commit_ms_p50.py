"""The offset commit after a checkpoint (consumer.commit per partition and
flush_commits): median. Source: the program's ckpt_commit span."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "ckpt_commit")
