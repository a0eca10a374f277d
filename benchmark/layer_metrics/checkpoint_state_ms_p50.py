"""Building the state tree of a checkpoint (sync, the families' save hooks,
the flusher's drain): median. Source: the program's ckpt_state span."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "ckpt_state")
