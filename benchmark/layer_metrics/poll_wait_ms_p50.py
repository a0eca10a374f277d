"""The dispatch loop waiting on the prefetch queue for its next batch
(PrefetchConsumer.poll): median. Source: the program's poll_wait span."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "poll_wait")
