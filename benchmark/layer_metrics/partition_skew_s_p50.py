"""How far apart the partitions' newest event times lie when a batch is
applied, in seconds: median over the window's batches. The watermark is the
newest over all partitions and waits for none; what -window.lateness has to
cover is this skew plus the stream's own delay. Source: apply's skew_s; a
program whose apply does not say reads nothing."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_arg(run, "apply", "skew_s")
