"""The SQL sink's executemany + commit, summed over the tables one chunk
flushed; median over the window's chunks that flushed. Source: the
program's sink_execute span, recorded by the sink inside its sink_put."""

from benchmark import inside_spans


def read(run):
    return inside_spans.flush_ms_per_chunk(run, "sink_execute")
