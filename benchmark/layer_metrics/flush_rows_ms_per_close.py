"""Making the rows a flush writes (rows_from_stores for flows_5m, a lazy
handle's extraction) and counting them, summed over the tables one chunk
flushed; median over the window's chunks that flushed. Under a slide most
of those chunks are slides. Source: the program's flush_rows span."""

from benchmark import inside_spans


def read(run):
    return inside_spans.flush_ms_per_chunk(run, "flush_rows")
