"""Time inside StreamWorker._write_rows (sink.write of every table) per
window close in the window. Source: span."""

from benchmark import reduce


def read(run):
    closes = len(reduce.close_spans(run))
    return None if not closes else \
        sum(reduce.window_ms(run, "sink_write")) / closes
