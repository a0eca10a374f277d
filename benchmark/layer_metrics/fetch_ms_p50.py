"""One fetch of the bus by the consumer, on the feed thread: median duration
of the fetches that took flows. The inside twin of the bus_fetch hook.
Source: the program's fetch span [rows]."""

import statistics

from benchmark import inside_spans, program_spans


def read(run):
    w = program_spans.window(run)
    took = [inside_spans.ms(s) for s in (w.named("fetch") if w else [])
            if s[5].get("rows")]
    return statistics.median(took) if took else None
