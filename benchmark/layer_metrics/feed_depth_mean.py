"""Decoded batches ready in the prefetch queue when the loop asked for one:
mean over the window's polls. 0 = the feed is the bottleneck, the
queue's depth (2) = the loop is. Source: poll_wait's depth."""

from benchmark import program_spans


def read(run):
    w = program_spans.window(run)
    depths = w.args("poll_wait", "depth") if w else []
    return sum(depths) / len(depths) if depths else None
