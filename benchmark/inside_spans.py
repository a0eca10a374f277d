"""The program's spans inside the layers the hooks time from outside
(PR 35): a flush's parts by the chunk that flushed, a publish's parts by
publish, a chunk's way from the feed thread to the dispatch loop.

Nesting is by thread and time: ``sink_records`` and ``sink_execute`` are
recorded by a sink, which knows no chunk, inside the ``flush`` span of
``StreamWorker._write_rows``, which carries the chunk whose ``apply``
closed the window (on the worker's thread, or on the flusher's with the
same chunk). Everything here returns None where the program has no such
span (a parent commit from before them) or its spans cannot be read
(``program_spans.window``).
"""

from __future__ import annotations

import bisect
import statistics

from benchmark import program_spans


def ms(span) -> float:
    return (span[2] - span[1]) * 1e3


def nested(w, parent: str, child: str, keep=None) -> list:
    """[(parent span, [the ``child`` spans on its thread that started
    inside it])] for the window's ``parent`` spans, in start order;
    ``keep(child span)`` may drop some."""
    by_thread: dict = {}
    for p in sorted(w.named(parent), key=lambda s: s[1]):
        by_thread.setdefault(p[3], []).append((p, []))
    starts = {t: [p[1] for p, _ in ps] for t, ps in by_thread.items()}
    for c in w.spans:
        if c[0] != child or c[3] not in by_thread \
                or (keep is not None and not keep(c)):
            continue
        i = bisect.bisect_right(starts[c[3]], c[1]) - 1
        if i >= 0 and c[1] < by_thread[c[3]][i][0][2]:
            by_thread[c[3]][i][1].append(c)
    return sorted((pc for ps in by_thread.values() for pc in ps),
                  key=lambda pc: pc[0][1])


def has(w, name: str) -> bool:
    return any(s[0] == name for s in w.spans)


def flush_ms_per_chunk(run, child: str, keep=None):
    """Median, over the chunks that flushed in the window, of the summed
    ms of the ``child`` spans inside that chunk's ``flush`` spans (a
    window close flushes six tables in one chunk, a slide five)."""
    w = program_spans.window(run)
    if not w or not has(w, child):
        return None
    chunks: dict = {}
    for flush, children in nested(w, "flush", child, keep):
        chunks[flush[4]] = chunks.get(flush[4], 0.0) + sum(
            ms(c) for c in children)
    return statistics.median(chunks.values()) if chunks else None


def per_publish(run, child: str, value):
    """For each ``snapshot_publish`` of the window, the sum of
    ``value(span)`` over the ``child`` spans inside it; None where there
    is none."""
    w = program_spans.window(run)
    if not w or not has(w, child):
        return None
    sums = [sum(value(c) for c in children)
            for _p, children in nested(w, "snapshot_publish", child)]
    return sums or None


def queue_wait_ms(run):
    """For each chunk applied in the window, the time between the end of
    its ``decode`` on the feed thread and the start of its ``apply``:
    what it spent ready in the prefetch queue."""
    w = program_spans.window(run)
    if not w:
        return None
    decoded = {s[4]: s[2] for s in w.spans
               if s[0] == "decode" and s[4] is not None}
    waits = [(a[1] - decoded[a[4]]) * 1e3 for a in w.named("apply")
             if a[4] in decoded]
    return waits or None
