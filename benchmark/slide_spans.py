"""The program's spans of a sliding window, a slide at a time.

Under ``-window.slide`` every ranked table records its own ``slide_close``
[window_end, states, rows] and ``ring_rotate`` [model, sub, dropped_sub,
ring_bytes] at a slide (``engine/windowed.py``): five of each with the
default models. A slide is the spans that share ``window_end`` (or
``sub``). Everything here returns None where the program has no such
spans (a tumbling cell, a parent commit from before them).
"""

from __future__ import annotations

import statistics

from benchmark import program_spans


def by_slide(run, name: str, key: str):
    """{value of ``key``: [spans of ``name`` in the window that carry it]},
    or None where there is none."""
    w = program_spans.window(run)
    out: dict = {}
    for s in (w.named(name) if w else []):
        if key in s[5]:
            out.setdefault(s[5][key], []).append(s)
    return out or None


def p50_per_slide(run, name: str, key: str, value):
    """Median over the window's slides of the sum of ``value(span)``
    over a slide's spans."""
    slides = by_slide(run, name, key)
    if not slides:
        return None
    return statistics.median(sum(value(s) for s in spans)
                             for spans in slides.values())
