"""A close's register decode on the worker thread: the spread_decode
spans (the planes' device->host copy, the HLL decode of the candidate
sources and their ranking) inside the window_close spans of one slot,
both detectors summed; median over the window's closes. A publish decodes
too (spread_decode outside any window_close): not counted here. Source:
the program's spread_decode span [model, rows] under window_close
[model, slot]. A program without the span reads nothing."""

import statistics

from benchmark import inside_spans, program_spans


def read(run):
    w = program_spans.window(run)
    if not w or not inside_spans.has(w, "spread_decode"):
        return None
    slots: dict = {}
    for close, decodes in inside_spans.nested(w, "window_close",
                                              "spread_decode"):
        if decodes:
            slot = close[5].get("slot")
            slots[slot] = slots.get(slot, 0.0) + sum(
                inside_spans.ms(d) for d in decodes)
    return statistics.median(slots.values()) if slots else None
