"""Share of a sketch family's group slots that lie below the live bound,
in per cent: what the conservative count-min update's estimate gathers
of a batch's padded groups (the largest of the families, in the last
device step before each checkpoint of the window; median). Source:
ckpt_state's hh_live_rows and hh_slots; a program whose ckpt_state does
not say reads nothing."""

import statistics

from benchmark import program_spans


def read(run):
    w = program_spans.window(run)
    live = w.args("ckpt_state", "hh_live_rows") if w else []
    slots = w.args("ckpt_state", "hh_slots") if w else []
    shares = [100.0 * rows / n for rows, n in zip(live, slots)]
    return statistics.median(shares) if shares else None
