"""Slides inside the measured window: distinct window ends among the
program's slide_close spans (one a ranked table a slide). Fixed by the phase
lock (a slide every 30 s of event time = 1,920,000 flows); reported so that
a run that lost it shows."""

from benchmark import slide_spans


def read(run):
    slides = slide_spans.by_slide(run, "slide_close", "window_end")
    return float(len(slides)) if slides else None
