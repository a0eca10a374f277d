"""Runs of the detector's own program (models/ddos.py::ddos_accumulate
alone, for the older sub-window of a poll that crosses only a detector
sub-window) for one polled batch: detector_dispatch spans inside one
apply span, mean over the window's batches that ran a device step.
Source: the program's spans; a program without the span reads 0."""

from benchmark import program_spans


def read(run):
    w = program_spans.window(run)
    if not w:
        return None
    steps = program_spans.per_parent(w, "apply", "step_dispatch")
    alone = program_spans.per_parent(w, "apply", "detector_dispatch")
    ran = [d for s, d in zip(steps, alone) if s]
    return sum(ran) / len(ran) if ran else None
