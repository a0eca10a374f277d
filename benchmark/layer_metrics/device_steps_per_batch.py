"""Device steps run for one polled batch: step_dispatch spans inside one
apply span, mean over the window's batches that ran any (a batch that
crosses a window slot or a detector sub-window is split and padded again).
Source: the program's spans."""

from benchmark import program_spans


def read(run):
    w = program_spans.window(run)
    counts = [n for n in program_spans.per_parent(w, "apply",
                                                  "step_dispatch") if n] \
        if w else []
    return sum(counts) / len(counts) if counts else None
