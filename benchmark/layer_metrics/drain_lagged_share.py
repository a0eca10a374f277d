"""Share of the window's flows_5m drains that left the newest partial on
the device, in per cent: the per-batch flush probe proved nothing closable
on the host and read only the step before last, so the host did not wait
for the step just dispatched. The rest are full drains: a close, a
checkpoint, and every batch that did not fill a device step (the loop is
keeping up, and such a partial is queued without a slot bound). Source:
wagg_wait's left; a program whose wagg_wait does not say reads nothing."""

from benchmark import program_spans


def read(run):
    w = program_spans.window(run)
    left = w.args("wagg_wait", "left") if w else []
    return (100.0 * sum(1 for n in left if n >= 1) / len(left)
            if left else None)
