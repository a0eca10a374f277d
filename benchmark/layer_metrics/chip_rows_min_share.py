"""The least-fed chip's valid rows over the chips' mean, over every global
step of the window, in per cent: 100 when the rows are spread evenly, less
when part-full steps (a batch cut at a window slot or a detector sub-window
is padded at its end) leave the trailing chips idle. Source: mesh_shard's
chip_rows, the host's mask summed by chip before it is sharded."""

from benchmark import program_spans


def read(run):
    w = program_spans.window(run)
    steps = w.args("mesh_shard", "chip_rows") if w else []
    if not steps:
        return None
    totals = [sum(chip) for chip in zip(*steps)]
    mean = sum(totals) / len(totals)
    return 100.0 * min(totals) / mean if mean else None
