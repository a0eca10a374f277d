"""Units held open for late rows when a checkpoint was taken, whose states it
carries beside the open ones: median over the window's checkpoints (of six:
five ranked tables and the detector). Source: ckpt_state's held_units; a
program whose ckpt_state does not say reads nothing."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_arg(run, "ckpt_state", "held_units")
