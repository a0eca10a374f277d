"""The cut of a polled batch at window-slot and detector sub-window
boundaries before the fused step (FusedPipeline._split_parts): median.
Source: the program's split_parts span [parts]."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "split_parts")
