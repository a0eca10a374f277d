"""The call of the jitted fused step until it returns to the loop (dispatch;
long only where dispatch blocks): median. Source: the program's
step_dispatch span."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "step_dispatch")
