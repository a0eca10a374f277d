"""The host fold of the spread families between dispatches
(FusedPipeline._run_chunks, ROADMAP A7): median. 0 where the loop ran
device steps and no spread family is configured. Source: the program's
spread_fold span."""

from benchmark import program_spans


def read(run):
    w = program_spans.window(run)
    if not w or not w.named("step_dispatch"):
        return None
    return program_spans.p50_ms(run, "spread_fold") or 0.0
