"""Host column assembly for one device step (pad_to + device_columns in
FusedPipeline._run_chunks): median. Source: the program's lane_build span."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_ms(run, "lane_build")
