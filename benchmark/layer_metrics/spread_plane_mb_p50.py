"""Bytes of the spread detectors' register planes in one checkpoint, in
MB (a byte a register in every form that leaves the device; both
detectors, and a held unit's beside the open one's): median. Source:
ckpt_state's spread_plane_bytes. A program without the counter, or a
configuration without the detectors, reads nothing."""

from benchmark import program_spans


def read(run):
    return program_spans.p50_arg(run, "ckpt_state", "spread_plane_bytes",
                                 1e-6)
