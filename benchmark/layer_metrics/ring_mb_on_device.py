"""Sub-window sketch states the ranked tables hold on the device after a
slide (the closed ones in the ring and the open one, all tables), in MB:
median over the window's slides. Source: ring_rotate [sub, ring_bytes]."""

from benchmark import slide_spans


def read(run):
    return slide_spans.p50_per_slide(
        run, "ring_rotate", "sub", lambda s: s[5].get("ring_bytes", 0) * 1e-6)
