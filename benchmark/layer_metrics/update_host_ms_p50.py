"""Host time of one batch step outside flush, checkpoint and publish:
StreamWorker._process less the flush_closed, snapshot_and_commit and
publish spans inside it (lane build, host->device copies, dispatch,
drains the step forces). Median over the window's batches."""

from benchmark import reduce


def read(run):
    inner = sorted((s for n in ("flush_closed", "snapshot_and_commit",
                                "publish") for s in run.in_window(n)),
                   key=lambda s: s[1])
    out = []
    for p in run.in_window("process"):
        held = sum(s[2] - s[1] for s in inner if p[1] <= s[1] and s[2] <= p[2])
        out.append((p[2] - p[1] - held) * 1e3)
    return reduce.p50(out)
