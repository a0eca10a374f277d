"""The per-batch flush probe: StreamWorker._flush_closed calls in the
window that emitted nothing. Each one drains the pending flows_5m device
partials to the host and folds them (models/window_agg.py), inline in the
dispatch loop. Median duration. Source: span."""

from benchmark import reduce


def read(run):
    return reduce.p50(reduce.window_ms(run, "flush_closed",
                                       lambda emitted: not emitted))
