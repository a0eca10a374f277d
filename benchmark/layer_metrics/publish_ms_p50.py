"""WorkerServePublisher.publish (extraction + snapshot swap, inline in the
dispatch loop): median duration in the window. Source: span."""

from benchmark import reduce


def read(run):
    return reduce.p50(reduce.window_ms(run, "publish"))
