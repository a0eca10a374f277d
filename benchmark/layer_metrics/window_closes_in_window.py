"""Window closes inside the measured window: flushes that wrote the
configuration's close table. Fixed by the phase lock (schedule.py);
reported so that a run that lost the lock shows."""

from benchmark import reduce


def read(run):
    return float(len(reduce.close_spans(run)))
