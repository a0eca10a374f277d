"""1 - (time an operation ran on the device) / (traced window), averaged
over the chips used, in per cent. Source: profiler trace."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
