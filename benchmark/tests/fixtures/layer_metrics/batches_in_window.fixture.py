"""A layer metric added as a file only: batch steps in the window."""


def read(run):
    return float(len(run.in_window("process")))
