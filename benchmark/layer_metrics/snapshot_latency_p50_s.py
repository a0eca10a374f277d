"""Taken -> visible: from the fetch that took a snapshot's newest flow off
the bus to the moment the reader first saw that version (prefetch queue,
batch step, publish, the reader's poll). Median over the versions first
seen in the window. Source: bus fetch log + the reader's publish log."""

import bisect

from benchmark import reduce


def read(run):
    if not run.publish_log:
        return None
    fetches = run.fetches()
    ends = [f[4] for f in fetches]  # flows fetched once it returned
    out = []
    for t_seen, _v, flows in run.publish_log:
        if run.t_a <= t_seen < run.t_b and flows:
            i = bisect.bisect_left(ends, flows)
            if i < len(fetches):
                out.append(t_seen - fetches[i][0])
    return reduce.p50(out)
