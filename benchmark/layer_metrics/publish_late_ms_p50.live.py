"""How late a refresh publish starts against its cadence (the loop asks once
a batch, after its flush and checkpoint): monotonic now less last publish +
-serve.refresh; median over the window's refresh publishes. Source: the
program's snapshot_publish span [late_ms, reason = refresh]."""

import statistics

from benchmark import program_spans


def read(run):
    w = program_spans.window(run)
    late = [s[5]["late_ms"] for s in (w.named("snapshot_publish") if w
                                      else [])
            if s[5].get("reason") == "refresh" and "late_ms" in s[5]]
    return statistics.median(late) if late else None
