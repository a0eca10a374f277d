"""Share of the rows a sketch family applied that went to its held unit, in
per cent: rows that came after their unit (a ranked table's window, the
detector's sub-window) had rolled and were folded into it, held open for
-window.lateness. The larger of the two kinds of family (the detector's
10 s grain makes it the larger). The tables take a step_dispatch's rows
into hh_unit. The detector takes its dd_rows into dd_unit (the run's
newest sub-window, which rides the fused step) and, for each older
sub-window of the run, the rows of a detector_dispatch into that span's
dd_unit (its own program alone). Over the window's spans, where the
program's gauges late_flows_folded{model=...} count the whole run. Source:
the two spans; a program whose step_dispatch does not say reads nothing."""

from benchmark import program_spans

APPLIED = ("open", "held")


def read(run):
    w = program_spans.window(run)
    if not w:
        return None
    steps = [s[5] for s in w.named("step_dispatch")]
    # (rows, the unit they went to) a dispatch, by kind of family
    tables = [(s["rows"], s.get("hh_unit")) for s in steps]
    detector = [(s.get("dd_rows", 0), s.get("dd_unit")) for s in steps] + [
        (s[5]["rows"], s[5].get("dd_unit"))
        for s in w.named("detector_dispatch")]
    shares = []
    for took in (tables, detector):
        applied = sum(n for n, unit in took if unit in APPLIED)
        if applied:
            shares.append(100.0 * sum(n for n, unit in took
                                      if unit == "held") / applied)
    return max(shares) if shares else None
