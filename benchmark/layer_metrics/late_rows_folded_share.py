"""Share of the rows a sketch family applied that went to its held unit, in
per cent: rows that came after their unit (a ranked table's window, the
detector's sub-window) had rolled and were folded into it, held open for
-window.lateness. The larger of the two kinds of family (the detector's
10 s grain makes it the larger). Source: step_dispatch's rows, hh_unit and
dd_unit; a program whose step_dispatch does not say reads nothing."""

from benchmark import program_spans


def read(run):
    w = program_spans.window(run)
    steps = [s[5] for s in w.named("step_dispatch")] if w else []
    shares = []
    for key in ("hh_unit", "dd_unit"):
        applied = sum(s["rows"] for s in steps
                      if s.get(key) in ("open", "held"))
        if applied:
            shares.append(100.0 * sum(
                s["rows"] for s in steps if s.get(key) == "held") / applied)
    return max(shares) if shares else None
