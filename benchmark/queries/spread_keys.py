"""Query check ``spread_keys``: a RESET check, and only that.
``/query/spread?model=<m>`` at the final version serves no row of a
closed window.

An entry of ``checks.queries`` names its ``path`` (the harness fetches it
once, after ``finalize()``) and the ``model`` it asks; the answer is the
ranked rows, ``{"model", "rows": [{"src_addr", "spread"}, ...]}``.

What the final snapshot covers: ``finalize()`` closes the open window by
force before it publishes for the last time (its rows are in the sink,
where the table kind ``ranked_spread`` checks them against exact distinct
counts), so the snapshot's window holds no flow and the rows have to be
none: the planes and the candidate table were reset with the close, and
no closed window is served as the open one. A surface that always
answered nothing would pass: whether the open window's answers are
RIGHT while flows arrive is checked by no file of this harness, which
fetches a fixed ``path`` once (ISSUE 47 asked for the open window's
``top_n`` sources by ``key=``; that needs a fetch before ``finalize()``:
PERF.md 7).

Returns the number of answers that differ: 1 for another model's answer,
else the rows served."""


def mismatches(run, con, q: dict) -> int:
    doc = run.final["queries"][q["name"]]
    if doc.get("model") != q["model"]:
        return 1
    return len(doc["rows"])
