"""Query check ``range_flows5m``: the answer of ``/query/range?model=
flows_5m`` at the final version, summed by key, equals the sink's rows of
the slots it names. Returns the number of groups that differ (an answer
that names no slot counts one)."""


def mismatches(run, con, q: dict) -> int:
    doc = run.final["queries"][q["name"]]
    got = {}
    for r in doc["rows"]:
        k = (int(r["timeslot"]), int(r["src_as"]), int(r["dst_as"]),
             int(r["etype"]))
        v = got.get(k, (0, 0, 0))
        got[k] = (v[0] + int(r["bytes"]), v[1] + int(r["packets"]),
                  v[2] + int(r["count"]))
    slots = {int(s) for s in doc["slots"]}
    want = {tuple(r[:4]): tuple(r[4:]) for r in con.execute(
        "SELECT timeslot, src_as, dst_as, etype, SUM(bytes), "
        "SUM(packets), SUM(count) FROM flows_5m GROUP BY 1, 2, 3, 4")
        if r[0] in slots}
    return (not slots) + sum(
        1 for k in set(want) | set(got) if want.get(k) != got.get(k))
