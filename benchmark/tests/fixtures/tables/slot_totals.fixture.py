"""A table kind added as a file only: per timeslot, SUM(bytes) and
SUM(count) of a sink table against the reference's totals, exactly."""


def want(ref, entry: dict, sums: dict) -> dict:
    return {slot: (int(p[0].sum()), int(p[2].sum()))
            for slot, p in sums.items()}


def read_sink(con, entry: dict, run) -> dict:
    return {int(r[0]): (int(r[1]), int(r[2])) for r in con.execute(
        f"SELECT timeslot, SUM(bytes), SUM(count) FROM {entry['name']} "
        f"GROUP BY 1")}


def control(ref, entry: dict, sums: dict, run) -> dict:
    return want(ref, entry, sums)


def compare(entry: dict, wanted: dict, got: dict, n_flows: int) -> dict:
    bad = sum(1 for s in set(wanted) | set(got)
              if wanted.get(s) != got.get(s))
    return {"slot_totals_mismatched": (bad, 0)}
