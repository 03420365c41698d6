"""Mean over the window's [perf] records (one per step) of the sum of the
named fields: host-loop phases on the program's own clock."""


def read(ctx, fields):
    records = ctx["record"]["window"]["perf"]
    rows = [sum(r[f] for f in fields) for r in records
            if all(f in r for f in fields)]
    if not rows:
        return None
    return sum(rows) / len(rows)
