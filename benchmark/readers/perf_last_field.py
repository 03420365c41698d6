"""A cumulative counter of the program, as the window's last [perf] record
has it (compile seconds, compiles, cache hits)."""


def read(ctx, field):
    records = ctx["record"]["window"]["perf"]
    if not records or field not in records[-1]:
        return None
    return records[-1][field]
