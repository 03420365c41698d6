"""One counter of the program over another, in percent, as the window's last
[perf] record has both (the compiled step's peak over the device's memory).
None where either is missing or the divisor is 0."""


def read(ctx, field, over):
    records = ctx["record"]["window"]["perf"]
    if not records:
        return None
    last = records[-1]
    if field not in last or not last.get(over):
        return None
    return 100.0 * last[field] / last[over]
