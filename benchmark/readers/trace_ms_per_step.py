"""A time of the reduced device trace (seconds over the traced steps, per
device), as milliseconds per step."""


def read(ctx, field):
    trace = ctx["trace"]
    if trace.get(field, 0.0) <= 0 or not trace["steps"]:
        return None
    return 1e3 * trace[field] / trace["steps"]
