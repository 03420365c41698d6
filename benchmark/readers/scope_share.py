"""Share of the device's busy time spent in operations under one
jax.named_scope of the program (self time, from the device trace; the scope
of an operation comes from the compiled step's HLO metadata)."""


def read(ctx, scope):
    trace = ctx["trace"]
    by_scope = trace["by_scope"]
    if not any(by_scope) or trace["busy_s"] <= 0:
        return None                 # the run carried no scopes
    inside = sum(t for path, t in by_scope.items()
                 if f"/{scope}/" in path or path.endswith("/" + scope))
    if inside <= 0:
        return None
    return 100.0 * inside / trace["busy_s"]
