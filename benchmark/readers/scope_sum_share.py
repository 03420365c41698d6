"""Share of the device's busy time spent under any of several named scopes
of the program (self time, from the device trace), each a `/`-joined path
of scope components as the program nests them (`moe/dispatch`), plus the
operations whose whole `op_name` is one of `op_names` (kernels the compiler
names itself, outside the program's scopes: XLA:TPU's grouped matrix
products are `ragged-dot-none`). As readers/scope_under_share.py, a scope
is a whole component of the `op_name` path and a transform's wrapper
around it (`jvp(loss)`) does not hide it. None where the run carried none
of them."""

import re


def under(scope):
    parts = [re.escape(p) + r"\)*" for p in scope.split("/")]
    return re.compile(r"(?:^|[/(])" + "/".join(parts) + r"(?:/|$)")


def read(ctx, scopes, op_names=()):
    trace = ctx["trace"]
    by_scope = trace["by_scope"]
    if not any(by_scope) or trace["busy_s"] <= 0:
        return None
    pats = [under(s) for s in scopes]
    inside = sum(t for path, t in by_scope.items()
                 if path in op_names or any(p.search(path) for p in pats))
    if inside <= 0:
        return None
    return 100.0 * inside / trace["busy_s"]
