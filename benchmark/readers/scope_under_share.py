"""Share of the device's busy time spent in operations under one named
scope of the program and under none of some others (self time, from the
device trace): what is left of `encoder` once `attention` and `mlp` are
taken out is the layer scan's own slicing and stacking.

A scope is a whole component of the operation's `op_name` path; a
transform's wrapper around it (`jvp(loss)`, `transpose(jvp(loss))`) does
not hide it."""

import re


def under(scope):
    return re.compile(rf"(?:^|[/(]){re.escape(scope)}\)*(?:/|$)")


def read(ctx, scope, outside):
    trace = ctx["trace"]
    if trace["busy_s"] <= 0:
        return None
    inside, outs = under(scope), [under(s) for s in outside]
    seconds = sum(t for path, t in trace["by_scope"].items()
                  if inside.search(path)
                  and not any(o.search(path) for o in outs))
    if seconds <= 0:
        return None
    return 100.0 * seconds / trace["busy_s"]
