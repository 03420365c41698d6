"""Share of the device's busy time spent in operations that belong to no
entry of the program's list of step scopes (self time, from the device
trace): operations whose `op_name` path has none of the scopes as a
component, and operations the compiler made with no `op_name` at all.
`scopes` is a copy of the program's list (training/pretrain.STEP_SCOPES);
a test of the program holds the two equal. None where the run carried no
scopes at all."""

import re


def read(ctx, scopes):
    trace = ctx["trace"]
    by_scope = trace["by_scope"]
    if not any(by_scope) or trace["busy_s"] <= 0:
        return None
    known = re.compile(
        r"(?:^|[/(])(?:" + "|".join(map(re.escape, scopes)) + r")\)*(?:/|$)")
    seconds = sum(t for path, t in by_scope.items()
                  if not known.search(path))
    return 100.0 * seconds / trace["busy_s"]
