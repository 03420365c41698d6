"""Share of the device's busy time spent in the named Pallas kernels, by
kernel name (the compiled step's HLO metadata names each custom call)."""

import re


def read(ctx, kernels):
    trace = ctx["trace"]
    pats = [re.compile(rf"[/(]{re.escape(k)}\)*/pallas_call") for k in kernels]
    seconds = sum(t for path, t in trace["by_scope"].items()
                  if any(p.search(path) for p in pats))
    if seconds <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * seconds / trace["busy_s"]
