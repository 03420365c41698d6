"""The routed layers' imbalance: over the layers, the largest ratio of the
most loaded held expert's tokens to the held experts' mean, from the
program's cumulative counters (`moe_l<L>_load_max`, `moe_l<L>_load_mean`)
as the window's last [perf] record has them. None where the program counts
no expert load."""

import re


def read(ctx):
    records = ctx["record"]["window"]["perf"]
    if not records:
        return None
    last = records[-1]
    ratios = [last[k] / last[k.replace("_load_max", "_load_mean")]
              for k in last if re.match(r"^moe_l\d+_load_max$", k)
              and last.get(k.replace("_load_max", "_load_mean"), 0) > 0]
    return max(ratios) if ratios else None
