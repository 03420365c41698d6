"""The routed experts' grouped matrix products against their roofline: the
least time the chip could take for the operations the routed (token, held
expert) pairs of the traced steps need (benchmark/harness/lm_flops.py:
6 x pairs x 3 x hidden x width a layer, forward and backward, recomputation
not counted) over the device time under the program's `moe/experts` scope
plus that of the operations whose whole `op_name` is one of `op_names`
(XLA:TPU names its grouped-product kernels itself, `ragged-dot-none`, and
drops the program's scope from them).
The pairs are the program's own cumulative counters (`moe_l<L>_pairs` of the
[perf] records: the record of step n counts through step n - 1). Operations
only: the bytes bound is not used (readers/kernel_roofline.py says why).
None where the run carried no such scope or counter."""

import re

from benchmark.readers.scope_sum_share import under


def read(ctx, op_names=()):
    trace, rec = ctx["trace"], ctx["record"]["window"]
    scope = under("moe/experts")
    seconds = sum(t for path, t in trace["by_scope"].items()
                  if path in op_names or scope.search(path))
    if seconds <= 0 or not trace["steps"]:
        return None
    by_step = {r["step"]: r for r in rec["perf"]}
    first = rec["traced_first_step"]
    lo, hi = by_step.get(first), by_step.get(first + trace["steps"])
    if lo is None or hi is None:
        return None
    keys = [k for k in hi if re.match(r"^moe_l\d+_pairs$", k)]
    if not keys or not all(k in lo for k in keys):
        return None
    pairs = sum(hi[k] - lo[k] for k in keys) / ctx["chips"]
    flops = ctx["flops"].moe_expert_flops(ctx["cell"]["config"], pairs)
    least = ctx["flops"].roofline_seconds(flops, 0.0, ctx["peaks"])
    return 100.0 * least["seconds"] / seconds
