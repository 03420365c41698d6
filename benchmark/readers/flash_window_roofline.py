"""The banded causal flash kernels against their roofline: the least time
the chip could take for the score and value products of the (query, key)
pairs inside the band of every document in the traced steps (the family's
`window_attention_flops` of the steps' `window_pairs`: per pair, windowed
layer and query head 12 x head size, forward and backward, recomputation
not counted) over the kernels' time in the device trace, by kernel name.
The pairs are those the mathematics needs, not the tiles a kernel visits,
so the share reads the same work whatever implements the band. Operations
only (readers/kernel_roofline.py says why). None where the run carried no
such kernel, no count of the band's pairs, or the family's arithmetic has no
band."""

import re


def read(ctx, kernels):
    trace, rec = ctx["trace"], ctx["record"]["window"]
    pats = [re.compile(rf"[/(]{re.escape(k)}\)*/pallas_call") for k in kernels]
    seconds = sum(t for path, t in trace["by_scope"].items()
                  if any(p.search(path) for p in pats))
    if (seconds <= 0 or "window_pairs" not in rec
            or not hasattr(ctx["flops"], "window_attention_flops")):
        return None
    first = rec["traced_first_step"]
    traced = [str(s) for s in range(first, first + trace["steps"])]
    if not all(s in rec["window_pairs"] for s in traced):
        return None
    pairs = sum(rec["window_pairs"][s] for s in traced) / ctx["chips"]
    flops = ctx["flops"].window_attention_flops(ctx["cell"]["config"], pairs)
    least = ctx["flops"].roofline_seconds(flops, 0.0, ctx["peaks"])
    return 100.0 * least["seconds"] / seconds
