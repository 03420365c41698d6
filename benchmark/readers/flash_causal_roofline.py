"""The causal grouped-head flash kernels against their roofline: the least
time the chip could take for the score and value products of the causal
triangle of every document in the traced steps (benchmark/harness/
lm_flops.py: 12 x attention layers x query heads x head size per (query,
key <= query) pair, forward and backward, recomputation not counted) over
the kernels' time in the device trace, by kernel name. Operations only
(readers/kernel_roofline.py says why). None where the run carried no such
kernel or no count of the documents' pairs."""

import re


def read(ctx, kernels):
    trace, rec = ctx["trace"], ctx["record"]["window"]
    pats = [re.compile(rf"[/(]{re.escape(k)}\)*/pallas_call") for k in kernels]
    seconds = sum(t for path, t in trace["by_scope"].items()
                  if any(p.search(path) for p in pats))
    if seconds <= 0 or "causal_pairs" not in rec:
        return None
    first = rec["traced_first_step"]
    traced = [str(s) for s in range(first, first + trace["steps"])]
    if not all(s in rec["causal_pairs"] for s in traced):
        return None
    pairs = sum(rec["causal_pairs"][s] for s in traced) / ctx["chips"]
    flops = ctx["flops"].causal_attention_flops(ctx["cell"]["config"], pairs)
    least = ctx["flops"].roofline_seconds(flops, 0.0, ctx["peaks"])
    return 100.0 * least["seconds"] / seconds
