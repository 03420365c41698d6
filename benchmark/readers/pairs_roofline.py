"""Kernels whose work is counted in (query, key) pairs, against their
roofline: the least time the chip could take for what the mathematics needs
of the traced steps' pairs, over the kernels' time in the device trace, by
kernel name. `pairs` names the window's count (`selected_pairs`: the
harness's own sum of min(topk, position + 1) over the real tokens;
`causal_pairs`: the documents' causal triangles), `flops` the family's
function of (configuration, pairs) for the operations, `bytes` (optional)
its function of (configuration, token slots) for the bytes the kernels
cannot avoid; where both are given the larger bound is the one used.
Recomputation is not counted, nor the tiles a kernel visits for one needed
pair in them. None where the run carried no such kernel, no such count, or
a family whose arithmetic has no such function."""

import re


def read(ctx, kernels, pairs, flops, bytes=None):
    trace, rec = ctx["trace"], ctx["record"]["window"]
    family = ctx["flops"]
    pats = [re.compile(rf"[/(]{re.escape(k)}\)*/pallas_call") for k in kernels]
    seconds = sum(t for path, t in trace["by_scope"].items()
                  if any(p.search(path) for p in pats))
    needed = [n for n in (flops, bytes) if n]
    if (seconds <= 0 or pairs not in rec
            or not all(hasattr(family, n) for n in needed)):
        return None
    first = rec["traced_first_step"]
    traced = [str(s) for s in range(first, first + trace["steps"])]
    if not all(s in rec[pairs] for s in traced):
        return None
    cfg, chips = ctx["cell"]["config"], ctx["chips"]
    count = sum(rec[pairs][s] for s in traced) / chips
    moved = 0.0
    if bytes:
        slots = rec["slot_tokens"] / rec["steps"] * trace["steps"] / chips
        moved = getattr(family, bytes)(cfg, slots)
    least = family.roofline_seconds(getattr(family, flops)(cfg, count), moved,
                                    ctx["peaks"])
    return 100.0 * least["seconds"] / seconds
