"""A kernel's share of its roofline: the least time the chip could take for
the work the algorithm needs in the traced steps (benchmark/harness/
flops.py; recomputation not counted) over the kernel's time in the device
trace, by kernel name.

Only a bound that holds is used. The operations bound always holds. The
HBM-bytes bound does not on this chip for operands of these sizes: XLA keeps
a micro-batch's activations (16.8 MB) in the TensorCore's 128 MiB VMEM
(memory space S(1) in the compiled step), and the LayerNorm kernels were
measured at 840-1150 GB/s against HBM's 819 GB/s (my chip run, PR 23). So
`attention` work counts operations only, and there is no roofline metric
for a kernel that is bound by bytes (PERF.md, Open questions)."""

import re


def _needs(ctx, work, steps):
    cell, rec, flops = ctx["cell"], ctx["record"]["window"], ctx["flops"]
    cfg = cell["config"]
    e, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    first = rec["traced_first_step"]
    traced = [str(s) for s in range(first, first + steps)]
    if work == "attention":
        if not all(s in rec["doc_len_sq"] for s in traced):
            return None
        chips = ctx["chips"]
        sq = sum(rec["doc_len_sq"][s] for s in traced) / chips
        return flops.attention_flops(e, n, sq), 0.0
    raise ValueError(f"unknown work {work!r}")


def read(ctx, kernels, work):
    trace = ctx["trace"]
    pats = [re.compile(rf"[/(]{re.escape(k)}\)*/pallas_call") for k in kernels]
    seconds = sum(t for path, t in trace["by_scope"].items()
                  if any(p.search(path) for p in pats))
    if seconds <= 0:
        return None
    needs = _needs(ctx, work, trace["steps"])
    if needs is None:
        return None
    least = ctx["flops"].roofline_seconds(needs[0], needs[1], ctx["peaks"])
    return 100.0 * least["seconds"] / seconds
