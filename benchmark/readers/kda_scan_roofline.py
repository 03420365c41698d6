"""The chunked gated delta-rule scan against its roofline: the least time
the chip could take for the operations and the HBM bytes the chunked
algorithm needs for the (token, KDA layer) pairs of the traced steps
(benchmark/harness/kimi_flops.py: `kda_scan_flops`, `kda_scan_bytes`,
forward and backward; the recomputed forward passes and the masked halves
of a chunk's triangles not counted) over the device time under the
program's `kda/scan` scope. The tokens are the program's own cumulative
counter (`kda_tokens` of the [perf] records: slots that are no padding, all
KDA layers; the record of step n counts through step n - 1). The bytes bound is used here, unlike in
readers/kernel_roofline.py: the scan's operands are (tokens, 4096) arrays of
a whole row, 134-268 MB each, which no on-chip memory holds. None where the
run carried no such scope or counter, or the family's arithmetic has no
scan."""

from benchmark.readers.scope_sum_share import under


def read(ctx):
    trace, rec = ctx["trace"], ctx["record"]["window"]
    scope = under("kda/scan")
    seconds = sum(t for path, t in trace["by_scope"].items()
                  if scope.search(path))
    if (seconds <= 0 or not trace["steps"]
            or not hasattr(ctx["flops"], "kda_scan_flops")):
        return None
    by_step = {r["step"]: r for r in rec["perf"]}
    first = rec["traced_first_step"]
    lo, hi = by_step.get(first), by_step.get(first + trace["steps"])
    if lo is None or hi is None or "kda_tokens" not in lo \
            or "kda_tokens" not in hi:
        return None
    tokens = (hi["kda_tokens"] - lo["kda_tokens"]) / ctx["chips"]
    cfg = ctx["cell"]["config"]
    least = ctx["flops"].roofline_seconds(
        ctx["flops"].kda_scan_flops(cfg, tokens),
        ctx["flops"].kda_scan_bytes(cfg, tokens), ctx["peaks"])
    return 100.0 * least["seconds"] / seconds
