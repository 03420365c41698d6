#!/usr/bin/env python
"""Long-context attention benchmark on the real chip.

The reference caps sequences at 512 (config/bert_pretraining_phase2_config
.json); long context is a first-class axis here, carried by two mechanisms:
the Pallas blockwise flash kernel on one chip (memory O(S) instead of the
O(S^2) score matrix) and ring attention over the `seq` mesh axis across
chips (ops/ring_attention.py, exercised on the virtual mesh by
__graft_entry__.dryrun_multichip stage 'ring_seq').

This script measures the single-chip half on hardware: fwd+bwd attention
throughput, flash vs XLA, across S in {512..8192} at BERT-Large head
geometry, and writes results/longcontext/longcontext.jsonl.

Usage: python scripts/longcontext_bench.py [--out results/longcontext]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def attention_flops(b: int, s: int, h: int, d: int) -> float:
    """Fwd+bwd matmul FLOPs: fwd QK^T + PV = 2 * 2*b*h*s*s*d; bwd ~2x fwd
    (dQ, dK, dV, and the recomputed/stored-prob products) = 4 dots."""
    fwd = 2 * 2 * b * h * s * s * d
    bwd = 2 * fwd
    return float(fwd + bwd)


def run_case(impl: str, b: int, s: int, h: int, d: int, steps: int = 20):
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.ops.attention import (dot_product_attention,
                                                make_attention_bias)

    rng = np.random.RandomState(0)
    shape = (b, s, h, d)
    q = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    k = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    v = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    bias = make_attention_bias(jnp.ones((b, s), jnp.int32), jnp.bfloat16)

    def loss(q, k, v):
        out = dot_product_attention(
            q, k, v, bias=bias, dropout_rng=None, dropout_rate=0.0,
            deterministic=True, impl=impl)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    grad_fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    # compile + warm
    val, grads = grad_fn(q, k, v)
    jax.block_until_ready(grads)
    t0 = time.perf_counter()
    for _ in range(steps):
        val, grads = grad_fn(q, k, v)
    jax.block_until_ready(grads)
    dt = (time.perf_counter() - t0) / steps
    tflops = attention_flops(b, s, h, d) / dt / 1e12
    return {"impl": impl, "batch": b, "seq": s, "heads": h, "head_dim": d,
            "ms_per_step": round(dt * 1e3, 3),
            "tflops_per_sec": round(tflops, 2),
            "value": float(val)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/longcontext")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seqs", type=int, nargs="+",
                    default=[512, 1024, 2048, 4096, 8192])
    ap.add_argument("--cpu", action="store_true",
                    help="machinery smoke test on the CPU backend, kernels "
                         "in interpret mode")
    args = ap.parse_args()

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # read when jax is imported
        os.environ["BPT_PALLAS_INTERPRET"] = "1"
    import jax

    dev = jax.devices()[0]
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, "longcontext.jsonl")
    records = []
    H, D = 16, 64  # BERT-Large head geometry
    # keep tokens-per-case roughly constant so every case does comparable
    # non-attention work; batch floors at 1
    for s in args.seqs:
        b = max(1, 8192 // s)
        for impl in ("pallas", "xla"):
            try:
                rec = run_case(impl, b, s, H, D, steps=args.steps)
            except Exception as e:  # OOM or lowering failure: record, go on
                rec = {"impl": impl, "batch": b, "seq": s,
                       "error": str(e)[:200]}
            rec["device"] = str(dev.device_kind)
            records.append(rec)
            print(json.dumps(rec))
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    ok = [r for r in records if "error" not in r]
    by = {}
    for r in ok:
        by.setdefault(r["seq"], {})[r["impl"]] = r
    print("\nseq  flash-TFLOP/s  xla-TFLOP/s  speedup")
    for s in sorted({r["seq"] for r in records}):
        d = by.get(s, {})
        flash = (f"{d['pallas']['tflops_per_sec']:12.1f}" if "pallas" in d
                 else f"{'FAILED':>12}")
        xla = (f"{d['xla']['tflops_per_sec']:11.1f}" if "xla" in d
               else f"{'FAILED':>11}")
        sp = (f"{d['pallas']['tflops_per_sec'] / max(d['xla']['tflops_per_sec'], 1e-9):6.2f}x"
              if "pallas" in d and "xla" in d else "")
        print(f"{s:5d}  {flash}  {xla}  {sp}")


if __name__ == "__main__":
    main()
