#!/usr/bin/env python
"""Pallas kernels vs their plain-XLA references, on whatever backend JAX has.

The interpret-mode tests (tests/test_pallas.py, tests/test_packing.py) prove
the kernels' arithmetic and tests/test_tpu_compile.py proves the TPU compiler
accepts them; neither shows that what Mosaic GENERATED computes the same
numbers. This runs the main path's kernels for real on the attached device at
the BERT-Large head geometry (bf16 operands, the production dtype) and
compares each against the repo's XLA reference of the same math, evaluated in
f32 at highest matmul precision:

- flash attention forward + backward, in every kernel variant a shape can
  select (both layouts; fused and split backward), with a padding bias,
  with packed segment ids, and with dropout (against a mirror that applies
  the IDENTICAL counter-hash keep mask, as tests/test_pallas.py does);
- the banded causal kernels (`flash_win_fwd`, `flash_win_bwd_dq`,
  `flash_win_bwd_dkv`) at the two cells' shapes, rows of 16,384 and heads of
  128: 64 query heads over 8 under a band of 512 on packed rows (laguna),
  28 over 4 under a band of 4,096 on full rows (smallthinker), forward and
  the three gradients against float32 attention taken a block of queries
  at a time over the keys its band reaches (`--band_seq`; 0 leaves it out);
- LayerNorm and fused residual+dropout+LayerNorm, forward + backward
  (the XLA fallbacks in ops/layernorm.py share the kernels' dropout hash);
- the gated delta-rule recurrence's kernels (ops/pallas/kda.py: `kda_fwd`,
  `kda_bwd`) through `ops/kda.kda_scan` at the published widths (heads of
  128, chunks of 64, 32 a block), forward and its five gradients, over a row
  of documents, against the XLA scans of the same call in f32.

    python tools/kernel_parity.py            # exit 0 = all within tolerance
    python tools/kernel_parity.py --seq 256 --heads 4   # a quicker shape

Prints one line per check (`max_err` is max |kernel - ref| over max |ref|)
and exits 1 if any exceeds its tolerance. On a non-TPU backend the kernels
run in interpret mode — a machinery check, not a statement about Mosaic.
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

B, S, H, D = 2, 512, 16, 64     # BERT-Large heads, phase-2 length (defaults)
RATE = 0.1
# bf16 operands: probs are rounded to bf16 (2^-8 relative) before the PV
# matmul, so kernel-vs-f32-reference errors sit near 1e-2 of the output
# scale; a wrong head slice or mask is O(1)
FWD_TOL, BWD_TOL = 3e-2, 5e-2
# the banded check's cells: (name, query heads, key/value heads, band, packed)
BAND_S, BAND_D = 16384, 128
BAND_CASES = [("laguna", 64, 8, 512, True),
              ("smallthinker", 28, 4, 4096, False)]
# the KDA check's row is KDA_ROW x S tokens of 2 H heads of KDA_D: at the
# defaults 4,096 tokens (two blocks of 32 chunks) of 32 heads of 128
KDA_ROW, KDA_D, KDA_CHUNK, KDA_BLOCK = 8, 128, 64, 32


def _rel_err(got, want) -> float:
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


def _attention_reference(q, k, v, bias, seg, keep, rate):
    """Dense f32 attention with the kernels' conventions: additive bias,
    block-diagonal segment mask, dropout on normalized probs through a
    GIVEN keep mask, pad (segment-0) rows zeroed."""
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.ops.attention import make_segment_attention_bias

    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(q.shape[-1])
    if bias is not None:
        sc = sc + bias.astype(jnp.float32)
    if seg is not None:
        sc = sc + make_segment_attention_bias(seg)
    p = jax.nn.softmax(sc, axis=-1)
    if keep is not None:
        p = jnp.where(keep, p / (1.0 - rate), 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    if seg is not None:
        out = out * (seg > 0).astype(out.dtype)[:, :, None, None]
    return out


def check_flash(fa, interpret: bool, report) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, S, H, D) * 0.5, jnp.bfloat16)
               for _ in range(3))
    mask = np.ones((B, S), np.float32)
    mask[:, S - S // 14:] = 0
    bias = jnp.asarray((1.0 - mask) * -10000.0)[:, None, None, :]
    seg_np = np.zeros((B, S), np.int32)     # 3 packed segments + pad tail
    for b in range(B):
        cuts = [0, S * (15 + 2 * b) // 51, S * 33 // 51, S * 47 // 51]
        for i in range(3):
            seg_np[b, cuts[i]:cuts[i + 1]] = i + 1
    seg = jnp.asarray(seg_np)
    valid = jnp.asarray(seg_np > 0)[:, :, None, None]
    seed = jnp.asarray(7, jnp.int32)
    keep = jnp.stack([jnp.stack([
        fa._keep_mask(seed, b * H + h, 0, 0, S, S, RATE)
        for h in range(H)]) for b in range(B)])

    cases = [("bias", bias, None, None), ("bias+dropout", bias, None, keep),
             ("segments", None, seg, None),
             ("segments+dropout", None, seg, keep)]
    heads_per_prog, max_panel = fa._heads_per_prog, fa._FUSED_BWD_MAX_PANEL
    # (layout, backward): what _use_native / _FUSED_BWD_MAX_PANEL select
    # for BERT shapes up to S=1024, for S=2048, and beyond — steered onto
    # this one small shape the way the tests' force_flash_path does
    variants = [("native", "fused"), ("bh", "fused"), ("bh", "split")]
    try:
        for layout, bwd in variants:
            fa._heads_per_prog = (heads_per_prog if layout == "native"
                                  else lambda h, d: 0)
            fa._FUSED_BWD_MAX_PANEL = max_panel if bwd == "fused" else 0
            for name, c_bias, c_seg, c_keep in cases:
                rate = RATE if c_keep is not None else 0.0

                def kernel(q, k, v):
                    out = fa.flash_attention(
                        q, k, v, c_bias, c_seg,
                        seed if c_keep is not None else None, rate,
                        interpret)
                    if c_seg is not None:
                        out = jnp.where(valid, out, 0)
                    return out

                def ref(q, k, v):
                    return _attention_reference(q, k, v, c_bias, c_seg,
                                                c_keep, rate)

                def loss(fn):
                    return lambda q, k, v: jnp.sum(
                        fn(q, k, v).astype(jnp.float32) ** 2)

                with jax.default_matmul_precision("highest"):
                    want = ref(q, k, v)
                    want_g = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
                got = jax.jit(kernel)(q, k, v)
                got_g = jax.jit(jax.grad(loss(kernel),
                                         argnums=(0, 1, 2)))(q, k, v)
                tag = f"flash[{layout},{bwd}] {name}"
                report(f"{tag} fwd", _rel_err(got, want), FWD_TOL)
                for which, g, w in zip("qkv", got_g, want_g):
                    report(f"{tag} d{which}", _rel_err(g, w), BWD_TOL)
    finally:
        fa._heads_per_prog = heads_per_prog
        fa._FUSED_BWD_MAX_PANEL = max_panel


def _band_reference(q, k, v, seg, window: int, chunk: int):
    """Float32 causal attention under a band of `window` positions, `chunk`
    queries at a time against the `chunk` keys of their own block and the
    blocks before it that the band reaches (the (S, S) scores of a row of
    16,384 are 1 GiB a head); each block rematerialised in the backward
    pass. seg: (B, S) packing segments or None."""
    import jax
    import jax.numpy as jnp

    b, s, h, d = q.shape
    hkv = k.shape[2]
    back = min(-(-(window - 1) // chunk) * chunk, s)
    q = q.astype(jnp.float32).reshape(b, s // chunk, chunk, hkv, h // hkv, d)
    front = lambda x, fill: jnp.pad(  # noqa: E731
        x, [(0, 0), (back, 0)] + [(0, 0)] * (x.ndim - 2),
        constant_values=fill)
    k, v = (front(x.astype(jnp.float32), 0.0) for x in (k, v))
    seg = jnp.ones((b, s), jnp.int32) if seg is None else seg
    segk = front(seg, -1)
    dist = (back + jnp.arange(chunk))[:, None] - jnp.arange(back + chunk)

    @jax.checkpoint
    def block(i):
        at = i * chunk
        kb = jax.lax.dynamic_slice_in_dim(k, at, back + chunk, 1)
        vb = jax.lax.dynamic_slice_in_dim(v, at, back + chunk, 1)
        sk = jax.lax.dynamic_slice_in_dim(segk, at, back + chunk, 1)
        sq = jax.lax.dynamic_slice_in_dim(seg, at, chunk, 1)
        sc = jnp.einsum("bqngd,bknd->bngqk", q[:, i], kb) / jnp.sqrt(d)
        ok = ((dist >= 0) & (dist < window))[None] \
            & (sq[:, :, None] == sk[:, None, :]) & (sq[:, :, None] > 0)
        sc = jnp.where(ok[:, None, None], sc, -1e30)
        p = jnp.where(ok[:, None, None], jax.nn.softmax(sc, axis=-1), 0.0)
        return jnp.einsum("bngqk,bknd->bqngd", p, vb)

    out = jax.lax.map(block, jnp.arange(s // chunk))    # (n, B, chunk, ...)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def check_flash_band(fa, interpret: bool, report, s: int) -> None:
    """The banded kernels at the cells' head shapes over one row of `s`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(4)
    seg_np = np.zeros((1, s), np.int32)     # lognormal documents, a pad tail
    at, doc = 0, 1
    while at < s - s // 64:
        n = int(min(s - s // 64 - at, max(8, rng.lognormal(6.5, 1.3))))
        seg_np[0, at:at + n] = doc
        at, doc = at + n, doc + 1
    for name, h, hkv, window, packed in BAND_CASES:
        blk = fa._pick_block(s, fa.DEFAULT_BLK_Q)
        nb = fa._band_steps(s, blk, fa._pick_block(s, fa.DEFAULT_BLK_K),
                            window)
        q, k, v, w = (jnp.asarray(rng.randn(1, s, n, BAND_D) * 0.5,
                                  jnp.bfloat16) for n in (h, hkv, hkv, h))
        seg = jnp.asarray(seg_np) if packed else None
        valid = (jnp.asarray(seg_np > 0)[:, :, None, None] if packed
                 else jnp.ones((1, s, 1, 1), bool))

        def kernel(q, k, v):
            return fa.flash_attention(q, k, v, None, seg, None, 0.0,
                                      interpret, True, window)

        def ref(q, k, v):
            return _band_reference(q, k, v, seg, window, blk)

        def both(fn):
            def loss(q, k, v):
                out = jnp.where(valid, fn(q, k, v).astype(jnp.float32), 0)
                return jnp.sum(out * w.astype(jnp.float32)), out
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                              has_aux=True))

        (_, got), got_g = both(kernel)(q, k, v)
        with jax.default_matmul_precision("highest"):
            (_, want), want_g = both(ref)(q, k, v)
        tag = (f"flash_win[{name}: {h}/{hkv} heads, band {window}, "
               f"{nb or 'panel'} steps]")
        report(f"{tag} fwd", _rel_err(got, want), FWD_TOL)
        for which, g, wg in zip("qkv", got_g, want_g):
            report(f"{tag} d{which}", _rel_err(g, wg), BWD_TOL)


def check_layernorm(interpret: bool, report) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bert_pytorch_tpu.ops import layernorm as ln
    from bert_pytorch_tpu.ops.pallas.layernorm import (
        add_dropout_layer_norm_pallas, layer_norm_pallas)

    E = H * D
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(B, S, E), jnp.bfloat16)
    res = jnp.asarray(rng.randn(B, S, E), jnp.bfloat16)
    scale = jnp.asarray(1.0 + 0.1 * rng.randn(E), jnp.float32)
    bias = jnp.asarray(0.1 * rng.randn(E), jnp.float32)
    seed = jnp.asarray(11, jnp.int32)

    def f32(fn):
        # the references see the same bf16 values, computed in f32
        return lambda x, *a: fn(x.astype(jnp.float32), *a)

    pairs = [
        ("layernorm",
         lambda x, s, b: layer_norm_pallas(x, s, b, 1e-12, interpret),
         f32(lambda x, s, b: ln._layer_norm_xla(x, s, b, 1e-12)),
         (x, scale, bias)),
        ("add_dropout_layernorm",
         lambda x, r, s, b: add_dropout_layer_norm_pallas(
             x, r, s, b, seed, RATE, 1e-12, interpret),
         f32(lambda x, r, s, b: ln._add_dropout_layer_norm_xla(
             x, r.astype(jnp.float32), s, b, seed, RATE, 1e-12)),
         (x, res, scale, bias)),
    ]
    for name, kernel, ref, args in pairs:
        argnums = tuple(range(len(args)))

        def loss(fn):
            return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)

        report(f"{name} fwd",
               _rel_err(jax.jit(kernel)(*args), ref(*args)), FWD_TOL)
        got_g = jax.jit(jax.grad(loss(kernel), argnums=argnums))(*args)
        want_g = jax.grad(loss(ref), argnums=argnums)(*args)
        for i, (g, w) in enumerate(zip(got_g, want_g)):
            report(f"{name} d_arg{i}", _rel_err(g, w), BWD_TOL)


def check_kda(interpret: bool, report) -> None:
    """`kda_scan` with the chunks of a block walked by the kernels, bf16
    products, against the same call on the XLA scans with float32 products
    at highest precision: a row of four documents and a padded tail."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bert_pytorch_tpu.ops import kda

    s, h, d = KDA_ROW * S, 2 * H, KDA_D
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = (unit(jax.random.normal(keys[0], (B, s, h, d))) * d ** -0.5)
    k = unit(jax.random.normal(keys[1], (B, s, h, d)))
    v = jax.random.normal(keys[2], (B, s, h, d))
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    g = -0.1 * jax.nn.softplus(jax.random.normal(keys[3], (B, s, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (B, s, h)))
    weight = jax.random.normal(keys[5], (B, s, h, d))
    starts = np.zeros((B, s), bool)
    starts[:, [0, 37, s // 3, s // 2 + 5]] = True
    starts[:, s - s // 50:] = True          # padding: one-slot documents
    starts = jnp.asarray(starts)

    def loss(mm_dtype):
        def fn(q, k, v, g, beta):
            out = kda.kda_scan(q, k, v, g, beta, starts, chunk=KDA_CHUNK,
                               block=KDA_BLOCK, mm_dtype=mm_dtype)
            return jnp.sum(weight * out), out
        return jax.jit(jax.value_and_grad(fn, argnums=range(5),
                                          has_aux=True))

    mode = kda.kernel_mode
    try:
        kda.kernel_mode = lambda *a: interpret      # the kernels
        (_, got), got_g = loss(jnp.bfloat16)(q, k, v, g, beta)
        kda.kernel_mode = lambda *a: None           # the XLA scans
        with jax.default_matmul_precision("highest"):
            (_, want), want_g = loss(jnp.float32)(
                *(x.astype(jnp.float32) for x in (q, k, v)), g, beta)
    finally:
        kda.kernel_mode = mode
    report("kda fwd", _rel_err(got, want), FWD_TOL)
    for which, a, w in zip(("q", "k", "v", "g", "beta"), got_g, want_g):
        report(f"kda d{which}", _rel_err(a, w), BWD_TOL)


def main(argv=None) -> int:
    global B, S, H
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=B)
    ap.add_argument("--seq", type=int, default=S,
                    help="multiple of 128, >= 512 keeps the packed layout "
                         "of the segment cases meaningful; smaller is for "
                         "interpret-mode rehearsals")
    ap.add_argument("--heads", type=int, default=H, help="of width 64")
    ap.add_argument("--band_seq", type=int, default=BAND_S,
                    help="row of the banded kernels' check (0: leave it "
                         "out; a few blocks for an interpret-mode "
                         "rehearsal)")
    args = ap.parse_args(argv)
    B, S, H = args.batch, args.seq, args.heads

    import jax

    from bert_pytorch_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    fa = importlib.import_module(
        "bert_pytorch_tpu.ops.pallas.flash_attention")
    dev = jax.devices()[0]
    interpret = dev.platform != "tpu"
    print(f"kernel_parity: platform={dev.platform} kind={dev.device_kind} "
          f"interpret={interpret} shape B{B} S{S} H{H} D{D} bf16")
    failures = []

    def report(name: str, err: float, tol: float) -> None:
        ok = err <= tol     # False for NaN too
        print(f"kernel_parity: {'ok  ' if ok else 'FAIL'} {name}: "
              f"max_err {err:.3e} (tol {tol:.0e})", flush=True)
        if not ok:
            failures.append(name)

    check_flash(fa, interpret, report)
    if args.band_seq:
        check_flash_band(fa, interpret, report, args.band_seq)
    check_layernorm(interpret, report)
    check_kda(interpret, report)
    if failures:
        print(f"kernel_parity: {len(failures)} check(s) FAILED: "
              + ", ".join(failures))
        return 1
    print("kernel_parity: all checks within tolerance")
    return 0


if __name__ == "__main__":
    sys.exit(main())
