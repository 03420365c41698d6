"""Fused multi-tensor LAMB update — the apex amp_C analogue.

Reference mapping (MIGRATION.md): apex `FusedLAMB` runs
`multi_tensor_applier` over chunked flat buckets with two CUDA kernels —
`multi_tensor_lamb` stage1 (Adam moments + update direction) and stage2
(trust-ratio apply). This module is the TPU-shaped equivalent: parameter
leaves flatten into deterministic size-capped buckets (the same greedy
assignment parallel/coalesce._bucketize uses for the norm reductions) and
each bucket runs ONE launch per stage, bounding the update to O(buckets)
kernels/fusions instead of O(leaves) — the long tail of small leaves
(biases, LayerNorm scales) rides inside the big buckets for free.

Both stages are PURELY elementwise; the trust-ratio NORMS between them
stay in optim/lamb.py's existing path (per-tensor or the bucketed
parallel/coalesce.NormReducer) so the reduction grouping is untouched.

Numerics contract (pinned in tests/test_pallas.py):

- The XLA fallback (`impl="xla"`, auto-selected off-TPU) evaluates the
  SAME `_stage1_math` body PER LEAF with the same scalar/constant
  producers as optim/lamb.py's unfused chain — structurally the same
  expressions, so `fused=True` off-TPU is bit-identical to
  `fused=False`.
- The Pallas kernel traces the identical math body on flat buckets.
  Between two separately COMPILED XLA programs, mul-add chains are not
  bitwise-stable on CPU — XLA/LLVM is free to contract `a*b + c*d` into
  an FMA (or factor shared operands) differently per program, a ±few-ulp
  ambiguity we measured even between interpret-mode Pallas and a
  straight-line trace of the same jaxpr. The kernel is therefore gated
  against the fallback at a few-ulp tolerance for stage1 and EXACTLY for
  stage2 (a single multiply admits no rewrite). On TPU only the Mosaic
  kernel runs, so no dual-program ambiguity exists in production.

On CPU the Pallas path runs in interpret mode so the test suite
exercises the same kernel code (repo convention, see layernorm.py).

ZeRO-1 sharded state: pass `mesh` + per-leaf `specs` (a NormReducer
carries both, derived from the plan's grad/shard layout) and each bucket
stage wraps in shard_map — local flatten/concat, zero collectives, out
under the same specs. Without specs, bucketing GSPMD-sharded leaves would
force gather/reshard traffic at the concat; values would still match.
"""

from __future__ import annotations

import functools
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec

from bert_pytorch_tpu.parallel.coalesce import DEFAULT_BUCKET_BYTES, _bucketize

ROWS = 256   # rows per grid step
LANES = 128  # lane width; flat buckets pad to (ROWS, LANES) tiles


def select_impl(impl: str = "auto") -> str:
    """'pallas' on TPU backends, 'xla' elsewhere; explicit values pass
    through (tests force 'pallas' to run the interpret-mode kernel on
    CPU)."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"impl must be auto|pallas|xla, got {impl!r}")
    return impl


# ---------------------------------------------------------------------------
# stage kernels — one math body, two dispatchers
# ---------------------------------------------------------------------------


def _stage1_math(g, mu, nu, pf, wd, denom, c1, c2, *, b1, b2, eps):
    """apex multi_tensor_lamb stage1: pre-normalized grad -> Adam moments
    -> update direction u (+ decoupled weight decay). One definition,
    traced identically by the Pallas kernel and the XLA fallback."""
    gn = g / denom
    mu = b1 * mu + (1 - b1) * gn
    nu = b2 * nu + (1 - b2) * jnp.square(gn)
    u = (mu / c1) / (jnp.sqrt(nu / c2) + eps) + wd * pf
    return mu, nu, u


def _stage1_kernel(scal_ref, g_ref, mu_ref, nu_ref, pf_ref, wd_ref,
                   mu_out, nu_out, u_out, *, b1, b2, eps):
    mu, nu, u = _stage1_math(
        g_ref[:], mu_ref[:], nu_ref[:], pf_ref[:], wd_ref[:],
        scal_ref[0, 0], scal_ref[0, 1], scal_ref[0, 2],
        b1=b1, b2=b2, eps=eps)
    mu_out[:] = mu
    nu_out[:] = nu
    u_out[:] = u


def _stage2_kernel(t_ref, u_ref, out_ref):
    # apex multi_tensor_lamb stage2: p -= lr*ratio*u, with t = -lr*ratio
    # precomputed per leaf and broadcast by the caller
    out_ref[:] = t_ref[:] * u_ref[:]


def _to_blocks(vec):
    """Pad a flat f32 vector to whole (ROWS, LANES) tiles and reshape to
    rows; returns (rows, original length). Zero padding is inert through
    both stages (u(0,...)=0/eps=0) and sliced off after the launch."""
    n = vec.shape[0]
    pad = (-n) % (ROWS * LANES)
    if pad:
        vec = jnp.concatenate([vec, jnp.zeros((pad,), vec.dtype)])
    return vec.reshape(-1, LANES), n


def _blk():
    return pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))


def _stage1_flat(scal, g, mu, nu, pf, wd, *, b1, b2, eps, use_pallas):
    if not use_pallas:
        return _stage1_math(g, mu, nu, pf, wd,
                            scal[0, 0], scal[0, 1], scal[0, 2],
                            b1=b1, b2=b2, eps=eps)
    g2, n = _to_blocks(g)
    mu2, _ = _to_blocks(mu)
    nu2, _ = _to_blocks(nu)
    pf2, _ = _to_blocks(pf)
    wd2, _ = _to_blocks(wd)
    Rp = g2.shape[0]
    mu3, nu3, u3 = pl.pallas_call(
        functools.partial(_stage1_kernel, b1=b1, b2=b2, eps=eps),
        grid=(Rp // ROWS,),
        in_specs=[
            pl.BlockSpec((1, 3), lambda i: (0, 0)),  # denom, c1, c2
            _blk(), _blk(), _blk(), _blk(), _blk(),
        ],
        out_specs=[_blk(), _blk(), _blk()],
        out_shape=[jax.ShapeDtypeStruct((Rp, LANES), jnp.float32)] * 3,
        name="lamb_stage1",
        interpret=jax.default_backend() != "tpu",
    )(scal, g2, mu2, nu2, pf2, wd2)
    return (mu3.reshape(-1)[:n], nu3.reshape(-1)[:n], u3.reshape(-1)[:n])


def _stage2_flat(t, u, *, use_pallas):
    if not use_pallas:
        return t * u
    t2, n = _to_blocks(t)
    u2, _ = _to_blocks(u)
    Rp = t2.shape[0]
    out = pl.pallas_call(
        _stage2_kernel,
        grid=(Rp // ROWS,),
        in_specs=[_blk(), _blk()],
        out_specs=_blk(),
        out_shape=jax.ShapeDtypeStruct((Rp, LANES), jnp.float32),
        name="lamb_stage2",
        interpret=jax.default_backend() != "tpu",
    )(t2, u2)
    return out.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# bucketed multi-tensor drivers
# ---------------------------------------------------------------------------


def _leaf_spec(s):
    return getattr(s, "spec", s)


def _maybe_shard_map(fn, mesh, specs, idxs, n_groups, outs_per_leaf):
    """Wrap a bucket fn in shard_map when a layout is given: scalar block
    replicated, every tensor group under its leaf's spec, outputs under
    the same specs (elementwise -> zero collectives inside)."""
    if mesh is None or specs is None:
        return fn
    from jax import shard_map

    sp = tuple(_leaf_spec(specs[i]) for i in idxs)
    out_specs = tuple(s for s in sp for _ in range(outs_per_leaf))
    return shard_map(fn, mesh=mesh,
                     in_specs=(PartitionSpec(),) + sp * n_groups,
                     out_specs=out_specs, check_vma=False)


def lamb_stage1(g_leaves: Sequence[Any], mu_leaves: Sequence[Any],
                nu_leaves: Sequence[Any], pf_leaves: Sequence[Any],
                wd_leaves: Sequence[float], *, denom, c1, c2,
                b1: float, b2: float, eps: float, impl: str = "auto",
                bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                mesh=None, specs: Optional[Sequence[Any]] = None,
                ) -> Tuple[List[Any], List[Any], List[Any]]:
    """Bucketed stage1 over aligned leaf lists (grads pre-cast f32,
    params pre-cast f32, per-leaf weight-decay floats). denom/c1/c2 may
    be traced scalars. Returns (mu', nu', u) leaf lists in input order,
    all f32, leaf-shaped."""
    use_pallas = select_impl(impl) == "pallas"
    scal = jnp.stack([jnp.asarray(denom, jnp.float32),
                      jnp.asarray(c1, jnp.float32),
                      jnp.asarray(c2, jnp.float32)]).reshape(1, 3)
    n_leaves = len(g_leaves)
    buckets = _bucketize([int(x.size) for x in g_leaves], bucket_bytes)
    mu_out: List[Any] = [None] * n_leaves
    nu_out: List[Any] = [None] * n_leaves
    u_out: List[Any] = [None] * n_leaves
    for idxs in buckets:
        wds = tuple(float(wd_leaves[i]) for i in idxs)

        def run(scal, *args, _wds=wds, _k=len(idxs)):
            gs, mus = args[:_k], args[_k:2 * _k]
            nus, pfs = args[2 * _k:3 * _k], args[3 * _k:]
            if not use_pallas:
                # per-leaf, python-float wd: structurally the same
                # expressions as the unfused optim/lamb.py chain
                # -> bit-identical to fused=False
                outs = []
                for x, m, v, pf, w in zip(gs, mus, nus, pfs, _wds):
                    outs += list(_stage1_math(
                        x, m, v, pf, w, scal[0, 0], scal[0, 1],
                        scal[0, 2], b1=b1, b2=b2, eps=eps))
                return tuple(outs)
            cat = lambda xs: jnp.concatenate([x.reshape(-1) for x in xs])
            wdf = jnp.concatenate([
                jnp.full((x.size,), w, jnp.float32)
                for x, w in zip(gs, _wds)])
            muf, nuf, uf = _stage1_flat(
                scal, cat(gs), cat(mus), cat(nus), cat(pfs), wdf,
                b1=b1, b2=b2, eps=eps, use_pallas=True)
            outs, off = [], 0
            for x in gs:
                sz, shp = int(x.size), x.shape
                outs += [muf[off:off + sz].reshape(shp),
                         nuf[off:off + sz].reshape(shp),
                         uf[off:off + sz].reshape(shp)]
                off += sz
            return tuple(outs)

        fn = _maybe_shard_map(run, mesh, specs, idxs, n_groups=4,
                              outs_per_leaf=3)
        res = fn(scal,
                 *[g_leaves[i] for i in idxs],
                 *[mu_leaves[i] for i in idxs],
                 *[nu_leaves[i] for i in idxs],
                 *[pf_leaves[i] for i in idxs])
        if not isinstance(res, tuple):
            res = (res,)
        for j, i in enumerate(idxs):
            mu_out[i], nu_out[i], u_out[i] = res[3 * j:3 * j + 3]
    return mu_out, nu_out, u_out


def lamb_stage2(t_leaves: Sequence[Any], u_leaves: Sequence[Any], *,
                impl: str = "auto",
                bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                mesh=None, specs: Optional[Sequence[Any]] = None,
                ) -> List[Any]:
    """Bucketed stage2: upd = t * u, with t = -lr*ratio already broadcast
    to each leaf's shape by the caller. Returns f32 leaf-shaped updates
    in input order (caller casts to the param dtype)."""
    use_pallas = select_impl(impl) == "pallas"
    buckets = _bucketize([int(x.size) for x in u_leaves], bucket_bytes)
    out: List[Any] = [None] * len(u_leaves)
    for idxs in buckets:

        def run(_scal, *args, _k=len(idxs)):
            ts, us = args[:_k], args[_k:]
            if not use_pallas:
                return tuple(t * u for t, u in zip(ts, us))
            cat = lambda xs: jnp.concatenate([x.reshape(-1) for x in xs])
            flat = _stage2_flat(cat(ts), cat(us), use_pallas=True)
            outs, off = [], 0
            for x in us:
                sz = int(x.size)
                outs.append(flat[off:off + sz].reshape(x.shape))
                off += sz
            return tuple(outs)

        fn = _maybe_shard_map(run, mesh, specs, idxs, n_groups=2,
                              outs_per_leaf=1)
        res = fn(jnp.zeros((1,), jnp.float32),
                 *[t_leaves[i] for i in idxs],
                 *[u_leaves[i] for i in idxs])
        if not isinstance(res, tuple):
            res = (res,)
        for j, i in enumerate(idxs):
            out[i] = res[j]
    return out
