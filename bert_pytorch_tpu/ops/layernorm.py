"""LayerNorm with a swappable fused (Pallas) implementation.

The reference used apex's FusedLayerNormAffineFunction CUDA kernel with a
pure-torch fallback (src/modeling.py:282-335, eps 1e-12). Here the roles are
mirrored: `_layer_norm_xla` is the always-correct reference path (XLA already
fuses it well), and `bert_pytorch_tpu.ops.pallas.layernorm` provides the
hand-tiled TPU kernel selected by ``fused=True`` on TPU backends.

Statistics are always computed in fp32 regardless of compute dtype — on TPU
bf16 accumulation of mean/variance loses enough precision to shift loss curves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _layer_norm_xla(x: jax.Array, scale: jax.Array, bias: jax.Array,
                    eps: float) -> jax.Array:
    orig_dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    y = (x32 - mean) * inv
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(orig_dtype)


@functools.partial(jax.jit, static_argnames=("eps", "fused"))
def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-12, fused: bool = False) -> jax.Array:
    """LayerNorm over the last axis. eps default matches the reference (1e-12).

    fused=True routes to the Pallas kernel on a TPU backend (or, with
    BPT_PALLAS_INTERPRET=1, its interpret mode elsewhere); other backends
    take the XLA path, which computes the same thing.
    """
    if fused and x.shape[-1] % 128 == 0:
        from bert_pytorch_tpu.ops.attention import (_pallas_interpret,
                                                    active_mesh)
        from bert_pytorch_tpu.ops.pallas.layernorm import layer_norm_pallas

        on_tpu = jax.default_backend() == "tpu"
        # BPT_PALLAS_INTERPRET=1: run the real kernel in interpret mode
        # on CPU so the multi-chip dryrun covers the production path
        interpret = not on_tpu and _pallas_interpret()
        if on_tpu or interpret:
            mesh = active_mesh()
            if mesh is None:
                return layer_norm_pallas(x, scale, bias, eps=eps,
                                         interpret=interpret)
            out = _layer_norm_sharded(mesh, x, scale, bias, eps, interpret)
            if out is not None:
                return out
    return _layer_norm_xla(x, scale, bias, eps)


def row_col_keep(seed, row0, rows, cols, rate: float):
    """Counter-hash keep mask over global (row, col) positions: two
    multiply-xorshift rounds on a per-position counter, integer threshold
    compare. THE single source of truth — the Pallas fused kernel
    (ops/pallas/layernorm) imports this same function, so fused and
    fallback paths draw identical masks by construction. Pure jnp (uint32
    VPU ops only), traceable inside Pallas kernels and plain XLA alike.
    Statistics rationale as flash_attention._keep_mask: two rounds keep
    rate bias < 5e-4 with chance-level cross-seed correlation."""
    r = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0) \
        + jnp.asarray(row0).astype(jnp.uint32)
    c = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    x = (r * jnp.uint32(0x9E3779B1)) ^ (c * jnp.uint32(0x85EBCA77))
    x = x ^ (jnp.asarray(seed).astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x > jnp.uint32(int(rate * float(2**32)))


def _hash_keep_mask(seed, shape, rate: float):
    """row_col_keep over a flattened (R, E) view of `shape`. Identical to
    the fused kernel's mask for the same seed on a SINGLE device; under a
    mesh the sharded kernel folds shard coordinates into the seed and
    numbers rows per-shard, so fused-vs-fallback runs only reproduce each
    other when unsharded."""
    R = 1
    for s in shape[:-1]:
        R *= s
    return row_col_keep(seed, 0, R, shape[-1], rate).reshape(shape)


def _add_dropout_layer_norm_xla(x, residual, scale, bias, seed, rate, eps):
    if rate > 0.0:
        keep = _hash_keep_mask(seed, x.shape, rate)
        x = jnp.where(keep, x / (1.0 - rate), jnp.zeros_like(x))
    return _layer_norm_xla(residual + x, scale, bias, eps)


def add_dropout_layer_norm(x, residual, scale, bias, seed, rate: float,
                           eps: float = 1e-12, fused: bool = False):
    """y = LayerNorm(residual + dropout(x, rate)) — the residual tail of
    every BertLayer (reference src/modeling.py:439-487: dense -> dropout ->
    LN(residual + .)), as ONE op.

    Why this exists: with dropout expressed in the XLA graph, the keep-mask
    bits and the dropped tensor are materialized to HBM and re-read by the
    backward pass, bloating the surrounding matmul fusions (the cost is not
    measured on this runtime). The fused path evaluates the
    mask from a counter hash of (row, col, seed) inside the kernel, forward
    and backward, so it never touches HBM. The XLA fallback uses the same
    hash, so both paths drop identical units; the difference from nn.Dropout
    is only WHICH units drop (counter hash vs threefry bits) — same
    Bernoulli(rate) statistics, same 1/(1-rate) scaling.

    seed: int32 scalar, fresh per call (derive from the step rng).
    """
    if fused and x.shape[-1] % 128 == 0:
        from bert_pytorch_tpu.ops.attention import (_pallas_interpret,
                                                    active_mesh)
        from bert_pytorch_tpu.ops.pallas.layernorm import (
            add_dropout_layer_norm_pallas)

        on_tpu = jax.default_backend() == "tpu"
        interpret = not on_tpu and _pallas_interpret()
        if on_tpu or interpret:
            mesh = active_mesh()
            if mesh is None:
                return add_dropout_layer_norm_pallas(
                    x, residual, scale, bias, seed, rate, eps, interpret)
            out = _adln_sharded(mesh, x, residual, scale, bias, seed, rate,
                                eps, interpret)
            if out is not None:
                return out
    return _add_dropout_layer_norm_xla(x, residual, scale, bias, seed, rate,
                                       eps)


def _adln_sharded(mesh, x, residual, scale, bias, seed, rate, eps,
                  interpret):
    """Fused residual-dropout-LN under shard_map (same partitioning as
    _layer_norm_sharded). Each shard folds its (data, seq) coordinates into
    the seed so shards draw decorrelated masks — without this, every batch
    shard would reuse the same (local-row, col) mask pattern."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from bert_pytorch_tpu.ops.pallas.layernorm import (
        add_dropout_layer_norm_pallas)

    if not {"data", "fsdp", "seq"} <= set(mesh.axis_names) or x.ndim != 3:
        return None
    sizes = dict(mesh.shape)
    dp = sizes.get("data", 1) * sizes.get("fsdp", 1)
    sp = sizes.get("seq", 1)
    if x.shape[0] % dp or x.shape[1] % sp:
        return None
    spec_x = P(("data", "fsdp"), "seq", None)

    def local(lx, lr, ls, lb, lseed):
        di = jax.lax.axis_index("data") * sizes.get("fsdp", 1) \
            + jax.lax.axis_index("fsdp")
        si = jax.lax.axis_index("seq")
        shard_seed = (lseed.astype(jnp.int32)
                      + (di * jnp.int32(sp) + si) * jnp.int32(0x3C6EF35F))
        return add_dropout_layer_norm_pallas(lx, lr, ls, lb, shard_seed,
                                             rate, eps, interpret)

    return shard_map(
        local, mesh=mesh,
        in_specs=(spec_x, spec_x, P(None), P(None), P()),  # seed: rank-0
        out_specs=spec_x, check_vma=False)(
            x, residual, scale, bias, jnp.asarray(seed, jnp.int32))


def _layer_norm_sharded(mesh, x, scale, bias, eps, interpret):
    """Pallas LN under shard_map (rowwise kernel: batch over (data, fsdp),
    seq over seq, E local). None -> caller falls back to XLA. Same rationale
    as ops/attention._flash_sharded: an SPMD-partitioned pallas_call would
    otherwise replicate its operands."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from bert_pytorch_tpu.ops.pallas.layernorm import layer_norm_pallas

    if not {"data", "fsdp", "seq"} <= set(mesh.axis_names) or x.ndim != 3:
        return None
    sizes = dict(mesh.shape)
    dp = sizes.get("data", 1) * sizes.get("fsdp", 1)
    sp = sizes.get("seq", 1)
    if x.shape[0] % dp or x.shape[1] % sp:
        return None
    spec_x = P(("data", "fsdp"), "seq", None)
    return shard_map(
        lambda lx, ls, lb: layer_norm_pallas(lx, ls, lb, eps, interpret),
        mesh=mesh, in_specs=(spec_x, P(None), P(None)), out_specs=spec_x,
        check_vma=False)(x, scale, bias)
