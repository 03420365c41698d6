"""Multi-head scaled-dot-product attention.

The reference computes attention as explicit torch matmuls with an additive
``(1-mask)*-10000`` bias (src/modeling.py:376-437, 843-851). Here the math
lives in one function with selectable implementation:

- ``xla``:    plain einsum path; XLA fuses softmax and handles MXU tiling.
  What the two seq-128 benchmark cells run (PERF.md section 4); its rate
  against the other paths is not measured on this runtime.
- ``xla_checkpoint``: the einsum path wrapped in jax.checkpoint so the
  (B, H, S, S) probabilities are recomputed in the backward pass instead of
  saved — XLA-attention speed with flash-like activation memory. Use it to
  fit batches the plain path OOMs on; at equal batch it loses a few percent
  to the recompute.
- ``pallas``: blockwise fused kernel (ops/pallas/flash_attention.py) that never
  materializes the (B, H, S, S) score matrix in HBM — the TPU analogue of
  flash attention; the packed seq-512 cell and the lfm2 cell run it
  (PERF.md section 4). Where VMEM allows (BERT-Large
  seq512 qualifies) the kernels consume the model's (B, S, H, D) layout
  directly — no (BH, S, D) transpose pass either side; longer sequences
  fall back to the transposing grid automatically.
- ``ring``:   sequence parallelism (ops/ring_attention.py) — under a mesh
  whose `seq` axis is nontrivial, K/V blocks rotate around the ring via
  ppermute while each device keeps its Q shard resident; O(S_local) memory
  per device. ``pallas`` (and so ``auto`` at long sequence lengths) also
  routes here when the ambient mesh shards the sequence axis (a Pallas
  kernel is an opaque custom-call that can't see across shards).

Softmax is computed in fp32 regardless of compute dtype; scores in bf16
accumulate enough error at seq 512 to perturb MLM loss.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp


# Everything between the projected q, k, v and the returned context, on
# whichever path (training/pretrain.STEP_SUBSCOPES: `attention` ->
# `attn_core`). JAX carries the scope into the custom rules' backward passes
# (the flash kernels', hash_dropout's): tests/test_step_scopes.py reads it in
# the compiled text.
CORE_SCOPE = "attn_core"


def _scoped(name: str):
    """Decorator: what the function traces sits under the named scope
    `name`. A context of its own for every call: jax.named_scope's object
    keeps its state on itself, so one shared by all calls is not
    re-entrant."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


def _pallas_interpret() -> bool:
    """BPT_PALLAS_INTERPRET=1 routes the Pallas kernels through interpret
    mode on non-TPU backends, so the multi-chip dryrun (virtual CPU mesh)
    exercises the production kernel path end-to-end instead of silently
    falling back to XLA. Off by default: interpret mode is orders of
    magnitude slower and only exists for validation."""
    return os.environ.get("BPT_PALLAS_INTERPRET", "0") == "1"


def active_mesh():
    """The ambient Mesh at trace time (jax.sharding.use_mesh, or the legacy
    `with mesh:` context), or None when absent/trivial. Pallas kernels are
    opaque custom-calls XLA's SPMD partitioner cannot split — calling one on
    sharded operands forces a replicate-then-repartition ("involuntary full
    rematerialization"). Under a nontrivial mesh the kernels must therefore
    go through shard_map so each device runs on its local shard."""
    # set by jax.sharding.use_mesh; trace-safe, unlike get_mesh()
    m = jax.sharding.get_abstract_mesh()
    if m.empty:
        # legacy `with mesh:` context, which the entry points still use
        from jax._src.mesh import thread_resources

        m = thread_resources.env.physical_mesh
    if m.empty or m.size == 1:
        return None
    return m


def mesh_layout(mesh, batch: int, heads: int):
    """Validate the (data, fsdp, model, seq) mesh vocabulary against a
    (batch, heads) attention shape. Returns the axis-size dict, or None when
    the layout rules a sharded kernel out (unknown axes, or batch/head count
    not divisible by their mesh extents) — callers fall back to the XLA
    path, which SPMD can partition arbitrarily."""
    if not {"data", "fsdp", "model", "seq"} <= set(mesh.axis_names):
        return None
    sizes = dict(mesh.shape)
    if batch % (sizes.get("data", 1) * sizes.get("fsdp", 1)):
        return None
    if heads % sizes.get("model", 1):
        return None
    return sizes


def flat_batch_head_shard(sizes) -> jax.Array:
    """Flat (data, fsdp, model) shard index — the per-shard dropout
    decorrelation fold shared by the sharded flash and ring paths."""
    return ((jax.lax.axis_index("data") * sizes.get("fsdp", 1)
             + jax.lax.axis_index("fsdp")) * sizes.get("model", 1)
            + jax.lax.axis_index("model"))


def _require_flash(q, k, interpret: bool) -> None:
    """Raise unless the flash kernel can serve this call."""
    if jax.default_backend() != "tpu" and not interpret:
        raise ValueError(
            f"attention impl='pallas' needs a TPU backend (this is "
            f"{jax.default_backend()!r}); set BPT_PALLAS_INTERPRET=1 to run "
            "the kernel in interpret mode, or use impl='auto'/'xla'")
    if q.shape[1] % 128 or not _self_attention_shapes(q, k):
        raise ValueError(
            "attention impl='pallas' needs self-attention shapes with seq "
            f"a multiple of 128, got q{tuple(q.shape)} k{tuple(k.shape)}; "
            "use impl='auto'/'xla'")


def _self_attention_shapes(q, k) -> bool:
    """Same batch, length and head size, and the query heads a multiple of
    the key/value heads (grouped heads; equal for BERT)."""
    return (q.shape[:2] == k.shape[:2] and q.shape[3] == k.shape[3]
            and q.shape[2] % k.shape[2] == 0)


def _flash_sharded(mesh, q, k, v, bias, segment_ids, seed, rate: float,
                   interpret: bool):
    """flash_attention under shard_map: batch over (data, fsdp), heads over
    model; seq/head_dim local. Returns None when the mesh layout rules out
    the kernel (under impl="auto" the caller then takes XLA attention).

    Dropout: the positional hash seed is decorrelated per shard by folding
    in the flat shard index — without this every batch/head shard would
    reuse identical keep-masks. segment_ids (packing) shard like the bias:
    batch over (data, fsdp), sequence local."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    b, s, h, d = q.shape
    sizes = mesh_layout(mesh, b, h)
    if sizes is None:
        return None
    if sizes.get("seq", 1) > 1:  # S-sharded: needs ring attention, not flash
        return None

    batch_axes = ("data", "fsdp")
    spec_qkv = P(batch_axes, None, "model", None)
    in_specs = [spec_qkv, spec_qkv, spec_qkv]
    args = [q, k, v]
    has_bias = bias is not None
    if has_bias:
        in_specs.append(P(batch_axes, None, None, None))
        args.append(bias)
    has_segments = segment_ids is not None
    if has_segments:
        in_specs.append(P(batch_axes, None))
        args.append(segment_ids)
    has_seed = seed is not None
    if has_seed:
        in_specs.append(P())
        args.append(jnp.asarray(seed, jnp.int32).reshape(()))

    def local(*a):
        it = iter(a)
        lq, lk, lv = next(it), next(it), next(it)
        lbias = next(it) if has_bias else None
        lseg = next(it) if has_segments else None
        lseed = next(it) if has_seed else None
        if lseed is not None:
            shard = flat_batch_head_shard(sizes).astype(jnp.int32)
            lseed = lseed ^ (shard * jnp.int32(-1640531527))  # 0x9E3779B9
        from bert_pytorch_tpu.ops.pallas.flash_attention import flash_attention

        return flash_attention(lq, lk, lv, bias=lbias, segment_ids=lseg,
                               dropout_seed=lseed, dropout_rate=rate,
                               interpret=interpret)

    return shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                     out_specs=spec_qkv, check_vma=False)(*args)

# Additive mask bias. The reference used -10000.0 (src/modeling.py:851); that
# value is representable in bf16 and large enough at fp32 softmax precision.
MASK_BIAS = -10000.0


def make_attention_bias(attention_mask: jax.Array,
                        dtype: jnp.dtype = jnp.float32) -> jax.Array:
    """(B, S) {0,1} mask -> (B, 1, 1, S) additive bias."""
    bias = (1.0 - attention_mask.astype(jnp.float32)) * MASK_BIAS
    return bias[:, None, None, :].astype(dtype)


# Packed-sequence (block-diagonal) masking constant. Deliberately the flash
# kernels' NEG_INF, not MASK_BIAS: the XLA fallback must produce the same
# exact-zero cross-segment probabilities the kernels do (exp underflows to
# 0.0 in fp32), which is what makes the no-cross-contamination guarantee
# bit-exact on every path.
SEGMENT_MASK_BIAS = -1e30


def make_segment_attention_bias(segment_ids: jax.Array,
                                dtype: jnp.dtype = jnp.float32) -> jax.Array:
    """(B, S) int packing segments (1..n, 0 = pad) -> (B, 1, S, S) additive
    bias: 0 where q and k share a non-pad segment, SEGMENT_MASK_BIAS
    elsewhere. The XLA-path mirror of the in-kernel segment mask."""
    qs = segment_ids[:, None, :, None]
    ks = segment_ids[:, None, None, :]
    allowed = (qs == ks) & (qs > 0)
    return jnp.where(allowed, 0.0, SEGMENT_MASK_BIAS).astype(dtype)


@_scoped(CORE_SCOPE)
def dot_product_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, H, D)
    v: jax.Array,  # (B, Sk, H, D)
    bias: Optional[jax.Array] = None,  # broadcastable to (B, H, Sq, Sk)
    segment_ids: Optional[jax.Array] = None,  # (B, S) packing segments
    dropout_rng: Optional[jax.Array] = None,
    dropout_rate: float = 0.0,
    deterministic: bool = True,
    impl: str = "xla",
    trainable_bias: bool = False,
    hash_dropout_impl: bool = True,
    causal: bool = False,
    window: Optional[int] = None,
    select=None,
    with_lse: bool = False,
) -> jax.Array:
    """Returns (B, Sq, H, D) in q.dtype.

    `causal`: a query attends to positions <= its own (of its own segment
    where rows are packed). k/v may carry fewer heads than q (grouped
    heads: query head i reads key/value head i // (H // Hkv)). Both are
    served by the flash kernels and the plain XLA path; neither by the ring
    path nor under a mesh that shards the kernel (an error there).
    `window` (with `causal` only; None: no band): a query attends to the
    last `window` positions, its own among them: 0 <= q_pos - k_pos <
    window, counted inside the query's own segment.
    `select` (with `causal` only; None: every allowed key): the keys each
    query token attends to, one selection for all of its heads, as
    ops/sparse_index.py packs it (`by_q`, `by_k`: the flash kernels' form,
    ops/pallas/flash_attention.select_blocks); a selected key that the
    causal or the segment condition excludes stays excluded.
    `with_lse` (with `select` only): returns (the context, lse (B, H, Sq)
    float32), each head's log-sum-exp of its scaled scores over the query's
    selected keys: the flash forward kernel's own residual, or the XLA
    path's `logsumexp` of its masked scores. Data for a caller that reads
    the same scores again (ops/sparse_index.index_kl), not a second
    differentiable output of the kernels.

    impl="auto" resolves by sequence length: measured on v5e, the plain XLA
    path (bf16 probs, fp32 softmax stats) beats the blockwise Pallas kernel
    up through seq 256 — the (B, H, S, S) matrix is small enough that XLA's
    fused attention wins on raw speed; the flash kernel earns its keep when
    the score matrix is too large to materialize (long-context phase 2+).

    `segment_ids` (B, S) int32, packed sequences: attention restricted to
    q_seg == k_seg blocks, 0 = pad attends nowhere. The flash kernels mask
    (and block-skip) in-kernel; the XLA paths add the dense
    make_segment_attention_bias; the ring path rotates the per-shard
    segment-id slab alongside K/V (ops/ring_attention.py) — the same
    exact-zero cross-segment probabilities on every impl, so packing
    composes with seq-sharded meshes too.

    WARNING: the pallas flash-attention path treats `bias` as a constant
    padding mask — its custom VJP returns a ZERO cotangent for bias. A caller
    differentiating through the bias (e.g. a trainable relative-position
    bias) must pass trainable_bias=True, which forces the XLA path where the
    bias gradient is exact.
    """
    seq = q.shape[1]
    requested = impl
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"attention window={window!r} needs causal=True and a width of "
            "at least 1 (the bidirectional paths have no band)")
    if select is not None and (not causal or window is not None
                               or bias is not None
                               or not (deterministic or dropout_rate == 0.0)):
        raise ValueError(
            "attention select= needs causal=True and takes no window, bias "
            "or dropout")
    if with_lse and select is None:
        raise ValueError(
            "attention with_lse=True hands out the log-sum-exp over a "
            "selection's keys: it needs select=")
    if impl == "auto":
        impl = "pallas" if seq > 256 else "xla"
    interpret = jax.default_backend() != "tpu" and _pallas_interpret()
    # Sequence-sharded mesh: route to ring attention (K/V blocks rotate over
    # the seq axis via ppermute; O(S_local) memory per device) for every impl
    # except the explicitly-XLA ones, where SPMD's gather-based lowering is
    # the caller's documented choice. impl="ring" forces the ring path.
    decoder = causal or k.shape[2] != q.shape[2]
    if decoder and (impl in ("ring", "xla_checkpoint")
                    or (impl == "pallas" and active_mesh() is not None)):
        raise NotImplementedError(
            "causal / grouped-head attention runs through the flash kernels "
            f"on one device or the plain XLA path, not impl={impl!r} under "
            "this mesh")
    if impl in ("ring", "pallas") and not trainable_bias:
        mesh = active_mesh()
        seq_sharded = mesh is not None and dict(mesh.shape).get("seq", 1) > 1
        if seq_sharded:
            from bert_pytorch_tpu.ops.ring_attention import ring_sharded

            rate = 0.0 if deterministic else dropout_rate
            out = ring_sharded(mesh, q, k, v, bias,
                               dropout_rng if rate > 0.0 else None, rate,
                               segment_ids=segment_ids)
            if out is not None:
                return out
        if impl == "ring":
            # no seq-sharded mesh (single chip / tests): dense math is exact
            return _xla_attention(q, k, v, bias, segment_ids, dropout_rng,
                                  dropout_rate, deterministic)
    if impl == "pallas" and not trainable_bias:
        if requested == "pallas":
            # asked for by name: the caller gets the kernel or an error,
            # never a silent XLA result ("auto" chooses, and may choose XLA)
            _require_flash(q, k, interpret)
        if ((jax.default_backend() == "tpu" or interpret)
                and seq % 128 == 0 and _self_attention_shapes(q, k)):
            from bert_pytorch_tpu.ops.pallas.flash_attention import (
                flash_attention)

            rate = 0.0 if deterministic else dropout_rate
            seed = None
            if rate > 0.0:
                # fold the dropout key into a 32-bit positional-hash seed
                seed = jax.random.randint(dropout_rng, (), 0, 2 ** 31 - 1,
                                          dtype=jnp.int32)
            mesh = active_mesh()
            if mesh is None and select is not None:
                from bert_pytorch_tpu.ops.pallas.flash_attention import (
                    flash_select_attention)

                out = flash_select_attention(q, k, v, segment_ids, *select,
                                             interpret)
                return out if with_lse else out[0]
            if mesh is None:
                return flash_attention(q, k, v, bias=bias,
                                       segment_ids=segment_ids,
                                       dropout_seed=seed, dropout_rate=rate,
                                       interpret=interpret, causal=causal,
                                       **({"window": int(window)}
                                          if window else {}))
            out = _flash_sharded(mesh, q, k, v, bias, segment_ids, seed,
                                 rate, interpret)
            if out is not None:
                return out
            if requested == "pallas":
                raise ValueError(
                    f"attention impl='pallas': mesh {dict(mesh.shape)} cannot "
                    f"shard the kernel for q{tuple(q.shape)} (batch over "
                    "data*fsdp, heads over model, seq local); use "
                    "impl='auto'/'xla'")

    if impl == "xla_checkpoint":
        ckpt = jax.checkpoint(
            _xla_attention,
            static_argnums=(6, 7, 8),
            policy=jax.checkpoint_policies.nothing_saveable)
        return ckpt(q, k, v, bias, segment_ids, dropout_rng, dropout_rate,
                    deterministic, hash_dropout_impl)

    return _xla_attention(q, k, v, bias, segment_ids, dropout_rng,
                          dropout_rate, deterministic, hash_dropout_impl,
                          causal, window, select, with_lse)


def unpack_select(by_q: jax.Array, keys: Optional[int] = None) -> jax.Array:
    """(B, rows, keys) bools from a selection packed by q block
    (B, W, rows, blk): query q selects key j * blk + c where bit j % 32 of
    word [j // 32, q, c] is set (ops/pallas/flash_attention.py, at
    `_select_tile`). `keys`: the row's length where `by_q` holds some of its
    queries only (a chunk's words); None: as many as the rows. The XLA
    path's dense mirror of the kernels' operand."""
    b, planes, rows, blk = by_q.shape
    j = jnp.arange((keys or rows) // blk)
    words = by_q[:, j // 32]                               # (B, nk, rows, blk)
    bits = (words >> (j % 32)[None, :, None, None]) & 1
    return (bits != 0).transpose(0, 2, 1, 3).reshape(b, rows, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def hash_dropout(x, seed, rate: float):
    """Dropout whose keep mask is the positional counter hash
    (ops/layernorm.row_col_keep) over the flattened (rows, last-axis) view,
    REGENERATED in the backward pass instead of saved — the (B, H, S, S)
    bool mask the autodiff of a bernoulli+where dropout keeps for backward
    never exists in HBM. Same construction the flash kernel and the fused
    residual-dropout-LN kernel use for their in-kernel masks; Bernoulli
    statistics, different stream than nn.Dropout."""
    return _hash_dropout_apply(x, seed, rate)


def _hash_dropout_apply(x, seed, rate):
    from bert_pytorch_tpu.ops.layernorm import _hash_keep_mask

    keep = _hash_keep_mask(seed, x.shape, rate)
    return jnp.where(keep, x / jnp.asarray(1.0 - rate, x.dtype),
                     jnp.zeros([], x.dtype))


def _hash_dropout_fwd(x, seed, rate):
    return _hash_dropout_apply(x, seed, rate), seed


def _hash_dropout_bwd(rate, seed, g):
    # dropout is linear: dx is the same mask-and-scale applied to g. The
    # integer seed primal gets the float0 cotangent JAX's convention
    # requires (an int32 zeros here trips stricter custom_vjp aval checks)
    return (_hash_dropout_apply(g, seed, rate),
            jax.custom_derivatives.zero_from_primal(
                jnp.asarray(seed, jnp.int32)))


hash_dropout.defvjp(_hash_dropout_fwd, _hash_dropout_bwd)


def _xla_attention(q, k, v, bias, segment_ids, dropout_rng,
                   dropout_rate: float, deterministic: bool,
                   hash_dropout_impl: bool = True,
                   causal: bool = False,
                   window: Optional[int] = None, select=None,
                   with_lse: bool = False) -> jax.Array:
    if k.shape[2] != q.shape[2]:    # grouped heads: one copy per query head
        group = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    depth = q.shape[-1]
    scale = 1.0 / jnp.sqrt(depth).astype(jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * scale
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    if segment_ids is not None:
        scores = scores + make_segment_attention_bias(segment_ids)
    if causal:
        sq, sk = scores.shape[-2:]
        rows, cols = jnp.arange(sq)[:, None], jnp.arange(sk)[None, :]
        allowed = rows >= cols
        if window is not None:
            allowed &= rows - cols < window
        scores = jnp.where(allowed, scores, SEGMENT_MASK_BIAS)
    if select is not None:
        scores = jnp.where(unpack_select(select[0])[:, None], scores,
                           SEGMENT_MASK_BIAS)
    # softmax statistics in fp32; the probabilities are cast to the compute
    # dtype BEFORE dropout so the (B, H, S, S) tensors XLA saves for the
    # backward pass (probs + dropped probs) are bf16 — this halves attention
    # activation memory and is what lets batch 64 fit on one v5e chip
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)

    if not deterministic and dropout_rate > 0.0:
        if hash_dropout_impl:
            # positional-hash dropout with the mask regenerated in backward:
            # no (B, H, S, S) mask tensor is saved for the bwd pass
            # (the flash path already generates its mask in-kernel the
            # same way)
            seed = jax.random.bits(dropout_rng, (),
                                   jnp.uint32).astype(jnp.int32)
            probs = hash_dropout(probs, seed, dropout_rate)
        else:
            # nn.Dropout-equivalent stream (config fused_dropout_ln=False:
            # the full pre-r5 dropout behavior, for A/B isolation)
            keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate,
                                        probs.shape)
            probs = jnp.where(
                keep, probs / jnp.asarray(1.0 - dropout_rate, q.dtype),
                jnp.zeros([], q.dtype))

    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    if segment_ids is not None:
        # pad (segment-0) queries attend nowhere; their degenerate softmax
        # is uniform garbage. Zero them to match the flash kernels' pad
        # contract exactly (flash_attention.py module docstring).
        out = out * (segment_ids > 0).astype(out.dtype)[:, :, None, None]
    if with_lse:
        return out, jax.nn.logsumexp(scores, axis=-1)
    return out
