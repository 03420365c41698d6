"""Ring attention — sequence/context parallelism over the `seq` mesh axis.

The reference handles long context only by curriculum (seq 128 -> 512 dataset
files) and sliding-window featurization (SURVEY §5.7); it has no sequence
parallelism of any kind. Here long context is first-class: when activations
are sharded along the sequence dimension of the `(data, fsdp, model, seq)`
mesh (parallel/mesh.py), attention runs as a ring — each device keeps its
local Q block resident and the K/V blocks (plus the K-side padding bias)
rotate around the `seq` axis via `lax.ppermute`, one neighbor hop per step.

Per ring step a device computes one (Sq_local, Sk_local) score tile and
folds it into streaming-softmax accumulators (running max `m`, normalizer
`l`, weighted-value sum `o` — the same fp32 statistics the Pallas flash
kernel keeps per tile, ops/pallas/flash_attention.py). No device ever
materializes a (S, S) score matrix or a gathered (S, D) K/V: per-device
attention memory is O(S_local * S_local) compute tiles and O(S_local)
state, and the K/V transfers ride nearest-neighbor ICI hops instead of an
all-gather. The final tile is unrolled out of the scan so the ring makes
exactly n-1 hops, and a bias-free call carries no bias tile at all.

Differentiation: two nested rematerializations. The whole ring is wrapped
in `jax.checkpoint` (ring_sharded), so a layer's forward saves only its
O(S_local) inputs — without this, `lax.scan` would stack its per-step
carry (the rotating K/V blocks) for EVERY layer simultaneously, i.e.
O(S_global) K/V per layer held across the whole model backward. The scan
body is additionally checkpointed so the recompute never saves score
tiles. Net: per-layer residual memory O(S_local); the K/V carry stack
(~one full-sequence K/V, still nowhere near the O(S^2) score matrix)
materializes only transiently inside a single layer's backward while
autodiff reverses the scan (`ppermute`'s transpose is the inverse
rotation).

Attention dropout follows the dense semantics `out = sum_k keep_k *
(p_k / (1-r)) * v_k` with p the *normalized* probabilities: the keep mask
scales only the value accumulation `o`, never the normalizer `l`. Keep
bits are drawn from a key folded with (q_shard, k_source_shard) so every
score tile of the global (S, S) matrix gets an independent stream and no
tile pair ever reuses masks, matching the decorrelation the sharded flash
path applies (ops/attention.py _flash_sharded).

Packed sequences (segment_ids): the per-shard (B, S_local) segment-id slab
rotates around the ring exactly like K/V — each device keeps its resident
q-side slab and masks every score tile with the same additive
`q_seg == k_seg` constant the flash kernels use (SEG_NEG = -1e30, so
cross-segment probabilities underflow to exact 0.0 in fp32 and the
no-contamination guarantee stays bit-exact on the ring path too). Pad
(segment-0) queries are zeroed after normalization, matching the flash
kernels' pad contract. A tile whose every key is foreign contributes
exp(-1e30 - m) == 0.0 to l/o once any real tile has raised the running max
m above SEG_NEG; until then the spurious mass it deposits is wiped by the
corr = exp(SEG_NEG - m_real) == 0.0 rescale — streaming softmax is
self-healing here, which is what makes segment masking compose with the
rotation without materializing any (S, S) structure.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def ring_attention_local(
    q: jax.Array,            # (B, Sq_local, H, D) — this shard's queries
    k: jax.Array,            # (B, Sk_local, H, D) — this shard's keys
    v: jax.Array,            # (B, Sk_local, H, D)
    kbias: Optional[jax.Array],   # (B, 1, 1, Sk_local) additive K-side bias
    axis_name: str,
    dropout_key: Optional[jax.Array] = None,
    dropout_rate: float = 0.0,
    segment_ids: Optional[jax.Array] = None,  # (B, S_local) packing slab
) -> jax.Array:
    """Ring attention over `axis_name`; call inside shard_map/pmap where the
    sequence dimension is sharded across that axis. Returns (B, Sq, H, D) in
    q.dtype.

    `segment_ids` is this shard's slab of the packed-sequence ids (1..n per
    row, 0 = pad): the q-side copy stays resident while the k-side copy
    rotates with K/V, and each tile is masked to `q_seg == k_seg` with the
    flash kernels' -1e30 constant (exact-zero cross-segment probabilities)."""
    n = lax.psum(1, axis_name)
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]
    b, sq, h, d = q.shape

    qf = q.astype(jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o0 = jnp.zeros((b, sq, h, d), jnp.float32)
    has_bias = kbias is not None
    if has_bias:
        kbias = kbias.astype(jnp.float32)
    has_seg = segment_ids is not None
    if has_seg:
        segment_ids = segment_ids.astype(jnp.int32)
        # (B, 1, Sq, 1) resident query slab, broadcast over heads and keys
        q_seg = segment_ids[:, None, :, None]
    # ring step i sees the block that ORIGINATED at shard (my - i) mod n;
    # the (q_shard, src) pair indexes this tile of the global score matrix
    my = lax.axis_index(axis_name)
    dropping = dropout_key is not None and dropout_rate > 0.0
    if dropping:
        dropout_key = jax.random.fold_in(dropout_key, my)

    def tile(m, l, o, kc, vc, bc, sc, i):
        """Fold one (Sq_local, Sk_local) score tile into the streaming
        softmax accumulators."""
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kc.astype(jnp.float32),
                            preferred_element_type=jnp.float32) * scale
        if bc is not None:
            scores = scores + bc                # (B,1,1,Sk) broadcasts
        if sc is not None:
            # same additive constant as the flash kernels' in-kernel mask:
            # exp(NEG_INF - m) underflows to exactly 0.0 once m is real
            allowed = (q_seg == sc[:, None, None, :]) & (q_seg > 0)
            scores = scores + jnp.where(allowed, 0.0, NEG_INF)
        blk_max = jnp.max(scores, axis=-1)      # (B, H, Sq)
        new_m = jnp.maximum(m, blk_max)
        corr = jnp.exp(m - new_m)               # (B, H, Sq)
        p = jnp.exp(scores - new_m[..., None])  # (B, H, Sq, Sk)
        new_l = l * corr + jnp.sum(p, axis=-1)
        pv = p
        if dropping:
            src = (my - i) % n
            keep = jax.random.bernoulli(
                jax.random.fold_in(dropout_key, src),
                1.0 - dropout_rate, p.shape)
            pv = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        new_o = (o * corr.transpose(0, 2, 1)[..., None]
                 + jnp.einsum("bhqk,bkhd->bqhd", pv,
                              vc.astype(jnp.float32)))
        return new_m, new_l, new_o

    def unpack(rot):
        """carry tail -> (kc, vc, bias-or-None, seg-or-None)."""
        it = iter(rot)
        kc, vc = next(it), next(it)
        bc = next(it) if has_bias else None
        sc = next(it) if has_seg else None
        return kc, vc, bc, sc

    def body(carry, i):
        m, l, o, *rot = carry
        kc, vc, bc, sc = unpack(rot)
        m, l, o = tile(m, l, o, kc, vc, bc, sc, i)
        rotated = lax.ppermute(tuple(rot), axis_name, perm)
        return (m, l, o) + tuple(rotated), None

    body = jax.checkpoint(body,
                          policy=jax.checkpoint_policies.nothing_saveable)
    carry0 = ((m0, l0, o0, k, v) + ((kbias,) if has_bias else ())
              + ((segment_ids,) if has_seg else ()))
    # n-1 compute+rotate steps, then the last tile unrolled (no wasted hop)
    carry, _ = lax.scan(body, carry0, jnp.arange(n - 1))
    m, l, o, *rot = carry
    kc, vc, bc, sc = unpack(rot)
    m, l, o = tile(m, l, o, kc, vc, bc, sc, n - 1)
    out = o / l.transpose(0, 2, 1)[..., None]
    if has_seg:
        # pad (segment-0) queries attend nowhere; their degenerate softmax
        # is uniform garbage. Zero them — the flash kernels' pad contract.
        out = out * (segment_ids > 0).astype(out.dtype)[:, :, None, None]
    return out.astype(q.dtype)


@functools.lru_cache(maxsize=32)
def _jitted_ring(mesh, rate: float, has_bias: bool, has_drop: bool,
                 has_seg: bool):
    """Build (and cache) the jitted shard_map program for one
    (mesh, dropout, segments) configuration. The jit makes the checkpointed
    ring work when called eagerly (tests, debugging) — under an outer jit the
    trace is simply inlined — and caching it keeps repeat eager calls from
    re-tracing; jax.jit's own cache handles shape changes."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from bert_pytorch_tpu.ops.attention import flat_batch_head_shard

    sizes = dict(mesh.shape)
    batch_axes = ("data", "fsdp")
    spec_qkv = P(batch_axes, "seq", "model", None)
    in_specs = [spec_qkv, spec_qkv, spec_qkv]
    if has_bias:
        in_specs.append(P(batch_axes, None, None, "seq"))
    if has_seg:
        in_specs.append(P(batch_axes, "seq"))
    if has_drop:
        in_specs.append(P())

    def local(*a):
        it = iter(a)
        lq, lk, lv = next(it), next(it), next(it)
        lbias = next(it) if has_bias else None
        lseg = next(it) if has_seg else None
        lkey = next(it) if has_drop else None
        if lkey is not None:
            # decorrelate the batch/head shards; the ring loop itself folds
            # in the (q_shard, k_source_shard) tile coordinates
            lkey = jax.random.fold_in(lkey, flat_batch_head_shard(sizes))
        ring = jax.checkpoint(
            lambda q_, k_, v_, b_, s_: ring_attention_local(
                q_, k_, v_, b_, "seq", dropout_key=lkey,
                dropout_rate=rate, segment_ids=s_),
            policy=jax.checkpoint_policies.nothing_saveable)
        return ring(lq, lk, lv, lbias, lseg)

    return jax.jit(shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                             out_specs=spec_qkv, check_vma=False))


def ring_sharded(mesh, q, k, v, bias, dropout_rng, rate: float,
                 segment_ids=None):
    """shard_map wrapper: batch over (data, fsdp), heads over model,
    sequence over seq — the dispatch target ops/attention.py uses when the
    ambient mesh has a nontrivial seq axis. `segment_ids` (B, S) enables
    packed-sequence masking (the slab rotates with K/V). Returns None when
    the layout doesn't fit (caller falls back to the XLA path, which
    handles arbitrary sharding through SPMD collectives at O(S^2) memory)."""
    from bert_pytorch_tpu.ops.attention import mesh_layout

    b, s, h, d = q.shape
    sizes = mesh_layout(mesh, b, h)
    if sizes is None or s % sizes.get("seq", 1) or q.shape != k.shape:
        return None
    if bias is not None and bias.shape != (b, 1, 1, s):
        return None  # ring rotates a K-side padding bias only
    if segment_ids is not None and segment_ids.shape != (b, s):
        return None

    args = [q, k, v]
    has_bias = bias is not None
    if has_bias:
        args.append(bias)
    has_seg = segment_ids is not None
    if has_seg:
        args.append(segment_ids)
    has_drop = dropout_rng is not None and rate > 0.0
    if has_drop:
        args.append(dropout_rng)
    return _jitted_ring(mesh, rate, has_bias, has_drop, has_seg)(*args)
