"""The learned index's three (chunk, S)-sized passes as TPU kernels
(ops/sparse_index.py has the mathematics and calls them a chunk of queries
at a time; its plain-XLA forms are what these are tested against).

In plain XLA each pass writes its per-head products to HBM and reads them
back: (16, 512, 16384) float32 index products and (8, 512, 16384) attention
scores a key/value head are 0.5 and 0.27 GB a chunk, several times over
(PERF.md section 6, PR 43, has the times). Here a (512, 512) tile's
per-head products live in VMEM and only the (chunk, S) results reach HBM.

Every kernel works ONE chunk of `C` queries (the flash kernels' q block)
against the key blocks of the row, its grid's last axis walking the key
blocks j = 0 .. S / blk - 1 in order; the chunk's number i comes in SMEM and
a key block after the chunk's own (j > i: wholly later than every query)
is skipped: nothing is computed, its K block is not fetched (the index map
clamps at i) and zeros are written.

- `index_scores` (`dsa_index_fwd`): I = sum_j w_j relu(qI_j . kI^T), the J
  heads' products never leaving VMEM;
- `mean_probs` (`dsa_probs`): the mean over the H heads of the main
  attention's probabilities over each row's selected keys, in ONE walk over
  the key blocks: each head's normaliser over the selected keys comes in,
  as the log-sum-exp that the main attention's forward kernel wrote for the
  same scores (`flash_select_attention`'s second output), and a tile writes
  (1/H) sum_n exp(s_n - lse_n);
- `index_scores_grads` (`dsa_index_bwd`): the cotangents of qI, kI and w
  for a cotangent of I, the products recomputed a tile at a time; dqI and dw
  stay in VMEM over the walk, dkI is written a key block a step (the
  chunk's own share: the caller adds the chunks').

Per-token vectors (w, dw, lse) travel lane-dense, (J, 1, C), and turn into
columns inside the kernels, as the flash kernels' row statistics do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from bert_pytorch_tpu.ops.pallas.flash_attention import (NEG_INF,
                                                         SELECT_WORD)

_VMEM_BYTES = 64 * 1024 * 1024


def supported(c: int, blk_k: int, d_idx: int, d: int) -> bool:
    """Shapes the kernels take: tiles of whole 128-lane blocks, index heads
    of 64-lane multiples, main heads of 128-lane multiples."""
    return (c % 128 == 0 and blk_k % 128 == 0 and d_idx % 64 == 0
            and d % 128 == 0)


def _call(kernel, name: str, grid: tuple, in_specs, out_specs, out_shape,
          interpret: bool = False):
    """pallas_call with the chunk's number as the one prefetched scalar
    (the index maps' last argument, the kernel's first ref): every axis of
    the grid is sequential."""
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs),
        out_shape=out_shape, name=name, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=_VMEM_BYTES))


def _chunk(i):
    return jnp.reshape(i, (1,)).astype(jnp.int32)


def _whole(shape):
    """A block that is the whole operand, the same at every step."""
    return pl.BlockSpec(shape, lambda *ids: (0,) * len(shape))


def _key_block(shape, axis: int):
    """The key block the step walks to along `axis`, clamped at the chunk's
    own: a skipped step fetches nothing new."""
    def at(*ids):
        j, i_ref = ids[-2], ids[-1]
        index = [0] * len(shape)
        index[axis] = jnp.minimum(j, i_ref[0])
        return tuple(index)
    return pl.BlockSpec(shape, at)


def _lanes(w):
    """(C, J) per-token values -> (J, 1, C), lane-dense."""
    return w.astype(jnp.float32).T[:, None, :]


def _head_products(q_ref, k, h):
    """Index head h's (C, blk_k) products against the key block."""
    return jax.lax.dot_general(
        q_ref[h], k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _each_head(heads: int, body) -> None:
    """body(h) for every index head, as a ROLLED loop: a tile's instructions
    once, whatever the heads (ops/pallas/flash_attention.py's lesson)."""
    jax.lax.fori_loop(0, heads, lambda h, _: body(h), None)


def _index_fwd_kernel(i_ref, q_ref, k_ref, w_ref, o_ref):
    j = pl.program_id(0)
    o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    @pl.when(j <= i_ref[0])
    def _():
        def head(h):
            o_ref[...] += w_ref[h, 0][:, None] * jax.nn.relu(
                _head_products(q_ref, k_ref[...], h))

        _each_head(q_ref.shape[0], head)


def index_scores(i, q_idx, k_idx, w_idx, blk_k: int, interpret: bool):
    """I (C, S) float32 of chunk number `i` (a traced int32): q_idx
    (C, J, d), k_idx (S, d), w_idx (C, J) float32. Key blocks after the
    chunk's own read zeros."""
    c, heads, d = q_idx.shape
    s = k_idx.shape[0]
    return _call(
        _index_fwd_kernel, "dsa_index_fwd", (s // blk_k,),
        [_whole((heads, c, d)), _key_block((blk_k, d), 0),
         _whole((heads, 1, c))],
        pl.BlockSpec((c, blk_k), lambda j, i_ref: (0, j)),
        jax.ShapeDtypeStruct((c, s), jnp.float32), interpret=interpret,
    )(_chunk(i), q_idx.transpose(1, 0, 2), k_idx, _lanes(w_idx))


def _selected(sel_ref, j):
    """The (C, blk_k) bools of key block j (traced) from the chunk's packed
    words (ops/pallas/flash_attention.py at `_select_tile`)."""
    bit = jnp.left_shift(jnp.int32(1), j % SELECT_WORD)
    return (sel_ref[j // SELECT_WORD] & bit) != 0


def _probs_kernel(i_ref, q_ref, k_ref, lse_ref, sel_ref, o_ref, *,
                  scale: float, group: int):
    j = pl.program_id(0)
    heads = q_ref.shape[0]
    o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    @pl.when(j <= i_ref[0])
    def _():
        selected = _selected(sel_ref, j)

        def head(t, _):
            # the forward kernel's scores (ops/pallas/flash_attention.py at
            # `_fwd_heads`: D = 128, the scale on the tile), so that its
            # log-sum-exp is these scores' own
            s = jax.lax.dot_general(
                q_ref[t], k_ref[t // group], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            o_ref[...] += jnp.exp(jnp.where(selected, s, NEG_INF)
                                  - lse_ref[t, 0][:, None])

        jax.lax.fori_loop(0, heads, head, None)
        # the mask once more: a row that selects nothing at all has every
        # score at NEG_INF and a log-sum-exp to match, and reads 1 a key
        o_ref[...] = jnp.where(selected, o_ref[...] / heads, 0.0)


def mean_probs(i, q, k, lse, by_q, blk_k: int, interpret: bool):
    """(C, S) float32: the mean over the H heads of the attention's
    probabilities over each row's selected keys, for chunk number `i`:
    q (C, H, D), k (S, Hkv, D), lse (H, C) float32 each head's log-sum-exp
    of its scores over the row's selected keys, by_q (W, C, blk_k) the
    chunk's packed selection. Key blocks after the chunk's own read
    zeros."""
    c, h, d = q.shape
    s, hkv = k.shape[0], k.shape[1]
    return _call(
        functools.partial(_probs_kernel, scale=1.0 / (d ** 0.5),
                          group=h // hkv),
        "dsa_probs", (s // blk_k,),
        [_whole((h, c, d)), _key_block((hkv, blk_k, d), 1),
         _whole((h, 1, c)), _whole(by_q.shape)],
        pl.BlockSpec((c, blk_k), lambda j, i_ref: (0, j)),
        jax.ShapeDtypeStruct((c, s), jnp.float32), interpret=interpret,
    )(_chunk(i), q.transpose(1, 0, 2), k.transpose(1, 0, 2),
      lse[:, None, :], by_q)


def _index_bwd_kernel(i_ref, q_ref, k_ref, w_ref, g_ref, dq_ref, dk_ref,
                      dw_ref):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        dq_ref[...] = jnp.zeros(dq_ref.shape, jnp.float32)
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)

    dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)

    @pl.when(j <= i_ref[0])
    def _():
        def head(h):
            k, g = k_ref[...], g_ref[...]
            s = _head_products(q_ref, k, h)
            dw_ref[h, 0, :] += jnp.sum(jax.nn.relu(s) * g, axis=-1,
                                       keepdims=True)[:, 0]
            ds = (jnp.where(s > 0, w_ref[h, 0][:, None], 0.0) * g).astype(
                k.dtype)
            dq_ref[h] += jnp.dot(ds, k, preferred_element_type=jnp.float32)
            dk_ref[...] += jax.lax.dot_general(
                ds, q_ref[h], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _each_head(q_ref.shape[0], head)


def index_scores_grads(i, q_idx, k_idx, w_idx, g, blk_k: int,
                       interpret: bool):
    """The cotangents (dq_idx (C, J, d), dk_idx (S, d), dw_idx (C, J), all
    float32) of `index_scores` for the cotangent g (C, S) of chunk number
    `i`; dk_idx is this chunk's share."""
    c, heads, d = q_idx.shape
    s = k_idx.shape[0]
    dq, dk, dw = _call(
        _index_bwd_kernel, "dsa_index_bwd", (s // blk_k,),
        [_whole((heads, c, d)), _key_block((blk_k, d), 0),
         _whole((heads, 1, c)),
         pl.BlockSpec((c, blk_k), lambda j, i_ref: (0, jnp.minimum(
             j, i_ref[0])))],
        [_whole((heads, c, d)),
         pl.BlockSpec((blk_k, d), lambda j, i_ref: (j, 0)),
         _whole((heads, 1, c))],
        [jax.ShapeDtypeStruct((heads, c, d), jnp.float32),
         jax.ShapeDtypeStruct((s, d), jnp.float32),
         jax.ShapeDtypeStruct((heads, 1, c), jnp.float32)],
        interpret=interpret,
    )(_chunk(i), q_idx.transpose(1, 0, 2), k_idx, _lanes(w_idx), g)
    return dq.transpose(1, 0, 2), dk, dw[:, 0, :].T
