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
  attention's probabilities over each row's selected keys. Two walks over
  the key blocks: the first takes each head's running max and sum (its
  softmax's normaliser over the selected keys), the second writes
  (1/H) sum_n exp(s_n - lse_n);
- `index_scores_grads` (`dsa_index_bwd`): the cotangents of qI, kI and w
  for a cotangent of I, the products recomputed a tile at a time; dqI and dw
  stay in VMEM over the walk, dkI is written a key block a step (the
  chunk's own share: the caller adds the chunks').

Per-token vectors (w, dw) travel lane-dense, (J, 1, C), and turn into
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
          scratch=(), interpret: bool = False):
    """pallas_call with the chunk's number as the one prefetched scalar
    (the index maps' last argument, the kernel's first ref): every axis of
    the grid is sequential."""
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM(shape, jnp.float32)
                            for shape in scratch]),
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


def _probs_kernel(i_ref, q_ref, k_ref, sel_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, group: int):
    phase, j = pl.program_id(0), pl.program_id(1)
    heads = q_ref.shape[0]
    live = j <= i_ref[0]

    def scores(t):
        s = jax.lax.dot_general(
            q_ref[t], k_ref[t // group], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        return jnp.where(_selected(sel_ref, j), s, NEG_INF)

    @pl.when((phase == 0) & (j == 0))
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)

    @pl.when((phase == 0) & live)
    def _():
        def head(t, _):
            s = scores(t)
            m = jnp.maximum(m_ref[t], jnp.max(s, axis=-1, keepdims=True))
            l_ref[t] = l_ref[t] * jnp.exp(m_ref[t] - m) + jnp.sum(
                jnp.exp(s - m), axis=-1, keepdims=True)
            m_ref[t] = m

        jax.lax.fori_loop(0, heads, head, None)

    @pl.when((phase == 1) & live)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def head(t, _):
            lse = m_ref[t] + jnp.log(jnp.maximum(l_ref[t], 1e-30))
            acc_ref[...] += jnp.exp(scores(t) - lse)

        jax.lax.fori_loop(0, heads, head, None)
        # the mask once more: a row that selects nothing at all has every
        # score at NEG_INF and a normaliser to match, and reads 1 a key
        o_ref[...] = jnp.where(_selected(sel_ref, j), acc_ref[...] / heads,
                               0.0)

    @pl.when((phase == 1) & jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)


def mean_probs(i, q, k, by_q, blk_k: int, interpret: bool):
    """(C, S) float32: the mean over the H heads of the attention's
    probabilities over each row's selected keys, for chunk number `i`:
    q (C, H, D), k (S, Hkv, D), by_q (W, C, blk_k) the chunk's packed
    selection."""
    c, h, d = q.shape
    s, hkv = k.shape[0], k.shape[1]
    return _call(
        functools.partial(_probs_kernel, scale=1.0 / (d ** 0.5),
                          group=h // hkv),
        "dsa_probs", (2, s // blk_k),
        [_whole((h, c, d)), _key_block((hkv, blk_k, d), 1),
         _whole(by_q.shape)],
        # the first walk writes nothing: its block stays the first
        pl.BlockSpec((c, blk_k), lambda ph, j, i_ref: (0, j * ph)),
        jax.ShapeDtypeStruct((c, s), jnp.float32),
        scratch=((h, c, 1), (h, c, 1), (c, blk_k)), interpret=interpret,
    )(_chunk(i), q.transpose(1, 0, 2), k.transpose(1, 0, 2), by_q)


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
