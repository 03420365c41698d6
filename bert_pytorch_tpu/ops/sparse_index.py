"""A learned index over the earlier tokens: scores, the exact selection of
each query's best keys, and the loss the index learns from (the sparse
training stage of "DeepSeek-V3.2-Exp: Boosting Long-Context Efficiency with
DeepSeek Sparse Attention"; models/keye.py has the layer around it).

For one packed row, query t and key s <= t of t's document (n_t such keys):

    I_ts = sum_j w_tj relu(qI_tj . kI_s)            J index heads, ONE key head
    S_t  = the min(K, n_t) keys with the largest I_ts, ties to the lower s
    p_ts = (1/H) sum_n softmax_{s in S_t}(q_tn . k_s,n//G / sqrt D)
    KL_t = sum_{s in S_t} p_ts (log p_ts - log softmax_{s in S_t}(I_ts))

Two ops, in the order a layer runs them (models/keye.Attention):
`index_select` makes the selection in the form the flash kernels read
(ops/pallas/flash_attention.py at `_select_tile`: bit-packed by block, by q
block and by k block) and its counters; the main attention's forward kernel
runs over it; `index_kl` then takes that kernel's own log-sum-exp over the
selected keys (lse_tn: `flash_select_attention`'s second output), so that
p_ts = (1/H) sum_n exp(s_tsn - lse_tn) is ONE reading of the scores, and
returns sum_t KL_t over the real tokens. The index scores, the selection and
both softmaxes are float32; the products take their operands as given
(bfloat16) and accumulate in float32.

Nothing of size (S, S) is alive: each op works the row a chunk of queries at
a time (the kernels' q block, 512 at the default blocks, which is the
source's `q_chunk_size`), each chunk against every key. `index_select`:
(chunk, S) scores, the exact K-th largest of each row, the chunk's selected
pairs packed. `index_kl`: the chunk's scores AGAIN ((S, S) float32 scores
are 1 GB a row; a chunk's are a quarter of the products of the walk over the
main attention's scores that the log-sum-exp spares), the selected pairs
unpacked from the chunk's words, the head-mean probabilities (a key/value
head's group of query heads at a time), the KL term and the index scores'
backward pass. On a TPU the chunk's three heavy passes (the index scores,
the head-mean probabilities, the index scores' backward pass) are
ops/pallas/sparse_index.py's kernels, whose per-head products stay in VMEM;
what is written here in plain XLA is what runs elsewhere and what those are
tested against.

The exact K-th largest (`kth_largest`): the scores' bits, reordered so that
unsigned integers sort as the floats do, and a bisection from the top bit
down: 32 counts of "how many keys are at or above the candidate" find the
largest value that K keys reach. `jax.lax.top_k`'s K-th value is the same
number and a sort of every row: on the chip the index pass of a layer and
row took 282 ms with it and 71 ms with the bisection (PERF.md section 6,
PR 43), so the bisection is the one way.

The selection is discrete and takes no gradient (`index_select` detaches
its inputs). `index_kl`'s gradient is a rule of its own (`jax.custom_vjp`).
KL_t's gradient with respect to the scores is softmax(I) - p on the selected
set, so the forward pass, which holds a chunk's scores and probabilities
anyway, applies it there (`index_scores_grads`) and keeps the cotangents of
the three SMALL inputs (qI, kI, w: 36 MB at 16,384 tokens) as residuals,
named `dsa_kl_grads` beside the packed selection `dsa_select`: a
rematerialising caller that saves both names (models/keye.REMAT_POLICIES)
runs each pass once a layer and step, and the backward pass is two
multiplications by the loss's cotangent. The main attention's q, k and
log-sum-exp enter as data (the target is detached), as do the packed words.

Scopes, each opened under its whole name (a loop's body keeps the scopes
opened in it, not the caller's): `attention/indexer` (the scores the
selection is made from), `attention/select` (the K-th score, the selected
pairs, their packing and counters), `attention/indexer_loss` (all of
`index_kl`: the scores again, the probabilities, the KL term and the index
scores' backward pass).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bert_pytorch_tpu.ops.attention import _pallas_interpret, unpack_select
from bert_pytorch_tpu.ops.pallas import sparse_index as kernels
from bert_pytorch_tpu.ops.pallas.flash_attention import (SELECT_WORD,
                                                         select_blocks)

NEG_INF = -1e30


class Selection(NamedTuple):
    by_q: jax.Array         # (B, W, S, blk_k) int32: the fwd and dq kernels'
    by_k: jax.Array         # (B, W, blk_q, S) int32: the dkv kernel's
    block_pairs: jax.Array  # (S // blk_k,) int32: selected pairs by k block
    candidates: jax.Array   # (2,) int32: causal pairs inside documents, as
    #                         [count // COUNT_UNIT, count % COUNT_UNIT]


# A 16,384-token row holds 134,225,920 causal pairs: 16 rows of a step wrap
# an int32 sum. Counted a chunk at a time (at most blk_q * S pairs) and kept
# in two halves, whose sums over rows and micro-batches stay inside int32
# for 32,768 chunks a step (telemetry/expert_load.py joins them on the
# host). The selected pairs need no such count: they are the sum of
# `block_pairs`, a block of which holds at most blk_k pairs a token (int32
# to 4.19 M tokens a step at 512).
COUNT_UNIT = 1 << 16


def ordered_bits(x: jax.Array) -> jax.Array:
    """float32 -> uint32 whose unsigned order is the floats' order (-0.0
    taken as +0.0 first)."""
    bits = jax.lax.bitcast_convert_type(x + 0.0, jnp.int32)
    flipped = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(flipped, jnp.uint32) ^ jnp.uint32(
        0x80000000)


def kth_largest(keys: jax.Array, k: int) -> jax.Array:
    """The k-th largest of each row of `keys` (rows, n) uint32, exactly; 0
    where that is 0 (a row with fewer than k keys above 0)."""
    def bit(i, found):
        candidate = found | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        reached = jnp.sum(keys >= candidate[:, None], axis=-1)
        return jnp.where(reached >= k, candidate, found)

    return jax.lax.fori_loop(0, 32, bit,
                             jnp.zeros(keys.shape[:1], jnp.uint32))


def index_scores(q_idx, k_idx, w_idx, products: bool = False):
    """I (C, S) float32 of a chunk of queries: q_idx (C, J, d), w_idx (C, J)
    float32, k_idx (S, d); with `products` also the heads' products
    (J, C, S), which `index_scores_grads` reads."""
    s = jnp.einsum("cjd,sd->jcs", q_idx, k_idx,
                   preferred_element_type=jnp.float32)
    scores = jnp.sum(
        jax.nn.relu(s) * w_idx.astype(jnp.float32).T[:, :, None], axis=0)
    return (scores, s) if products else scores


def index_scores_grads(q_idx, k_idx, w_idx, s, g) -> tuple:
    """The cotangents of q_idx, k_idx and w_idx for the cotangent g (C, S)
    of `index_scores`, from its heads' products s (J, C, S); written out,
    so that the products' cotangent meets the MXU in the operands' dtype."""
    w = w_idx.astype(jnp.float32).T[:, :, None]
    dw = jnp.sum(jax.nn.relu(s) * g[None], axis=-1).T
    ds = (jnp.where(s > 0, w, 0.0) * g[None]).astype(q_idx.dtype)
    # two plain products over the heads' rows side by side, (J C, S)
    heads, c, keys = ds.shape
    ds = ds.reshape(heads * c, keys)
    dq = jnp.dot(ds, k_idx, preferred_element_type=jnp.float32)
    dk = jax.lax.dot_general(
        ds, q_idx.transpose(1, 0, 2).reshape(heads * c, -1),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return dq.reshape(heads, c, -1).transpose(1, 0, 2), dk, dw


def select_keys(scores, allowed, topk: int):
    """The (C, S) bools of each row's min(topk, allowed) largest `scores`
    among the `allowed` keys, ties to the lower key."""
    keys = jnp.where(allowed, ordered_bits(scores), jnp.uint32(0))
    kth = kth_largest(keys, topk)[:, None]
    above = keys > kth
    tied = allowed & (keys == kth) & (kth > 0)
    room = topk - jnp.sum(above, axis=-1)
    # almost always the tie is the K-th key alone: the ranks are counted
    # only where some row has more tied keys than room
    return above | jax.lax.cond(
        jnp.any(jnp.sum(tied, axis=-1) > room),
        lambda: tied & (jnp.cumsum(tied, axis=-1) <= room[:, None]),
        lambda: tied)


def mean_probs(q, k, sel, lse=None) -> jax.Array:
    """(C, S) float32: the mean over the H query heads of the attention's
    probabilities over each row's selected keys; q (C, H, D), k (S, Hkv, D),
    a key/value head's group at a time; `lse` (H, C): each head's
    log-sum-exp of its scores over the row's selected keys (None: taken
    here). A row that selects nothing reads zeros."""
    c, h, d = q.shape
    hkv = k.shape[1]
    groups = q.reshape(c, hkv, h // hkv, d).transpose(1, 0, 2, 3)
    if lse is not None:
        lse = lse.reshape(hkv, h // hkv, c)

    def group(total, inputs):
        qg, kg, lg = inputs
        s = jnp.einsum("cgd,sd->gcs", qg, kg,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        s = jnp.where(sel, s, NEG_INF)
        if lg is None:
            lg = jax.nn.logsumexp(s, axis=-1)
        p = jnp.exp(s - lg[:, :, None])
        return total + jnp.sum(jnp.where(sel, p, 0.0), axis=0), None

    total, _ = jax.lax.scan(group, jnp.zeros(sel.shape, jnp.float32),
                            (groups, k.transpose(1, 0, 2), lse))
    return total / h


def _pack_by_q(sel, blk_k: int):
    """(W, C, blk_k) int32 words of a chunk's (C, S) selected pairs."""
    c, s = sel.shape
    nk = s // blk_k
    planes = -(-nk // SELECT_WORD)
    bits = sel.reshape(c, nk, blk_k).astype(jnp.int32) << (
        jnp.arange(nk, dtype=jnp.int32) % SELECT_WORD)[None, :, None]
    bits = jnp.pad(bits, ((0, 0), (0, planes * SELECT_WORD - nk), (0, 0)))
    return jnp.sum(bits.reshape(c, planes, SELECT_WORD, blk_k),
                   axis=2).transpose(1, 0, 2)


def _use_kernels(impl: str, blk_q: int, blk_k: int, d_idx: int,
                 d: int = 128) -> tuple:
    """(run the chunk's heavy passes as ops/pallas/sparse_index.py's
    kernels?, in interpret mode?): on a TPU (or under BPT_PALLAS_INTERPRET=1)
    unless `impl` is "xla", where the shapes are ones the kernels take (`d`:
    the main attention's head width, where its scores are read)."""
    interpret = jax.default_backend() != "tpu" and _pallas_interpret()
    on = (impl != "xla" and (jax.default_backend() == "tpu" or interpret)
          and blk_q == blk_k and kernels.supported(blk_q, blk_k, d_idx, d))
    return on, interpret


def _chunks(x, n: int, axis: int = 0):
    """`x` as the scan over a row's n chunks reads it: `axis` cut into n
    and the cuts moved to the front."""
    x = x.reshape(x.shape[:axis] + (n, -1) + x.shape[axis + 1:])
    return jnp.moveaxis(x, axis, 0)


def _select_row(q_idx, k_idx, w_idx, seg, topk: int,
                impl: str) -> Selection:
    """One row's selection and counters."""
    s = seg.shape[0]
    blk_q, blk_k, _, wq = select_blocks(s)
    nq, nk = s // blk_q, s // blk_k
    cols = jnp.arange(s, dtype=jnp.int32)
    fused, interpret = _use_kernels(impl, blk_q, blk_k, q_idx.shape[-1])

    def chunk(carry, inputs):
        by_k, block_pairs, candidates = carry
        i, qi, wi, segc = inputs
        rows = i * blk_q + jnp.arange(blk_q, dtype=jnp.int32)
        with jax.named_scope("attention/indexer"):
            scores = (kernels.index_scores(i, qi, k_idx, wi, blk_k, interpret)
                      if fused else index_scores(qi, k_idx, wi))
        with jax.named_scope("attention/select"):
            allowed = ((segc[:, None] == seg[None, :]) & (segc[:, None] > 0)
                       & (cols[None, :] <= rows[:, None]))
            sel = select_keys(scores, allowed, topk)
            by_q = _pack_by_q(sel, blk_k)
            plane = jax.lax.dynamic_index_in_dim(by_k, i // SELECT_WORD, 0,
                                                 keepdims=False)
            by_k = jax.lax.dynamic_update_index_in_dim(
                by_k, plane + (sel.astype(jnp.int32)
                               << (i % SELECT_WORD)), i // SELECT_WORD, 0)
            block_pairs = block_pairs + jnp.sum(
                sel.reshape(blk_q, nk, blk_k), axis=(0, 2), dtype=jnp.int32)
            count = jnp.sum(allowed, dtype=jnp.int32)
            candidates = candidates + jnp.stack(
                [count // COUNT_UNIT, count % COUNT_UNIT])
        return (by_k, block_pairs, candidates), by_q

    init = (jnp.zeros((wq, blk_q, s), jnp.int32),
            jnp.zeros((nk,), jnp.int32), jnp.zeros((2,), jnp.int32))
    (by_k, block_pairs, candidates), by_q = jax.lax.scan(
        chunk, init, (jnp.arange(nq, dtype=jnp.int32), _chunks(q_idx, nq),
                      _chunks(w_idx, nq), _chunks(seg, nq)))
    # (nq, W, blk_q, blk_k) -> (W, S, blk_k)
    by_q = by_q.transpose(1, 0, 2, 3).reshape(-1, s, blk_k)
    return Selection(by_q, by_k, block_pairs, candidates)


def index_select(q_idx, k_idx, w_idx, segment_ids, topk: int,
                 impl: str = "auto") -> Selection:
    """q_idx (B, S, J, d), k_idx (B, S, d), w_idx (B, S, J) float32: the
    index's queries, its one key head and its head weights, rotated and
    scaled; segment_ids (B, S), the packing contract's. -> Selection (the
    module docstring): integers, made from detached inputs.
    `impl`: "xla" keeps the chunk's passes in plain XLA, anything else
    takes the kernels of ops/pallas/sparse_index.py where they run
    (`_use_kernels`)."""
    q_idx, k_idx, w_idx = (jax.lax.stop_gradient(x)
                           for x in (q_idx, k_idx, w_idx))
    rows = [_select_row(q_idx[b], k_idx[b], w_idx[b], segment_ids[b], topk,
                        impl) for b in range(q_idx.shape[0])]
    return Selection(
        checkpoint_name(jnp.stack([r.by_q for r in rows]), "dsa_select"),
        checkpoint_name(jnp.stack([r.by_k for r in rows]), "dsa_select"),
        sum(r.block_pairs for r in rows), sum(r.candidates for r in rows))


def _kl_row(q_idx, k_idx, w_idx, q, k, lse, by_q, impl: str,
            with_grads: bool):
    """One row's KL sum, and with `with_grads` its gradients with respect to
    q_idx, k_idx and w_idx; lse (H, S), by_q (W, S, blk_k)."""
    s = k_idx.shape[0]
    blk_q, blk_k, _, _ = select_blocks(s)
    nq = s // blk_q
    fused, interpret = _use_kernels(impl, blk_q, blk_k, q_idx.shape[-1],
                                    q.shape[-1])

    def chunk(carry, inputs):
        dk_idx, kl_sum = carry
        i, qi, wi, qm, lse_c, words = inputs
        with jax.named_scope("attention/indexer_loss"):
            sel = unpack_select(words[None], s)[0]
            if fused:
                scores = kernels.index_scores(i, qi, k_idx, wi, blk_k,
                                              interpret)
                p = kernels.mean_probs(i, qm, k, lse_c, words, blk_k,
                                       interpret)
            else:
                scores, products = index_scores(qi, k_idx, wi, products=True)
                p = mean_probs(qm, k, sel, lse_c)
            log_pi = jnp.where(sel, scores, NEG_INF)
            log_pi = log_pi - jax.nn.logsumexp(log_pi, axis=-1, keepdims=True)
            live = sel & (p > 0.0)
            kl_sum = kl_sum + jnp.sum(jnp.where(
                live, p * (jnp.log(jnp.where(live, p, 1.0)) - log_pi), 0.0))
            grads = ()
            if with_grads:
                g = jnp.where(sel, jnp.exp(log_pi) - p, 0.0)
                dqi, dki, dwi = (
                    kernels.index_scores_grads(i, qi, k_idx, wi, g, blk_k,
                                               interpret)
                    if fused else index_scores_grads(qi, k_idx, wi, products,
                                                     g))
                dk_idx = dk_idx + dki
                grads = (dqi, dwi)
        return (dk_idx, kl_sum), grads

    (dk_idx, kl_sum), grads = jax.lax.scan(
        chunk, (jnp.zeros(k_idx.shape, jnp.float32),
                jnp.zeros([], jnp.float32)),
        (jnp.arange(nq, dtype=jnp.int32), _chunks(q_idx, nq),
         _chunks(w_idx, nq), _chunks(q, nq), _chunks(lse, nq, 1),
         _chunks(by_q, nq, 1)))
    if not with_grads:
        return kl_sum, ()
    dq_idx, dw_idx = grads
    return kl_sum, (dq_idx.reshape(q_idx.shape).astype(q_idx.dtype),
                    dk_idx.astype(k_idx.dtype),
                    dw_idx.reshape(w_idx.shape).astype(w_idx.dtype))


def _kl_rows(q_idx, k_idx, w_idx, q, k, lse, by_q, impl, with_grads):
    """`_kl_row` over the batch: the sums added, the gradients stacked."""
    sums, grads = zip(*(
        _kl_row(q_idx[b], k_idx[b], w_idx[b], q[b], k[b], lse[b], by_q[b],
                impl, with_grads) for b in range(q.shape[0])))
    return sum(sums), tuple(jnp.stack(g) for g in zip(*grads))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def index_kl(q_idx, k_idx, w_idx, q, k, lse, by_q,
             impl: str = "auto") -> jax.Array:
    """sum over the real tokens t of KL_t (the module docstring), () float32.
    q_idx, k_idx, w_idx: as `index_select` took them; q (B, S, H, D),
    k (B, S, Hkv, D): the main attention's, as its kernels read them;
    lse (B, H, S) float32: its log-sum-exp over each query's selected keys
    (`dot_product_attention(..., select=, with_lse=True)`); by_q: the
    selection's. Differentiable in q_idx, k_idx and w_idx; the rest is data
    (no gradient reaches it). `impl`: as `index_select`'s."""
    return _kl_rows(q_idx, k_idx, w_idx, q, k, lse, by_q, impl, False)[0]


def _kl_fwd(q_idx, k_idx, w_idx, q, k, lse, by_q, impl):
    kl_sum, grads = _kl_rows(q_idx, k_idx, w_idx, q, k, lse, by_q, impl,
                             True)
    grads = tuple(checkpoint_name(g, "dsa_kl_grads") for g in grads)
    return kl_sum, (grads, q, k, lse, by_q)


def _kl_bwd(impl, saved, g):
    (dq_idx, dk_idx, dw_idx), q, k, lse, by_q = saved
    with jax.named_scope("attention/indexer_loss"):
        scaled = tuple((g * d.astype(jnp.float32)).astype(d.dtype)
                       for d in (dq_idx, dk_idx, dw_idx))
    return scaled + (jnp.zeros_like(q), jnp.zeros_like(k),
                     jnp.zeros_like(lse),
                     jax.custom_derivatives.zero_from_primal(by_q))


index_kl.defvjp(_kl_fwd, _kl_bwd)


def full_row_selected_pairs(seq_len: int, topk: int) -> int:
    """sum over t of min(topk, t + 1): what ONE document that fills a row
    selects (31,458,304 of 134,225,920 causal pairs at 16,384 and 2,048)."""
    full = min(topk, seq_len)
    return full * (full + 1) // 2 + (seq_len - full) * topk
