"""The `keye` family: a pre-norm decoder whose every layer attends over the
keys a learned index selects (a lightweight indexer scores every earlier
token of the document and each query attends to its `sa_topk` best, one
selection for all of its heads), then routes over softmax-scored experts.
The language model of a vision-language model; the vision tower is not here.

Written from the family's public config (config.KeyeConfig names the keys).
For x of shape (T, hidden), every layer alike; H query heads on Hkv
key/value heads of D; J index heads of d on ONE index key head;
K = `sa_topk`; n_t = t's position inside its document + 1:

    a = RMSNorm(x; input_layernorm)
    q = RMSNorm_D(a Wq) (H heads), kk = RMSNorm_D(a Wk) (Hkv heads),
    v = a Wv (Hkv heads); no bias; one (D,) gain each for q and kk
    q, kk = rot(q), rot(kk): the whole head, `rope_theta`, rotate-half, p the
            position inside the document
    the indexer, reading ai = stop_gradient(a):
        qI = rot_d(ai WIq) (J heads of d);  kI = rot_d(LayerNorm_d(ai WIk))
        wI = (ai WIw) / sqrt(J d)                       (J weights a token)
        I_ts = sum_j wI_tj relu(qI_tj . kI_s)           float32, s <= t, s of
                                                        t's document
    S_t = the min(K, n_t) keys with the largest I_ts, ties to the lower s
    s_tsn = q_tn . kk_s,n//G / sqrt D for s in S_t
    o_tn = softmax_s(s_tsn) v_s,n//G;   h = x + concat_n(o_tn) Wo
    m = RMSNorm(h; post_attention_layernorm)
    E_t = the k largest of m Wr;  w_te = softmax over those k
    y = h + sum_{e in E_t, e held} w_te W2_e(silu(W1_e m_t) * W3_e m_t)

then `final_norm` and an UNTIED head. The step's loss is L = L_LM + L_I:

    L_LM = next-token cross-entropy over the positions whose successor is
           in the same document
    p_ts = stop_gradient((1/H) sum_n softmax_s(s_tsn))  for s in S_t
    L_I  = sum over layers of mean over real tokens t of
           KL(p_t || softmax_{s in S_t}(I_ts))

so the indexer's leaves (index_q_proj, index_k_proj, index_w_proj, the
LayerNorm's) take their gradient from L_I alone (its input is detached, the
selection is discrete, I enters nowhere else) and every other parameter
from L_LM alone: the sparse training stage of DeepSeek-V3.2-Exp's sparse
attention, carried from that model's one latent key head to Hkv key/value
heads (ops/sparse_index.py has the two ops and the gradient rule).

A layer's order: the index scores and the exact selection
(`index_select`), the main attention's forward kernel over the selection,
which hands out its log-sum-exp beside the context, then the KL term
(`index_kl`), whose target p_ts is exp(s_tsn - lse_tn) averaged over the
heads: the attention's own normaliser, so the scores are read once more and
not twice.

What the config does not say and this reading sets (the benchmark's
configuration file lists each under `assumed` with its reason): q_norm and
k_norm; on text the three `mrope_section` position components are all p, so
the frequency pairs form the plain table (tests/test_keye.py builds the
general one and says so); the indexer reads
the normed input directly; LayerNorm on kI, the rotation of all d index
dims at the layer's theta, the weight scale 1 / sqrt(J d); the KL term's
weight 1 and its mean over real tokens; ties to the lower index;
`q_chunk_size` / `kv_chunk_size` as the tiling of the index scores, NOT as a
selection by blocks; SiLU; pre-norm.

A padding slot (segment 0) attends nowhere, selects nothing and adds
nothing to L_I; it is routed like any token.

Norms, the router, rotary, both softmaxes, the index scores' sum over heads
and the losses are float32; matrix products (the indexer's among them) take
`dtype` operands (bfloat16) and accumulate in float32. The rotation of q
and k (ops/decoder_ops.rotary) takes the head norms' float32 and hands on
`dtype`: at heads of 128 on a TPU one kernel call a direction
(ops/pallas/rotary.py: `rotary_fwd` / `rotary_bwd`), plain jax.numpy
everywhere else and for the index heads of 64.

Layers are separate modules in a Python loop, each rematerialised under
`checkpoint_activations` (`remat_policy`: REMAT_POLICIES; "dense" keeps
decoder.DENSE_SAVED (the forward kernel's context and log-sum-exp among
them) and the selection with the KL term's small gradients, so the backward
pass runs neither the forward kernel nor either of the two index passes
again). The model hands back the final norm's output and the head, not
logits: the loss (losses.next_token_loss_blocked) takes the head a block of
tokens at a time.

Scopes: under `attention`: `indexer` (its projections, norm, rotation and
the scores), `select`, `attn_core` (ops/attention.py), `rotary`,
`indexer_loss`; the routed FFN `moe/router|dispatch|experts|combine`
(ops/moe.py); `rmsnorm`, `lm_head`, `loss`.
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bert_pytorch_tpu.config import KeyeConfig
from bert_pytorch_tpu.models import losses
from bert_pytorch_tpu.models.decoder import (DENSE_SAVED, LOSS_BLOCK_ROWS,
                                             RMSNorm, RoutedExperts, _init,
                                             _Linear, expert_scalars)
# models/families.py takes the family's `keep_float32` (the router is read
# in float32) from this module
from bert_pytorch_tpu.models.decoder import keep_float32  # noqa: F401
from bert_pytorch_tpu.ops.attention import dot_product_attention
from bert_pytorch_tpu.ops.decoder_ops import rotary
from bert_pytorch_tpu.ops.sparse_index import (full_row_selected_pairs,
                                               index_kl, index_select)

Dtype = Any

# What the rematerialised layer keeps beside its input. "dense": the shared
# names and, of ops/sparse_index.py, the packed selection and the KL term's
# gradients with respect to the indexer's three small outputs (64 + 36 MB a
# layer at 16,384 tokens), so that the backward pass runs neither index pass
# (scores and selection; scores again, the KL term's probabilities and the
# scores' backward) at all.
REMAT_POLICIES = {
    "nothing": jax.checkpoint_policies.nothing_saveable,
    "dense": jax.checkpoint_policies.save_only_these_names(
        *DENSE_SAVED, "dsa_select", "dsa_kl_grads"),
}
REMAT_POLICIES["auto"] = REMAT_POLICIES["dense"]


class _LayerNorm(nn.Module):
    """(x - mean) * rsqrt(var + eps) * scale + bias over the last axis, in
    float32 (the index key head's)."""
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],),
                          jnp.float32)
        x = x.astype(jnp.float32)
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + self.eps) * scale + bias


class Attention(nn.Module):
    config: KeyeConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, segment_ids, position_ids):
        cfg = self.config
        h, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        j, di = cfg.sa_indexer_num_heads, cfg.sa_indexer_head_dim
        bsz, s, e = x.shape
        # three tensors (LAMB takes one trust ratio each), one product
        kernels = [self.param(f"{n}_proj", _init(cfg), (e, heads * d),
                              jnp.float32)
                   for n, heads in (("q", h), ("k", hkv), ("v", hkv))]
        qkv = jnp.dot(x.astype(self.dtype),
                      jnp.concatenate(kernels, axis=1).astype(self.dtype),
                      preferred_element_type=jnp.float32).astype(self.dtype)
        qkv = checkpoint_name(qkv, "in_proj_out")
        q, k, v = jnp.split(qkv, [h * d, (h + hkv) * d], axis=-1)
        q = q.reshape(bsz, s, h, d)
        k = k.reshape(bsz, s, hkv, d)
        v = v.reshape(bsz, s, hkv, d)
        q = RMSNorm(cfg.norm_eps, jnp.float32, name="q_norm")(q)
        k = RMSNorm(cfg.norm_eps, jnp.float32, name="k_norm")(k)
        with jax.named_scope("rotary"):
            q, k = (rotary(u, position_ids, cfg.rope_theta,
                           out_dtype=self.dtype) for u in (q, k))

        index_kernels = [
            self.param(f"index_{n}_proj", _init(cfg), (e, width), jnp.float32)
            for n, width in (("q", j * di), ("k", di), ("w", j))]
        index_k_norm = _LayerNorm(cfg.norm_eps, name="index_k_norm")
        with jax.named_scope("indexer"):
            # the indexer learns from its own loss: nothing of it reaches x
            proj = jnp.dot(
                jax.lax.stop_gradient(x).astype(self.dtype),
                jnp.concatenate(index_kernels, axis=1).astype(self.dtype),
                preferred_element_type=jnp.float32)
            q_idx, k_idx, w_idx = jnp.split(proj, [j * di, (j + 1) * di],
                                            axis=-1)
            k_idx = index_k_norm(k_idx)
            q_idx = rotary(q_idx.reshape(bsz, s, j, di), position_ids,
                           cfg.rope_theta).astype(self.dtype)
            k_idx = rotary(k_idx[:, :, None, :], position_ids,
                           cfg.rope_theta)[:, :, 0].astype(self.dtype)
            w_idx = w_idx / float(j * di) ** 0.5
        # the selection, the main attention over it, then the KL term,
        # which takes its target's normaliser from the attention's kernel
        picked = index_select(q_idx, k_idx, w_idx, segment_ids, cfg.sa_topk,
                              cfg.attention_impl)
        ctx, lse = dot_product_attention(
            q, k, v, segment_ids=segment_ids, impl=cfg.attention_impl,
            causal=True, select=(picked.by_q, picked.by_k), with_lse=True)
        kl_sum = index_kl(
            q_idx, k_idx, w_idx, jax.lax.stop_gradient(q),
            jax.lax.stop_gradient(k), jax.lax.stop_gradient(lse),
            picked.by_q, cfg.attention_impl)
        out = _Linear(e, cfg, self.dtype, name="out_proj")(
            ctx.reshape(bsz, s, h * d))
        return out, (kl_sum, picked.block_pairs, picked.candidates)


class DecoderLayer(nn.Module):
    config: KeyeConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, segment_ids, position_ids):
        cfg = self.config
        normed = RMSNorm(cfg.norm_eps, self.dtype, name="input_layernorm")(x)
        attended, picked = Attention(cfg, self.dtype, name="attention")(
            normed, segment_ids, position_ids)
        h = x + attended
        normed = RMSNorm(cfg.norm_eps, self.dtype,
                         name="post_attention_layernorm")(h)
        out, load, dropped = RoutedExperts(cfg, self.dtype, name="moe")(
            normed)
        # the KL term feeds nothing of the layer: tied to the layer's
        # output, it runs before the next layer starts, and the q, k and
        # words it reads are not left waiting while further layers run
        # (the output is dead in the backward pass's recomputation, so the
        # tie makes nothing run twice)
        y, picked = jax.lax.optimization_barrier((h + out, picked))
        return y, load, dropped, picked


class KeyeForCausalLM(nn.Module):
    """(input_ids, segment_ids, position_ids), each (B, S) -> (the final
    norm's output (B, S, hidden) in `dtype`, the head (V, hidden) in
    `dtype`, per layer: tokens per held expert (L, E_held) int32 and held
    pairs not computed (L,) int32, and the selection's: the KL sums over
    real tokens (L,) float32, selected pairs by key block (L, S / blk)
    int32, candidate pairs in two halves (L, 2) int32). segment_ids: the
    packing contract's (1..n per row, 0 = pad); position_ids restart at each
    document."""
    config: KeyeConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, input_ids, segment_ids, position_ids):
        cfg = self.config
        layer_cls = DecoderLayer
        if cfg.checkpoint_activations:
            layer_cls = nn.remat(DecoderLayer,
                                 policy=REMAT_POLICIES[cfg.remat_policy])
        with jax.named_scope("decoder"):
            table = self.param("embed_tokens", _init(cfg),
                               (cfg.vocab_size, cfg.hidden_size),
                               jnp.float32)
            head = self.param("lm_head", _init(cfg),
                              (cfg.vocab_size, cfg.hidden_size), jnp.float32)
            with jax.named_scope("embeddings"):
                x = table.astype(self.dtype)[input_ids]
            loads, drops, picks = [], [], []
            for i in range(cfg.num_hidden_layers):
                x, load, dropped, picked = layer_cls(
                    cfg, self.dtype, name=f"layer_{i}")(
                        x, segment_ids, position_ids)
                loads.append(load)
                drops.append(dropped)
                picks.append(picked)
            x = RMSNorm(cfg.norm_eps, self.dtype, name="final_norm")(x)
        return (x, head.astype(self.dtype), jnp.stack(loads),
                jnp.stack(drops), tuple(jnp.stack(p) for p in zip(*picks)))


def selection_scalars(kl, block_pairs, candidates, real_tokens) -> dict:
    """A micro-batch's scalars of the selection (telemetry/expert_load.py
    sums them): real tokens, and per layer the KL sum over its real tokens,
    the candidate pairs (causal pairs inside documents) in the two halves
    of ops/sparse_index.COUNT_UNIT, whose int32 sums over a step do not
    wrap where the count's would, and the selected pairs by key block (a
    layer's selected pairs are their sum)."""
    scalars = {"dsa_tokens": real_tokens}
    for layer in range(kl.shape[0]):
        scalars[f"dsa_l{layer}_kl"] = kl[layer]
        scalars[f"dsa_l{layer}_candidates_hi"] = candidates[layer, 0]
        scalars[f"dsa_l{layer}_candidates_lo"] = candidates[layer, 1]
        for j in range(block_pairs.shape[1]):
            scalars[f"dsa_l{layer}_kb{j}"] = block_pairs[layer, j]
    return scalars


def total_loss(lm_loss, indexer_kl):
    """L = L_LM + L_I, the KL term at weight 1."""
    return lm_loss + indexer_kl


def pretrain_loss_fn_builder(model) -> Callable:
    """loss_fn_builder of training/pretrain.build_pretrain_step: L_LM (the
    head a block of tokens at a time) + L_I, both terms among the step's
    `means` (`lm_loss`, `indexer_kl`), and the routed layers' and the
    selection's counters among its scalars."""
    cfg = model.config

    def loss_fn(params, batch, dropout_rng, deterministic: bool = False):
        hidden, head, load, dropped, picked = model.apply(
            {"params": params}, batch["input_ids"], batch["segment_ids"],
            batch["position_ids"])
        lm_loss, count = losses.next_token_loss_blocked(
            hidden, head, batch["input_ids"], batch["segment_ids"],
            LOSS_BLOCK_ROWS)
        kl = picked[0]
        with jax.named_scope("loss"):
            real = jnp.sum(batch["segment_ids"] > 0).astype(jnp.int32)
            indexer_kl = jnp.sum(kl) / jnp.maximum(real, 1)
            loss = total_loss(lm_loss, indexer_kl)
        with jax.named_scope("metrics"):
            scalars = expert_scalars(cfg, count, batch["input_ids"].size,
                                     load, dropped)
            scalars.update(selection_scalars(*picked, real))
        return loss, {"scalars": scalars,
                      "means": {"lm_loss": lm_loss, "indexer_kl": indexer_kl}}

    return loss_fn


def train_flops_per_row(cfg: KeyeConfig, seq_len: int) -> float:
    """Forward + backward FLOPs of one full row of seq_len tokens, as this
    rank computes them: 6 x weights x tokens for the dense products (each
    token through num_experts_per_tok * held / total experts on average);
    12 x H x D for every SELECTED pair of the main attention; 6 x J x d for
    every causal pair of the index scores; and the KL term's second reading
    of the main attention's scores, 2 x H x D a selected pair (forward
    only: its target is detached). An upper estimate for packed rows."""
    e, d = cfg.hidden_size, cfg.head_dim
    h, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    j, di = cfg.sa_indexer_num_heads, cfg.sa_indexer_head_dim
    layer = (e * (h + 2 * hkv) * d + h * d * e + e * (j * di + di + j)
             + e * cfg.router_width
             + 3 * e * cfg.moe_intermediate_size * cfg.num_experts_per_tok
             * cfg.num_experts / cfg.router_width)
    weights = cfg.vocab_size * e + cfg.num_hidden_layers * layer
    selected = full_row_selected_pairs(seq_len, cfg.sa_topk)
    causal = seq_len * (seq_len + 1) // 2
    pairs = (12.0 + 2.0) * h * d * selected + 6.0 * j * di * causal
    return 6.0 * weights * seq_len + cfg.num_hidden_layers * pairs
