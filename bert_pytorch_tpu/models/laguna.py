"""The `laguna` family: a pre-norm decoder whose layers all attend, three
over a sliding window to one over the whole document, with a head count and
a rotary table per layer and a sigmoid gate per head on the attention's
output; a dense SwiGLU MLP in the leading layer, sigmoid-routed experts
beside one shared expert after it.

Written from the family's public config (config.LagunaConfig names the
keys). For x of shape (T, hidden), layer l of kind t_l (`layer_types[l]`:
full | sliding), H_l = `num_attention_heads_per_layer[l]`, Hkv key/value
heads, D = `head_dim`:

    a = RMSNorm(x; input_layernorm)
    q, k, v = a Wq (H_l heads of D), a Wk, a Wv (Hkv heads); no bias, no
              q/k norm
    q[..., :R_t], k[..., :R_t] = rot(q[..., :R_t]), rot(k[..., :R_t]);
              the other D - R_t dims pass. R_t = D * partial_rotary_factor
              of the kind's `rope_parameters`;
              rot(u) = u * (c cos(p f)) + rotate_half(u) * (c sin(p f)),
              p the position inside the document
        sliding (rope_type default): f_i = theta^(-2i/R), c = 1
        full (rope_type yarn):       e_i = theta^(-2i/R);
              dim(n) = R ln(L / (2 pi n)) / (2 ln theta), L =
              original_max_position_embeddings;
              lo = max(floor(dim(beta_fast)), 0),
              hi = min(ceil(dim(beta_slow)), R - 1);
              r_i = clip((i - lo) / (hi - lo), 0, 1);
              f_i = (e_i / factor) r_i + e_i (1 - r_i); c = attention_factor
              (ops/decoder_ops.rotary_table)
    s_ij = q_i . k_j / sqrt D  over j of i's document, j <= i, and
                               i - j < sliding_window if sliding
    o_n = softmax(s) v for query head n, reading key/value head
          n // (H_l / Hkv)
    g = sigmoid(a Wg), Wg (hidden, H_l);  h = x + concat_n(g_n o_n) Wo
    m = RMSNorm(h; post_attention_layernorm)
    dense:   y = h + W2(silu(W1 m) * W3 m), `intermediate_size` wide
    sparse:  sc = sigmoid(m W_r) (all experts);
             E_i = the k largest of sc_i + b (b a held buffer, no gradient);
             w_ie = scaling * sc_ie / (sum_{e' in E_i} sc_ie' + 1e-6)
             y = h + shared(m)
                   + sum_{e in E_i, e held} w_ie W2_e(silu(W1_e m_i) * W3_e m_i)

with RMSNorm as models/decoder.py's (eps 1e-6 here), `shared` one SwiGLU
expert of the experts' width on every token, unweighted. After the last
layer one more RMSNorm (`final_norm`), and the logits are that times an
UNTIED `lm_head` (V, hidden) transposed. The layer holds the experts
`experts_held` (ops/moe.py): what the absent experts would add is left out.

What the config does not say and this reading sets (the benchmark's
configuration file lists each under `assumed` with its reason): the router's
score function, renormalisation and selection bias (the convention of the
256-expert, 8-a-token, scaling-factor, one-shared-expert models, which is
what ops/moe.route computes); `gating` as ONE sigmoid gate per query head,
computed from the normed input a; SiLU; pre-norm; the rotated dims are the
head's first R.

A padding slot (segment 0) attends nowhere; it is routed like any token.

Norms, the router, rotary, attention's softmax, the gate's sigmoid and the
loss are float32; matrix products take `dtype` operands (bfloat16) and
accumulate in float32. The rotation (ops/decoder_ops.rotary) reads q's and
k's heads where the fused projection left them and hands them on in
`dtype`: at heads of 128 on a TPU one kernel call a direction
(ops/pallas/rotary.py: `rotary_fwd` / `rotary_bwd`, float32 in VMEM), plain
jax.numpy everywhere else.

Layers are separate modules in a Python loop (their kind, head count and
table are static), each rematerialised under `checkpoint_activations`
(`remat_policy`: decoder.LM_REMAT_POLICIES). The trunk, the loss (the head a
block of tokens at a time) and the router's float32 are the shared module's
(models/decoder.py).

Scopes: a layer's projections and kernels are `attention/attention_window`
or `attention/attention_full` (as smallthinker's: what reads `attention`
reads both kinds), its rotation `attention/rotary`, its gate
`attention/gate`; the routed FFN `moe/router|dispatch|experts|combine`
(ops/moe.py) and `moe/shared`; the dense FFN `mlp`; `rmsnorm`, `lm_head`,
`loss`.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bert_pytorch_tpu.config import LagunaConfig
from bert_pytorch_tpu.models.decoder import (CausalLMTrunk, DenseMLP,
                                             RMSNorm, RoutedExperts, _init,
                                             _Linear, band_pairs)
# models/families.py takes the family's loss builder and `keep_float32`
# (the router and its selection bias are read in float32) from this module
from bert_pytorch_tpu.models.decoder import (  # noqa: F401
    keep_float32, pretrain_loss_fn_builder)
from bert_pytorch_tpu.ops.attention import dot_product_attention
from bert_pytorch_tpu.ops.decoder_ops import rotary, rotary_table

Dtype = Any


class Attention(nn.Module):
    config: LagunaConfig
    kind: str           # "sliding": the band of `sliding_window`; "full"
    heads: int          # this layer's query heads
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, segment_ids, position_ids):
        cfg = self.config
        h, hkv, d = self.heads, cfg.num_key_value_heads, cfg.head_dim
        bsz, s, e = x.shape
        window = cfg.sliding_window if self.kind == "sliding" else 0
        layer = "attention_window" if window else "attention_full"
        inv_freq, rotated, factor = rotary_table(d, cfg.rope(self.kind))
        with jax.named_scope(layer):
            # three tensors (LAMB takes one trust ratio each), one product
            kernels = [self.param(f"{n}_proj", _init(cfg), (e, heads * d),
                                  jnp.float32)
                       for n, heads in (("q", h), ("k", hkv), ("v", hkv))]
            qkv = jnp.dot(
                x.astype(self.dtype),
                jnp.concatenate(kernels, axis=1).astype(self.dtype),
                preferred_element_type=jnp.float32).astype(self.dtype)
            qkv = checkpoint_name(qkv, "in_proj_out")
            v = qkv[..., (h + hkv) * d:].reshape(bsz, s, hkv, d)
        with jax.named_scope("rotary"):
            # q's and k's heads, read where the product left them
            q, k = (rotary(qkv, position_ids, inv_freq=inv_freq,
                           rotated=rotated, factor=factor,
                           out_dtype=self.dtype, heads=(first, n, d))
                    for first, n in ((0, h), (h, hkv)))
        with jax.named_scope(layer):
            ctx = dot_product_attention(
                q, k, v, segment_ids=segment_ids, impl=cfg.attention_impl,
                causal=True, window=window or None)
        gate_kernel = self.param("gate_proj", _init(cfg), (e, h),
                                 jnp.float32)
        with jax.named_scope("gate"):
            gate = jax.nn.sigmoid(jnp.dot(
                x.astype(self.dtype), gate_kernel.astype(self.dtype),
                preferred_element_type=jnp.float32))
            ctx = (ctx.astype(jnp.float32) * gate[..., None]).astype(
                self.dtype)
        with jax.named_scope(layer):
            return _Linear(e, cfg, self.dtype, name="out_proj")(
                ctx.reshape(bsz, s, h * d))


class DecoderLayer(nn.Module):
    config: LagunaConfig
    kind: str
    heads: int
    ffn: str            # "dense" or "moe"
    dtype: Dtype = jnp.bfloat16

    routed = property(lambda self: self.ffn == "moe")

    @nn.compact
    def __call__(self, x, segment_ids, position_ids):
        cfg = self.config
        normed = RMSNorm(cfg.norm_eps, self.dtype, name="input_layernorm")(x)
        h = x + Attention(cfg, self.kind, self.heads, self.dtype,
                          name="attention")(normed, segment_ids, position_ids)
        normed = RMSNorm(cfg.norm_eps, self.dtype,
                         name="post_attention_layernorm")(h)
        if self.ffn == "dense":
            return (h + DenseMLP(cfg, self.dtype, name="mlp")(normed),
                    jnp.zeros((cfg.num_experts,), jnp.int32),
                    jnp.zeros([], jnp.int32))
        out, load, dropped = RoutedExperts(cfg, self.dtype, name="moe")(
            normed)
        with jax.named_scope("moe/shared"):
            out = out + DenseMLP(cfg, self.dtype,
                                 cfg.shared_expert_intermediate_size,
                                 name="shared_expert")(normed)
        return h + out, load, dropped


class LagunaForCausalLM(CausalLMTrunk):
    """decoder.CausalLMTrunk over this family's layers: the loads and drops
    are the routed layers' (n_routed, E_held) and (n_routed,)."""
    layer = DecoderLayer


def train_flops_per_row(cfg: LagunaConfig, seq_len: int) -> float:
    """Forward + backward FLOPs of one full row of seq_len tokens, as this
    rank computes them: 6 x weights x tokens for the dense products (each
    token through num_experts_per_tok * held / total routed experts on
    average, and the shared one) + 12 x the layer's heads x D for every
    (query, key) pair of its causal triangle or band. An upper estimate for
    packed rows (documents shorter than the row attend less)."""
    e, d, hkv = cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads
    expert = 3 * e * cfg.moe_intermediate_size
    weights, attention = cfg.vocab_size * e, 0.0
    for kind, h, ffn in cfg.layer_kinds:
        weights += e * (h + 2 * hkv) * d + e * h + h * d * e
        if ffn == "dense":
            weights += 3 * e * cfg.intermediate_size
        else:
            weights += e * cfg.router_width + expert * (
                1 + cfg.num_experts_per_tok * cfg.num_experts
                / cfg.router_width)
        attention += 12.0 * h * d * band_pairs(
            seq_len, cfg.sliding_window if kind == "sliding" else 0)
    return 6.0 * weights * seq_len + attention
