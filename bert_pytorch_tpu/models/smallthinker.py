"""The `smallthinker` family: a pre-norm decoder whose layers all attend,
three over a sliding window with rotary positions to one over the whole
document without positions, each followed by softmax-routed ReLU-gated
experts whose router reads the layer's input.

Written from the family's public config (config.SmallThinkerConfig names
the keys). For x of shape (T, hidden), layer l with w_l =
`sliding_window_layout[l]` and p_l = `rope_layout[l]` computes

    r = x W_r                                  router logits, float32, from
                                               the layer's INPUT
    a = RMSNorm(x; input_layernorm)
    q, k, v = a Wq (H heads of D), a Wk, a Wv (Hkv heads); no bias, no norm
    if p_l: q, k = rotary(q), rotary(k)        all of D, rotate-half,
                                               positions restart per document
    s_ij = q_i . k_j / sqrt D  over j of i's document with j <= i, and
                               i - j < sliding_window_size if w_l
    h = x + concat_heads(softmax(s) v) Wo      query head n reads key/value
                                               head n // (H / Hkv)
    m = RMSNorm(h; post_attention_layernorm)
    E_i = the k largest of r_i;  g_ie = exp(r_ie) / sum_{e' in E_i} exp(r_ie')
    y = h + sum_{e in E_i, e held} g_ie W2_e(relu(W1_e m_i) * W3_e m_i)

with RMSNorm as models/decoder.py's (eps 1e-6 here). After the last layer
one more RMSNorm (`final_norm`), and the logits are that times an UNTIED
`lm_head` (V, hidden) transposed. No dense layer, no shared expert, no
selection bias, no scaling factor. The layer holds the experts
`experts_held` (ops/moe.py): what the absent experts would add is left out.

A padding slot (segment 0) attends nowhere; it is routed like any token.

Norms, the router's logits and the softmax over the selected, rotary,
attention's softmax and the loss are float32; matrix products take `dtype`
operands (bfloat16) and accumulate in float32. The router's gradient
reaches x, not m. The rotation (ops/decoder_ops.rotary) reads q's and k's
heads where the fused projection left them and hands them on in `dtype`: at
heads of 128 on a TPU one kernel call a direction (ops/pallas/rotary.py:
`rotary_fwd` / `rotary_bwd`, float32 in VMEM), plain jax.numpy everywhere
else.

Layers are separate modules in a Python loop (their window and positions are
static, so one scan does not carry them), each rematerialised under
`checkpoint_activations` (`remat_policy`: decoder.LM_REMAT_POLICIES). The
trunk, the loss (the head a block of tokens at a time) and the router's
float32 are the shared module's (models/decoder.py).

Scopes: a layer's attention is `attention/attention_window` or
`attention/attention_full` (both under `attention`, so that what reads the
one reads both kinds), a banded layer's rotation
`attention/attention_window/rotary`, the experts
`moe/router|dispatch|experts|combine` (ops/moe.py), `rmsnorm`, `lm_head`,
`loss`.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bert_pytorch_tpu.config import SmallThinkerConfig
from bert_pytorch_tpu.models.decoder import (CausalLMTrunk, RMSNorm,
                                             RoutedExperts, _init, _Linear,
                                             band_pairs)
# models/families.py takes the family's loss builder and `keep_float32`
# (the router is read in float32) from this module
from bert_pytorch_tpu.models.decoder import (  # noqa: F401
    keep_float32, pretrain_loss_fn_builder)
from bert_pytorch_tpu.ops.attention import dot_product_attention
from bert_pytorch_tpu.ops.decoder_ops import rotary

Dtype = Any


class Attention(nn.Module):
    config: SmallThinkerConfig
    window: int         # the band's width; 0: the whole document
    rope: bool
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, segment_ids, position_ids):
        cfg = self.config
        h, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        bsz, s, e = x.shape
        with jax.named_scope("attention_window" if self.window
                             else "attention_full"):
            # three tensors (LAMB takes one trust ratio each), one product
            kernels = [self.param(f"{n}_proj", _init(cfg), (e, heads * d),
                                  jnp.float32)
                       for n, heads in (("q", h), ("k", hkv), ("v", hkv))]
            qkv = jnp.dot(
                x.astype(self.dtype),
                jnp.concatenate(kernels, axis=1).astype(self.dtype),
                preferred_element_type=jnp.float32).astype(self.dtype)
            qkv = checkpoint_name(qkv, "in_proj_out")
            q, k, v = (u.reshape(bsz, s, -1, d) for u in jnp.split(
                qkv, [h * d, (h + hkv) * d], axis=-1))
            if self.rope:
                with jax.named_scope("rotary"):
                    # q's and k's heads, read where the product left them
                    q, k = (rotary(qkv, position_ids, cfg.rope_theta,
                                   out_dtype=self.dtype, heads=(first, n, d))
                            for first, n in ((0, h), (h, hkv)))
            ctx = dot_product_attention(
                q, k, v, segment_ids=segment_ids, impl=cfg.attention_impl,
                causal=True, window=self.window or None)
            return _Linear(e, cfg, self.dtype, name="out_proj")(
                ctx.reshape(bsz, s, h * d))


class DecoderLayer(nn.Module):
    config: SmallThinkerConfig
    window: int
    rope: bool
    dtype: Dtype = jnp.bfloat16

    routed = True       # every layer's load is the trunk's to stack

    @nn.compact
    def __call__(self, x, segment_ids, position_ids):
        cfg = self.config
        normed = RMSNorm(cfg.norm_eps, self.dtype, name="input_layernorm")(x)
        h = x + Attention(cfg, self.window, self.rope, self.dtype,
                          name="attention")(normed, segment_ids, position_ids)
        normed = RMSNorm(cfg.norm_eps, self.dtype,
                         name="post_attention_layernorm")(h)
        # the router reads the layer's input, ahead of the attention
        out, load, dropped = RoutedExperts(cfg, self.dtype, name="moe")(
            normed, router_input=x)
        return h + out, load, dropped


class SmallThinkerForCausalLM(CausalLMTrunk):
    """decoder.CausalLMTrunk over this family's layers: every layer is
    routed, so the loads are (L, E_held) and the drops (L,)."""
    layer = DecoderLayer


def train_flops_per_row(cfg: SmallThinkerConfig, seq_len: int) -> float:
    """Forward + backward FLOPs of one full row of seq_len tokens, as this
    rank computes them: 6 x weights x tokens for the dense products (each
    token through num_experts_per_tok * held / total experts on average) +
    12 x heads x D for every (query, key) pair of a layer's causal triangle
    or band. An upper estimate for packed rows (documents shorter than the
    row attend less)."""
    e, d = cfg.hidden_size, cfg.head_dim
    h, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    layer = (e * (h + 2 * hkv) * d + h * d * e + e * cfg.router_width
             + 3 * e * cfg.moe_intermediate_size * cfg.num_experts_per_tok
             * cfg.num_experts / cfg.router_width)
    weights = cfg.vocab_size * e + cfg.num_hidden_layers * layer
    pairs = sum(band_pairs(seq_len, window) for window, _ in cfg.layer_kinds)
    return 6.0 * weights * seq_len + 12.0 * h * d * pairs
