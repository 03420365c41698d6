"""The `lfm2_moe` family: a pre-norm decoder whose layers differ in kind.

Written from the family's public config (config.Lfm2MoeConfig names the
keys). For x of shape (T, hidden), layer l computes

    h = x + Op_l(RMSNorm(x; operator_norm))
    y = h + FFN_l(RMSNorm(h; ffn_norm))

with RMSNorm(x; w) = x * rsqrt(mean(x^2) + eps) * w. After the last layer
one more RMSNorm (`embedding_norm`), and the logits are that times the
embedding table transposed (tied). The operator is

- `attention`: q = x Wq (H heads of D), k = x Wk, v = x Wv (Hkv heads), no
  biases; q and k RMS-normed over D with one (D,) gain each; rotary over all
  of D (rotate-half) at positions that restart per document; softmax(q k^T /
  sqrt D) over earlier-or-equal positions of the same document, H / Hkv
  query heads to a key/value head; concatenated heads times Wo;
- `conv`: [B, C, X] = split(x W_in, 3); u = B * X; c = a depthwise causal
  convolution of u over `conv_L_cache` taps that does not reach across a
  document boundary; output (C * c) W_out;

and the FFN is a dense SwiGLU MLP (W2(silu(W1 x) * W3 x)) in the first
`num_dense_layers` layers and sigmoid-routed experts after them
(ops/moe.py): scores sigmoid(x Wg) over `experts_total` experts, the
`num_experts_per_tok` largest of score + expert_bias selected, weights the
selected scores over their sum, and the sum over selected AND held experts
of weight * expert(x). The layer holds the experts `experts_held`.

Norms, the router, rotary, the convolution's elementwise part, softmax and
the loss are float32; matrix products take `dtype` operands (bfloat16) and
accumulate in float32.

Layers are separate modules in a Python loop (they differ in kind, so one
scan does not carry them). With `checkpoint_activations` each layer is
rematerialised; `remat_policy` says what the backward pass finds saved
beside the layer's input (models/decoder.LM_REMAT_POLICIES). What the
decoder families share (RMSNorm, the products, the dense MLP, the routed
experts, the counters) is models/decoder.py's.
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bert_pytorch_tpu.config import Lfm2MoeConfig
from bert_pytorch_tpu.models import losses
from bert_pytorch_tpu.models.decoder import (LM_REMAT_POLICIES, DenseMLP,
                                             RMSNorm, RoutedExperts, _init,
                                             _Linear, expert_scalars)
# models/families.py takes the family's `keep_float32` from this module
from bert_pytorch_tpu.models.decoder import keep_float32  # noqa: F401
from bert_pytorch_tpu.ops.attention import dot_product_attention
from bert_pytorch_tpu.ops.decoder_ops import rotary, short_conv

Dtype = Any


class ShortConv(nn.Module):
    config: Lfm2MoeConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, segment_ids, position_ids):
        cfg = self.config
        e = cfg.hidden_size
        bcx = _Linear(3 * e, cfg, self.dtype, name="in_proj")(x)
        bcx = checkpoint_name(bcx, "in_proj_out")
        weight = self.param("conv_weight", _init(cfg),
                            (e, cfg.conv_L_cache), jnp.float32)
        if cfg.conv_bias:
            raise NotImplementedError("conv_bias: the source has none")
        # what is no projection (STEP_SUBSCOPES: `conv` -> `in_proj`, `mix`,
        # `out_proj`): the float32 split, the two gates, the convolution
        with jax.named_scope("mix"):
            b, c, xg = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
            y = c * short_conv(b * xg, weight, position_ids)
        return _Linear(e, cfg, self.dtype, name="out_proj")(y)


class Attention(nn.Module):
    config: Lfm2MoeConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, segment_ids, position_ids):
        cfg = self.config
        h, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
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
        q = rotary(q, position_ids, cfg.rope_theta).astype(self.dtype)
        k = rotary(k, position_ids, cfg.rope_theta).astype(self.dtype)
        ctx = dot_product_attention(q, k, v, segment_ids=segment_ids,
                                    impl=cfg.attention_impl, causal=True)
        return _Linear(e, cfg, self.dtype, name="out_proj")(
            ctx.reshape(bsz, s, h * d))


class DecoderLayer(nn.Module):
    config: Lfm2MoeConfig
    operator: str
    ffn: str
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, segment_ids, position_ids):
        cfg = self.config
        normed = RMSNorm(cfg.norm_eps, self.dtype, name="operator_norm")(x)
        if self.operator == "conv":
            op = ShortConv(cfg, self.dtype, name="conv")(
                normed, segment_ids, position_ids)
        else:
            op = Attention(cfg, self.dtype, name="attention")(
                normed, segment_ids, position_ids)
        h = x + op
        normed = RMSNorm(cfg.norm_eps, self.dtype, name="ffn_norm")(h)
        load = jnp.zeros((cfg.num_experts,), jnp.int32)
        dropped = jnp.zeros([], jnp.int32)
        if self.ffn == "dense":
            out = DenseMLP(cfg, self.dtype, name="mlp")(normed)
        else:
            out, load, dropped = RoutedExperts(cfg, self.dtype, name="moe")(
                normed)
        return h + out, load, dropped


class Lfm2MoeForCausalLM(nn.Module):
    """(input_ids, segment_ids, position_ids), each (B, S) -> (logits
    (B, S, V) float32, per sparse layer: tokens per held expert (n_sparse,
    E_held) int32 and held pairs not computed (n_sparse,) int32).
    segment_ids: the packing contract's (1..n per row, 0 = pad);
    position_ids restart at each document."""
    config: Lfm2MoeConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, input_ids, segment_ids, position_ids):
        cfg = self.config
        layer_cls = DecoderLayer
        if cfg.checkpoint_activations:
            layer_cls = nn.remat(DecoderLayer,
                                 policy=LM_REMAT_POLICIES[cfg.remat_policy])
        with jax.named_scope("decoder"):
            table = self.param("embed_tokens", _init(cfg),
                               (cfg.vocab_size, cfg.hidden_size),
                               jnp.float32)
            with jax.named_scope("embeddings"):
                x = table.astype(self.dtype)[input_ids]
            loads, drops = [], []
            for i, (operator, ffn) in enumerate(cfg.layer_kinds):
                x, load, dropped = layer_cls(
                    cfg, operator, ffn, self.dtype, name=f"layer_{i}")(
                        x, segment_ids, position_ids)
                if ffn == "moe":
                    loads.append(load)
                    drops.append(dropped)
            x = RMSNorm(cfg.norm_eps, self.dtype, name="embedding_norm")(x)
            with jax.named_scope("lm_head"):
                logits = jnp.dot(x, table.astype(self.dtype).T,
                                 preferred_element_type=jnp.float32)
        n_held = cfg.num_experts
        return (logits,
                jnp.stack(loads) if loads
                else jnp.zeros((0, n_held), jnp.int32),
                jnp.stack(drops) if drops else jnp.zeros((0,), jnp.int32))


def pretrain_loss_fn_builder(model) -> Callable:
    """loss_fn_builder of training/pretrain.build_pretrain_step: next-token
    cross-entropy over packed rows, and the layers' expert counters as
    scalars of the step (summed over its micro-batches)."""
    def loss_fn(params, batch, dropout_rng, deterministic: bool = False):
        logits, load, dropped = model.apply(
            {"params": params}, batch["input_ids"], batch["segment_ids"],
            batch["position_ids"])
        with jax.named_scope("loss"):
            loss, count = losses.next_token_loss(
                logits, batch["input_ids"], batch["segment_ids"])
        with jax.named_scope("metrics"):
            scalars = expert_scalars(model.config, count,
                                     batch["input_ids"].size, load, dropped)
        return loss, {"scalars": scalars}

    return loss_fn


def train_flops_per_row(cfg: Lfm2MoeConfig, seq_len: int) -> float:
    """Forward + backward FLOPs of one full row of seq_len tokens, as this
    rank computes them: 6 x weights x tokens for the dense products (each
    token through num_experts_per_tok * held / total experts on average) +
    the causal half of 12 x layers x heads x D x S^2 for attention. An upper
    estimate for packed rows (documents shorter than the row attend less)."""
    e, d = cfg.hidden_size, cfg.head_dim
    h, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    weights = cfg.vocab_size * e
    attn_layers = 0
    for operator, ffn in cfg.layer_kinds:
        if operator == "conv":
            weights += 3 * e * e + e * e
        else:
            weights += e * (h + 2 * hkv) * d + h * d * e
            attn_layers += 1
        if ffn == "dense":
            weights += 3 * e * cfg.intermediate_size
        else:
            weights += e * cfg.router_width + (
                3 * e * cfg.moe_intermediate_size * cfg.num_experts_per_tok
                * cfg.num_experts / cfg.router_width)
    return (6.0 * weights * seq_len
            + 6.0 * attn_layers * h * d * seq_len * seq_len)
