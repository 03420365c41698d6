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
beside the layer's input (LM_REMAT_POLICIES).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bert_pytorch_tpu.config import Lfm2MoeConfig
from bert_pytorch_tpu.models import losses
from bert_pytorch_tpu.ops import moe as moe_ops
from bert_pytorch_tpu.ops.attention import dot_product_attention
from bert_pytorch_tpu.ops.decoder_ops import rms_norm, rotary, short_conv

Dtype = Any

# What remat_policy="dense" keeps of a layer besides its input: the output
# of the operator's input projection (attention's fused q/k/v, the
# convolution's B/C/X), which spares the backward pass the operator's
# RMSNorm and that matmul; and the causal flash kernel's output and
# log-sum-exp (ops/pallas/flash_attention.py names them), which spares it a
# second run of the forward kernel (67 of 303 ms of attention a step on a
# v5e, PERF.md PR 26). The FFN's last product needs no saving: in a
# pre-norm block nothing downstream of it is recomputed, so its recompute is
# dead code. The same two policy names as models/bert.py, so that
# training/pretrain.resolve_remat_policy decides for this block as it does
# for BERT's.
DENSE_SAVED = ("in_proj_out", "flash_out", "flash_lse")
LM_REMAT_POLICIES = {
    "nothing": jax.checkpoint_policies.nothing_saveable,
    "dense": jax.checkpoint_policies.save_only_these_names(*DENSE_SAVED),
}
LM_REMAT_POLICIES["auto"] = LM_REMAT_POLICIES["dense"]


# RMSNorm, _Linear, DenseMLP and RoutedExperts are the decoder families'
# (models/kimi_linear.py imports them): `config` is either family's.
def _init(cfg) -> Callable:
    return nn.initializers.normal(stddev=cfg.initializer_range)


class RMSNorm(nn.Module):
    eps: float
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        return rms_norm(x, scale, self.eps, self.dtype)


class _Linear(nn.Module):
    """x @ kernel, no bias: `dtype` operands, float32 accumulation, the
    result in `out_dtype` (default `dtype`)."""
    features: int
    config: Any
    dtype: Dtype = jnp.bfloat16
    out_dtype: Any = None

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", _init(self.config),
                            (x.shape[-1], self.features), jnp.float32)
        return jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype),
                       preferred_element_type=jnp.float32).astype(
                           self.out_dtype or self.dtype)


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


class DenseMLP(nn.Module):
    """SwiGLU MLP of `features` (default: the config's dense width)."""
    config: Any
    dtype: Dtype = jnp.bfloat16
    features: Optional[int] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        f = self.features or cfg.intermediate_size
        gate = _Linear(f, cfg, self.dtype, name="w1")(x)
        up = _Linear(f, cfg, self.dtype, name="w3")(x)
        hidden = (jax.nn.silu(gate.astype(jnp.float32))
                  * up.astype(jnp.float32)).astype(self.dtype)
        return _Linear(cfg.hidden_size, cfg, self.dtype, name="w2")(hidden)


def routed_window_rows(cfg, n_tokens: int) -> int:
    """Sorted pairs a window of ops/moe.held_experts works on, for a
    micro-batch of `n_tokens`: twice this rank's even share of the pairs."""
    pairs = n_tokens * cfg.num_experts_per_tok
    return min(-(-2 * pairs * cfg.num_experts // cfg.router_width // 512)
               * 512, pairs)


class RoutedExperts(nn.Module):
    """The routed FFN over the experts this rank holds. Returns (the partial
    sum (B, S, E) in `dtype`, tokens per held expert (E_held,) int32, held
    pairs not computed () int32). The router reads `router_input` where it
    is given (models/smallthinker.py: the layer's input, ahead of the
    attention) and the tokens the experts compute on otherwise; how it
    scores and what gates an expert are the config's `router_scores` and
    `expert_activation`."""
    config: Any
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, router_input=None):
        cfg = self.config
        bsz, s, e = x.shape
        f, n_held = cfg.moe_intermediate_size, cfg.num_experts
        router = self.param("router", _init(cfg), (e, cfg.router_width),
                            jnp.float32)
        init = _init(cfg)
        # the selection bias: a held buffer (no gradient, no update) drawn
        # like the weights, so that a fresh model selects by score + bias
        bias = (self.param("expert_bias", init, (cfg.router_width,),
                           jnp.float32)
                if cfg.use_expert_bias else None)
        w1 = self.param("experts_w1", init, (n_held, e, f), jnp.float32)
        w3 = self.param("experts_w3", init, (n_held, e, f), jnp.float32)
        w2 = self.param("experts_w2", init, (n_held, f, e), jnp.float32)
        tokens = x.reshape(bsz * s, e).astype(self.dtype)
        routing = moe_ops.route(
            tokens if router_input is None
            else router_input.reshape(bsz * s, e),
            router, bias, cfg.num_experts_per_tok, cfg.norm_topk_prob,
            float(cfg.routed_scaling_factor), cfg.router_scores)
        out, load, dropped = moe_ops.held_experts(
            tokens, routing, w1.astype(self.dtype), w3.astype(self.dtype),
            w2.astype(self.dtype), cfg.held_range,
            routed_window_rows(cfg, bsz * s), cfg.expert_activation)
        return out.astype(self.dtype).reshape(bsz, s, e), load, dropped


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


def init_inputs(batch) -> Tuple:
    """model.init's inputs from one micro-batch of the loader's fields."""
    return tuple(jnp.asarray(batch[k]) for k in
                 ("input_ids", "segment_ids", "position_ids"))


def keep_float32(path: Tuple) -> bool:
    """Parameters the step reads in float32 whatever the compute dtype: the
    router and its selection bias (the router is float32 by the family's
    equations; a bfloat16 copy would move top-k selections)."""
    keys = [str(getattr(k, "key", k)) for k in path]
    return keys[-1] in ("router", "expert_bias")


def expert_scalars(cfg, count, n_tokens: int, load, dropped) -> dict:
    """A micro-batch's scalars of the decoder families (telemetry/
    expert_load.py sums them): predicted positions, (token, expert) pairs
    routed, and per routed layer each held expert's tokens, the held pairs
    not computed and the windows the layer's loop ran (its trip count, from
    the pairs it was handed: ops/moe.live_windows)."""
    scalars = {"lm_positions": count,
               "moe_pairs_routed": jnp.asarray(
                   n_tokens * cfg.num_experts_per_tok, jnp.int32)}
    window_rows = routed_window_rows(cfg, n_tokens)
    for layer in range(load.shape[0]):
        scalars[f"moe_l{layer}_dropped"] = dropped[layer]
        scalars[f"moe_l{layer}_windows"] = moe_ops.live_windows(
            jnp.sum(load[layer]), window_rows)
        for j in range(load.shape[1]):
            scalars[f"moe_l{layer}_e{j}"] = load[layer, j]
    return scalars


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
