"""What the decoder families share: the modules, the trunk, the counters and
the blocked loss of a pre-norm causal LM of routed experts over packed rows.

A family is one module beside this one (models/lfm2_moe.py, kimi_linear.py,
smallthinker.py, laguna.py, keye.py: its attention or mixer, its layer, its
FLOPs, and whatever of the below it does not share). A family's module
imports this one and no other family's; this one imports none of them. `cfg`
and `config` below are any family's config (config.DecoderConfig): what is
read of it here every one of them has, under these names.

RMSNorm(x; w) = x * rsqrt(mean(x^2) + eps) * w in float32; a product takes
`dtype` operands (bfloat16) and accumulates in float32; the router and its
selection bias are float32 (`keep_float32`).
"""

from __future__ import annotations

from typing import Any, Callable, ClassVar, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from bert_pytorch_tpu.models import losses
from bert_pytorch_tpu.ops import moe as moe_ops
from bert_pytorch_tpu.ops.decoder_ops import rms_norm

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

# tokens a block of the loss (losses.next_token_loss_blocked): (2048, V)
# float32 logits are 103 to 168 MB at the cells' 12,544 to 20,480 rows
LOSS_BLOCK_ROWS = 2048


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


class CausalLMTrunk(nn.Module):
    """The trunk of a family with an untied head: (input_ids, segment_ids,
    position_ids), each (B, S) -> (the final norm's output (B, S, hidden) in
    `dtype`, the head (V, hidden) in `dtype`, per routed layer: tokens per
    held expert (n_routed, E_held) int32 and held pairs not computed
    (n_routed,) int32). The model hands back the norm's output and the head,
    not logits: the loss takes the head a block of tokens at a time.

    A family subclasses it under its public name (flax names the top-level
    scope after the class) and sets `layer`: a module of fields (config, *a
    `config.layer_kinds` entry, dtype), called on (x, segment_ids,
    position_ids) -> (x, load, dropped), whose `routed` says whether its
    FFN is the routed one (a dense layer's load is not stacked)."""
    config: Any
    dtype: Dtype = jnp.bfloat16

    layer: ClassVar[Any]

    @nn.compact
    def __call__(self, input_ids, segment_ids, position_ids):
        cfg = self.config
        layer_cls = self.layer
        if cfg.checkpoint_activations:
            layer_cls = nn.remat(self.layer,
                                 policy=LM_REMAT_POLICIES[cfg.remat_policy])
        with jax.named_scope("decoder"):
            table = self.param("embed_tokens", _init(cfg),
                               (cfg.vocab_size, cfg.hidden_size),
                               jnp.float32)
            head = self.param("lm_head", _init(cfg),
                              (cfg.vocab_size, cfg.hidden_size), jnp.float32)
            with jax.named_scope("embeddings"):
                x = table.astype(self.dtype)[input_ids]
            loads, drops = [], []
            for i, kind in enumerate(cfg.layer_kinds):
                layer = layer_cls(cfg, *kind, self.dtype, name=f"layer_{i}")
                x, load, dropped = layer(x, segment_ids, position_ids)
                if layer.routed:
                    loads.append(load)
                    drops.append(dropped)
            x = RMSNorm(cfg.norm_eps, self.dtype, name="final_norm")(x)
        return (x, head.astype(self.dtype),
                jnp.stack(loads) if loads
                else jnp.zeros((0, cfg.num_experts), jnp.int32),
                jnp.stack(drops) if drops else jnp.zeros((0,), jnp.int32))


def init_inputs(batch) -> Tuple:
    """model.init's inputs from one micro-batch of the loader's fields."""
    return tuple(jnp.asarray(batch[k]) for k in
                 ("input_ids", "segment_ids", "position_ids"))


def keep_float32(path: Tuple) -> bool:
    """Parameters the step reads in float32 whatever the compute dtype: the
    router and its selection bias (the router is float32 by the families'
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
    """loss_fn_builder of training/pretrain.build_pretrain_step for a model
    that returns CausalLMTrunk's four: next-token cross-entropy over packed
    rows, the head a block of tokens at a time, and the routed layers'
    expert counters as scalars of the step (summed over its
    micro-batches)."""
    cfg = model.config

    def loss_fn(params, batch, dropout_rng, deterministic: bool = False):
        hidden, head, load, dropped = model.apply(
            {"params": params}, batch["input_ids"], batch["segment_ids"],
            batch["position_ids"])
        loss, count = losses.next_token_loss_blocked(
            hidden, head, batch["input_ids"], batch["segment_ids"],
            LOSS_BLOCK_ROWS)
        with jax.named_scope("metrics"):
            scalars = expert_scalars(cfg, count, batch["input_ids"].size,
                                     load, dropped)
        return loss, {"scalars": scalars}

    return loss_fn


def band_pairs(length: int, window: int) -> int:
    """(query, key) pairs of one document of `length` tokens: key <= query,
    and under a band (`window` > 0) query - key < window."""
    if not window or length <= window:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window
