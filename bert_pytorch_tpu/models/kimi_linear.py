"""The `kimi_linear` family: a pre-norm decoder of gated delta-rule
linear-attention layers and latent-attention layers without positions.

Written from the family's public config (config.KimiLinearConfig names the
keys). For x of shape (T, hidden), layer l computes

    h = x + Mixer_l(RMSNorm(x; input_norm))
    y = h + FFN_l(RMSNorm(h; ffn_norm))

with RMSNorm as models/decoder.py's. After the last layer one more RMSNorm
(`final_norm`), and the logits are that times an UNTIED `lm_head`
(V, hidden) transposed. Source layers count from 1; which are of which kind
is `linear_attn_config`'s two lists (3 KDA : 1 MLA as published). The mixer
is

- `kda` (H heads, Dk = Dv = D; H D channels): q, k, v = x Wq, x Wk, x Wv, no
  bias; each through a depthwise causal convolution of
  `short_conv_kernel_size` taps (ops/decoder_ops.short_conv: a tap before a
  document's first token is zero) and SiLU; q and k L2-normalised per head
  (x * rsqrt(sum x^2 + 1e-6)), q times D^-1/2. Decay, per channel:
  g_t = -exp(A_log[h]) * softplus(W_f2 (W_f1 x_t) + dt_bias) (hidden ->
  gate_rank -> H D), alpha_t = exp(g_t); beta_t = sigmoid(W_b x_t) (H). Per
  head, with S (D x D) zero before the first token of each document:

      S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t

  (ops/kda.py computes it in chunks of `kda_chunk_size`). Output:
  Wo (RMSNorm_head(o_t; gain (D,)) * sigmoid(W_g2 (W_g1 x_t) + b_g)) (gate
  hidden -> gate_rank -> H D; Wo H D -> hidden);
- `mla`, no positions (`mla_use_nope`: no rotary anywhere; order comes from
  the KDA layers): q = x Wq -> H heads of qk_nope + qk_rope (192);
  [c, k_r] = x W_kva (kv_lora_rank + qk_rope); [k_n, v] = RMSNorm(c; kv_norm)
  W_kvb -> H x (qk_nope + v_head); k_h = [k_n,h ; k_r] (k_r shared by the
  heads); softmax(q k^T / sqrt 192) over earlier-or-equal positions of the
  same document; heads of v_head concatenated times Wo;

and the FFN is a dense SwiGLU MLP in the first `first_k_dense_replace`
layers and, after them, sigmoid-routed experts (ops/moe.py: the
`num_experts_per_token` largest of score + selection bias, weights the
selected scores over their sum times `routed_scaling_factor`, the sum over
selected AND held experts) plus ONE shared SwiGLU expert of the experts'
width on every token, unweighted.

A padding slot (segment 0) stands at position 0 of a document of its own:
its convolution taps are zero, its KDA state restarts, it attends nowhere.

Norms, the router, the convolutions' and gates' elementwise parts, decays,
beta, the KDA state, softmax and the loss are float32; matrix products take
`dtype` operands (bfloat16) and accumulate in float32.

Layers are separate modules in a Python loop, each rematerialised under
`checkpoint_activations` (`remat_policy`: decoder.LM_REMAT_POLICIES). The
trunk is the shared module's (decoder.CausalLMTrunk): it hands back the final
norm's output and the head, not logits: the loss
(losses.next_token_loss_blocked) takes the head a block of tokens at a time,
so that no (T, V) float32 logits exist (16,384 x 20,480 x 4 B = 1.3 GB and
as much again for their gradient).
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bert_pytorch_tpu.config import KimiLinearConfig
from bert_pytorch_tpu.models import losses
from bert_pytorch_tpu.models.decoder import (LOSS_BLOCK_ROWS, CausalLMTrunk,
                                             DenseMLP, RMSNorm,
                                             RoutedExperts, _init, _Linear,
                                             expert_scalars)
from bert_pytorch_tpu.ops.attention import dot_product_attention
from bert_pytorch_tpu.ops.decoder_ops import short_conv
from bert_pytorch_tpu.ops.kda import kda_scan, kernel_mode

Dtype = Any

# chunks a block of the KDA scan (ops/kda.py): 32 x 64 tokens
KDA_BLOCK_CHUNKS = 32


def _low_rank(x, down, up, dtype):
    """(x down) up: the decay's and the gate's two-matrix projections;
    float32 out."""
    low = jnp.dot(x.astype(dtype), down.astype(dtype),
                  preferred_element_type=jnp.float32).astype(dtype)
    return jnp.dot(low, up.astype(dtype), preferred_element_type=jnp.float32)


class KimiDeltaAttention(nn.Module):
    config: KimiLinearConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, segment_ids, position_ids):
        cfg = self.config
        h, d, taps = (cfg.kda_num_heads, cfg.kda_head_dim,
                      cfg.short_conv_kernel_size)
        bsz, s, e = x.shape
        init, rank = _init(cfg), cfg.gate_rank
        # tensors of their own (LAMB takes one trust ratio each), one product
        proj = [self.param(f"{n}_proj", init, (e, h * d), jnp.float32)
                for n in "qkv"]
        conv = [self.param(f"{n}_conv", init, (h * d, taps), jnp.float32)
                for n in "qkv"]
        f_down = self.param("f_a_proj", init, (e, rank), jnp.float32)
        f_up = self.param("f_b_proj", init, (rank, h * d), jnp.float32)
        # A in [1, 16) and a decay step dt in [1e-3, 0.1) at rest, as the
        # family's linear-attention layers start (config file, `assumed`)
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                key, shape, jnp.float32, 1.0, 16.0)), (h,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (h * d,))
        b_proj = self.param("b_proj", init, (e, h), jnp.float32)
        g_down = self.param("g_a_proj", init, (e, rank), jnp.float32)
        g_up = self.param("g_b_proj", init, (rank, h * d), jnp.float32)
        g_bias = self.param("g_bias", nn.initializers.zeros, (h * d,),
                            jnp.float32)
        o_norm = self.param("o_norm", nn.initializers.ones, (d,),
                            jnp.float32)

        qkv = jnp.dot(x.astype(self.dtype),
                      jnp.concatenate(proj, axis=1).astype(self.dtype),
                      preferred_element_type=jnp.float32).astype(self.dtype)
        qkv = checkpoint_name(qkv, "in_proj_out")

        @jax.checkpoint     # the backward pass keeps qkv, not six (T, H D)
        def conv_qkv(qkv, *weights):
            with jax.named_scope("kda/conv"):
                q, k, v = (
                    jax.nn.silu(short_conv(u, w, position_ids)).reshape(
                        bsz, s, h, d)
                    for u, w in zip(jnp.split(qkv, 3, axis=-1), weights))
                q, k = (u * jax.lax.rsqrt(jnp.sum(
                    jnp.square(u), axis=-1, keepdims=True) + 1e-6)
                        for u in (q, k))
                return ((q * d ** -0.5).astype(self.dtype),
                        k.astype(self.dtype), v.astype(self.dtype))

        @jax.checkpoint
        def gates(x, f_down, f_up, a_log, dt_bias, b_proj):
            with jax.named_scope("kda/gates"):
                f = _low_rank(x, f_down, f_up, self.dtype) + dt_bias
                g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(f).reshape(
                    bsz, s, h, d)
                beta = jax.nn.sigmoid(jnp.dot(
                    x.astype(self.dtype), b_proj.astype(self.dtype),
                    preferred_element_type=jnp.float32))
                return g, beta

        @jax.checkpoint
        def gated(o, x, g_down, g_up, g_bias, o_norm):
            with jax.named_scope("kda/out"):
                gate = _low_rank(x, g_down, g_up, self.dtype) + g_bias
                # the heads' RMSNorm, written out: it is `kda/out`'s, not
                # the `rmsnorm` scope's
                o = o * jax.lax.rsqrt(jnp.mean(
                    jnp.square(o), axis=-1, keepdims=True)
                                      + cfg.norm_eps) * o_norm
                return (o.reshape(bsz, s, h * d)
                        * jax.nn.sigmoid(gate)).astype(self.dtype)

        q, k, v = conv_qkv(qkv, *conv)
        g, beta = gates(x, f_down, f_up, a_log, dt_bias, b_proj)
        o = kda_scan(q, k, v, g, beta, position_ids == 0,
                     chunk=cfg.kda_chunk_size, block=KDA_BLOCK_CHUNKS,
                     mm_dtype=self.dtype)
        y = gated(o, x, g_down, g_up, g_bias, o_norm)
        with jax.named_scope("kda/out"):
            return _Linear(e, cfg, self.dtype, name="out_proj")(y)


def _dt_bias_init(key, shape):
    """softplus^-1 of a step drawn log-uniformly from [1e-3, 0.1)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    jnp.log(1e-3), jnp.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class LatentAttention(nn.Module):
    config: KimiLinearConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, segment_ids, position_ids):
        cfg = self.config
        h, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        bsz, s, e = x.shape
        q = _Linear(h * (dn + dr), cfg, self.dtype, name="q_proj")(x)
        kva = _Linear(cfg.kv_lora_rank + dr, cfg, self.dtype,
                      name="kv_a_proj")(x)
        # what a rematerialising caller may keep (DENSE_SAVED)
        q, kva = (checkpoint_name(u, "in_proj_out") for u in (q, kva))
        latent, k_shared = jnp.split(kva, [cfg.kv_lora_rank], axis=-1)
        latent = RMSNorm(cfg.norm_eps, self.dtype, name="kv_norm")(latent)
        kv = _Linear(h * (dn + dv), cfg, self.dtype, name="kv_b_proj")(
            latent).reshape(bsz, s, h, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_shared[:, :, None, :],
                                            (bsz, s, h, dr))], axis=-1)
        ctx = dot_product_attention(
            q.reshape(bsz, s, h, dn + dr), k, kv[..., dn:],
            segment_ids=segment_ids, impl=cfg.attention_impl, causal=True)
        return _Linear(e, cfg, self.dtype, name="out_proj")(
            ctx.reshape(bsz, s, h * dv))


class DecoderLayer(nn.Module):
    config: KimiLinearConfig
    mixer: str
    ffn: str
    dtype: Dtype = jnp.bfloat16

    routed = property(lambda self: self.ffn == "moe")

    @nn.compact
    def __call__(self, x, segment_ids, position_ids):
        cfg = self.config
        normed = RMSNorm(cfg.norm_eps, self.dtype, name="input_norm")(x)
        if self.mixer == "kda":
            mixed = KimiDeltaAttention(cfg, self.dtype, name="kda")(
                normed, segment_ids, position_ids)
        else:
            mixed = LatentAttention(cfg, self.dtype, name="attention")(
                normed, segment_ids, position_ids)
        h = x + mixed
        normed = RMSNorm(cfg.norm_eps, self.dtype, name="ffn_norm")(h)
        load = jnp.zeros((cfg.num_experts,), jnp.int32)
        dropped = jnp.zeros([], jnp.int32)
        if self.ffn == "dense":
            out = DenseMLP(cfg, self.dtype, name="mlp")(normed)
        else:
            out, load, dropped = RoutedExperts(cfg, self.dtype, name="moe")(
                normed)
            with jax.named_scope("moe/shared"):
                out = out + DenseMLP(
                    cfg, self.dtype, cfg.moe_intermediate_size,
                    name="shared_expert")(normed)
        return h + out, load, dropped


class KimiLinearForCausalLM(CausalLMTrunk):
    """decoder.CausalLMTrunk over this family's layers: the loads and drops
    are the routed layers' (n_routed, E_held) and (n_routed,). position_ids
    are 0 at padding."""
    layer = DecoderLayer


def keep_float32(path) -> bool:
    """Parameters the step reads in float32 whatever the compute dtype: the
    router and its selection bias (decoder.keep_float32's), and the decay's
    A_log and dt_bias (float32 by the family's equations)."""
    return str(getattr(path[-1], "key", path[-1])) in (
        "router", "expert_bias", "A_log", "dt_bias")


def pretrain_loss_fn_builder(model) -> Callable:
    """loss_fn_builder of training/pretrain.build_pretrain_step: next-token
    cross-entropy over packed rows, the head a block of tokens at a time;
    the routed layers' counters (decoder.expert_scalars), and the KDA scans'
    useful work, counted from the rows the scans are given: tokens that are
    no padding (times the KDA layers) and documents started (state resets a
    layer, padding slots included), each summed over the micro-batches; and
    `kda_kernel_tokens`, the `kda_tokens` of the layers whose scan walks its
    chunks with the Pallas kernels (ops/kda.kernel_mode, asked when the step
    is traced, as `kda_scan` asks it: all of them or, on the XLA scans, 0)."""
    cfg = model.config
    kda_layers = sum(mixer == "kda" for mixer, _ in cfg.layer_kinds)

    def loss_fn(params, batch, dropout_rng, deterministic: bool = False):
        hidden, head, load, dropped = model.apply(
            {"params": params}, batch["input_ids"], batch["segment_ids"],
            batch["position_ids"])
        loss, count = losses.next_token_loss_blocked(
            hidden, head, batch["input_ids"], batch["segment_ids"],
            LOSS_BLOCK_ROWS)
        with jax.named_scope("metrics"):
            kda_tokens = kda_layers * jnp.sum(batch["segment_ids"] > 0,
                                              dtype=jnp.int32)
            on_kernels = kernel_mode(cfg.kda_head_dim, cfg.kda_head_dim,
                                     cfg.kda_chunk_size,
                                     KDA_BLOCK_CHUNKS) is not None
            scalars = dict(
                expert_scalars(cfg, count, batch["input_ids"].size, load,
                               dropped),
                kda_tokens=kda_tokens,
                kda_kernel_tokens=kda_tokens * int(on_kernels),
                kda_resets=jnp.sum(batch["position_ids"] == 0,
                                   dtype=jnp.int32))
        return loss, {"scalars": scalars}

    return loss_fn


def train_flops_per_row(cfg: KimiLinearConfig, seq_len: int) -> float:
    """Forward + backward FLOPs of one full row of seq_len tokens, as this
    rank computes them: 6 x weights x tokens for the dense products (each
    token through num_experts_per_token * held / total routed experts on
    average, and the shared one); the KDA recurrence as chunked, per token
    and head with C the chunk and D the head: the causal halves of K K^T,
    Q K^T and the triangle times U (C D each), the triangular solve for two
    right-hand sides (2 C D), three products with the D x D state (6 D^2),
    backward twice that; and the causal half of 6 x heads x (192 + 128) x
    S^2 for latent attention. An upper estimate for packed rows (documents
    attend less than a row)."""
    e, hd = cfg.hidden_size, cfg.kda_num_heads * cfg.kda_head_dim
    c, d = cfg.kda_chunk_size, cfg.kda_head_dim
    h = cfg.num_attention_heads
    dqk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    expert = 3 * e * cfg.moe_intermediate_size
    weights = cfg.vocab_size * e
    scan = attn = 0.0
    for mixer, ffn in cfg.layer_kinds:
        if mixer == "kda":
            weights += (4 * e * hd + 2 * (e + hd) * cfg.gate_rank
                        + e * cfg.kda_num_heads)
            scan += 3.0 * cfg.kda_num_heads * (5 * c * d + 6 * d * d)
        else:
            weights += (e * h * dqk + e * (cfg.kv_lora_rank
                                           + cfg.qk_rope_head_dim)
                        + cfg.kv_lora_rank * h * (cfg.qk_nope_head_dim
                                                  + cfg.v_head_dim)
                        + h * cfg.v_head_dim * e)
            attn += 3.0 * h * (dqk + cfg.v_head_dim)
        if ffn == "dense":
            weights += 3 * e * cfg.intermediate_size
        else:
            weights += e * cfg.router_width + expert * (
                1 + cfg.num_experts_per_tok * cfg.num_experts
                / cfg.router_width)
    return (6.0 * weights * seq_len + scan * seq_len
            + attn * seq_len * seq_len)
