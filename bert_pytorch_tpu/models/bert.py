"""BERT model zoo, TPU-first.

Capability parity with the reference's src/modeling.py (BertModel + 7 task
heads, config-driven NSP/pooler/token-type, tied MLM decoder, activation
checkpointing), re-designed for XLA rather than translated:

- Every kernel init is wrapped in `nn.with_logical_partitioning`, so the same
  module runs replicated, FSDP-sharded, or tensor-parallel purely by changing
  the logical-axis rules in `bert_pytorch_tpu.parallel.sharding` — no NCCL-era
  module wrappers (reference wrapped with DDP at run_pretraining.py:260).
- The encoder stack is a `nn.scan` over one BertLayer (layer-stacked params),
  which keeps compile time O(1) in depth; activation checkpointing is
  `nn.remat` around the scanned layer (reference: torch.utils.checkpoint in
  sqrt(L) chunks, src/modeling.py:495-520). `config.stacked_params=False`
  swaps the scan for L per-layer modules (params under encoder/layer_{i});
  backward wgrads then write per-layer leaves directly instead of
  dynamic_update_slice into the (L, ...) stack — the perf trade is
  documented on BertEncoder.
- Compute dtype is bf16 with fp32 params and fp32 softmax/LayerNorm
  statistics; there is no GradScaler anywhere (reference: apex AMP O2 +
  dynamic loss scaling).
- Attention-mask handling matches the reference's additive (1-mask)*-1e4 bias
  (src/modeling.py:843-851).

Shape glossary: B batch, S sequence, H heads, D head_dim, E hidden, F mlp.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bert_pytorch_tpu.config import BertConfig
from bert_pytorch_tpu.models.losses import segment_onehot
from bert_pytorch_tpu.ops.activations import ACT2FN
from bert_pytorch_tpu.ops.attention import dot_product_attention, make_attention_bias
from bert_pytorch_tpu.ops.layernorm import add_dropout_layer_norm, layer_norm

Dtype = Any


def _dense_init(config: BertConfig):
    return nn.initializers.normal(stddev=config.initializer_range)


class LayerNorm(nn.Module):
    """Affine LayerNorm, eps 1e-12 (reference src/modeling.py:311-335); params
    fp32, dispatches to the fused Pallas kernel on TPU when config asks."""

    epsilon: float = 1e-12
    fused: bool = True

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        dim = x.shape[-1]
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones, ("norm",)),
            (dim,), jnp.float32)
        bias = self.param(
            "bias",
            nn.with_logical_partitioning(nn.initializers.zeros, ("norm",)),
            (dim,), jnp.float32)
        return layer_norm(x, scale, bias, eps=self.epsilon, fused=self.fused)


class ResidualDropoutLayerNorm(nn.Module):
    """LN(residual + dropout(x)) as one op — the tail of both residual
    sites in every BertLayer (reference src/modeling.py:439-487). The
    dropout mask comes from a counter hash (seeded from the 'dropout' rng
    per call site), evaluated inside the fused kernel in forward AND
    backward so it never exists in HBM
    (ops/layernorm.add_dropout_layer_norm). Param names
    match LayerNorm so checkpoints are interchangeable."""

    rate: float
    epsilon: float = 1e-12
    fused: bool = True
    fused_dropout: bool = True

    @nn.compact
    def __call__(self, x: jax.Array, residual: jax.Array,
                 deterministic: bool = True) -> jax.Array:
        dim = x.shape[-1]
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones, ("norm",)),
            (dim,), jnp.float32)
        bias = self.param(
            "bias",
            nn.with_logical_partitioning(nn.initializers.zeros, ("norm",)),
            (dim,), jnp.float32)
        if deterministic or self.rate == 0.0:
            return layer_norm(residual + x, scale, bias, eps=self.epsilon,
                              fused=self.fused)
        if not self.fused_dropout:
            x = nn.Dropout(self.rate)(x, deterministic=False)
            return layer_norm(residual + x, scale, bias, eps=self.epsilon,
                              fused=self.fused)
        # one u32 of randomness per call site per step seeds the whole mask
        seed = jax.random.bits(self.make_rng("dropout"), (),
                               jnp.uint32).astype(jnp.int32)
        return add_dropout_layer_norm(x, residual, scale, bias, seed,
                                      rate=self.rate, eps=self.epsilon,
                                      fused=self.fused)


class BertEmbeddings(nn.Module):
    """word + position (+ token-type iff config.next_sentence) embeddings,
    then LayerNorm and dropout (reference src/modeling.py:338-373).

    `position_ids` (B, S) overrides the default arange positions — packed
    rows (data/packing.py) reset positions per segment so every example
    keeps the position-embedding stream it would see unpacked."""

    config: BertConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, input_ids: jax.Array,
                 token_type_ids: Optional[jax.Array],
                 deterministic: bool = True,
                 position_ids: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        # tables shard on vocab only; an embed-sharded table turns every
        # lookup into an involuntary XLA reshard against batch-sharded
        # activations (see parallel/mesh.py DEFAULT_LOGICAL_AXIS_RULES)
        word = nn.Embed(
            cfg.vocab_size, cfg.hidden_size,
            embedding_init=nn.with_logical_partitioning(
                _dense_init(cfg), ("vocab", "embed_out")),
            dtype=self.dtype, param_dtype=jnp.float32,
            name="word_embeddings")
        pos = nn.Embed(
            cfg.max_position_embeddings, cfg.hidden_size,
            embedding_init=nn.with_logical_partitioning(
                _dense_init(cfg), (None, "embed_out")),
            dtype=self.dtype, param_dtype=jnp.float32,
            name="position_embeddings")

        seq_len = input_ids.shape[-1]
        if position_ids is None:
            position_ids = jnp.arange(seq_len, dtype=jnp.int32)[None, :]
        x = word(input_ids) + pos(position_ids)

        # Token-type embeddings exist only in NSP mode — the reference skips
        # them entirely for RoBERTa-style runs (src/modeling.py:345-348).
        if cfg.next_sentence:
            tok_type = nn.Embed(
                cfg.type_vocab_size, cfg.hidden_size,
                embedding_init=nn.with_logical_partitioning(
                    _dense_init(cfg), (None, "embed_out")),
                dtype=self.dtype, param_dtype=jnp.float32,
                name="token_type_embeddings")
            if token_type_ids is None:
                token_type_ids = jnp.zeros_like(input_ids)
            x = x + tok_type(token_type_ids)

        x = LayerNorm(fused=cfg.fused_ops, name="layer_norm")(x)
        if (cfg.fused_dropout_ln and not deterministic
                and cfg.hidden_dropout_prob > 0.0):
            # same regenerate-in-backward hash dropout as the attention
            # probs and the residual sites — no saved mask tensor
            from bert_pytorch_tpu.ops.attention import hash_dropout

            seed = jax.random.bits(self.make_rng("dropout"), (),
                                   jnp.uint32).astype(jnp.int32)
            x = hash_dropout(x, seed, cfg.hidden_dropout_prob)
        else:
            x = nn.Dropout(cfg.hidden_dropout_prob)(
                x, deterministic=deterministic)
        return x


class BertSelfAttention(nn.Module):
    """Self-attention with a single fused QKV projection.

    The reference used three separate Q/K/V Linears (src/modeling.py:388-392);
    one (E, 3, H, D) projection keeps the MXU busy with a single large matmul
    and makes tensor-parallel sharding a one-axis annotation.
    """

    config: BertConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, hidden: jax.Array, attention_bias: jax.Array,
                 segment_ids: Optional[jax.Array] = None,
                 deterministic: bool = True) -> jax.Array:
        cfg = self.config
        n_heads, head_dim = cfg.num_attention_heads, cfg.head_dim

        if cfg.kfac_taps:
            self.sow("kfac_in", "qkv_tap", hidden)
        qkv = nn.DenseGeneral(
            features=(3, n_heads, head_dim), axis=-1,
            kernel_init=nn.with_logical_partitioning(
                _dense_init(cfg), ("embed", None, "heads", "kv")),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros, (None, "heads", "kv")),
            dtype=self.dtype, param_dtype=jnp.float32,
            name="qkv")(hidden)
        if cfg.kfac_taps:
            qkv = self.perturb("qkv_tap", qkv)
        qkv = checkpoint_name(qkv, "qkv_out")   # DENSE_SAVED, below
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

        # "auto" resolves by sequence length inside dot_product_attention
        # (XLA attention through seq 256, Pallas flash beyond).
        # fused_ops=False is the no-Pallas escape hatch (config.py): long
        # sequences then get attention-only recompute, which has flash-like
        # activation memory without the Pallas kernel.
        impl = cfg.attention_impl
        if impl == "auto" and not cfg.fused_ops:
            impl = "xla_checkpoint" if hidden.shape[1] > 256 else "xla"
        dropout_rng = None
        if not deterministic and cfg.attention_probs_dropout_prob > 0.0:
            dropout_rng = self.make_rng("dropout")
        ctx = dot_product_attention(
            q, k, v, bias=attention_bias,
            segment_ids=segment_ids,
            dropout_rng=dropout_rng,
            dropout_rate=cfg.attention_probs_dropout_prob,
            deterministic=deterministic,
            impl=impl,
            hash_dropout_impl=cfg.fused_dropout_ln)

        if cfg.kfac_taps:
            self.sow("kfac_in", "output_tap", ctx)
        out = nn.DenseGeneral(
            features=cfg.hidden_size, axis=(-2, -1),
            kernel_init=nn.with_logical_partitioning(
                _dense_init(cfg), ("heads", "kv", "embed")),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros, ("embed",)),
            dtype=self.dtype, param_dtype=jnp.float32,
            name="output")(ctx)
        if cfg.kfac_taps:
            out = self.perturb("output_tap", out)
        return out


class BertLayer(nn.Module):
    """attention -> add&LN -> MLP(bias_gelu) -> add&LN
    (reference src/modeling.py:439-493)."""

    config: BertConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, hidden: jax.Array, attention_bias: jax.Array,
                 segment_ids: Optional[jax.Array] = None,
                 deterministic: bool = True) -> jax.Array:
        cfg = self.config

        # named_scope tags every op in the block with a stable prefix so a
        # profiler trace maps buckets to code (attention vs mlp vs head)
        # instead of fused-op soup — the per-phase attribution PERF.md's
        # step_scope_share metrics read ("Demystifying BERT")
        with jax.named_scope("attention"):
            attn_out = BertSelfAttention(cfg, dtype=self.dtype,
                                         name="attention")(
                hidden, attention_bias, segment_ids, deterministic)
            hidden = ResidualDropoutLayerNorm(
                rate=cfg.hidden_dropout_prob, fused=cfg.fused_ops,
                fused_dropout=cfg.fused_dropout_ln,
                name="attention_layer_norm")(attn_out, hidden, deterministic)
            if cfg.debug_taps:
                self.sow("debug_taps", "attention_out", hidden)

        # MLP. Activation applied on the pre-bias output + bias, mirroring the
        # reference's fused LinearActivation bias_gelu (src/modeling.py:141-180)
        # — on TPU, XLA fuses this into the matmul epilogue.
        with jax.named_scope("mlp"):
            act = ACT2FN[cfg.hidden_act]
            if cfg.kfac_taps:
                self.sow("kfac_in", "intermediate_tap", hidden)
            inter = nn.Dense(
                cfg.intermediate_size,
                kernel_init=nn.with_logical_partitioning(
                    _dense_init(cfg), ("embed", "mlp")),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros, ("mlp",)),
                dtype=self.dtype, param_dtype=jnp.float32,
                name="intermediate")(hidden)
            if cfg.kfac_taps:
                inter = self.perturb("intermediate_tap", inter)
            inter = act(inter)
            if cfg.kfac_taps:
                self.sow("kfac_in", "mlp_output_tap", inter)
            mlp_out = nn.Dense(
                cfg.hidden_size,
                kernel_init=nn.with_logical_partitioning(
                    _dense_init(cfg), ("mlp", "embed")),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros, ("embed",)),
                dtype=self.dtype, param_dtype=jnp.float32,
                name="mlp_output")(inter)
            if cfg.kfac_taps:
                mlp_out = self.perturb("mlp_output_tap", mlp_out)
            mlp_out = checkpoint_name(mlp_out, "mlp_out")   # DENSE_SAVED
            hidden = ResidualDropoutLayerNorm(
                rate=cfg.hidden_dropout_prob, fused=cfg.fused_ops,
                fused_dropout=cfg.fused_dropout_ln,
                name="output_layer_norm")(mlp_out, hidden, deterministic)
            if cfg.debug_taps:
                self.sow("debug_taps", "mlp_out", hidden)
        return hidden


class _EncoderBody(nn.Module):
    """Scan body: one BertLayer returning flax-scan's (carry, ys) shape."""

    config: BertConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, hidden: jax.Array, attention_bias: jax.Array,
                 segment_ids: Optional[jax.Array] = None,
                 deterministic: bool = True):
        hidden = BertLayer(self.config, dtype=self.dtype, name="layer")(
            hidden, attention_bias, segment_ids, deterministic)
        return hidden, None


# What remat_policy="dense" keeps of a layer besides its input, by
# checkpoint_name: the outputs of the qkv and mlp_output projections. These
# two pay for their bytes on a v5e: a 0.27 ms and a 0.38 ms matmul spared
# for 50 MB and 17 MB that cross HBM three times (stacked by the forward
# scan, sliced out and read by the backward scan). The attention output
# projection and anything (T, F)-wide (the intermediate projection, and the
# erf-GELU's two residuals: its output, which the mlp_output matmul keeps
# for its weight gradient, and its derivative, ops/activations.py) cost
# more to keep than to redo, the latter because XLA then fuses the erf-GELU
# into the input of its consumers (PERF.md, PR 25: the subsets raced at
# BERT-Large b64 s128, where the GELU still kept three values of its own).
DENSE_SAVED = ("qkv_out", "mlp_out")

_REMAT_POLICIES = {
    "nothing": jax.checkpoint_policies.nothing_saveable,
    "dense": jax.checkpoint_policies.save_only_these_names(*DENSE_SAVED),
}
# remat_policy="auto", in order of preference: the entry point takes the
# first whose compiled step the device holds and the last whatever it
# needs (training/pretrain.resolve_remat_policy); a model built with
# "auto" still in its config takes the first.
REMAT_AUTO_ORDER = ("dense", "nothing")
_REMAT_POLICIES["auto"] = _REMAT_POLICIES[REMAT_AUTO_ORDER[0]]


class BertEncoder(nn.Module):
    """N stacked BertLayers via nn.scan (layer-stacked params), or — with
    config.stacked_params=False — a fully-unrolled Python loop over L
    separate BertLayer modules (per-layer params).

    Stacked: compile time stays constant in depth and XLA sees one loop
    body — the TPU-correct replacement for the reference's Python loop over
    24 modules (src/modeling.py:495-536), but backward wgrads accumulate by
    dynamic_update_slice into the (L, ...) stacked grad buffers even at full
    scan_unroll. Unstacked: params live under encoder/layer_{i} with no
    leading L axis, wgrads write straight into per-layer leaves (no DUS
    traffic), compile time O(L).
    checkpoint_activations=True wraps the (scanned or per-layer) body in
    nn.remat (reference: torch checkpointing in sqrt(L) chunks). What the
    backward pass then finds saved is config.remat_policy's to say, by the
    one table above for both layouts: by default ("auto") the layer's input
    and DENSE_SAVED, so the qkv and mlp_output matmuls run once and the
    rest of the layer twice; "nothing" (the layer's input alone, the whole
    layer runs twice) where the entry point found, from the compiled step's
    memory against the device's, that the saved values do not fit.
    """

    config: BertConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, hidden: jax.Array, attention_bias: jax.Array,
                 segment_ids: Optional[jax.Array] = None,
                 deterministic: bool = True) -> jax.Array:
        cfg = self.config

        if not cfg.stacked_params:
            layer_cls = BertLayer
            if cfg.checkpoint_activations:
                layer_cls = nn.remat(
                    BertLayer,
                    static_argnums=(4,),  # (self, hidden, bias, seg, det.)
                    policy=_REMAT_POLICIES[cfg.remat_policy],
                )
            for i in range(cfg.num_hidden_layers):
                hidden = layer_cls(cfg, dtype=self.dtype,
                                   name=f"layer_{i}")(
                    hidden, attention_bias, segment_ids, deterministic)
            return hidden

        body_cls = _EncoderBody
        if cfg.checkpoint_activations:
            body_cls = nn.remat(
                _EncoderBody,
                static_argnums=(4,),  # (self, hidden, bias, seg, det.)
                policy=_REMAT_POLICIES[cfg.remat_policy],
            )

        ScannedLayers = nn.scan(
            body_cls,
            variable_axes={"params": 0, "perturbations": 0, "kfac_in": 0,
                           "debug_taps": 0},
            split_rngs={"params": True, "dropout": True},
            in_axes=(nn.broadcast, nn.broadcast, nn.broadcast),
            length=cfg.num_hidden_layers,
            metadata_params={nn.PARTITION_NAME: "layers"},
            unroll=min(cfg.scan_unroll, cfg.num_hidden_layers),
        )
        hidden, _ = ScannedLayers(cfg, dtype=self.dtype, name="layers")(
            hidden, attention_bias, segment_ids, deterministic)
        return hidden


class BertPooler(nn.Module):
    """tanh(dense([CLS])) (reference src/modeling.py:538-552).

    `positions` (B, G) int32: gather each of G tokens per row instead of
    row position 0 — packed rows hold several examples, each with its own
    [CLS] (data/packing.py nsp_positions), so the pooled output becomes
    (B, G, E). Empty slots gather position 0; their NSP label is -1 and the
    loss ignores them."""

    config: BertConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, hidden: jax.Array,
                 positions: Optional[jax.Array] = None) -> jax.Array:
        if positions is None:
            cls = hidden[:, 0]
        else:
            cls = jnp.take_along_axis(hidden, positions[..., None], axis=1)
        if self.config.kfac_taps:
            self.sow("kfac_in", "dense_tap", cls)
        out = nn.Dense(
            self.config.hidden_size,
            # 'embed_head': replicated contracting dim, like _head_dense —
            # an fsdp-sharded (E, E) pooler kernel forces the same
            # involuntary batch->embed reshard of the (B, E) cls slice
            kernel_init=nn.with_logical_partitioning(
                _dense_init(self.config), ("embed_head", "embed_out")),
            dtype=self.dtype, param_dtype=jnp.float32,
            name="dense")(cls)
        if self.config.kfac_taps:
            # tapped pre-activation (K-FAC's G is grad w.r.t. Wa+b, not tanh)
            out = self.perturb("dense_tap", out)
        return jnp.tanh(out)


class BertModel(nn.Module):
    """Encoder trunk: embeddings -> encoder -> (optional) pooler.

    Returns (sequence_output, pooled_output); pooled_output is None unless
    config.next_sentence (reference src/modeling.py:837-864: pooler only runs
    in NSP mode).

    Packed sequences (--packing): `position_ids` resets positions per
    segment, `segment_ids` (1..n per row, 0 = pad) restricts attention to
    block-diagonal q_seg == k_seg blocks, and `nsp_positions` (B, G) makes
    the pooler gather each segment's first token instead of row position 0
    (pooled becomes (B, G, E)).
    """

    config: BertConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, input_ids: jax.Array,
                 token_type_ids: Optional[jax.Array] = None,
                 attention_mask: Optional[jax.Array] = None,
                 deterministic: bool = True,
                 position_ids: Optional[jax.Array] = None,
                 segment_ids: Optional[jax.Array] = None,
                 nsp_positions: Optional[jax.Array] = None,
                 ) -> Tuple[jax.Array, Optional[jax.Array]]:
        cfg = self.config
        if attention_mask is None:
            attention_mask = (segment_ids > 0 if segment_ids is not None
                              else jnp.ones_like(input_ids))
        bias = make_attention_bias(attention_mask, dtype=jnp.float32)

        with jax.named_scope("embeddings"):
            x = BertEmbeddings(cfg, dtype=self.dtype, name="embeddings")(
                input_ids, token_type_ids, deterministic, position_ids)
        if cfg.debug_taps:
            # "_out" suffix: a sow name must not collide with a child
            # module name ("embeddings" is the BertEmbeddings submodule)
            self.sow("debug_taps", "embeddings_out", x)
        x = nn.with_logical_constraint(x, ("data", "seq", "embed_act"))
        x = BertEncoder(cfg, dtype=self.dtype, name="encoder")(
            x, bias, segment_ids, deterministic)
        x = nn.with_logical_constraint(x, ("data", "seq", "embed_act"))

        pooled = None
        if cfg.next_sentence:
            with jax.named_scope("pooler"):
                pooled = BertPooler(cfg, dtype=self.dtype, name="pooler")(
                    x, nsp_positions)
            if cfg.debug_taps:
                self.sow("debug_taps", "pooled", pooled)
        return x, pooled


class BertMLMHead(nn.Module):
    """transform (dense+act+LN) then decode against the tied word-embedding
    matrix plus a free bias (reference src/modeling.py:555-600)."""

    config: BertConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, hidden: jax.Array,
                 word_embedding: jax.Array) -> jax.Array:
        cfg = self.config
        x = nn.Dense(
            cfg.hidden_size,
            # 'embed_head' (replicated), not 'embed' (fsdp): an fsdp-sharded
            # contracting dim on this (E, E) kernel makes GSPMD reshard the
            # batch-sharded (B, S/P, E) hidden embed-major — the involuntary
            # full rematerialization the 2x2-mesh gate catches; the ZeRO
            # memory saved (E*E/N) is noise next to the (V, E) tables that
            # stay properly sharded
            kernel_init=nn.with_logical_partitioning(
                _dense_init(cfg), ("embed_head", "embed_out")),
            dtype=self.dtype, param_dtype=jnp.float32,
            name="transform")(hidden)
        act = cfg.hidden_act if cfg.hidden_act != "bias_gelu" else "gelu"
        x = ACT2FN[act](x)
        x = LayerNorm(fused=cfg.fused_ops, name="layer_norm")(x)

        # Tied decoder: logits = x @ E^T + b (reference ties decoder.weight to
        # word embeddings at src/modeling.py:563-574).
        logits = jnp.einsum("bse,ve->bsv", x,
                            word_embedding.astype(self.dtype),
                            preferred_element_type=jnp.float32)
        bias = self.param(
            "bias",
            nn.with_logical_partitioning(nn.initializers.zeros, ("vocab",)),
            (cfg.vocab_size,), jnp.float32)
        return logits + bias


def _head_dense(cfg: BertConfig, features: int, name: str, dtype: Dtype):
    # 'embed_head' (replicated), NOT 'embed' (fsdp): these are few-KB
    # classifier kernels whose fsdp-sharded contracting dim makes GSPMD
    # reshard the batch-sharded pooled activations embed-major — an
    # involuntary full rematerialization on (data x fsdp) meshes for a
    # memory win of kilobytes (same reasoning as the replicated norm/pos
    # tables in parallel/mesh.py; caught by the 2x2-mesh reshard gate)
    return nn.Dense(
        features,
        kernel_init=nn.with_logical_partitioning(
            _dense_init(cfg), ("embed_head", None)),
        dtype=dtype, param_dtype=jnp.float32, name=name)


class BertForPreTraining(nn.Module):
    """MLM + NSP heads (reference src/modeling.py:867-929).

    masked_positions=None (dense): prediction_logits are fp32 (B, S, V) — the
    reference's shape. masked_positions=(B, P) int32: hidden states are
    gathered at those positions BEFORE the MLM transform/decoder, so logits
    are (B, P, V). Phase 1 scores at most max_predictions_per_seq=20 of 128
    positions, so the gathered head does ~6x less vocab-matmul work and never
    materializes the (B, S, V) fp32 logits — the dominant memory/FLOP cost on
    TPU. Returns (prediction_logits, seq_relationship_logits (B,2) | None).

    Packed batches (position_ids/segment_ids/nsp_positions, see BertModel):
    the NSP head scores every packed segment — seq_relationship_logits
    become (B, G, 2), paired with the loader's (B, G) per-segment labels.
    """

    config: BertConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 deterministic: bool = True, masked_positions=None,
                 position_ids=None, segment_ids=None, nsp_positions=None):
        cfg = self.config
        bert = BertModel(cfg, dtype=self.dtype, name="bert")
        seq_out, pooled = bert(input_ids, token_type_ids, attention_mask,
                               deterministic, position_ids=position_ids,
                               segment_ids=segment_ids,
                               nsp_positions=nsp_positions)
        word_emb = bert.variables["params"]["embeddings"]["word_embeddings"][
            "embedding"]
        word_emb = _unbox(word_emb)
        with jax.named_scope("mlm_head"):
            if masked_positions is not None:
                seq_out = jnp.take_along_axis(
                    seq_out, masked_positions[..., None], axis=1)
                # the gather drops the encoder output's layout annotation;
                # without re-constraining, SPMD propagates a vocab-major
                # layout back through the tied decoder and the embedding
                # grad scatter-add pays a replicate-then-repartition
                # (involuntary reshard)
                seq_out = nn.with_logical_constraint(
                    seq_out, ("data", None, "embed_act"))
            mlm_logits = BertMLMHead(cfg, dtype=self.dtype,
                                     name="cls_predictions")(
                seq_out, word_emb)
        if cfg.debug_taps:
            self.sow("debug_taps", "mlm_logits", mlm_logits)
        nsp_logits = None
        if cfg.next_sentence:
            with jax.named_scope("nsp_head"):
                if cfg.kfac_taps:
                    self.sow("kfac_in", "cls_seq_relationship_tap", pooled)
                nsp_logits = _head_dense(cfg, 2, "cls_seq_relationship",
                                         self.dtype)(pooled)
                if cfg.kfac_taps:
                    nsp_logits = self.perturb("cls_seq_relationship_tap",
                                              nsp_logits)
                nsp_logits = nsp_logits.astype(jnp.float32)
            if cfg.debug_taps:
                self.sow("debug_taps", "nsp_logits", nsp_logits)
        return mlm_logits.astype(jnp.float32), nsp_logits


class BertForMaskedLM(nn.Module):
    """MLM head only (reference src/modeling.py:931-990)."""

    config: BertConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 deterministic: bool = True, masked_positions=None,
                 position_ids=None, segment_ids=None):
        cfg = self.config.replace(next_sentence=False)
        bert = BertModel(cfg, dtype=self.dtype, name="bert")
        seq_out, _ = bert(input_ids, token_type_ids, attention_mask,
                          deterministic, position_ids=position_ids,
                          segment_ids=segment_ids)
        word_emb = _unbox(
            bert.variables["params"]["embeddings"]["word_embeddings"][
                "embedding"])
        if masked_positions is not None:
            seq_out = jnp.take_along_axis(
                seq_out, masked_positions[..., None], axis=1)
            seq_out = nn.with_logical_constraint(
                seq_out, ("data", None, "embed_act"))
        logits = BertMLMHead(cfg, dtype=self.dtype, name="cls_predictions")(
            seq_out, word_emb)
        return logits.astype(jnp.float32)


class BertForNextSentencePrediction(nn.Module):
    """NSP head only (reference src/modeling.py:992-1051)."""

    config: BertConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 deterministic: bool = True):
        cfg = self.config.replace(next_sentence=True)
        _, pooled = BertModel(cfg, dtype=self.dtype, name="bert")(
            input_ids, token_type_ids, attention_mask, deterministic)
        return _head_dense(cfg, 2, "cls_seq_relationship", self.dtype)(
            pooled).astype(jnp.float32)


def positions_from_segment_ids(segment_ids: jax.Array,
                               max_segments: int) -> jax.Array:
    """(B, S) packed segment ids (1..G, 0 = pad) -> (B, G) row position of
    each segment's FIRST token — the per-segment [CLS] every pooled head
    gathers. Computed in-graph so a serving batch needs no extra host
    field beyond the packing contract (serving/engine.BATCH_FIELDS); an
    empty segment slot resolves to position 0, whose gathered output is
    ignored because its label/placement is absent."""
    hits = segment_onehot(segment_ids, max_segments)          # (B, G, S)
    return jnp.argmax(hits, axis=-1).astype(jnp.int32)


class BertForSequenceClassification(nn.Module):
    """Pooled -> dropout -> linear(num_labels)
    (reference src/modeling.py:1053-1110).

    Packed rows (`position_ids`/`segment_ids`, data/packing.py contract):
    each row holds up to `max_segments` independent (pair) examples; the
    pooler gathers every segment's first token ([CLS]) instead of row
    position 0, so logits become (B, G, num_labels) — per-segment labels
    (-1 = empty slot) pair with them in the packed finetune loss. The
    plain path (segment_ids=None) is byte-identical to the pre-packing
    module: (B, num_labels) from the row-0 pool."""

    config: BertConfig
    num_labels: int = 2
    max_segments: int = 8
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 deterministic: bool = True, position_ids=None,
                 segment_ids=None):
        cfg = self.config.replace(next_sentence=True)  # pooler required
        pooled_positions = None
        if segment_ids is not None:
            pooled_positions = positions_from_segment_ids(
                segment_ids, self.max_segments)
        _, pooled = BertModel(cfg, dtype=self.dtype, name="bert")(
            input_ids, token_type_ids, attention_mask, deterministic,
            position_ids=position_ids, segment_ids=segment_ids,
            nsp_positions=pooled_positions)
        pooled = nn.Dropout(cfg.hidden_dropout_prob)(
            pooled, deterministic=deterministic)
        return _head_dense(cfg, self.num_labels, "classifier", self.dtype)(
            pooled).astype(jnp.float32)


class BertForMultipleChoice(nn.Module):
    """(B, C, S) inputs flattened to (B*C, S), scored, reshaped to (B, C)
    (reference src/modeling.py:1112-1179).

    Packed rows: 2-D `input_ids` with `segment_ids` score every packed
    segment independently — (B, G) scalar scores, one per segment. The
    finetune packer places each example's C choices as C CONSECUTIVE
    segments of one row, so the loss regroups (B, G) -> (B, G/C, C) and
    softmaxes within each group; serving submits one segment per choice
    and softmaxes host-side. Same head params either way."""

    config: BertConfig
    num_choices: int = 2
    max_segments: int = 8
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 deterministic: bool = True, position_ids=None,
                 segment_ids=None):
        cfg = self.config.replace(next_sentence=True)
        if input_ids.ndim == 2:  # packed / per-segment scoring path
            pooled_positions = None
            if segment_ids is not None:
                pooled_positions = positions_from_segment_ids(
                    segment_ids, self.max_segments)
            _, pooled = BertModel(cfg, dtype=self.dtype, name="bert")(
                input_ids, token_type_ids, attention_mask, deterministic,
                position_ids=position_ids, segment_ids=segment_ids,
                nsp_positions=pooled_positions)
            pooled = nn.Dropout(cfg.hidden_dropout_prob)(
                pooled, deterministic=deterministic)
            scores = _head_dense(cfg, 1, "classifier", self.dtype)(pooled)
            return scores[..., 0].astype(jnp.float32)  # (B,) or (B, G)
        B, C, S = input_ids.shape
        flat = lambda t: None if t is None else t.reshape(B * C, S)
        _, pooled = BertModel(cfg, dtype=self.dtype, name="bert")(
            flat(input_ids), flat(token_type_ids), flat(attention_mask),
            deterministic)
        pooled = nn.Dropout(cfg.hidden_dropout_prob)(
            pooled, deterministic=deterministic)
        scores = _head_dense(cfg, 1, "classifier", self.dtype)(pooled)
        return scores.reshape(B, C).astype(jnp.float32)


class BertForSentenceEmbedding(nn.Module):
    """Mean-pooled sentence embedding + a linear probe head.

    No reference equivalent — this head opens the batch-embed/retrieval
    serving workload (ROADMAP item 3): `embeddings` are the L2-normalized
    fp32 mean of the encoder outputs over each example's REAL tokens
    (mask-weighted einsum, so the contraction is structurally identical
    packed and unpacked), `logits` are a linear probe over the same mean
    — the supervised objective that finetunes the encoder toward
    separable embeddings (classification-style CE on proxy labels).

    Plain path: attention_mask defines one segment per row ->
    (B, E) embeddings, (B, num_labels) logits. Packed path (segment_ids):
    one embedding per segment -> (B, G, E) / (B, G, num_labels)."""

    config: BertConfig
    num_labels: int = 2
    max_segments: int = 8
    normalize: bool = True
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 deterministic: bool = True, position_ids=None,
                 segment_ids=None):
        cfg = self.config.replace(next_sentence=False)
        if attention_mask is None:
            attention_mask = (segment_ids > 0 if segment_ids is not None
                              else jnp.ones_like(input_ids))
        seq_out, _ = BertModel(cfg, dtype=self.dtype, name="bert")(
            input_ids, token_type_ids, attention_mask, deterministic,
            position_ids=position_ids, segment_ids=segment_ids)
        packed = segment_ids is not None
        if packed:
            onehot = segment_onehot(segment_ids, self.max_segments)
        else:
            onehot = (attention_mask > 0)[:, None, :]        # (B, 1, S)
        onehot = onehot.astype(jnp.float32)
        # fp32 mask-weighted mean: pad/foreign slots contribute exactly 0
        # to the contraction, which is what makes the packed and unpacked
        # means the same bits (tests/test_finetune_packing.py pins it)
        sums = jnp.einsum("bgs,bse->bge", onehot,
                          seq_out.astype(jnp.float32))
        counts = jnp.maximum(onehot.sum(-1)[..., None], 1.0)
        mean = sums / counts                                  # (B, G, E)
        emb = mean
        if self.normalize:
            emb = emb / jnp.sqrt(
                jnp.maximum(jnp.sum(emb * emb, axis=-1, keepdims=True),
                            1e-12))
        logits = _head_dense(cfg, self.num_labels, "classifier",
                             self.dtype)(
            mean.astype(self.dtype)).astype(jnp.float32)
        if not packed:
            emb, logits = emb[:, 0], logits[:, 0]
        return emb, logits


class BertForTokenClassification(nn.Module):
    """Per-token linear head (reference src/modeling.py:1181-1253); loss uses
    ignore_index -100 on [SPC]/subword positions (reference src/ner_dataset.py).

    `position_ids`/`segment_ids` (packed rows, data/packing.py contract):
    several examples share one row with per-segment positions and
    block-diagonal attention — the per-token head is segment-local by
    construction, so a packed row's logits demux by slicing (the inference
    server's multi-tenant batching path, serving/batcher.py)."""

    config: BertConfig
    num_labels: int = 2
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 deterministic: bool = True, position_ids=None,
                 segment_ids=None):
        cfg = self.config
        seq_out, _ = BertModel(cfg, dtype=self.dtype, name="bert")(
            input_ids, token_type_ids, attention_mask, deterministic,
            position_ids=position_ids, segment_ids=segment_ids)
        seq_out = nn.Dropout(cfg.hidden_dropout_prob)(
            seq_out, deterministic=deterministic)
        return _head_dense(cfg, self.num_labels, "classifier", self.dtype)(
            seq_out).astype(jnp.float32)


class BertForQuestionAnswering(nn.Module):
    """Per-token (start, end) logits (reference src/modeling.py:1255-1308).

    `position_ids`/`segment_ids` as in BertForTokenClassification: packed
    rows hold several (question, context) requests, each attending only
    within its own segment, so per-request span logits are row slices."""

    config: BertConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 deterministic: bool = True, position_ids=None,
                 segment_ids=None):
        cfg = self.config
        seq_out, _ = BertModel(cfg, dtype=self.dtype, name="bert")(
            input_ids, token_type_ids, attention_mask, deterministic,
            position_ids=position_ids, segment_ids=segment_ids)
        logits = _head_dense(cfg, 2, "qa_outputs", self.dtype)(
            seq_out).astype(jnp.float32)
        start_logits, end_logits = logits[..., 0], logits[..., 1]
        return start_logits, end_logits


def _unbox(x):
    """Strip flax Partitioned metadata boxes when reading raw variables."""
    return x.unbox() if hasattr(x, "unbox") else x
