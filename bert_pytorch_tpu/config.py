"""Model + run configuration system.

Capability parity with the reference's three-level config precedence
(CLI > JSON run config > argparse defaults; reference run_pretraining.py:70-167
and :152-166 for the SUPPRESS-parser trick) and its `BertConfig`
(reference src/modeling.py:188-283), re-expressed as a frozen dataclass so it
can ride through `jax.jit` closures and pytree metadata without hashing issues.

Run configs reference model configs via ``model_config_file``
(reference run_pretraining.py:82,224); model configs also carry tokenizer /
data-pipeline keys (``vocab_file``, ``lowercase``, ``tokenizer``) consumed by
the dataset layer (reference run_pretraining.py:359-364).
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import re
from typing import Any, Dict, Optional, Tuple

# The names `remat_policy` takes in every family: the keys of
# models/bert._REMAT_POLICIES, models/decoder.LM_REMAT_POLICIES and
# models/keye.REMAT_POLICIES.
REMAT_POLICIES = ("nothing", "dense", "auto")


def _check_remat_policy(name: str) -> None:
    if name not in REMAT_POLICIES:
        raise ValueError(f"remat_policy {name!r}: this program has "
                         f"{list(REMAT_POLICIES)}")


@dataclasses.dataclass(frozen=True)
class BertConfig:
    """Architecture config for the BERT encoder family.

    Field set matches the reference `BertConfig` (src/modeling.py:191-214) plus
    the tokenizer/data keys its JSON model configs carry
    (config/bert_large_uncased_config.json). Frozen + hashable so a config can
    be a static argument to jitted builders.
    """

    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    hidden_act: str = "gelu"
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    initializer_range: float = 0.02
    output_all_encoded_layers: bool = False
    # NSP on/off; when False the token-type embedding and pooler are skipped
    # (reference src/modeling.py:345-348, :855-858 behavior).
    next_sentence: bool = False
    # Tokenizer / data-pipeline keys carried by model config JSONs.
    model_name: Optional[str] = None
    tokenizer: str = "wordpiece"
    vocab_file: Optional[str] = None
    lowercase: bool = True
    # TPU-native additions (absent in reference; defaults preserve parity).
    dtype: str = "bfloat16"          # compute dtype; params stay fp32
    fused_ops: bool = True            # use Pallas kernels where available
    checkpoint_activations: bool = False
    # Attention implementation (resolved in ops/attention.py):
    #   "xla"            plain einsum path; fastest through seq 256 on v5e
    #   "xla_checkpoint" xla path with probs rematerialized in backward
    #                    (flash-like memory at XLA speed)
    #   "pallas"         blockwise flash kernel; wins when the (S, S) score
    #                    matrix is too large to materialize (long context)
    #   "auto"           xla through seq 256, pallas beyond (measured v5e
    #                    crossover)
    attention_impl: str = "auto"
    # What the backward pass finds saved when checkpoint_activations=True
    # (models/bert.py _REMAT_POLICIES; nothing is read without the flag):
    #   "auto"     "dense" where the compiled step fits the device, else
    #              "nothing": run_pretraining.py compiles the step once in
    #              set-up and holds the compiler's peak against the device's
    #              bytes_limit (training/pretrain.resolve_remat_policy). A
    #              model built outside that entry point takes "dense".
    #   "dense"    the layer's input and the outputs of its qkv and
    #              mlp_output projections (2T(3E+E) bytes a layer): those
    #              two matmuls run once, the rest of the layer twice. The
    #              other two projections' outputs cost more to keep than
    #              to recompute on a v5e (PERF.md, PR 25)
    #   "nothing"  the layer's input alone: the whole layer runs twice (max
    #              memory savings — the reference's torch.utils.checkpoint)
    # A value other than "auto" is taken as written.
    remat_policy: str = "auto"
    # lax.scan unroll factor for the layer stack. 1 = compiled while loop
    # (O(1) compile time in depth — the multi-chip default). Higher values
    # unroll the loop body; num_hidden_layers removes the loop entirely,
    # which on v5e removes the dynamic-update-slice traffic of stacking
    # saved activations / sliced params in the loop carry — a measured ~15%
    # step-time win at BERT-Large seq128 b48 (and it frees enough HBM for
    # batch 56-64 un-rematted), at the cost of O(L) compile time.
    # Ignored when stacked_params=False (that path is inherently a full
    # unroll over per-layer modules).
    scan_unroll: int = 1
    # Parameter layout of the encoder stack. True (default): one nn.scan
    # module whose params carry a leading (L, ...) stacked-layer axis — O(1)
    # compile time in depth, but even at full scan_unroll the backward pass
    # accumulates each layer's weight gradient via dynamic_update_slice into
    # the (L, ...) grad buffer (its share of step time is not measured on
    # this runtime). False: the encoder is built as L separate BertLayer
    # modules (params under encoder/layer_0 .. layer_{L-1}, no leading L
    # axis), so wgrads write straight into per-layer leaves — no DUS
    # traffic, at the cost of O(L) compile time (always fully unrolled).
    # Checkpoints convert losslessly between the two layouts
    # (models/pretrained.py stack_layer_tree/unstack_layer_tree). With
    # dropout off, training trajectories are identical up to reduction
    # order; with dropout on they are statistically equivalent but not
    # bit-equal — the scan folds the dropout rng by layer index while the
    # per-layer modules fold it by module path, so the two layouts draw
    # different per-layer masks.
    stacked_params: bool = True
    # K-FAC activation/output-grad taps on encoder linear layers (sow +
    # perturb). Off by default: taps add intermediates collections that the
    # K-FAC train step consumes (optim/kfac.py).
    kfac_taps: bool = False
    # Postmortem-debug taps at every jax.named_scope boundary (embeddings,
    # per-layer attention & mlp, pooler, mlm/nsp heads): sow into the
    # 'debug_taps' collection so tools/replay.py --bisect can report the
    # first tensor to go non-finite in a replayed step. Off by default —
    # the sows are Python-gated, so the compiled train step is unchanged.
    debug_taps: bool = False
    # Counter-hash dropout across ALL training dropout sites: each residual
    # tail (dense -> dropout -> LN(residual + .)) fuses into one op whose
    # mask is evaluated in-kernel (ops/layernorm.add_dropout_layer_norm),
    # and the embeddings + XLA-attention-probs sites regenerate their hash
    # masks in the backward pass instead of saving them
    # (ops/attention.hash_dropout). Same Bernoulli statistics as nn.Dropout,
    # different (deterministic counter-based) random stream; measured +13.8
    # MFU points at BERT-Large seq128. False restores the full
    # nn.Dropout-stream behavior at every site (A/B isolation /
    # pre-r5 reproduction). Training only — eval paths are unchanged.
    # Caveat: each site's whole mask derives from ONE 32-bit seed drawn per
    # step, so over a long run a site can (birthday-bound, ~2^16 steps)
    # draw the same seed twice and reuse an identical mask for that step —
    # harmless for training statistics, but not the "fresh bits every
    # element" guarantee of nn.Dropout's threefry stream.
    fused_dropout_ln: bool = True

    def __post_init__(self):
        _check_remat_policy(self.remat_policy)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "BertConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    @classmethod
    def from_json_file(cls, path: str) -> "BertConfig":
        with open(path, "r", encoding="utf-8") as f:
            return cls.from_dict(json.load(f))

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json_string(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True) + "\n"

    def replace(self, **kw: Any) -> "BertConfig":
        return dataclasses.replace(self, **kw)

    @property
    def head_dim(self) -> int:
        if self.hidden_size % self.num_attention_heads != 0:
            raise ValueError(
                f"hidden_size ({self.hidden_size}) must be a multiple of "
                f"num_attention_heads ({self.num_attention_heads})"
            )
        return self.hidden_size // self.num_attention_heads


# Keys a model config JSON may carry that no model reads: where the
# configuration comes from and how it was cut (benchmark/configs/*.json,
# benchmark/README.md), and the family selector itself.
DOCUMENTED_DATA_KEYS = ("model_type", "source", "reduced", "assumed",
                        "layout")


class DecoderConfig:
    """What the config classes of the decoder families share
    (models/decoder.py has the modules that read them). Each family's class
    is a frozen dataclass of its source's keys beside this base, which has
    no fields: a class's field order and `to_dict()` are its own.

    A class says what its file may carry beside its fields (`_IGNORED`),
    which fields a JSON list is read into as a tuple (`_TUPLES`: the config
    is a static field of the modules) and, where the source nests groups,
    how they are read into flat fields (`_read_groups`). Every class has,
    as a field or a property, the names the shared modules read:
    `num_experts` (held), `experts_total`, `experts_held`,
    `num_experts_per_tok`, `moe_intermediate_size`, `norm_eps`,
    `norm_topk_prob`, `use_expert_bias`, `routed_scaling_factor`,
    `router_scores`, `expert_activation`."""

    _IGNORED = ()
    _TUPLES = ()

    def __post_init__(self):
        _check_remat_policy(self.remat_policy)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]):
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = [k for k in d if k not in known
                   and k not in DOCUMENTED_DATA_KEYS
                   and k not in cls._IGNORED]
        kw = {k: v for k, v in d.items() if k in known}
        unknown += cls._read_groups(d, kw)
        if unknown:
            raise ValueError(f"{cls.model_type} model config: unknown "
                             f"key(s) {sorted(unknown)}")
        for key in cls._TUPLES:
            if kw.get(key) is not None:
                kw[key] = tuple(kw[key])
        cfg = cls(**kw)
        cfg.check()
        return cfg

    @classmethod
    def _read_groups(cls, d: Dict[str, Any], kw: Dict[str, Any]) -> list:
        """Reads the source's nested groups into `kw`'s flat fields; returns
        the groups' keys that no field takes."""
        return []

    def check(self) -> None:
        """Raises where the config asks for what the family's program is not
        written for, or is cut inconsistently."""
        self.layer_kinds

    @classmethod
    def from_json_file(cls, path: str):
        with open(path, "r", encoding="utf-8") as f:
            return cls.from_dict(json.load(f))

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def replace(self, **kw: Any):
        return dataclasses.replace(self, **kw)

    @property
    def router_width(self) -> int:
        return int(self.experts_total or self.num_experts)

    @property
    def held_range(self) -> Tuple[int, int]:
        lo, hi = self.experts_held or (0, self.num_experts)
        if hi - lo != self.num_experts or not 0 <= lo < hi <= self.router_width:
            raise ValueError(
                f"experts_held {self.experts_held} is not a range of "
                f"num_experts={self.num_experts} out of {self.router_width}")
        return int(lo), int(hi)


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig(DecoderConfig):
    """Architecture config of the `lfm2_moe` family (LiquidAI LFM2 with
    routed experts): a pre-norm decoder whose blocks differ in kind — a
    gated short convolution or causal grouped-query attention as the
    operator, a dense SwiGLU MLP in the leading layers and sigmoid-routed
    experts after them (models/lfm2_moe.py has the equations).

    Keys are the source's (`config.json` of the model). A run may hold one
    expert-parallel rank's share of each layer: `num_experts` experts, the
    half-open range `experts_held` out of `experts_total` (the router keeps
    that width), and `vocab_size` rows of the vocabulary. `layers_kept`
    names the source layers a cut stack keeps (their kinds are read from
    the published `layer_types`); the first `num_dense_layers` of the stack
    have the dense MLP.
    """

    model_type: str = "lfm2_moe"
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_experts: int = 64
    num_experts_per_tok: int = 4
    layer_types: Tuple[str, ...] = ()
    layers_kept: Optional[Tuple[int, ...]] = None
    experts_total: Optional[int] = None
    experts_held: Optional[Tuple[int, int]] = None
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    initializer_range: float = 0.02
    model_name: Optional[str] = None
    # run settings, as BertConfig's
    dtype: str = "bfloat16"
    checkpoint_activations: bool = False
    remat_policy: str = "auto"
    attention_impl: str = "auto"

    # source keys that carry no size of this program's (or a nested group)
    _IGNORED = ("rope_parameters", "vocab_rows_total", "vocab_rows_held")
    _TUPLES = ("layer_types", "layers_kept", "experts_held")

    @classmethod
    def _read_groups(cls, d: Dict[str, Any], kw: Dict[str, Any]) -> list:
        rope = d.get("rope_parameters") or {}
        if "rope_theta" in rope:
            kw["rope_theta"] = float(rope["rope_theta"])
        return []

    # what models/decoder.RoutedExperts reads of every decoder family
    router_scores = property(lambda self: "sigmoid")
    expert_activation = property(lambda self: "silu")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(operator, ffn) of every layer of the stack as run: operator
        "conv" or "attention", ffn "dense" or "moe"."""
        kept = (self.layers_kept if self.layers_kept is not None
                else tuple(range(self.num_hidden_layers)))
        if len(kept) != self.num_hidden_layers:
            raise ValueError(
                f"layers_kept {kept} does not name num_hidden_layers="
                f"{self.num_hidden_layers} layers")
        if not self.layer_types or max(kept) >= len(self.layer_types):
            raise ValueError(
                "layer_types must give the kind of every source layer "
                f"that is kept (have {len(self.layer_types)}, kept {kept})")
        self.held_range
        kinds = []
        for j, i in enumerate(kept):
            kind = self.layer_types[i]
            if kind not in ("conv", "full_attention"):
                raise ValueError(f"unknown layer type {kind!r}")
            kinds.append(("conv" if kind == "conv" else "attention",
                          "dense" if j < self.num_dense_layers else "moe"))
        return tuple(kinds)


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig(DecoderConfig):
    """Architecture config of the `kimi_linear` family (Moonshot Kimi
    Linear): a pre-norm decoder of gated delta-rule linear-attention layers
    (KDA) and latent-attention layers without positions (MLA, NoPE) in the
    published 3 : 1 order, a dense SwiGLU MLP in the leading layers and
    sigmoid-routed experts beside a shared expert after them, an untied
    head (models/kimi_linear.py has the equations).

    Keys are the source's (`config.json` of the model; `linear_attn_config`
    is its nested group: layer numbers count from 1). A run may hold one
    expert-parallel rank's share, as Lfm2MoeConfig's: `num_experts` experts,
    the range `experts_held` of `experts_total`, `vocab_size` rows of the
    vocabulary, and the source layers `layers_kept` (numbered from 1 like
    the group's lists). `kda_chunk_size` and `kda_gate_rank` are not in the
    source: the family's conventions (64; the head width).
    """

    model_type: str = "kimi_linear"
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    num_expert_group: int = 1
    topk_group: int = 1
    moe_renormalize: bool = True
    moe_router_activation_func: str = "sigmoid"
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    # linear_attn_config, flattened
    kda_layers: Tuple[int, ...] = ()
    full_attn_layers: Tuple[int, ...] = ()
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_chunk_size: int = 64
    kda_gate_rank: Optional[int] = None
    layers_kept: Optional[Tuple[int, ...]] = None
    experts_total: Optional[int] = None
    experts_held: Optional[Tuple[int, int]] = None
    initializer_range: float = 0.02
    model_name: Optional[str] = None
    # run settings, as BertConfig's
    dtype: str = "bfloat16"
    checkpoint_activations: bool = False
    remat_policy: str = "auto"
    attention_impl: str = "auto"

    # source keys that carry no size of this program's
    _IGNORED = ("head_dim", "hidden_act", "model_max_length",
                "moe_layer_freq", "num_nextn_predict_layers", "rope_scaling",
                "rope_theta", "use_grouped_topk", "vocab_rows_total",
                "vocab_rows_held", "linear_attn_config")
    _GROUP = {"kda_layers": "kda_layers",
              "full_attn_layers": "full_attn_layers",
              "num_heads": "kda_num_heads", "head_dim": "kda_head_dim",
              "short_conv_kernel_size": "short_conv_kernel_size"}
    _TUPLES = ("kda_layers", "full_attn_layers", "layers_kept",
               "experts_held")

    @classmethod
    def _read_groups(cls, d: Dict[str, Any], kw: Dict[str, Any]) -> list:
        group = d.get("linear_attn_config") or {}
        kw.update({cls._GROUP[k]: v for k, v in group.items()
                   if k in cls._GROUP})
        return [f"linear_attn_config.{k}" for k in group
                if k not in cls._GROUP]

    # the names models/decoder.py's modules read
    norm_eps = property(lambda self: self.rms_norm_eps)
    num_experts_per_tok = property(lambda self: self.num_experts_per_token)
    norm_topk_prob = property(lambda self: self.moe_renormalize)
    use_expert_bias = property(lambda self: True)
    router_scores = property(lambda self: self.moe_router_activation_func)
    expert_activation = property(lambda self: "silu")

    @property
    def gate_rank(self) -> int:
        return int(self.kda_gate_rank or self.kda_head_dim)

    @property
    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer, ffn) of every layer of the stack as run: mixer "kda" or
        "mla", ffn "dense" or "moe"."""
        kept = (self.layers_kept if self.layers_kept is not None
                else tuple(range(1, self.num_hidden_layers + 1)))
        if len(kept) != self.num_hidden_layers:
            raise ValueError(
                f"layers_kept {kept} does not name num_hidden_layers="
                f"{self.num_hidden_layers} layers")
        unsupported = [
            name for name, bad in (
                ("q_lora_rank", self.q_lora_rank is not None),
                ("mla_use_nope=false", not self.mla_use_nope),
                ("num_expert_group", self.num_expert_group != 1
                 or self.topk_group != 1),
                ("moe_router_activation_func",
                 self.moe_router_activation_func != "sigmoid"),
                ("tie_word_embeddings", self.tie_word_embeddings),
                ("num_key_value_heads",
                 self.num_key_value_heads != self.num_attention_heads),
                ("num_shared_experts", self.num_shared_experts != 1)) if bad]
        if unsupported:
            raise NotImplementedError(
                f"kimi_linear: not written for {unsupported} (the source "
                "model uses none of them)")
        self.held_range
        kinds = []
        for j, i in enumerate(kept):
            if (i in self.kda_layers) == (i in self.full_attn_layers):
                raise ValueError(
                    f"source layer {i} must stand in exactly one of "
                    "linear_attn_config's kda_layers and full_attn_layers")
            kinds.append(("kda" if i in self.kda_layers else "mla",
                          "dense" if j < self.first_k_dense_replace
                          else "moe"))
        return tuple(kinds)


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig(DecoderConfig):
    """Architecture config of the `smallthinker` family (PowerInfer
    SmallThinker): a pre-norm decoder whose every layer is causal
    grouped-query attention, over the whole document WITHOUT positions or
    over the last `sliding_window_size` tokens WITH rotary positions
    (`sliding_window_layout` and `rope_layout`, one 0/1 a layer), then
    softmax-routed ReLU-gated experts whose router reads the layer's input,
    ahead of the attention; an untied head (models/smallthinker.py has the
    equations).

    Keys are the source's (`config.json` of the model). A run may hold one
    expert-parallel rank's share, as Lfm2MoeConfig's:
    `moe_num_primary_experts` experts, the range `experts_held` of
    `experts_total` (the router keeps that width) and `vocab_size` rows of
    the vocabulary; a cut stack gives the two layouts of the layers it
    keeps (`num_hidden_layers` entries each).
    """

    model_type: str = "smallthinker"
    vocab_size: int = 151936
    hidden_size: int = 2560
    head_dim: int = 128
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    rope_layout: Tuple[int, ...] = ()
    sliding_window_layout: Tuple[int, ...] = ()
    sliding_window_size: int = 4096
    rope_theta: float = 1500000.0
    rope_scaling: Optional[Any] = None
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 16384
    tie_word_embeddings: bool = False
    experts_total: Optional[int] = None
    experts_held: Optional[Tuple[int, int]] = None
    initializer_range: float = 0.02
    model_name: Optional[str] = None
    # run settings, as BertConfig's
    dtype: str = "bfloat16"
    checkpoint_activations: bool = False
    remat_policy: str = "auto"
    attention_impl: str = "auto"

    # keys of the configuration's file that carry no size of this program's
    _IGNORED = ("vocab_rows_total", "vocab_rows_held")
    _TUPLES = ("rope_layout", "sliding_window_layout", "experts_held")

    # the names models/decoder.py's modules read
    norm_eps = property(lambda self: self.rms_norm_eps)
    num_experts = property(lambda self: self.moe_num_primary_experts)
    num_experts_per_tok = property(
        lambda self: self.moe_num_active_primary_experts)
    moe_intermediate_size = property(lambda self: self.moe_ffn_hidden_size)
    use_expert_bias = property(lambda self: False)
    routed_scaling_factor = property(lambda self: 1.0)
    router_scores = property(lambda self: "softmax")
    expert_activation = property(lambda self: "relu")

    @property
    def layer_kinds(self) -> Tuple[Tuple[int, bool], ...]:
        """(window, rope) of every layer of the stack as run: the band's
        width (0: the whole document) and whether q and k take rotary
        positions."""
        n = self.num_hidden_layers
        if len(self.rope_layout) != n or len(self.sliding_window_layout) != n:
            raise ValueError(
                "rope_layout and sliding_window_layout must give one entry "
                f"for each of num_hidden_layers={n} layers (have "
                f"{len(self.rope_layout)} and "
                f"{len(self.sliding_window_layout)})")
        unsupported = [
            name for name, bad in (
                ("moe_primary_router_apply_softmax=false",
                 not self.moe_primary_router_apply_softmax),
                ("rope_scaling", self.rope_scaling is not None),
                ("tie_word_embeddings", self.tie_word_embeddings),
                ("sliding_window_size < 1", self.sliding_window_size < 1),
                ("num_attention_heads not a multiple of num_key_value_heads",
                 self.num_attention_heads % self.num_key_value_heads != 0),
            ) if bad]
        if unsupported:
            raise NotImplementedError(
                f"smallthinker: not written for {unsupported} (the source "
                "model uses none of them)")
        self.held_range
        return tuple((self.sliding_window_size if w else 0, bool(r))
                     for w, r in zip(self.sliding_window_layout,
                                     self.rope_layout))


@dataclasses.dataclass(frozen=True)
class LagunaConfig(DecoderConfig):
    """Architecture config of the `laguna` family (poolside Laguna): a
    pre-norm decoder whose every layer is causal grouped-query attention
    with a sigmoid gate per head on its output, over the last
    `sliding_window` tokens or over the whole document (`layer_types`), with
    a head count PER LAYER (`num_attention_heads_per_layer`) and rotary
    tables per kind of layer (`rope_parameters`: the windowed layers rotate
    the whole head, the full layers a part of it under YaRN); a dense SwiGLU
    MLP or sigmoid-routed experts beside one shared expert after it
    (`mlp_layer_types`); an untied head (models/laguna.py has the
    equations).

    Keys are the source's (`config.json` of the model; `rope_parameters` is
    its nested group, one sub-group a kind of layer). A run may hold one
    expert-parallel rank's share, as Lfm2MoeConfig's: `num_experts` experts,
    the range `experts_held` of `experts_total` (the router keeps that
    width) and `vocab_size` rows of the vocabulary; a cut stack gives the
    three per-layer lists of the layers it keeps (`num_hidden_layers`
    entries each).
    """

    model_type: str = "laguna"
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 262144
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    tie_word_embeddings: bool = False
    gating: bool = True
    sliding_window: int = 512
    layer_types: Tuple[str, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    num_attention_heads_per_layer: Tuple[int, ...] = ()
    moe_apply_router_weight_on_input: bool = False
    partial_rotary_factor: float = 0.5
    moe_routed_scaling_factor: float = 2.5
    # rope_parameters, a sub-group a kind of layer, each as sorted
    # (key, value) pairs (the config is a static field of the modules)
    rope_full_attention: Tuple[Tuple[str, Any], ...] = ()
    rope_sliding_attention: Tuple[Tuple[str, Any], ...] = ()
    experts_total: Optional[int] = None
    experts_held: Optional[Tuple[int, int]] = None
    initializer_range: float = 0.02
    model_name: Optional[str] = None
    # run settings, as BertConfig's
    dtype: str = "bfloat16"
    checkpoint_activations: bool = False
    remat_policy: str = "auto"
    attention_impl: str = "auto"

    # keys of the configuration's file that carry no size of this program's
    _IGNORED = ("vocab_rows_total", "vocab_rows_held", "rope_parameters")
    _ROPE_KEYS = ("rope_theta", "rope_type", "factor",
                  "original_max_position_embeddings", "beta_slow",
                  "beta_fast", "attention_factor", "partial_rotary_factor")
    _TUPLES = ("layer_types", "mlp_layer_types",
               "num_attention_heads_per_layer", "experts_held")

    @classmethod
    def _read_groups(cls, d: Dict[str, Any], kw: Dict[str, Any]) -> list:
        unknown = []
        for kind, group in (d.get("rope_parameters") or {}).items():
            if kind == "original_max_position_embeddings":
                continue        # the full layers' sub-group repeats it
            if kind not in ("full_attention", "sliding_attention"):
                unknown.append(f"rope_parameters.{kind}")
                continue
            unknown += [f"rope_parameters.{kind}.{k}" for k in group
                        if k not in cls._ROPE_KEYS]
            kw[f"rope_{kind}"] = tuple(sorted(group.items()))
        return unknown

    # the names models/decoder.py's modules read. The config says
    # nothing of how the router scores, renormalises or selects: the
    # convention of its family of models (256 experts, 8 a token, a scaling
    # factor, one shared expert), which is what ops/moe.route has
    norm_eps = property(lambda self: self.rms_norm_eps)
    norm_topk_prob = property(lambda self: True)
    use_expert_bias = property(lambda self: True)
    routed_scaling_factor = property(
        lambda self: self.moe_routed_scaling_factor)
    router_scores = property(lambda self: "sigmoid")
    expert_activation = property(lambda self: "silu")

    def rope(self, kind: str) -> Dict[str, Any]:
        """The rotary parameters of a kind of layer ("full" or "sliding"):
        its sub-group of `rope_parameters`, `partial_rotary_factor` from the
        top level where the sub-group has none."""
        group = dict(getattr(self, f"rope_{kind}_attention"))
        group.setdefault("partial_rotary_factor", self.partial_rotary_factor)
        return group

    @property
    def layer_kinds(self) -> Tuple[Tuple[str, int, str], ...]:
        """(attention, query heads, ffn) of every layer of the stack as run:
        attention "sliding" or "full", ffn "dense" or "moe"."""
        n = self.num_hidden_layers
        lists = (self.layer_types, self.mlp_layer_types,
                 self.num_attention_heads_per_layer)
        if any(len(x) != n for x in lists):
            raise ValueError(
                "layer_types, mlp_layer_types and "
                "num_attention_heads_per_layer must give one entry for each "
                f"of num_hidden_layers={n} layers (have "
                f"{[len(x) for x in lists]})")
        attention = {"sliding_attention": "sliding",
                     "full_attention": "full"}
        ffn = {"dense": "dense", "sparse": "moe"}
        bad = [t for t in self.layer_types if t not in attention]
        bad += [t for t in self.mlp_layer_types if t not in ffn]
        if bad:
            raise ValueError(f"unknown layer type(s) {sorted(set(bad))}")
        ropes = {kind: self.rope(kind) for kind in ("full", "sliding")
                 if kind in (attention[t] for t in self.layer_types)}
        unsupported = [
            name for name, bad in (
                ("attention_bias", self.attention_bias),
                ("gating=false", self.gating is not True),
                ("tie_word_embeddings", self.tie_word_embeddings),
                ("moe_apply_router_weight_on_input",
                 self.moe_apply_router_weight_on_input),
                ("sliding_window < 1", self.sliding_window < 1),
                ("shared_expert_intermediate_size != moe_intermediate_size",
                 self.shared_expert_intermediate_size
                 != self.moe_intermediate_size),
                ("a head count that is no multiple of num_key_value_heads",
                 any(h % self.num_key_value_heads
                     for h in self.num_attention_heads_per_layer)),
                ("rope_parameters without rope_theta",
                 any("rope_theta" not in r for r in ropes.values())),
                ("rope_type other than default and yarn",
                 any(r.get("rope_type", "default") not in ("default", "yarn")
                     for r in ropes.values())),
            ) if bad]
        if unsupported:
            raise NotImplementedError(
                f"laguna: not written for {unsupported} (the source model "
                "uses none of them)")
        self.held_range
        return tuple((attention[a], int(h), ffn[m]) for a, h, m in zip(*[
            self.layer_types, self.num_attention_heads_per_layer,
            self.mlp_layer_types]))


@dataclasses.dataclass(frozen=True)
class KeyeConfig(DecoderConfig):
    """Architecture config of the `keye` family (the language model of
    Kwai Keye-VL-2.0): a pre-norm decoder whose every layer is causal
    grouped-query attention over the keys a learned index SELECTS (`sa_*`:
    an indexer of `sa_indexer_num_heads` heads on one key head scores every
    earlier token of the document, and a query attends to its `sa_topk`
    best), with per-head RMS norms on q and k and rotary positions over the
    whole head, then softmax-routed SwiGLU experts; an untied head
    (models/keye.py has the equations). The vision tower is not part of
    this program.

    Keys are the source's (`config.json` of the model); its two nested
    groups are read into flat fields (`sa_config.topk` -> `sa_topk`,
    `rope_scaling.mrope_section` -> `mrope_section`). A run may hold one
    expert-parallel rank's share, as Lfm2MoeConfig's: `num_experts` (and
    `num_local_experts`) experts, the range `experts_held` of
    `experts_total` (the router keeps that width) and `vocab_size` rows of
    the vocabulary.
    """

    model_type: str = "keye"
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 6144       # a dense layer's; the source has none
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 262144
    max_window_layers: int = 48         # read with use_sliding_window only
    attention_bias: bool = False
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = ()
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    num_experts: int = 128
    num_local_experts: int = 128
    num_experts_per_tok: int = 8
    rope_theta: float = 10000000.0
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    rope_type: str = "default"
    sa_indexer_head_dim: int = 64
    sa_indexer_num_heads: int = 16
    sa_indexer_num_kv_heads: int = 1
    sa_q_chunk_size: int = 512
    sa_kv_chunk_size: int = 512
    sa_topk: int = 2048
    sliding_window: Optional[int] = None
    use_sliding_window: bool = False
    tie_word_embeddings: bool = False
    experts_total: Optional[int] = None
    experts_held: Optional[Tuple[int, int]] = None
    initializer_range: float = 0.02
    model_name: Optional[str] = None
    # run settings, as BertConfig's
    dtype: str = "bfloat16"
    checkpoint_activations: bool = False
    remat_policy: str = "auto"
    attention_impl: str = "auto"

    # keys of the configuration's file that carry no size of this program's
    _IGNORED = ("vocab_rows_total", "vocab_rows_held", "published",
                "rope_scaling", "sa_config")
    _SA_KEYS = ("indexer_head_dim", "indexer_num_heads",
                "indexer_num_kv_heads", "q_chunk_size", "kv_chunk_size",
                "topk")
    _ROPE_KEYS = ("mrope_section", "rope_type", "type")
    _SA_CHUNK = 512     # DEFAULT_BLK_Q and DEFAULT_BLK_K of the kernels
    _TUPLES = ("mlp_only_layers", "mrope_section", "experts_held")

    @classmethod
    def _read_groups(cls, d: Dict[str, Any], kw: Dict[str, Any]) -> list:
        unknown = []
        for key, value in (d.get("sa_config") or {}).items():
            if key in cls._SA_KEYS:
                kw[f"sa_{key}"] = value
            else:
                unknown.append(f"sa_config.{key}")
        rope = d.get("rope_scaling") or {}
        unknown += [f"rope_scaling.{k}" for k in rope
                    if k not in cls._ROPE_KEYS]
        if "mrope_section" in rope:
            kw["mrope_section"] = rope["mrope_section"]
        for key in ("rope_type", "type"):
            if key in rope:
                kw["rope_type"] = rope[key]
        return unknown

    # the names models/decoder.py's modules read
    norm_eps = property(lambda self: self.rms_norm_eps)
    use_expert_bias = property(lambda self: False)
    routed_scaling_factor = property(lambda self: 1.0)
    router_scores = property(lambda self: "softmax")
    expert_activation = property(lambda self: "silu")

    def check(self) -> None:
        """Raises where the config asks for what models/keye.py is not
        written for, or is cut inconsistently."""
        unsupported = [
            name for name, bad in (
                ("attention_bias", self.attention_bias),
                ("hidden_act other than silu", self.hidden_act != "silu"),
                ("decoder_sparse_step != 1", self.decoder_sparse_step != 1),
                ("mlp_only_layers", bool(self.mlp_only_layers)),
                ("norm_topk_prob=false", not self.norm_topk_prob),
                ("use_sliding_window", self.use_sliding_window
                 or self.sliding_window is not None),
                ("tie_word_embeddings", self.tie_word_embeddings),
                ("rope_type other than default", self.rope_type != "default"),
                ("an mrope_section that does not add up to head_dim / 2",
                 sum(self.mrope_section) * 2 != self.head_dim),
                ("sa_config.indexer_num_kv_heads != 1",
                 self.sa_indexer_num_kv_heads != 1),
                ("sa_config.topk < 1", self.sa_topk < 1),
                # the index scores are tiled as the flash kernels' blocks
                # are (ops/pallas/flash_attention.select_blocks: 512 by
                # 512; a shorter row is one smaller tile), which is the
                # form the packed selection has
                (f"sa_config.q_chunk_size / kv_chunk_size other than "
                 f"{self._SA_CHUNK}",
                 (self.sa_q_chunk_size, self.sa_kv_chunk_size)
                 != (self._SA_CHUNK, self._SA_CHUNK)),
                ("num_local_experts != num_experts",
                 self.num_local_experts != self.num_experts),
                ("num_attention_heads not a multiple of num_key_value_heads",
                 self.num_attention_heads % self.num_key_value_heads != 0),
            ) if bad]
        if unsupported:
            raise NotImplementedError(
                f"keye: not written for {unsupported} (the source model "
                "uses none of them)")
        self.held_range


MODEL_FAMILIES = {"bert": BertConfig, "lfm2_moe": Lfm2MoeConfig,
                  "kimi_linear": KimiLinearConfig,
                  "smallthinker": SmallThinkerConfig,
                  "laguna": LagunaConfig, "keye": KeyeConfig}


def load_model_config(path: str):
    """The model config of an entry point, by family: `model_type` picks the
    config class ("bert" where the key is absent). An unknown `model_type`
    is an error, and so is a key the family does not know (other than
    DOCUMENTED_DATA_KEYS): a config of another architecture must not be
    trimmed into a BERT silently."""
    with open(path, "r", encoding="utf-8") as f:
        d = json.load(f)
    family = d.get("model_type", "bert")
    if family not in MODEL_FAMILIES:
        raise ValueError(
            f"{path}: unknown model_type {family!r} (this program runs "
            f"{sorted(MODEL_FAMILIES)})")
    cls = MODEL_FAMILIES[family]
    if cls is BertConfig:
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(k for k in d if k not in known
                         and k not in DOCUMENTED_DATA_KEYS)
        if unknown:
            raise ValueError(
                f"{path}: BERT model config carries key(s) BertConfig does "
                f"not know: {unknown}")
    return cls.from_dict(d)


# student presets: `student_<L>l_<H>` names a depth-L, width-H student of
# whatever teacher config it is derived from (training/distill.py). The
# rule, not a table, so any size is nameable; the canonical BERT-Base
# students are student_6l_768 (half depth) and student_4l_512.
_STUDENT_PRESET = re.compile(r"^student_(\d+)l_(\d+)$")


def is_student_preset(name: str) -> bool:
    return bool(_STUDENT_PRESET.match(name or ""))


def student_config(preset: str, teacher: "BertConfig") -> "BertConfig":
    """Derive a student architecture from `teacher` by preset name.

    `student_<L>l_<H>` -> num_hidden_layers=L, hidden_size=H,
    intermediate_size=4H (BERT's MLP ratio), num_attention_heads=H//64
    (BERT's 64-wide heads) lowered until it divides H. Everything else —
    vocab/tokenizer keys, dropout, dtype, fused ops, attention impl,
    parameter layout — is inherited from the teacher, so students train
    and serve through the exact code paths the teacher does (the point
    of the distillation factory: a student is just a checkpoint).
    """
    m = _STUDENT_PRESET.match(preset or "")
    if not m:
        raise ValueError(
            f"unknown student preset {preset!r}; expected student_<L>l_<H> "
            "(e.g. student_6l_768, student_4l_512)")
    layers, hidden = int(m.group(1)), int(m.group(2))
    if layers < 1 or hidden < 1:
        raise ValueError(f"student preset {preset!r}: depth and width "
                         "must be >= 1")
    heads = max(1, hidden // 64)
    while hidden % heads:
        heads -= 1
    return teacher.replace(
        num_hidden_layers=layers,
        hidden_size=hidden,
        num_attention_heads=heads,
        intermediate_size=4 * hidden,
    )


def pad_vocab_size(vocab_size: int, multiple: int = 8) -> int:
    """Pad vocab to a multiple (reference pads to 8 at every load site,
    run_pretraining.py:227-228). On TPU the MXU lane width makes 128 the
    natural multiple for the embedding/decoder matmul; callers pick."""
    return ((vocab_size + multiple - 1) // multiple) * multiple


def explicit_cli_keys(parser: argparse.ArgumentParser,
                      argv: Optional[list] = None) -> set:
    """Which destinations were explicitly given on the command line —
    found by re-parsing with every default suppressed (argparse has no
    public API for this). Shared by merge_args_with_config's CLI-wins
    precedence and run_pretraining's stream-flag validation, so the two
    can never drift on what counts as 'passed'."""
    suppressed = copy.deepcopy(parser)
    for action in suppressed._actions:  # noqa: SLF001
        action.default = argparse.SUPPRESS
    return set(vars(suppressed.parse_args(argv)))


def merge_args_with_config(
    parser: argparse.ArgumentParser,
    argv: Optional[list] = None,
    config_key: str = "config_file",
) -> argparse.Namespace:
    """Three-level precedence: CLI > JSON run config > parser defaults.

    Mirrors the reference's mechanism (run_pretraining.py:152-166): parse once
    normally, then re-parse with all defaults suppressed to learn which flags
    the user explicitly passed; JSON config values override defaults but never
    explicit CLI flags.
    """
    args = parser.parse_args(argv)

    config_path = getattr(args, config_key, None)
    if not config_path:
        return args

    with open(config_path, "r", encoding="utf-8") as f:
        config = json.load(f)

    explicit = explicit_cli_keys(parser, argv)

    for key, value in config.items():
        if key in explicit:
            continue  # CLI wins
        # Keys the entry point doesn't declare (e.g. data-pipeline hints)
        # attach to the namespace rather than crashing.
        setattr(args, key, value)
    return args
