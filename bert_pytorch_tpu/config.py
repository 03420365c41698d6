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
from typing import Any, Dict, Optional


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
    #   "dots"     every matmul output, the attention core's (B, H, S, S)
    #              scores and context included (dots_saveable)
    #   "mlp_only" everything but the (B, S, F) wide-MLP activations
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
    # the (L, ...) grad buffer (a measured 9.4% of seq512 step time,
    # docs/PERF.md). False: the encoder is built as L separate BertLayer
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
