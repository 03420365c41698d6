"""What `run_pretraining.main` asks of a model family, one record a family.

`config.MODEL_FAMILIES` maps a config's `model_type` to its config class;
`FAMILIES` maps the same keys to everything else the pretraining entry point
has to choose by family: the model, what `model.init` takes, the loader's
objective, the step builder's keywords, the FLOPs of a row, the flags the
family cannot run with, and its cumulative `[perf]` counters. `main` looks
the record up once (`family_of(config)`); a new family is a new record here,
not a branch there.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Optional, Tuple

import jax.numpy as jnp

from bert_pytorch_tpu.config import MODEL_FAMILIES
from bert_pytorch_tpu.models import (decoder, keye, kimi_linear, laguna,
                                     lfm2_moe, smallthinker)
from bert_pytorch_tpu.models.bert import BertForPreTraining
from bert_pytorch_tpu.telemetry.expert_load import ExpertLoadCounters
from bert_pytorch_tpu.telemetry.stepwatch import flops_per_seq


@dataclasses.dataclass(frozen=True)
class Family:
    # (config, compute dtype) -> the pretraining module
    make_model: Callable[[Any, Any], Any]
    # one micro-batch of the loader's fields -> model.init's inputs
    init_inputs: Callable[[Mapping[str, Any]], Tuple]
    # the loader's objective: what it masks and which fields it yields
    objective: str
    # True: the step scores only the masked positions, through the gathered
    # MLM head, so --max_predictions_per_seq reaches the step builder and a
    # packed row's cap grows with its segments. False: no such head.
    mlm_head: bool
    # further keywords of training/pretrain.build_pretrain_step
    step_kwargs: Mapping[str, Any]
    # (config, seq_len, predictions a row) -> forward + backward FLOPs of a row
    train_flops_per_row: Callable[[Any, int, int], float]
    # parsed args -> why the family cannot run with them, or None
    refusal: Callable[[Any], Optional[str]]
    # () -> the family's cumulative [perf] counters (`update(step scalars)`,
    # `fields()`), or None where it has none
    make_counters: Callable[[], Optional[Any]]


def _bert_init_inputs(batch) -> Tuple:
    return tuple(jnp.asarray(batch[k]) for k in
                 ("input_ids", "token_type_ids", "attention_mask"))


def _decoder_refusal(args) -> Optional[str]:
    """The decoder families' one refusal (every record `_decoder_family`
    makes)."""
    if not (args.kfac or args.stream_dir or args.stacked_params != "auto"
            or args.steps_per_loop > 1):
        return None
    names = ", ".join(repr(name) for name, family in FAMILIES.items()
                      if family.refusal is _decoder_refusal)
    return (f"the decoder families (model_type {names}) "
            "train through the offline data plane with LAMB/Adam, one step "
            "a dispatch: --kfac, --stream_dir, --stacked_params and "
            "--steps_per_loop do not apply to them")


def _decoder_family(module, model_cls) -> Family:
    """A causal-LM family over packed rows, from its model module."""
    return Family(
        make_model=lambda config, dtype: model_cls(config, dtype=dtype),
        init_inputs=decoder.init_inputs,     # a packed causal-LM batch's
        objective="clm",
        mlm_head=False,
        step_kwargs={"loss_fn_builder": module.pretrain_loss_fn_builder,
                     "keep_float32": module.keep_float32},
        # the family's own formula (never BERT's): an upper estimate for
        # packed rows, whose documents attend less than a full row
        train_flops_per_row=lambda config, seq_len, n_pred:
            module.train_flops_per_row(config, seq_len),
        refusal=_decoder_refusal,
        make_counters=ExpertLoadCounters)


FAMILIES = {
    "bert": Family(
        make_model=lambda config, dtype: BertForPreTraining(config,
                                                            dtype=dtype),
        init_inputs=_bert_init_inputs,
        objective="mlm",
        mlm_head=True,
        step_kwargs={},
        train_flops_per_row=lambda config, seq_len, n_pred: flops_per_seq(
            config, seq_len, config.vocab_size, n_pred),
        refusal=lambda args: None,
        make_counters=lambda: None),
    "lfm2_moe": _decoder_family(lfm2_moe, lfm2_moe.Lfm2MoeForCausalLM),
    "kimi_linear": _decoder_family(kimi_linear,
                                   kimi_linear.KimiLinearForCausalLM),
    "smallthinker": _decoder_family(smallthinker,
                                    smallthinker.SmallThinkerForCausalLM),
    "laguna": _decoder_family(laguna, laguna.LagunaForCausalLM),
    "keye": _decoder_family(keye, keye.KeyeForCausalLM),
}


def family_name(config) -> str:
    """The key (of MODEL_FAMILIES, FAMILIES and the families of
    training/pretrain.STEP_SUBSCOPES) whose config class `config` is."""
    for name, cls in MODEL_FAMILIES.items():
        if type(config) is cls:
            return name
    raise ValueError(f"no model family for {type(config).__name__}")


def family_of(config) -> Family:
    """The record of the family whose config class `config` is."""
    return FAMILIES[family_name(config)]
