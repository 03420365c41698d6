"""The account of the compiled step (training/pretrain.STEP_SCOPES): both
step builders put every operation they trace under an entry of the one
list, the benchmark's readers carry that same list, and the scopes the
benchmark's metrics read by name are in it."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bert_pytorch_tpu.config import BertConfig
from bert_pytorch_tpu.models import BertForPreTraining
from bert_pytorch_tpu.optim import schedulers
from bert_pytorch_tpu.optim.kfac import KFAC, KFACConfig
from bert_pytorch_tpu.optim.lamb import default_weight_decay_mask, lamb
from bert_pytorch_tpu.telemetry import HealthConfig, init_telemetry_state
from bert_pytorch_tpu.training import (build_pretrain_step, init_kfac_state,
                                       make_sharded_state)
from bert_pytorch_tpu.training.pretrain import (STEP_SCOPES,
                                                build_kfac_pretrain_step,
                                                stack_microbatches,
                                                step_scope)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRICS = os.path.join(ROOT, "benchmark", "layer_metrics")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\S+\s+([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _toy_step(kfac_on: bool, **config):
    """A two-layer BERT's train step as the entry point builds it: two
    micro-batches, gathered MLM head, health pack; LAMB with remat, bf16
    gradients and the fault-injection drill, or K-FAC. `config` overrides
    fields of the model's."""
    cfg = BertConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, next_sentence=True, dtype="float32",
        fused_ops=False, attention_impl="xla", hidden_dropout_prob=0.1,
        attention_probs_dropout_prob=0.1, kfac_taps=kfac_on,
        checkpoint_activations=not kfac_on).replace(**config)
    model = BertForPreTraining(cfg, dtype=jnp.float32)
    sched = schedulers.make_schedule("poly", 1e-3, 100, warmup=0.1)
    tx = lamb(sched, weight_decay=0.01,
              weight_decay_mask=default_weight_decay_mask)
    rng = np.random.RandomState(0)
    rows, seq = 8, 16
    ids = rng.randint(5, 128, (rows, seq)).astype(np.int32)
    labels = np.full((rows, seq), -1, np.int32)
    labels[:, 3], ids[:, 3] = ids[:, 3], 3
    batch = {k: jnp.asarray(v) for k, v in stack_microbatches({
        "input_ids": ids,
        "token_type_ids": np.zeros((rows, seq), np.int32),
        "attention_mask": np.ones((rows, seq), np.int32),
        "masked_lm_labels": labels,
        "next_sentence_labels": rng.randint(0, 2, (rows,)).astype(np.int32),
    }, 2).items()}
    sample = (batch["input_ids"][0], batch["token_type_ids"][0],
              batch["attention_mask"][0])
    state, _ = make_sharded_state(jax.random.PRNGKey(0),
                                  lambda r: model.init(r, *sample), tx)
    common = dict(schedule=sched, accum_steps=2, max_predictions=4,
                  health=HealthConfig(action="skip"), nan_inject_step=3)
    if kfac_on:
        kfac = KFAC(KFACConfig(learning_rate=sched))
        state, perts = init_kfac_state(model, kfac, state, sample)
        step = build_kfac_pretrain_step(model, tx, kfac, perts, **common)
    else:
        step = build_pretrain_step(model, tx, grad_dtype=jnp.bfloat16,
                                   **common)
    return step, state.replace(telemetry=init_telemetry_state()), batch


@pytest.mark.parametrize("kfac_on", [False, True], ids=["lamb", "kfac"])
def test_every_traced_instruction_is_under_a_step_scope(kfac_on):
    step, state, batch = _toy_step(kfac_on)
    text = jax.jit(step, donate_argnums=(0,)).lower(
        state, batch, jax.random.PRNGKey(0)).compile().as_text()
    found = {}
    for line in text.splitlines():
        m, op = _INSTR.match(line), _OP_NAME.search(line)
        # a parameter's op_name is the argument's name; a bare op_name
        # (`add`, `reduce_sum`) is the scalar combiner inside a reduce, a
        # sort or a scatter, which never runs as an operation of its own
        if m and op and m.group(1) != "parameter" \
                and op.group(1).startswith("jit("):
            found.setdefault(step_scope(op.group(1)), set()).add(op.group(1))
    assert None not in found, sorted(found[None])[:20]
    want = {"attention", "mlp", "mlm_head", "nsp_head", "pooler",
            "embeddings", "loss", "optimizer", "grad_norm", "health",
            "metrics", "encoder", "bert", "grad_accum"}
    want |= set() if kfac_on else {"param_cast"}    # the bf16 cast
    assert want <= set(found), want - set(found)
    if kfac_on:
        assert any("/optimizer/kfac/" in p for p in found["optimizer"])
    else:
        # recomputation cuts across the list: a recomputed attention
        # operation is `attention`
        assert any("rematted_computation" in p for p in found["attention"])


def test_first_match_and_transform_wrappers():
    path = ("jit(train_step)/grad_accum/while/body/closed_call/"
            "transpose(jvp(BertForPreTraining))/bert/encoder/while/body/")
    assert step_scope(path + "layers/layer/attention/attention/qkv/dot") \
        == "attention"
    assert step_scope(path + "dynamic_update_slice") == "encoder"
    assert step_scope("jit(train_step)/grad_accum/while/body/closed_call/"
                      "transpose(jvp(loss))/mul") == "loss"
    assert step_scope("jit(train_step)/grad_accum/while/body/add") \
        == "grad_accum"
    assert step_scope("jit(train_step)/optimizer/kfac/dot") == "optimizer"
    assert step_scope("jit(_threefry_fold_in)/mlp_like/attention_bias") \
        is None
    assert step_scope("") is None


def _metric(name):
    with open(os.path.join(METRICS, name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def test_the_benchmarks_readers_carry_the_programs_list():
    assert tuple(_metric("unscoped_share.train")["args"]["scopes"]) \
        == STEP_SCOPES
    carry = _metric("scan_carry_share.train")["args"]
    assert carry["scope"] in STEP_SCOPES
    # what is taken out of `encoder` is what the list matches before it
    before = STEP_SCOPES[:STEP_SCOPES.index(carry["scope"])]
    assert set(carry["outside"]) <= set(before)
    for name in ("attention_share.train", "mlm_head_share.train",
                 "mlp_share.train", "optimizer_share.train"):
        assert _metric(name)["args"]["scope"] in STEP_SCOPES, name
    assert _metric("recompute_share.train")["args"]["scope"] \
        not in STEP_SCOPES       # it stands beside the sum, not in it
