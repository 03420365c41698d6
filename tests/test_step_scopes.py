"""The account of the compiled step (training/pretrain.STEP_SCOPES): both
step builders put every operation they trace under an entry of the one
list, the benchmark's readers carry that same list, and the scopes the
benchmark's metrics read by name are in it. Its second level
(STEP_SUBSCOPES): every child a family's step is declared to open is in
that family's compiled step, forward and backward, under the pattern the
benchmark's reader looks with; the metric files name declared paths only;
an executable without a declared path is told from one that has them."""

import functools
import glob
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bert_pytorch_tpu.analysis import hlo
from bert_pytorch_tpu.config import BertConfig
from bert_pytorch_tpu.models import BertForPreTraining
from bert_pytorch_tpu.optim import schedulers
from bert_pytorch_tpu.optim.kfac import KFAC, KFACConfig
from bert_pytorch_tpu.optim.lamb import default_weight_decay_mask, lamb
from bert_pytorch_tpu.telemetry import HealthConfig, init_telemetry_state
from bert_pytorch_tpu.training import (build_pretrain_step, init_kfac_state,
                                       make_sharded_state)
from bert_pytorch_tpu.training.pretrain import (FORWARD_ONLY_SUBSCOPES,
                                                LM_STEP_SCOPES, STEP_SCOPES,
                                                STEP_SUBSCOPES,
                                                build_kfac_pretrain_step,
                                                stack_microbatches,
                                                step_scope, step_subscopes)

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


# -- the second level ---------------------------------------------------------

# a decoder family's toy: the module of its own tests (TOY, SEED, `ref`,
# `_packed`), its config class and its model module
DECODERS = {
    "lfm2_moe": ("tests.test_lfm2_moe", "Lfm2MoeConfig"),
    "kimi_linear": ("tests.test_kimi_linear", "KimiLinearConfig"),
    "smallthinker": ("tests.test_smallthinker", "SmallThinkerConfig"),
    "laguna": ("tests.test_laguna", "LagunaConfig"),
    "keye": ("tests.test_keye", "KeyeConfig"),
}
FAMILIES = ("bert",) + tuple(DECODERS)


def _decoder_step(family):
    """The family's toy step as its own tests build it: packed rows, two
    micro-batches, remat, bf16 gradients, LAMB."""
    import run_pretraining
    from bert_pytorch_tpu import config as configs
    from bert_pytorch_tpu.models.families import FAMILIES as FAMILY_RECORDS
    from bert_pytorch_tpu.training.state import TrainState

    toy_module, config_cls = DECODERS[family]
    toy = importlib.import_module(toy_module)
    models = importlib.import_module("bert_pytorch_tpu.models." + family)
    cfg = getattr(configs, config_cls).from_dict(toy.TOY).replace(
        dtype="float32", checkpoint_activations=True, attention_impl="xla")
    params = toy.ref.init_params(toy.SEED, toy.ref.sizes_from_config(toy.TOY))
    if family == "lfm2_moe":
        params = toy.lm_adapter.to_program_tree(params)
    model = FAMILY_RECORDS[family].make_model(cfg, jnp.float32)
    sched = schedulers.make_schedule("poly", 0.004, 100, warmup=0.1)
    tx = run_pretraining.make_optimizer("lamb", sched)
    state = TrainState(step=jnp.zeros([], jnp.int32), params=params,
                       opt_state=tx.init(params))
    step = build_pretrain_step(
        model, tx, schedule=sched, accum_steps=2, grad_dtype=jnp.bfloat16,
        loss_fn_builder=models.pretrain_loss_fn_builder,
        keep_float32=models.keep_float32)
    batch = {k: jnp.stack([jnp.asarray(v)] * 2) for k, v in zip(
        ("input_ids", "segment_ids", "position_ids"), toy._packed())}
    return step, state, batch


@functools.lru_cache(maxsize=None)
def _family_text(family):
    """The compiled text of the family's toy step."""
    step, state, batch = (_toy_step(False) if family == "bert"
                          else _decoder_step(family))
    return jax.jit(step).lower(state, batch,
                               jax.random.PRNGKey(0)).compile().as_text()


def _op_names(text):
    return {op for op in _OP_NAME.findall(text) if op.startswith("jit(")}


@pytest.mark.parametrize("family", FAMILIES)
def test_every_declared_subscope_is_in_the_familys_step(family):
    """Found with the reader's own pattern, in the forward pass and in the
    backward pass (an op_name that holds `transpose(`; FORWARD_ONLY_SUBSCOPES
    in the forward pass alone); and the count the run's fingerprint takes of
    the same text misses none."""
    from benchmark.readers.scope_sum_share import under

    paths = step_subscopes(family)
    assert paths
    names = _op_names(_family_text(family))
    for path in paths:
        hits = [n for n in names if under(path).search(n)]
        assert hits, path
        assert any("transpose(" in n for n in hits) == (
            path not in FORWARD_ONLY_SUBSCOPES), path
    fp = hlo.fingerprint_of(hlo.parse_hlo_module(_family_text(family),
                                                 paths))
    assert list(fp["scope_counts"]) == list(paths)
    assert all(fp["scope_counts"].values()), fp["scope_counts"]
    assert hlo.stale_scopes_warning(fp) is None
    # nothing another family opens: the declaration says who opens what
    for path in set(step_subscopes()) - set(paths):
        assert not [n for n in names if under(path).search(n)][:3], path


def test_an_executable_without_a_declared_scope_is_told_by_name():
    """A warm compile cache can serve the executable of a program that
    opened fewer scopes (JAX keeps op_names out of the cache key)."""
    paths = step_subscopes("bert")
    older = re.sub(r'/attn_core(?=[/"])', "", _family_text("bert"))
    fp = hlo.fingerprint_of(hlo.parse_hlo_module(older, paths))
    assert fp["scope_counts"]["attention/attn_core"] == 0
    assert fp["scope_counts"]["attention/qkv"] > 0
    warning = hlo.stale_scopes_warning(fp)
    assert "attention/attn_core" in warning and "qkv" not in warning
    assert "clear the compile cache" in warning
    # no scopes asked for: none counted, nothing to warn of
    plain = hlo.fingerprint_of(hlo.parse_hlo_module(older))
    assert "scope_counts" not in plain
    assert hlo.stale_scopes_warning(plain) is None


def test_the_second_level_is_a_tree_under_the_first():
    from benchmark.readers.scope_sum_share import under
    from bert_pytorch_tpu.config import MODEL_FAMILIES

    paths = step_subscopes()
    assert len(set(paths)) == len(paths)
    for parent, children in STEP_SUBSCOPES.items():
        # a parent is an entry of the first level or a declared path
        assert (parent in STEP_SCOPES + LM_STEP_SCOPES
                or parent in paths), parent
        for child, families in children.items():
            assert families and set(families) <= set(MODEL_FAMILIES), child
            if parent in paths:     # whoever opens a child opens its parent
                up, name = parent.rsplit("/", 1)
                assert set(families) <= set(STEP_SUBSCOPES[up][name])
    for path in paths:
        # the first level's answer does not change: a path is its root's
        root = path.split("/")[0]
        assert step_scope("jit(train_step)/" + path + "/dot",
                          LM_STEP_SCOPES) == root
        # the program's pattern is the reader's
        assert hlo.scope_pattern(path).pattern == under(path).pattern
    assert {f for c in STEP_SUBSCOPES.values() for fs in c.values()
            for f in fs} == set(MODEL_FAMILIES)


def _metric_files():
    return sorted(glob.glob(os.path.join(METRICS, "*.json")))


@pytest.mark.parametrize("path", _metric_files(),
                         ids=lambda p: os.path.basename(p)[:-5])
def test_a_metric_names_declared_scopes_and_kernels_only(path):
    """A scope path in a metric's file is an entry of the first level or a
    path of STEP_SUBSCOPES; a kernel whose share of the busy time is read
    (`kernel_share`) is the `name=` of a `pallas_call` of ops/pallas/ (the
    roofline readers' flash kernels take their names from
    flash_attention._kernel_name, and each cell's traffic file expects them
    in the step's HLO)."""
    with open(path, encoding="utf-8") as f:
        metric = json.load(f)
    args = metric.get("args", {})
    first = set(STEP_SCOPES + LM_STEP_SCOPES)
    paths = set(step_subscopes())
    # by one name: the first level, a child's own name (scope_share's
    # files: `attention_window`), or what cuts across the list
    single = first | {p.rsplit("/", 1)[1] for p in paths} | {
        "rematted_computation"}
    scopes = list(args.get("scopes", [])) + list(args.get("outside", []))
    scopes += [args["scope"]] if "scope" in args else []
    assert not [s for s in scopes
                if s not in (paths if "/" in s else single)]
    if metric["reader"] == "kernel_share":
        sources = "".join(
            open(p, encoding="utf-8").read() for p in glob.glob(os.path.join(
                ROOT, "bert_pytorch_tpu", "ops", "pallas", "*.py")))
        assert not [k for k in args["kernels"] if f'"{k}"' not in sources]
