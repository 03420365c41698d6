"""models/families.py: one record a model family, read once by
run_pretraining.main. The table's keys are config.MODEL_FAMILIES'; each
record builds, initialises and steps its family's model with nothing but
what the record says; the lfm2_moe record refuses the flags it names."""

import json
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bert_pytorch_tpu.config import (MODEL_FAMILIES, BertConfig,  # noqa: E402
                                     KeyeConfig, KimiLinearConfig,
                                     LagunaConfig, Lfm2MoeConfig,
                                     SmallThinkerConfig)
from bert_pytorch_tpu.models.families import FAMILIES, family_of  # noqa: E402

VOCAB, SEQ = 2048, 64
LFM2_TOY = {
    "model_type": "lfm2_moe", "vocab_size": VOCAB, "hidden_size": 32,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "num_hidden_layers": 2, "num_dense_layers": 1, "num_attention_heads": 2,
    "num_key_value_heads": 1, "num_experts": 2, "num_experts_per_tok": 2,
    "experts_total": 4, "experts_held": [0, 2],
    "layer_types": ["conv", "full_attention"], "layers_kept": [0, 1],
    "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
    "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "max_position_embeddings": 1024,
}
KIMI_TOY = {
    "model_type": "kimi_linear", "vocab_size": VOCAB, "hidden_size": 32,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "num_hidden_layers": 2, "layers_kept": [3, 4], "first_k_dense_replace": 1,
    "num_attention_heads": 2, "num_key_value_heads": 2, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "linear_attn_config": {"kda_layers": [1, 2, 3], "full_attn_layers": [4],
                           "num_heads": 2, "head_dim": 8,
                           "short_conv_kernel_size": 4},
    "num_experts": 2, "experts_total": 4, "experts_held": [0, 2],
    "num_experts_per_token": 2, "kda_chunk_size": 16,
}
SMALLTHINKER_TOY = {
    "model_type": "smallthinker", "vocab_size": VOCAB, "hidden_size": 32,
    "head_dim": 8, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "moe_ffn_hidden_size": 16,
    "moe_num_primary_experts": 2, "experts_total": 4, "experts_held": [0, 2],
    "moe_num_active_primary_experts": 2, "rope_layout": [0, 1],
    "sliding_window_layout": [0, 1], "sliding_window_size": 8,
}
LAGUNA_TOY = {
    "model_type": "laguna", "vocab_size": VOCAB, "hidden_size": 32,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "num_hidden_layers": 2,
    "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 8,
    "num_attention_heads_per_layer": [2, 4],
    "layer_types": ["full_attention", "sliding_attention"],
    "mlp_layer_types": ["dense", "sparse"], "sliding_window": 8,
    "num_experts": 2, "experts_total": 4, "experts_held": [0, 2],
    "num_experts_per_tok": 2,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
}
KEYE_TOY = {
    "model_type": "keye", "vocab_size": VOCAB, "hidden_size": 32,
    "moe_intermediate_size": 16, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "num_experts": 2, "num_local_experts": 2, "experts_total": 4,
    "experts_held": [0, 2], "num_experts_per_tok": 2,
    "rope_scaling": {"mrope_section": [1, 1, 2], "rope_type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "q_chunk_size": 512,
                  "kv_chunk_size": 512, "topk": 8},
}
TINY = {
    "bert": BertConfig(
        vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=SEQ, dtype="float32", fused_ops=False,
        attention_impl="xla"),
    "lfm2_moe": Lfm2MoeConfig.from_dict(LFM2_TOY).replace(dtype="float32"),
    "kimi_linear": KimiLinearConfig.from_dict(KIMI_TOY).replace(
        dtype="float32"),
    "smallthinker": SmallThinkerConfig.from_dict(SMALLTHINKER_TOY).replace(
        dtype="float32"),
    "laguna": LagunaConfig.from_dict(LAGUNA_TOY).replace(dtype="float32"),
    "keye": KeyeConfig.from_dict(KEYE_TOY).replace(dtype="float32"),
}


def test_family_table_has_a_record_for_every_config_family():
    assert set(FAMILIES) == set(MODEL_FAMILIES)
    for name, cfg in TINY.items():
        assert family_of(cfg) is FAMILIES[name]
    with pytest.raises(ValueError, match="no model family"):
        family_of(object())


def _loader_batch(tmp_path, objective, rows):
    from benchmark.harness import corpus
    from bert_pytorch_tpu.data.sharded import (
        HostShardSampler, PretrainingDataLoader, ShardIndex)

    d = str(tmp_path / "data")
    corpus.write_shards(d, {"samples": 64, "shards": 2, "lengths": {
        "kind": "lognormal", "median": 20, "sigma": 0.6, "min": 16,
        "max": SEQ}}, SEQ, VOCAB, 11)
    index = ShardIndex(sorted(str(p) for p in Path(d).rglob("*.hdf5")))
    loader = PretrainingDataLoader(
        index, HostShardSampler(len(index), world_size=1, rank=0, seed=3),
        batch_size=rows, mask_token_index=103, max_pred_per_seq=10,
        masked_lm_prob=0.15, vocab_size=VOCAB, seed=3, packing=True,
        packing_max_segments=4, packing_lookahead=8, objective=objective)
    batch = next(iter(loader))
    loader.close()
    return batch


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_record_builds_initialises_and_steps_its_family(name, tmp_path):
    """Everything main chooses by family, taken from the record alone: the
    model, model.init's inputs from a loader batch of the record's
    objective, and one optimizer step with the record's step keywords."""
    import run_pretraining
    from bert_pytorch_tpu.optim import schedulers
    from bert_pytorch_tpu.telemetry import init_telemetry_state
    from bert_pytorch_tpu.training import build_pretrain_step
    from bert_pytorch_tpu.training.pretrain import stack_microbatches
    from bert_pytorch_tpu.training.state import TrainState

    family, cfg, accum, max_pred_row = FAMILIES[name], TINY[name], 2, 14
    model = family.make_model(cfg, jnp.float32)
    stacked = stack_microbatches(
        _loader_batch(tmp_path, family.objective, rows=2 * accum), accum)
    params = model.init(jax.random.PRNGKey(0), *family.init_inputs(
        {k: v[0] for k, v in stacked.items()}))["params"]
    schedule = schedulers.make_schedule("poly", 0.004, 100, warmup=0.0)
    tx = run_pretraining.make_optimizer("lamb", schedule)
    state = TrainState(step=jnp.zeros([], jnp.int32), params=params,
                       opt_state=tx.init(params),
                       telemetry=init_telemetry_state())
    step = build_pretrain_step(
        model, tx, schedule=schedule, accum_steps=accum,
        max_predictions=max_pred_row if family.mlm_head else None,
        **family.step_kwargs)
    new_state, metrics = jax.jit(step)(
        state, {k: jnp.asarray(v) for k, v in stacked.items()},
        jax.random.PRNGKey(1))
    assert int(new_state.step) == 1
    # seeded weights: the loss is the uniform guess (BERT: plus NSP's,
    # at most ln 2; keye: its second term beside it, which is not in it)
    assert -0.5 < float(metrics.get("lm_loss", metrics["loss"])) \
        - np.log(VOCAB) < np.log(2) + 0.5
    assert ("indexer_kl" in metrics) == (name == "keye")
    moved = jax.tree.map(lambda a, b: bool(jnp.any(a != b)),
                         state.params, new_state.params)
    assert any(jax.tree.leaves(moved))
    assert family.train_flops_per_row(cfg, SEQ, max_pred_row) > 0
    # the cumulative [perf] counters read the step's own scalars
    counters = family.make_counters()
    routed = any(k.startswith("moe_l") for k in metrics)
    assert (counters is not None) == routed
    if counters is not None:
        counters.update({k: float(v) for k, v in metrics.items()})
        assert counters.fields()["moe_l0_dropped"] == 0
        assert ("dsa_selected_pairs" in counters.fields()) == (name == "keye")


@pytest.mark.parametrize("flags", [
    ["--kfac"], ["--stream_dir", "corpus"], ["--stacked_params", "true"],
    ["--steps_per_loop", "2"]], ids=lambda f: f[0].lstrip("-"))
@pytest.mark.parametrize("toy", [LFM2_TOY, KIMI_TOY, LAGUNA_TOY, KEYE_TOY],
                         ids=["lfm2_moe", "kimi_linear", "laguna", "keye"])
def test_decoder_families_refuse_what_they_cannot_run_with(flags, toy,
                                                           tmp_path):
    import run_pretraining

    cfg_path = tmp_path / "toy.json"
    cfg_path.write_text(json.dumps(toy))
    argv = ["--model_config_file", str(cfg_path), "--output_dir",
            str(tmp_path / "out"), "--tensorboard", "off"] + flags
    if "--stream_dir" not in flags:
        argv += ["--input_dir", str(tmp_path)]
    with pytest.raises(SystemExit) as e:
        run_pretraining.main(argv)
    message = str(e.value)
    assert ("'lfm2_moe', 'kimi_linear', 'smallthinker', 'laguna', 'keye'"
            in message)
    for flag in ("--kfac", "--stream_dir", "--stacked_params",
                 "--steps_per_loop"):
        assert flag in message


def test_bert_refuses_none_of_them():
    import argparse

    args = argparse.Namespace(kfac=True, stream_dir="corpus",
                              stacked_params="true", steps_per_loop=2)
    assert FAMILIES["bert"].refusal(args) is None
    assert FAMILIES["lfm2_moe"].refusal(args)
    # the decoder families' ONE refusal
    assert FAMILIES["kimi_linear"].refusal is FAMILIES["lfm2_moe"].refusal
    assert FAMILIES["laguna"].refusal is FAMILIES["lfm2_moe"].refusal
    assert FAMILIES["keye"].refusal is FAMILIES["lfm2_moe"].refusal
    args = argparse.Namespace(kfac=False, stream_dir=None,
                              stacked_params="auto", steps_per_loop=1)
    assert FAMILIES["lfm2_moe"].refusal(args) is None


DECODERS = sorted(set(FAMILIES) - {"bert"})
MODELS_DIR = os.path.join(ROOT, "bert_pytorch_tpu", "models")


def _models_imported(module: str) -> set:
    """The modules of bert_pytorch_tpu/models that models/<module>.py
    imports, read from its source."""
    import ast

    with open(os.path.join(MODELS_DIR, f"{module}.py")) as f:
        tree = ast.parse(f.read())
    prefix, found = "bert_pytorch_tpu.models", set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module == prefix:
                found.update(alias.name for alias in node.names)
            elif node.module.startswith(prefix + "."):
                found.add(node.module[len(prefix) + 1:].split(".")[0])
        elif isinstance(node, ast.Import):
            found.update(alias.name[len(prefix) + 1:].split(".")[0]
                         for alias in node.names
                         if alias.name.startswith(prefix + "."))
    return found


@pytest.mark.parametrize("name", DECODERS)
def test_a_family_module_imports_no_other_family(name):
    """What the decoder families share is models/decoder.py's: a change to
    one family's module is a change to one family's program."""
    imported = _models_imported(name)
    assert "decoder" in imported
    assert not imported & (set(DECODERS) - {name}), imported


def test_the_shared_decoder_module_imports_no_family():
    assert not _models_imported("decoder") & set(DECODERS)
    # and the block of the loss is one number in the package
    assigned = [
        path for path in Path(ROOT, "bert_pytorch_tpu").rglob("*.py")
        if any(line.startswith("LOSS_BLOCK_ROWS =")
               for line in path.read_text().splitlines())]
    assert [p.name for p in assigned] == ["decoder.py"]


# to_dict()'s keys of each decoder config class, in order, as they stood
# before the classes took their shared members from config.DecoderConfig
# (PR 46): a run's header, a bundle's manifest and a checkpoint's metadata
# carry them
TO_DICT_KEYS = {
    "lfm2_moe": (
        "model_type vocab_size hidden_size intermediate_size "
        "moe_intermediate_size num_hidden_layers num_dense_layers "
        "num_attention_heads num_key_value_heads num_experts "
        "num_experts_per_tok layer_types layers_kept experts_total experts_held "
        "conv_L_cache conv_bias norm_eps norm_topk_prob use_expert_bias "
        "routed_scaling_factor rope_theta max_position_embeddings "
        "initializer_range model_name dtype checkpoint_activations remat_policy "
        "attention_impl"),
    "kimi_linear": (
        "model_type vocab_size hidden_size intermediate_size "
        "moe_intermediate_size num_hidden_layers first_k_dense_replace "
        "num_attention_heads num_key_value_heads kv_lora_rank q_lora_rank "
        "qk_nope_head_dim qk_rope_head_dim v_head_dim mla_use_nope num_experts "
        "num_experts_per_token num_shared_experts num_expert_group topk_group "
        "moe_renormalize moe_router_activation_func routed_scaling_factor "
        "rms_norm_eps tie_word_embeddings kda_layers full_attn_layers "
        "kda_num_heads kda_head_dim short_conv_kernel_size kda_chunk_size "
        "kda_gate_rank layers_kept experts_total experts_held initializer_range "
        "model_name dtype checkpoint_activations remat_policy attention_impl"),
    "smallthinker": (
        "model_type vocab_size hidden_size head_dim num_hidden_layers "
        "num_attention_heads num_key_value_heads moe_ffn_hidden_size "
        "moe_num_primary_experts moe_num_active_primary_experts "
        "moe_primary_router_apply_softmax norm_topk_prob rope_layout "
        "sliding_window_layout sliding_window_size rope_theta rope_scaling "
        "rms_norm_eps max_position_embeddings tie_word_embeddings experts_total "
        "experts_held initializer_range model_name dtype checkpoint_activations "
        "remat_policy attention_impl"),
    "laguna": (
        "model_type vocab_size hidden_size intermediate_size num_hidden_layers "
        "num_attention_heads num_key_value_heads head_dim "
        "max_position_embeddings attention_bias rms_norm_eps num_experts "
        "num_experts_per_tok moe_intermediate_size "
        "shared_expert_intermediate_size tie_word_embeddings gating "
        "sliding_window layer_types mlp_layer_types "
        "num_attention_heads_per_layer moe_apply_router_weight_on_input "
        "partial_rotary_factor moe_routed_scaling_factor rope_full_attention "
        "rope_sliding_attention experts_total experts_held initializer_range "
        "model_name dtype checkpoint_activations remat_policy attention_impl"),
    "keye": (
        "model_type vocab_size hidden_size intermediate_size num_hidden_layers "
        "num_attention_heads num_key_value_heads head_dim "
        "max_position_embeddings max_window_layers attention_bias hidden_act "
        "rms_norm_eps decoder_sparse_step mlp_only_layers moe_intermediate_size "
        "norm_topk_prob num_experts num_local_experts num_experts_per_tok "
        "rope_theta mrope_section rope_type sa_indexer_head_dim "
        "sa_indexer_num_heads sa_indexer_num_kv_heads sa_q_chunk_size "
        "sa_kv_chunk_size sa_topk sliding_window use_sliding_window "
        "tie_word_embeddings experts_total experts_held initializer_range "
        "model_name dtype checkpoint_activations remat_policy attention_impl"),
}


@pytest.mark.parametrize("name", DECODERS)
def test_decoder_config_classes_share_their_members(name):
    from bert_pytorch_tpu.config import DecoderConfig

    cls = MODEL_FAMILIES[name]
    assert issubclass(cls, DecoderConfig)
    for member in ("from_dict", "from_json_file", "to_dict", "replace",
                   "router_width", "held_range"):
        assert member not in vars(cls), member
        assert member in vars(DecoderConfig), member
    assert " ".join(cls().to_dict()) == TO_DICT_KEYS[name]
    # the family's name is in the one unknown-key message
    with pytest.raises(ValueError, match=f"{name} model config: unknown "
                                         r"key\(s\) \['conv_L_cache_2'\]"):
        cls.from_dict({"conv_L_cache_2": 3})
    assert TINY[name].held_range == (0, 2) and TINY[name].router_width == 4
