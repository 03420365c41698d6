"""The laguna cell of the benchmark on the CPU: the metrics that are its own,
its cut (every width as published against the catalog's row, the parameter
count from the reference's shapes), the family's arithmetic (laguna_flops:
per-layer head counts in both kinds of attention; the band's pairs against a
brute-force count), that the readers it brought return None, and do not
raise, on a run of a program that lacks the family's scopes, kernels and
counters (the parent commit's), the readers on a run of the family, and the
family module's own pieces (the tree it keeps, the matrices it samples, the
faults it can plant). The cell's whole rehearsal (`benchmark/run.py
--rehearse`, a minute on the CPU) is the builder's and not in this file."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import (kimi_flops, laguna_flops,  # noqa: E402
                               lm_flops, smallthinker_flops, spec)

CELL = "laguna-ep8-clm-16k-packed"
CONFIG = "laguna-xs2-33b-a3b-ep8"
MANIFEST = spec.load_manifest(ROOT)
NEW_METRICS = [
    "attention_window_share.laguna.train",
    "attention_full_share.laguna.train", "rotary_share.laguna.train",
    "flash_window_roofline.laguna", "flash_causal_roofline.laguna",
    "moe_share.laguna.train", "moe_dispatch_share.laguna.train",
    "moe_shared_share.laguna.train", "moe_experts_roofline.laguna",
    "expert_load_max_over_mean.laguna", "mlp_share.laguna.train",
    "lm_head_share.laguna.train", "rmsnorm_share.laguna.train",
    "recompute_share.laguna.train", "unscoped_share.laguna.train"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
KINDS = [(0, 48, "dense"), (512, 64, "moe"), (512, 64, "moe"),
         (512, 64, "moe"), (0, 48, "moe")]


def test_the_cells_own_metrics():
    found = spec.find_cell(MANIFEST, CELL, ROOT)
    assert found["family"] == "laguna" and found["chips"] == 1
    t = found["traffic"]
    assert (t["seq_len"], t["local_batch"], t["accum"]) == (16384, 1, 2)
    # the kimi cell's corpus and its order, so that its packing carries over
    kimi = spec.find_cell(MANIFEST, "kimi-linear-ep32-clm-16k-packed", ROOT)
    assert t["corpus"] == kimi["traffic"]["corpus"]
    assert t["corpus"]["order_seed"] == 0
    assert t["extra_args"] == kimi["traffic"]["extra_args"] == [
        "--packing", "--packing_max_segments", "32", "--packing_lookahead",
        "11", "--checkpoint_activations"]
    assert (t["learning_rate"], t["warmup_proportion"], t["max_steps"]) == (
        0.004, 0.128, 1563)
    assert t["min_window_steps"] == 16 and t["trace_steps"] == 3
    assert t["limits"]["tie_tol"] > 0 and t["limits"]["why"]
    assert t["expect_kernels"] == [
        "flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv",
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]
    mine = [m["name"] for m in spec.metrics_of_cell(MANIFEST, CELL,
                                                    "per_layer")]
    assert set(NEW_METRICS) <= set(mine)
    # every list-less metric is asked of the cell
    assert {"attention_share.train", "attention_core_share.train",
            "optimizer_share.train", "device_idle_share.train",
            "setup_lower_s", "step_hbm_share"} <= set(mine)
    assert not {"mlm_head_share.train", "conv_share.train", "moe_share.train",
                "moe_share.kimi.train", "flash_causal_roofline",
                "flash_window_roofline", "mla_flash_roofline",
                "unscoped_share.smallthinker.train"} & set(mine)
    for m in MANIFEST["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert spec.load_layer_metric(m["name"], ROOT)["layer"] == \
                m["layer"]
    # the list of scopes the program's account has, without the mixers this
    # family has none of, plus the two kinds of attention layer
    from bert_pytorch_tpu.training.pretrain import LM_STEP_SCOPES

    scopes = spec.load_layer_metric("unscoped_share.laguna.train",
                                    ROOT)["args"]["scopes"]
    assert [s for s in scopes if not s.startswith("attention_")] == [
        s for s in LM_STEP_SCOPES if s not in ("kda", "conv")]


def test_configuration_states_the_cut_and_every_width_as_published():
    cfg = spec.find_cell(MANIFEST, CELL, ROOT)["config"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == [
        "layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
        "num_experts", "num_hidden_layers", "vocab_size"]
    published = {
        "hidden_size": 2048, "intermediate_size": 8192, "head_dim": 128,
        "num_attention_heads": 48, "num_key_value_heads": 8,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "num_experts_per_tok": 8, "moe_routed_scaling_factor": 2.5,
        "sliding_window": 512, "partial_rotary_factor": 0.5, "gating": True,
        "attention_bias": False, "rms_norm_eps": 1e-06,
        "max_position_embeddings": 262144, "tie_word_embeddings": False,
        "moe_apply_router_weight_on_input": False}
    assert {k: cfg[k] for k in published} == published
    full = cfg["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["factor"], full["beta_fast"],
            full["original_max_position_embeddings"],
            full["partial_rotary_factor"]) == ("yarn", 64, 64, 4096, 0.5)
    assert cfg["rope_parameters"]["sliding_attention"]["rope_theta"] == 10000
    assert (cfg["experts_total"], cfg["vocab_rows_total"]) == (256, 100352)
    assert cfg["vocab_size"] * 8 == 100352 and cfg["vocab_size"] % 128 == 0
    assert cfg["experts_held"] == [0, 32] and cfg["num_experts"] == 32
    # the leading dense layer and one whole period of the published 3 : 1
    assert cfg["layer_types"] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cfg["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert laguna_flops.layer_kinds(cfg) == KINDS
    for key in ("router", "selection_bias", "gating", "activation",
                "norm_placement", "rotary", "weights", "embedding_init",
                "optimizer", "dtype", "packing", "dropout", "remat_policy"):
        assert cfg["assumed"][key]
    assert "NOT taken" in cfg["assumed"]["gating"]
    assert "8 chips share each layer" in cfg["layout"]
    assert "further pipeline stages" in cfg["layout"]
    assert cfg["remat_policy"] == "dense"
    # the catalog's row: every key under its name, but the keys cut
    if os.path.exists(CATALOG):
        row = next(json.loads(ln) for ln in open(CATALOG)
                   if '"name": "Laguna-XS.2"' in ln)
        assert cfg["source"] == entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert cfg[key] == value, key
            elif isinstance(value, list):      # a per-layer list: a prefix
                assert cfg[key] == value[:len(cfg[key])], key


def test_parameter_count_and_flops_of_the_cut():
    import jax

    from benchmark.reference import laguna_ref as ref

    cfg = spec.find_cell(MANIFEST, CELL, ROOT)["config"]
    shapes = ref.param_shapes(ref.sizes_from_config(cfg))

    def count(tree):
        return sum(int(np.prod(s)) for s in jax.tree.leaves(
            tree, is_leaf=lambda x: isinstance(x, tuple)))

    # ISSUE 41: 691.6 M parameters; layer 0 79.79 M, a windowed routed layer
    # 142.22 M, the full routed layer 133.80 M, the tables 51.38 M
    assert count(shapes) == 691624960
    assert [count(shapes[f"layer_{i}"]) for i in range(5)] == [
        79794176, 142217472, 142217472, 142217472, 133796096]
    attention = shapes["layer_1"]["attention"]
    assert count(attention) - count(attention["gate_proj"]) == 37748736
    assert count(attention["gate_proj"]) == 131072
    assert count(shapes["layer_0"]["attention"]["gate_proj"]) == 98304
    moe = shapes["layer_1"]["moe"]
    assert count({k: moe[k] for k in moe if k.startswith("experts_")}) == \
        100663296
    assert count(moe["router"]) == 524288
    assert count(shapes["layer_1"]["shared_expert"]) == 3145728
    assert count(shapes["embed_tokens"]) + count(shapes["lm_head"]) == \
        51380224
    # 11.1 GB at 16 bytes a parameter, 12.4 GB at 18, of the chip's 16.91
    assert 16 * count(shapes) < 11.1e9 and 18 * count(shapes) < 12.5e9
    per_token = laguna_flops.dense_weights_per_token(cfg)
    assert per_token == pytest.approx(275.84e6, rel=1e-4)
    held = 4 * 3 * 2048 * 512 * 8 * 32 / 256    # 32 of 256 -> 1 expert
    assert abs(held / per_token - 0.046) < 0.002    # ~5 % of the products
    # per (query, key) pair: 4 x 128 a query head, x 3 with the backward;
    # the full layers have 2 x 48 query heads, the windowed 3 x 64
    assert laguna_flops.causal_attention_flops(cfg, 10, False) == \
        2 * 96 * 256 * 10
    assert laguna_flops.causal_attention_flops(cfg, 10) == 6 * 96 * 256 * 10
    assert laguna_flops.window_attention_flops(cfg, 10) == \
        6 * 192 * 256 * 10
    assert laguna_flops.moe_expert_flops(cfg, 1) == 6 * 3 * 2048 * 512
    # a full row: the band keeps 6.2 % of its pairs
    full = laguna_flops.band_pairs(16384, 0)
    band = laguna_flops.band_pairs(16384, 512)
    assert (full, band) == (134225920, 512 * 513 // 2 + (16384 - 512) * 512)
    step = laguna_flops.train_flops(cfg, 32768, 2 * full, 2 * band)
    assert step == pytest.approx(
        6 * per_token * 32768 + 6 * 256 * (96 * 2 * full + 192 * 2 * band))
    # and the program's own estimate for a full row is the same arithmetic
    from bert_pytorch_tpu.config import LagunaConfig
    from bert_pytorch_tpu.models import laguna

    program = LagunaConfig.from_dict(
        {k: v for k, v in cfg.items() if k != "remat_policy"})
    assert laguna.train_flops_per_row(program, 16384) == pytest.approx(
        laguna_flops.train_flops(cfg, 16384, full, band), rel=1e-9)


@pytest.mark.parametrize("window", [0, 5, 16, 512])
def test_window_pairs_against_a_brute_force_count(window):
    """The family's count of a step's (query, key) pairs from its segment
    ids, per document L (L + 1) / 2 and under a band W (W + 1) / 2 +
    (L - W) W for L > W, against counting the allowed pairs one by one."""
    from benchmark.families import laguna as family

    rng = np.random.default_rng(window)
    seg = np.zeros((2, 3, 64), np.int32)
    for row in seg.reshape(-1, 64):
        cuts = np.sort(rng.choice(np.arange(1, 60), 3, replace=False))
        for g, (a, b) in enumerate(zip([0, *cuts[:-1]], cuts)):
            row[a:b] = g + 1                    # a padded tail after cuts[-1]
    want = 0
    for row in seg.reshape(-1, 64):
        for i in range(64):
            for j in range(i + 1):
                want += int(row[i] > 0 and row[i] == row[j]
                            and (not window or i - j < window))
    assert family.document_pairs(seg, window) == want
    found = spec.find_cell(MANIFEST, CELL, ROOT)
    cell = dict(found, config=dict(found["config"],
                                   sliding_window=window or 1))
    extras = family.window_extras({7: seg}, {7: {"moe_l0_dropped": 0}}, cell)
    assert extras["causal_pairs"][7] == family.document_pairs(seg, 0)
    assert extras["window_pairs"][7] == family.document_pairs(
        seg, window or 1)
    assert extras["dropped_pairs"] == 0


@pytest.mark.parametrize("flops", [lm_flops, kimi_flops, smallthinker_flops,
                                   laguna_flops],
                         ids=["lfm2-arithmetic", "kimi-arithmetic",
                              "smallthinker-arithmetic", "own-arithmetic"])
@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_reader_finds_nothing_in_a_run_without_the_family(name, flops):
    """The driver lays these files over the parent's checkout: a traced run
    of a program with none of the family's scopes, kernels or counters
    must leave the metric out, not raise."""
    metric = spec.load_layer_metric(name, ROOT)
    read = spec.load_reader(metric["reader"], ROOT)
    bert_trace = {"by_scope": {"jit(train_step)/bert/encoder/scan/dot": 1.0},
                  "busy_s": 1.0, "window_s": 1.0, "steps": 3}
    ctx = {"trace": bert_trace, "chips": 1, "flops": flops,
           "peaks": flops.peaks("TPU v5 lite"),
           "cell": spec.find_cell(MANIFEST, CELL, ROOT),
           "record": {"window": {"perf": [{"step": 7, "compiles": 9}],
                                 "traced_first_step": 7}}}
    value = read(ctx, **metric.get("args", {}))
    if name == "unscoped_share.laguna.train":
        assert value == 100.0       # nothing there is under the LM list
    else:
        assert value is None


def test_readers_on_a_run_of_the_family():
    cell = spec.find_cell(MANIFEST, CELL, ROOT)
    pre = "jit(train_step)/grad_accum/jvp(M)/decoder/layer_1/"
    back = ("jit(train_step)/grad_accum/transpose(jvp(M))/decoder/checkpoint/"
            "rematted_computation/layer_2/")
    full = "jit(train_step)/grad_accum/jvp(M)/decoder/layer_0/"
    trace = {"by_scope": {
        pre + "attention/attention_window/attn_core/flash_win_fwd/"
        "pallas_call": 0.1,
        back + "attention/attention_window/attn_core/flash_win_bwd_dq/"
        "pallas_call": 0.1,
        pre + "attention/attention_window/dot_general": 0.03,
        pre + "attention/rotary/mul": 0.04, pre + "attention/gate/mul": 0.01,
        full + "attention/attention_full/attn_core/flash_fwd/pallas_call":
            0.1,
        full + "attention/attention_full/out_proj/dot_general": 0.02,
        full + "mlp/w1/dot_general": 0.06,
        "ragged-dot-none": 0.04, pre + "moe/dispatch/sort": 0.01,
        pre + "moe/router/dot": 0.02, pre + "moe/combine/scatter-add": 0.02,
        pre + "moe/shared/shared_expert/w1/dot_general": 0.03,
        pre + "post_attention_layernorm/rmsnorm/rsqrt": 0.05,
        "jit(train_step)/grad_accum/jvp(loss)/while/body/checkpoint/"
        "lm_head/dot_general": 0.1,
        "jit(train_step)/grad_accum/jvp(loss)/while/body/checkpoint/loss/"
        "reduce": 0.1, "jit(train_step)/optimizer/mul": 0.07, "": 0.1},
        "busy_s": 1.0, "window_s": 1.0, "steps": 2}
    perf = [dict({"step": s}, **{f"moe_l{i}_pairs": 1000.0 * (s - 1)
                                 for i in range(4)},
                 moe_l0_load_max=300.0, moe_l0_load_mean=200.0,
                 moe_l1_load_max=250.0, moe_l1_load_mean=200.0)
            for s in range(5, 12)]
    ctx = {"trace": trace, "chips": 1, "flops": laguna_flops, "cell": cell,
           "peaks": {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e12},
           "record": {"window": {"perf": perf, "traced_first_step": 7,
                                 "causal_pairs": {"7": 2e6, "8": 2e6},
                                 "window_pairs": {"7": 1e6, "8": 1e6}}}}
    got = spec.read_layer_metrics(MANIFEST, CELL, ctx)
    v = {k: m["value"] for k, m in got.items()}
    assert set(NEW_METRICS) <= set(v)
    assert v["attention_window_share.laguna.train"] == pytest.approx(23.0)
    assert v["attention_full_share.laguna.train"] == pytest.approx(12.0)
    assert v["rotary_share.laguna.train"] == pytest.approx(4.0)
    # the accepted metrics read both kinds, the rotation and the gate: all
    # sit under `attention`; and the kernels under either kind's `attn_core`
    assert v["attention_share.train"] == pytest.approx(40.0)
    assert v["attention_core_share.train"] == pytest.approx(30.0)
    assert v["optimizer_share.train"] == pytest.approx(7.0)
    assert v["mlp_share.laguna.train"] == pytest.approx(6.0)
    assert v["moe_share.laguna.train"] == pytest.approx(12.0)
    assert v["moe_dispatch_share.laguna.train"] == pytest.approx(3.0)
    assert v["moe_shared_share.laguna.train"] == pytest.approx(3.0)
    assert v["rmsnorm_share.laguna.train"] == pytest.approx(5.0)
    assert v["recompute_share.laguna.train"] == pytest.approx(10.0)
    assert v["lm_head_share.laguna.train"] == pytest.approx(20.0)
    assert v["unscoped_share.laguna.train"] == pytest.approx(10.0)
    assert v["expert_load_max_over_mean.laguna"] == pytest.approx(1.5)
    # 2 x 4,000 (token, held expert) pairs in steps 7-8 over 0.04 s
    assert v["moe_experts_roofline.laguna"] == pytest.approx(
        100 * 6 * 8000 * 3 * 2048 * 512 / 1e12 / 0.04)
    # the band: 3 layers x 64 heads x 2e6 pairs over the 0.2 s of
    # flash_win_*; the full layers: 2 x 48 heads x 4e6 pairs over 0.1 s
    assert v["flash_window_roofline.laguna"] == pytest.approx(
        100 * 6 * 192 * 256 * 2e6 / 1e12 / 0.2)
    assert v["flash_causal_roofline.laguna"] == pytest.approx(
        100 * 6 * 96 * 256 * 4e6 / 1e12 / 0.1)


def test_family_keeps_the_tree_samples_both_kinds_and_plants_its_faults(
        monkeypatch):
    import jax

    from benchmark.families import laguna as family
    from benchmark.reference import laguna_ref as ref

    found = spec.find_cell(MANIFEST, CELL, ROOT)
    cfg = dict(found["config"], **found["traffic"]["rehearse"]["config"])
    sizes = family.sizes(cfg, found["traffic"])
    # the rehearsal keeps the groups of the real size: 6 and 8 query heads
    # to the one key/value head
    assert sizes["layer_heads"] == (6, 8, 8, 8, 6) and sizes["kv_heads"] == 1
    params = family.weights({"seed": 2 ** 31 + 3}, sizes)
    leaf_norms, _, sample = family.adapter_functions(sizes)
    norms = leaf_norms(params)
    assert len(norms) == len(jax.tree.leaves(params))
    assert norms["['layer_1']['moe']['experts_w1']"].shape == (4,)
    assert norms["['layer_1']['moe']['router']"].shape == (1,)
    sampled = sample(params)
    assert sorted(sampled) == sorted(
        [f"layer_{i}/attention/{n}" for i in (0, 1)
         for n in ("q_proj", "gate_proj", "out_proj/kernel")]
        + ["layer_0/mlp/w1/kernel", "layer_0/mlp/w2/kernel"]
        + [f"layer_{i}/{n}" for i in (1, 4)
           for n in ("moe/experts_w1", "moe/experts_w2", "moe/router",
                     "shared_expert/w1/kernel", "shared_expert/w2/kernel")])
    assert sampled["layer_1/moe/experts_w1"].shape == (128, 64)  # expert 0
    # the selection bias is one held draw, whatever the seed, and not zero;
    # the planted fault `zero_bias` hands the program zeros
    other = ref.init_params(5, sizes)
    assert not (other["lm_head"] == params["lm_head"]).all()
    bias = params["layer_2"]["moe"]["expert_bias"]
    assert (other["layer_2"]["moe"]["expert_bias"] == bias).all()
    assert float(abs(bias).max()) > 0
    monkeypatch.setattr(ref, "init_params", lambda seed, sz: params)
    broken = family.weights({"seed": 2 ** 31 + 3, "fault": "zero_bias"},
                            sizes)
    assert not np.asarray(broken["layer_2"]["moe"]["expert_bias"]).any()
    assert (broken["layer_2"]["moe"]["router"]
            == params["layer_2"]["moe"]["router"]).all()
    # the table's rows are unit, every other matrix N(0, init_range)
    assert float(params["embed_tokens"].std()) == pytest.approx(1.0, abs=0.02)
    assert float(params["lm_head"].std()) == pytest.approx(
        cfg["initializer_range"], rel=0.05)
    with pytest.raises(ValueError, match="unknown fault"):
        family._break_program("sideways")
    assert family.TOPK_KEY == "num_experts_per_tok"
    assert found["config"][family.TOPK_KEY] == 8
