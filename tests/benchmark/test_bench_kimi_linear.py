"""The kimi_linear cell of the benchmark on the CPU: the metrics that are its
own, its cut (every width as published, the parameter count from the
reference's shapes), the family's arithmetic (kimi_flops), its adapter, that
the readers it brought return None, and do not raise, on a run of a program
that lacks the family's scopes and counters (the parent commit's), and the
cell rehearsed end to end: a sound run comes out correct, a step that
returns its state unchanged and a program that selects its experts by score
alone do not."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import kimi_flops, lm_flops, spec  # noqa: E402
from tests.benchmark.test_bench_rehearse import _run  # noqa: E402

CELL = "kimi-linear-ep32-clm-16k-packed"
CONFIG = "kimi-linear-48b-a3b-ep32"
MANIFEST = spec.load_manifest(ROOT)
NEW_METRICS = ["kda_share.train", "kda_scan_share.train", "kda_scan_roofline",
               "mla_flash_roofline", "moe_share.kimi.train",
               "lm_head_share.kimi.train", "rmsnorm_share.kimi.train",
               "recompute_share.kimi.train", "unscoped_share.kimi.train",
               "expert_load_max_over_mean.kimi",
               "moe_dispatch_share.kimi.train", "moe_experts_roofline.kimi"]


def test_the_cells_own_metrics():
    found = spec.find_cell(MANIFEST, CELL, ROOT)
    assert found["family"] == "kimi_linear" and found["chips"] == 1
    t = found["traffic"]
    assert (t["seq_len"], t["local_batch"], t["accum"]) == (16384, 1, 2)
    assert t["corpus"]["lengths"] == {"kind": "lognormal", "median": 1400,
                                      "sigma": 1.3, "min": 16, "max": 16384}
    assert t["limits"]["tie_tol"] > 0 and "--packing" in t["extra_args"]
    # ISSUE 33's parameters: the smallest look-ahead that reads 98 % real
    # tokens (the traffic file has the sweep), 16 steps a window at least
    assert t["extra_args"][t["extra_args"].index(
        "--packing_lookahead") + 1] == "11"
    assert t["min_window_steps"] == 16
    assert t["expect_kernels"] == [
        "mla_flash_fwd", "mla_flash_bwd_dq", "mla_flash_bwd_dkv"]
    mine = [m["name"] for m in spec.metrics_of_cell(MANIFEST, CELL,
                                                    "per_layer")]
    assert set(NEW_METRICS) <= set(mine) and "attention_share.train" in mine
    # what reads BERT's heads, or lfm2's scopes under lfm2's names, is not
    # asked of this cell; and this cell's are asked of no other
    assert not {"mlm_head_share.train", "layernorm_share.train",
                "conv_share.train", "moe_share.train",
                "unscoped_share.lm.train", "flash_causal_roofline"} & set(mine)
    for m in MANIFEST["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert spec.load_layer_metric(m["name"], ROOT)["layer"] == \
                m["layer"]


def test_configuration_states_the_cut_and_every_width_as_published():
    cfg = spec.find_cell(MANIFEST, CELL, ROOT)["config"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == [
        "first_k_dense_replace", "linear_attn_config", "num_experts",
        "num_hidden_layers", "vocab_size"]
    published = {
        "hidden_size": 2304, "intermediate_size": 9216,
        "moe_intermediate_size": 1024, "num_attention_heads": 32,
        "num_key_value_heads": 32, "head_dim": 72, "kv_lora_rank": 512,
        "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "num_experts_per_token": 8,
        "num_shared_experts": 1, "routed_scaling_factor": 2.446,
        "first_k_dense_replace": 1, "rms_norm_eps": 1e-05,
        "mla_use_nope": True, "tie_word_embeddings": False,
        "num_expert_group": 1, "topk_group": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "model_max_length": 1048576}
    assert {k: cfg[k] for k in published} == published
    lin = cfg["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert (cfg["experts_total"], cfg["vocab_rows_total"]) == (256, 163840)
    assert kimi_flops.layer_kinds(cfg) == [
        ("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"),
        ("kda", "moe")]
    for key in ("kda_chunk_size", "kda_gate_rank", "kda_decay_init",
                "expert_bias", "weights", "optimizer", "dtype", "packing"):
        assert cfg["assumed"][key]
    assert "32 chips share each layer" in cfg["layout"]
    # the catalog's row: every number under its key, but the keys cut
    row = next(json.loads(ln) for ln in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if "Kimi-Linear-48B" in ln) if os.path.exists(
            "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if row:
        assert cfg["source"] == entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert cfg[key] == value, key


def test_parameter_count_and_flops_of_the_cut():
    import jax

    from benchmark.reference import kimi_linear_ref as ref

    cfg = spec.find_cell(MANIFEST, CELL, ROOT)["config"]
    shapes = ref.param_shapes(ref.sizes_from_config(cfg))

    def count(tree):
        return sum(int(np.prod(s)) for s in jax.tree.leaves(
            tree, is_leaf=lambda x: isinstance(x, tuple)))

    assert count(shapes) == 602450816           # ISSUE 33: 602 M parameters
    assert count(shapes["layer_1"]["kda"]) == 39518368
    assert count(shapes["layer_3"]["attention"]) == 29114880
    assert count(shapes["layer_1"]["moe"]) == 57213184      # 8 held + router
    per_token = kimi_flops.dense_weights_per_token(cfg)
    assert per_token == pytest.approx(335.59e6, rel=1e-3)
    held = 4 * 3 * 2304 * 1024 * 8 * 8 / 256        # 8 of 256 -> 1/4 expert
    assert abs(held / per_token - 0.021) < 0.002    # ~2 % of the products
    # one MLA layer, 32 heads: 6 x 32 x (192 + 128) per causal pair
    assert kimi_flops.causal_attention_flops(cfg, 10) == 6 * 32 * 320 * 10
    assert kimi_flops.causal_attention_flops(cfg, 10, False) == \
        2 * 32 * 320 * 10
    # the scan, a (token, layer) pair: 32 heads x (5 C D + 6 D^2), x 3
    assert kimi_flops.kda_scan_flops(cfg, 1) == 3 * 32 * (
        5 * 64 * 128 + 6 * 128 * 128)
    assert kimi_flops.kda_scan_bytes(cfg, 1) == 38 * 4096
    assert kimi_flops.train_flops(cfg, 100, 10) == pytest.approx(
        600 * per_token + kimi_flops.kda_scan_flops(cfg, 400)
        + kimi_flops.causal_attention_flops(cfg, 10))


@pytest.mark.parametrize("flops", [lm_flops, kimi_flops],
                         ids=["lfm2-arithmetic", "own-arithmetic"])
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
    if name == "unscoped_share.kimi.train":
        assert value == 100.0       # nothing there is under the LM list
    else:
        assert value is None


def test_readers_on_a_run_of_the_family():
    cell = spec.find_cell(MANIFEST, CELL, ROOT)
    pre = "jit(train_step)/grad_accum/jvp(M)/decoder/layer_1/"
    back = ("jit(train_step)/grad_accum/transpose(jvp(M))/decoder/checkpoint/"
            "rematted_computation/layer_2/")
    trace = {"by_scope": {
        pre + "kda/kda/scan/while/body/dot_general": 0.2,
        back + "kda/kda/scan/while/body/dot_general": 0.1,
        pre + "kda/kda/conv/mul": 0.05, pre + "kda/kda/gates/exp": 0.05,
        pre + "kda/out_proj/dot_general": 0.05,
        pre + "moe/shared/shared_expert/w1/dot_general": 0.02,
        "ragged-dot-none": 0.04, pre + "moe/dispatch/sort": 0.01,
        pre + "moe/router/dot": 0.03,
        pre + "ffn_norm/rmsnorm/rsqrt": 0.05,
        "jit(train_step)/grad_accum/jvp(M)/decoder/layer_3/attention/"
        "mla_flash_fwd/pallas_call": 0.1,
        "jit(train_step)/grad_accum/jvp(loss)/while/body/checkpoint/"
        "lm_head/dot_general": 0.1,
        "jit(train_step)/grad_accum/jvp(loss)/while/body/checkpoint/loss/"
        "reduce": 0.1, "": 0.1},
        "busy_s": 1.0, "window_s": 1.0, "steps": 2}
    perf = [{"step": s, "kda_tokens": 131072.0 * (s - 1),
             "moe_l0_pairs": 4000.0 * (s - 1),
             "moe_l1_pairs": 4200.0 * (s - 1),
             "moe_l0_load_max": 300.0, "moe_l0_load_mean": 200.0,
             "moe_l1_load_max": 250.0, "moe_l1_load_mean": 200.0}
            for s in range(5, 12)]
    ctx = {"trace": trace, "chips": 1, "flops": kimi_flops, "cell": cell,
           "peaks": {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e12},
           "record": {"window": {"perf": perf, "traced_first_step": 7,
                                 "causal_pairs": {"7": 1e6, "8": 1e6}}}}
    got = spec.read_layer_metrics(
        {**MANIFEST, "per_layer": [m for m in MANIFEST["per_layer"]
                                   if m["name"] in NEW_METRICS]}, CELL, ctx)
    v = {k: m["value"] for k, m in got.items()}
    assert sorted(v) == sorted(NEW_METRICS)
    assert v["kda_share.train"] == pytest.approx(45.0)
    assert v["kda_scan_share.train"] == pytest.approx(30.0)
    assert v["moe_share.kimi.train"] == pytest.approx(10.0)
    assert v["rmsnorm_share.kimi.train"] == pytest.approx(5.0)
    assert v["recompute_share.kimi.train"] == pytest.approx(10.0)
    assert v["lm_head_share.kimi.train"] == pytest.approx(20.0)
    assert v["unscoped_share.kimi.train"] == pytest.approx(10.0)
    assert v["expert_load_max_over_mean.kimi"] == pytest.approx(1.5)
    assert v["moe_dispatch_share.kimi.train"] == pytest.approx(1.0)
    # 2 x 8,200 (token, held expert) pairs in steps 7-8 over the 0.04 s of
    # the grouped products (the shared expert's are not among them)
    assert v["moe_experts_roofline.kimi"] == pytest.approx(
        100 * 6 * 16400 * 3 * 2304 * 1024 / 1e12 / 0.04)
    # 262,144 (token, layer) pairs in steps 7-8 over 0.3 s at 1e12 / 1e12:
    # the operations bound is the larger one here
    cfg = cell["config"]
    ops = kimi_flops.kda_scan_flops(cfg, 262144)
    assert ops > kimi_flops.kda_scan_bytes(cfg, 262144)
    assert v["kda_scan_roofline"] == pytest.approx(100 * ops / 1e12 / 0.3)
    assert v["mla_flash_roofline"] == pytest.approx(
        100 * 6 * 32 * 320 * 2e6 / 1e12 / 0.1)


def test_adapter_keeps_the_tree_and_samples_what_the_issue_names():
    import jax

    from benchmark.harness import kimi_adapter
    from benchmark.reference import kimi_linear_ref as ref

    found = spec.find_cell(MANIFEST, CELL, ROOT)
    cfg = dict(found["config"], **found["traffic"]["rehearse"]["config"])
    sizes = ref.sizes_from_config(cfg)
    params = ref.init_params(2 ** 31 + 3, sizes)
    norms = kimi_adapter.leaf_norms(params)
    assert len(norms) == len(jax.tree.leaves(params))
    assert norms["['layer_1']['moe']['experts_w1']"].shape == (8,)
    assert norms["['layer_1']['moe']['router']"].shape == (1,)
    sampled = kimi_adapter.sample_matrices(params, sizes["kinds"])
    assert sorted(sampled) == sorted(
        ["layer_0/kda/" + n for n in ("q_proj", "out_proj/kernel",
                                      "f_b_proj", "b_proj", "g_b_proj")]
        + ["layer_3/attention/kv_b_proj/kernel",
           "layer_3/attention/q_proj/kernel", "layer_0/mlp/w1/kernel",
           "layer_0/mlp/w2/kernel"]
        + [f"layer_{i}/{n}" for i in (1, 4) for n in (
            "moe/experts_w1", "moe/experts_w2", "shared_expert/w1/kernel",
            "shared_expert/w2/kernel", "moe/router")])
    assert sampled["layer_1/moe/experts_w1"].shape == (128, 64)  # expert 0


@pytest.mark.parametrize("fault,correct", [
    (None, True), ("noop_step", False), ("zero_bias", False)],
    ids=["sound", "step-returns-state-unchanged",
         "experts-selected-by-score-alone"])
def test_rehearsed_cell(fault, correct):
    args = ["--workload", CELL, "--seed", str(2**31 + 17), "--seconds", "1",
            "--trace", "0", "--rehearse"]
    if fault:
        args += ["--fault", fault]
    proc, last = _run(args)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert last["device"]["platform"] == "cpu" and last["metrics"] == {}
    assert last["attempted"] >= 1 and last["failed"] == 0
    assert last["correct"] is correct, proc.stdout[-4000:]
    compared = last["compared"]
    assert last["correct"] is all(row["ok"] for row in compared.values())
    # the family's own checks, on top of the driver's
    assert compared["dropped_pairs"] == {"value": 0, "limit": 0, "ok": True}
    assert compared["kda_tokens"]["ok"] and compared["kda_tokens"]["value"] > 0
    assert sum(name.startswith("experts_l1_") for name in compared) == 8
    if fault:
        assert not compared["grad_gap"]["ok"]
    if fault == "noop_step":
        assert not compared["delta_gap"]["ok"]
    if fault == "zero_bias":
        # the reference selects by score + b: other experts, other counts
        gaps = [row["value"] for name, row in compared.items()
                if name.startswith("experts_l1_")]
        assert min(gaps) > 100, gaps
