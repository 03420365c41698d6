"""The smallthinker cell of the benchmark on the CPU: the metrics that are its
own, its cut (every width as published, the parameter count from the
reference's shapes), the family's arithmetic (smallthinker_flops; the band's
pairs against a brute-force count), that the readers it brought return None,
and do not raise, on a run of a program that lacks the family's scopes,
kernels and counters (the parent commit's), and the cell rehearsed end to
end: a sound run comes out correct; a step that returns its state unchanged,
a program without the band and a program whose router reads the experts'
input do not."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import (kimi_flops, lm_flops,  # noqa: E402
                               smallthinker_flops, spec)
from tests.benchmark.test_bench_rehearse import _run  # noqa: E402

CELL = "smallthinker-ep8-clm-16k-fullrow"
CONFIG = "smallthinker-21b-a3b-ep8"
MANIFEST = spec.load_manifest(ROOT)
NEW_METRICS = [
    "attention_window_share.train", "attention_full_share.train",
    "flash_window_roofline", "flash_causal_roofline.smallthinker",
    "moe_share.smallthinker.train", "moe_dispatch_share.smallthinker.train",
    "moe_experts_roofline.smallthinker",
    "expert_load_max_over_mean.smallthinker",
    "lm_head_share.smallthinker.train", "rmsnorm_share.smallthinker.train",
    "recompute_share.smallthinker.train",
    "unscoped_share.smallthinker.train"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_cells_own_metrics():
    found = spec.find_cell(MANIFEST, CELL, ROOT)
    assert found["family"] == "smallthinker" and found["chips"] == 1
    t = found["traffic"]
    assert (t["seq_len"], t["local_batch"], t["accum"]) == (16384, 1, 2)
    # every document fills a row
    assert t["corpus"]["lengths"] == {"kind": "lognormal", "median": 16384,
                                      "sigma": 0.0, "min": 16384,
                                      "max": 16384}
    from benchmark.harness import corpus

    assert set(corpus.quantile_lengths(t["corpus"]["lengths"], 64)) == {16384}
    assert t["limits"]["tie_tol"] > 0 and "--packing" in t["extra_args"]
    assert "--packing_lookahead" not in t["extra_args"]     # the default
    assert (t["learning_rate"], t["warmup_proportion"], t["max_steps"]) == (
        0.004, 0.128, 1563)
    assert t["min_window_steps"] == 16 and t["trace_steps"] == 3
    assert t["expect_kernels"] == [
        "flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv",
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]
    mine = [m["name"] for m in spec.metrics_of_cell(MANIFEST, CELL,
                                                    "per_layer")]
    assert set(NEW_METRICS) <= set(mine)
    # every list-less metric is asked of the cell (the two new scopes sit
    # under `attention`), but the dense FFN's, which this family has none of
    assert {"attention_share.train", "optimizer_share.train",
            "device_idle_share.train"} <= set(mine)
    assert not {"mlp_share.train", "mlm_head_share.train",
                "conv_share.train", "moe_share.train", "moe_share.kimi.train",
                "flash_causal_roofline", "mla_flash_roofline",
                "unscoped_share.kimi.train"} & set(mine)
    for m in MANIFEST["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert spec.load_layer_metric(m["name"], ROOT)["layer"] == \
                m["layer"]
        if m["name"] == "mlp_share.train":
            assert CELL not in m["workloads"] and len(m["workloads"]) == 5


def test_configuration_states_the_cut_and_every_width_as_published():
    cfg = spec.find_cell(MANIFEST, CELL, ROOT)["config"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == [
        "moe_num_primary_experts", "num_hidden_layers", "rope_layout",
        "sliding_window_layout", "vocab_size"]
    published = {
        "hidden_size": 2560, "head_dim": 128, "num_attention_heads": 28,
        "num_key_value_heads": 4, "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "sliding_window_size": 4096, "rope_theta": 1500000,
        "rope_scaling": None, "rms_norm_eps": 1e-06,
        "max_position_embeddings": 16384, "tie_word_embeddings": False}
    assert {k: cfg[k] for k in published} == published
    assert (cfg["experts_total"], cfg["vocab_rows_total"]) == (64, 151936)
    assert cfg["vocab_size"] * 8 == 151936 and cfg["experts_held"] == [0, 8]
    # two whole periods of the published layouts
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] == [
        0, 1, 1, 1, 0, 1, 1, 1]
    assert smallthinker_flops.layer_kinds(cfg) == [
        (0, False), (4096, True), (4096, True), (4096, True)] * 2
    for key in ("router_input", "expert_activation", "secondary_experts",
                "router_softmax", "attention_bias", "weights", "optimizer",
                "dtype", "packing", "remat_policy"):
        assert cfg["assumed"][key]
    assert "8 chips share each layer" in cfg["layout"]
    assert "further pipeline stages" in cfg["layout"]
    # the catalog's row: every key under its name, but the keys cut
    if os.path.exists(CATALOG):
        row = next(json.loads(ln) for ln in open(CATALOG)
                   if "SmallThinker-21BA3B" in ln)
        assert cfg["source"] == entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert cfg[key] == value, key
            elif isinstance(value, list):      # a layout: a prefix of it
                assert cfg[key] == value[:len(cfg[key])], key


def test_parameter_count_and_flops_of_the_cut():
    import jax

    from benchmark.reference import smallthinker_ref as ref

    cfg = spec.find_cell(MANIFEST, CELL, ROOT)["config"]
    shapes = ref.param_shapes(ref.sizes_from_config(cfg))

    def count(tree):
        return sum(int(np.prod(s)) for s in jax.tree.leaves(
            tree, is_leaf=lambda x: isinstance(x, tuple)))

    # ISSUE 35: 643.85 M parameters, a layer 68,326,400
    assert count(shapes) == 643852800
    assert count(shapes["layer_0"]) == count(shapes["layer_5"]) == 68326400
    assert count(shapes["layer_1"]["attention"]) == 20971520
    assert count(shapes["layer_1"]["moe"]) == 163840 + 47185920
    per_token = smallthinker_flops.dense_weights_per_token(cfg)
    # 8 x 25.6 M + the head's 48.6 M: 0.51 GFLOP a token forward
    assert per_token == pytest.approx(8 * 25.559e6 + 48.62e6, rel=1e-3)
    held = 8 * 3 * 2560 * 768 * 6 * 8 / 64
    assert abs(held / per_token - 0.14) < 0.005     # ~14 % of the products
    # per (query, key) pair of a layer: 28 heads x 4 x 128, x 3 with the
    # backward; the full layers are 2 of the 8, the windowed 6
    assert smallthinker_flops.causal_attention_flops(cfg, 10, False) == \
        2 * 14336 * 10
    assert smallthinker_flops.causal_attention_flops(cfg, 10) == \
        6 * 14336 * 10
    assert smallthinker_flops.window_attention_flops(cfg, 10) == \
        18 * 14336 * 10
    assert smallthinker_flops.moe_expert_flops(cfg, 1) == 6 * 3 * 2560 * 768
    # ISSUE 35: a full row holds 134,225,920 causal pairs and 58,722,304
    # (43.7 %) inside the band; 37,889 pairs a token over the 8 layers
    full = smallthinker_flops.band_pairs(16384, 0)
    band = smallthinker_flops.band_pairs(16384, 4096)
    assert (full, band) == (134225920, 58722304)
    assert (2 * full + 6 * band) / 16384 == pytest.approx(37889, rel=1e-4)
    step = smallthinker_flops.train_flops(cfg, 32768, 2 * full, 2 * band)
    assert step == pytest.approx(103e12, rel=0.01)      # 103 TFLOP a step
    assert step == pytest.approx(
        6 * per_token * 32768 + 3 * 14336 * (2 * 2 * full + 6 * 2 * band))


@pytest.mark.parametrize("window", [0, 5, 16, 4096])
def test_window_pairs_against_a_brute_force_count(window):
    """The family's count of a step's (query, key) pairs from its segment
    ids, per document L (L + 1) / 2 and under a band W (W + 1) / 2 +
    (L - W) W for L > W, against counting the allowed pairs one by one."""
    from benchmark.families import smallthinker as family

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
    # the band is the cell's, whatever `sizes` was last called with
    found = spec.find_cell(MANIFEST, CELL, ROOT)
    family.sizes(found["config"], {})
    cell = dict(found, config=dict(found["config"],
                                   sliding_window_size=window or 1))
    extras = family.window_extras({7: seg}, {7: {"moe_l0_dropped": 0}}, cell)
    assert extras["causal_pairs"][7] == family.document_pairs(seg, 0)
    assert extras["window_pairs"][7] == family.document_pairs(
        seg, window or 1)
    assert extras["dropped_pairs"] == 0


@pytest.mark.parametrize("flops", [lm_flops, kimi_flops, smallthinker_flops],
                         ids=["lfm2-arithmetic", "kimi-arithmetic",
                              "own-arithmetic"])
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
    if name == "unscoped_share.smallthinker.train":
        assert value == 100.0       # nothing there is under the LM list
    else:
        assert value is None


def test_the_band_reader_on_another_familys_run():
    """lfm2's traced run through the band's reader (its kernels are
    `flash_fwd`, its record has no `window_pairs`, its arithmetic no band):
    nothing, and no error."""
    metric = spec.load_layer_metric("flash_window_roofline", ROOT)
    read = spec.load_reader(metric["reader"], ROOT)
    trace = {"by_scope": {"jit(s)/jvp(M)/decoder/layer_2/attention/"
                          "flash_fwd/pallas_call": 0.5,
                          "jit(s)/decoder/layer_2/attention/"
                          "flash_win_fwd/pallas_call": 0.1},
             "busy_s": 1.0, "window_s": 1.0, "steps": 2}
    ctx = {"trace": trace, "chips": 1, "flops": lm_flops,
           "peaks": lm_flops.peaks("TPU v5 lite"),
           "cell": spec.find_cell(MANIFEST, "lfm2-ep8-clm-8k-packed", ROOT),
           "record": {"window": {"traced_first_step": 7,
                                 "causal_pairs": {"7": 1e6, "8": 1e6}}}}
    assert read(ctx, **metric["args"]) is None
    ctx["record"]["window"]["window_pairs"] = {"7": 1e6}    # a step short
    ctx["flops"] = smallthinker_flops
    assert read(ctx, **metric["args"]) is None


def test_readers_on_a_run_of_the_family():
    cell = spec.find_cell(MANIFEST, CELL, ROOT)
    pre = "jit(train_step)/grad_accum/jvp(M)/decoder/layer_1/"
    back = ("jit(train_step)/grad_accum/transpose(jvp(M))/decoder/checkpoint/"
            "rematted_computation/layer_2/")
    full = "jit(train_step)/grad_accum/jvp(M)/decoder/layer_0/"
    trace = {"by_scope": {
        pre + "attention/attention_window/flash_win_fwd/pallas_call": 0.1,
        back + "attention/attention_window/flash_win_bwd_dq/pallas_call": 0.1,
        pre + "attention/attention_window/dot_general": 0.05,
        full + "attention/attention_full/flash_fwd/pallas_call": 0.2,
        full + "attention/attention_full/out_proj/dot_general": 0.05,
        "ragged-dot-none": 0.04, pre + "moe/dispatch/sort": 0.01,
        pre + "moe/router/dot": 0.03, pre + "moe/combine/scatter-add": 0.02,
        pre + "post_attention_layernorm/rmsnorm/rsqrt": 0.05,
        "jit(train_step)/grad_accum/jvp(loss)/while/body/checkpoint/"
        "lm_head/dot_general": 0.1,
        "jit(train_step)/grad_accum/jvp(loss)/while/body/checkpoint/loss/"
        "reduce": 0.1, "jit(train_step)/optimizer/mul": 0.05, "": 0.1},
        "busy_s": 1.0, "window_s": 1.0, "steps": 2}
    perf = [dict({"step": s}, **{f"moe_l{i}_pairs": 1000.0 * (s - 1)
                                 for i in range(8)},
                 moe_l0_load_max=300.0, moe_l0_load_mean=200.0,
                 moe_l1_load_max=250.0, moe_l1_load_mean=200.0)
            for s in range(5, 12)]
    ctx = {"trace": trace, "chips": 1, "flops": smallthinker_flops,
           "cell": cell,
           "peaks": {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e12},
           "record": {"window": {"perf": perf, "traced_first_step": 7,
                                 "causal_pairs": {"7": 2e6, "8": 2e6},
                                 "window_pairs": {"7": 1e6, "8": 1e6}}}}
    got = spec.read_layer_metrics(MANIFEST, CELL, ctx)
    v = {k: m["value"] for k, m in got.items()}
    assert set(NEW_METRICS) <= set(v)
    assert v["attention_window_share.train"] == pytest.approx(25.0)
    assert v["attention_full_share.train"] == pytest.approx(25.0)
    # the accepted metric reads both kinds: they sit under `attention`
    assert v["attention_share.train"] == pytest.approx(50.0)
    assert v["optimizer_share.train"] == pytest.approx(5.0)
    assert "mlp_share.train" not in v
    assert v["moe_share.smallthinker.train"] == pytest.approx(10.0)
    assert v["moe_dispatch_share.smallthinker.train"] == pytest.approx(3.0)
    assert v["rmsnorm_share.smallthinker.train"] == pytest.approx(5.0)
    assert v["recompute_share.smallthinker.train"] == pytest.approx(10.0)
    assert v["lm_head_share.smallthinker.train"] == pytest.approx(20.0)
    assert v["unscoped_share.smallthinker.train"] == pytest.approx(10.0)
    assert v["expert_load_max_over_mean.smallthinker"] == pytest.approx(1.5)
    # 2 x 8,000 (token, held expert) pairs in steps 7-8 over 0.04 s
    assert v["moe_experts_roofline.smallthinker"] == pytest.approx(
        100 * 6 * 16000 * 3 * 2560 * 768 / 1e12 / 0.04)
    # the band: 6 windowed layers x 2e6 pairs over the 0.2 s of flash_win_*;
    # the full layers: 2 x 4e6 pairs over the 0.2 s of flash_*
    assert v["flash_window_roofline"] == pytest.approx(
        100 * 6 * 3 * 14336 * 2e6 / 1e12 / 0.2)
    assert v["flash_causal_roofline.smallthinker"] == pytest.approx(
        100 * 2 * 3 * 14336 * 4e6 / 1e12 / 0.2)


def test_family_keeps_the_tree_and_samples_both_kinds_of_layer():
    import jax

    from benchmark.families import smallthinker as family
    from benchmark.reference import smallthinker_ref as ref

    found = spec.find_cell(MANIFEST, CELL, ROOT)
    cfg = dict(found["config"], **found["traffic"]["rehearse"]["config"])
    sizes = family.sizes(cfg, found["traffic"])
    params = family.weights({"seed": 2 ** 31 + 3}, sizes)
    leaf_norms, _, sample = family.adapter_functions(sizes)
    norms = leaf_norms(params)
    assert len(norms) == len(jax.tree.leaves(params))
    assert norms["['layer_1']['moe']['experts_w1']"].shape == (8,)
    assert norms["['layer_1']['moe']['router']"].shape == (1,)
    sampled = sample(params)
    assert sorted(sampled) == sorted(
        [f"layer_{i}/attention/{n}" for i in (0, 1)
         for n in ("q_proj", "out_proj/kernel")]
        + [f"layer_{i}/moe/{n}" for i in (0, 3)
           for n in ("experts_w1", "experts_w2", "router")])
    assert sampled["layer_0/moe/experts_w1"].shape == (128, 64)  # expert 0
    same = ref.init_params(2 ** 31 + 3, sizes)
    assert (same["lm_head"] == params["lm_head"]).all()
    with pytest.raises(ValueError, match="unknown fault"):
        family._break_program("sideways")


@pytest.mark.parametrize("fault,correct", [
    (None, True), ("noop_step", False), ("no_band", False),
    ("router_after_attention", False)],
    ids=["sound", "step-returns-state-unchanged", "program-without-the-band",
         "router-reads-the-experts-input"])
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
    # the family's own checks, on top of the driver's: every layer routes
    assert compared["dropped_pairs"] == {"value": 0, "limit": 0, "ok": True}
    assert sum(name.startswith("experts_l1_") for name in compared) == 8
    if fault == "noop_step":
        assert not compared["delta_gap"]["ok"]
        assert not compared["grad_gap"]["ok"]
    gaps = [row["value"] for name, row in compared.items()
            if name.startswith("experts_l1_")]
    if fault == "no_band":
        # the windowed layers saw the whole document: another gradient (and
        # the full layer 0's routing as it was)
        assert not compared["grad_diff"]["ok"]
        assert not compared["grad_gap"]["ok"] and gaps[0] < 30
    if fault == "router_after_attention":
        # other logits, other experts, other counts, in every layer
        assert not compared["grad_diff"]["ok"]
        assert not compared["grad_gap"]["ok"] and min(gaps) > 50, gaps
    if fault is None:
        assert max(gaps) < 50, gaps
