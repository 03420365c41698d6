"""The keye cell of the benchmark on the CPU: the metrics that are its own,
its cut (every width as published against the catalog's row, the parameter
count from the reference's shapes), the family's arithmetic (keye_flops: the
selected pairs against a brute-force count, 31,458,304 of a full row's
134,225,920), that the readers it brought return None, and do not raise, on
a run of a program that lacks the family's scopes, kernels and counters (the
parent commit's), the readers on a run of the family, the family module's
own pieces (the tree it keeps, the matrices it samples, the faults it can
plant) and its decision on what is discrete: the selected pairs by key block
against the reference's near ties, the window's selected pairs exactly, both
loss terms. The cell's whole rehearsal (`benchmark/run.py --rehearse`, under
a minute on the CPU) is the builder's and not in this file."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import (keye_flops, kimi_flops,  # noqa: E402
                               laguna_flops, lm_flops, smallthinker_flops,
                               spec)

CELL = "keye-ep8-clm-16k-fullrow"
CONFIG = "keye-vl2-30b-a3b-ep8"
MANIFEST = spec.load_manifest(ROOT)
NEW_METRICS = [
    "indexer_share.keye.train", "select_share.keye.train",
    "indexer_loss_share.keye.train", "rotary_share.keye.train",
    "flash_select_roofline.keye", "dsa_index_roofline.keye",
    "dsa_probs_roofline.keye", "selected_pairs_share.keye",
    "moe_share.keye.train", "moe_dispatch_share.keye.train",
    "moe_experts_roofline.keye", "expert_load_max_over_mean.keye",
    "lm_head_share.keye.train", "rmsnorm_share.keye.train",
    "recompute_share.keye.train", "unscoped_share.keye.train",
    "attention_rest_share.keye.train", "moe_rest_share.keye.train"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FULL, SELECTED = 134_225_920, 31_458_304


def test_the_cells_own_metrics():
    found = spec.find_cell(MANIFEST, CELL, ROOT)
    assert found["family"] == "keye" and found["chips"] == 1
    t = found["traffic"]
    assert (t["seq_len"], t["local_batch"], t["accum"]) == (16384, 1, 2)
    # the smallthinker cell's corpus and arguments: every document fills a row
    other = spec.find_cell(MANIFEST, "smallthinker-ep8-clm-16k-fullrow", ROOT)
    assert t["corpus"] == other["traffic"]["corpus"]
    assert t["extra_args"] == other["traffic"]["extra_args"] == [
        "--packing", "--packing_max_segments", "32",
        "--checkpoint_activations", "--vocab_pad_multiple", "16"]
    assert (t["learning_rate"], t["warmup_proportion"], t["max_steps"]) == (
        0.004, 0.128, 1563)
    assert t["min_window_steps"] == 16 and t["trace_steps"] == 3
    assert t["limits"]["tie_tol"] > t["limits"]["select_tie_tol"] > 0
    assert 0 < t["limits"]["select_gap_share"] < 1
    assert t["limits"]["why"]
    assert t["limits"]["kl_rel"] > t["limits"]["loss_rel"] > 0
    assert t["expect_kernels"] == ["flash_sel_fwd", "flash_sel_bwd_dq",
                                   "flash_sel_bwd_dkv"]
    mine = [m["name"] for m in spec.metrics_of_cell(MANIFEST, CELL,
                                                    "per_layer")]
    assert set(NEW_METRICS) <= set(mine)
    # every list-less metric is asked of the cell
    assert {"attention_share.train", "attention_core_share.train",
            "optimizer_share.train", "device_idle_share.train",
            "setup_lower_s", "step_hbm_share"} <= set(mine)
    assert not {"mlm_head_share.train", "conv_share.train", "moe_share.train",
                "flash_causal_roofline", "flash_window_roofline",
                "rotary_share.laguna.train",
                "unscoped_share.smallthinker.train"} & set(mine)
    for m in MANIFEST["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert spec.load_layer_metric(m["name"], ROOT)["layer"] == \
                m["layer"]
    from bert_pytorch_tpu.training.pretrain import LM_STEP_SCOPES

    scopes = spec.load_layer_metric("unscoped_share.keye.train",
                                    ROOT)["args"]["scopes"]
    assert scopes == [s for s in LM_STEP_SCOPES if s not in ("kda", "conv",
                                                             "mlp")]
    # the rehearsal keeps a group of 8 and rows longer and shorter than topk
    r = t["rehearse"]
    assert (r["config"]["num_attention_heads"],
            r["config"]["num_key_value_heads"]) == (8, 1)
    assert r["config"]["sa_config"]["topk"] < r["corpus"]["lengths"]["median"]


def test_configuration_states_the_cut_and_every_width_as_published():
    cfg = spec.find_cell(MANIFEST, CELL, ROOT)["config"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == [
        "num_experts", "num_hidden_layers", "num_local_experts", "vocab_size"]
    published = {
        "hidden_size": 2048, "intermediate_size": 6144, "head_dim": 128,
        "num_attention_heads": 32, "num_key_value_heads": 4,
        "moe_intermediate_size": 768, "num_experts_per_tok": 8,
        "norm_topk_prob": True, "attention_bias": False,
        "rms_norm_eps": 1e-06, "rope_theta": 10000000,
        "max_position_embeddings": 262144, "tie_word_embeddings": False,
        "decoder_sparse_step": 1, "mlp_only_layers": [],
        "sliding_window": None, "use_sliding_window": False}
    assert {k: cfg[k] for k in published} == published
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert (cfg["experts_total"], cfg["vocab_rows_total"]) == (128, 151936)
    assert cfg["vocab_size"] * 8 == 151936 and cfg["vocab_size"] % 16 == 0
    assert cfg["experts_held"] == [0, 16]
    assert cfg["num_experts"] == cfg["num_local_experts"] == 16
    assert cfg["num_hidden_layers"] == 6
    for key in ("q_k_norm", "mrope_on_text", "indexer_input",
                "indexer_key_norm", "indexer_rotary", "indexer_weights",
                "selection", "chunk_sizes", "indexer_loss",
                "expert_activation", "router", "weights", "embedding_init",
                "optimizer", "dtype", "packing", "dropout", "remat_policy"):
        assert cfg["assumed"][key]
    assert "NOT taken" in cfg["assumed"]["chunk_sizes"]
    assert "8 chips share each layer" in cfg["layout"]
    assert "further pipeline stages" in cfg["layout"]
    assert "vision tower" in cfg["layout"]
    assert "absent" in cfg["published"]["vision_tower"]
    assert (cfg["published"]["num_hidden_layers"],
            cfg["published"]["num_experts"],
            cfg["published"]["vocab_size"]) == (48, 128, 151936)
    assert cfg["remat_policy"] == "dense"
    # the catalog's row: every key under its name, but the keys cut
    if os.path.exists(CATALOG):
        row = next(json.loads(ln) for ln in open(CATALOG)
                   if '"name": "Keye-VL-2.0-30B-A3B"' in ln)
        assert cfg["source"] == entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in entry["reduced"] and key != "model_type":
                assert cfg[key] == value, key


def test_parameter_count_and_flops_of_the_cut():
    import jax

    from benchmark.reference import keye_ref as ref

    cfg = spec.find_cell(MANIFEST, CELL, ROOT)["config"]
    shapes = ref.param_shapes(ref.sizes_from_config(cfg))

    def count(tree):
        return sum(int(np.prod(s)) for s in jax.tree.leaves(
            tree, is_leaf=lambda x: isinstance(x, tuple)))

    # ISSUE 43: 659 M parameters; a layer 96.9 M: attention 18.87 M, the
    # indexer 2.26 M, the router 0.26 M, 16 experts 75.50 M; tables 77.8 M
    assert count(shapes) == 659_190_016
    attention = shapes["layer_3"]["attention"]
    indexer = {k: v for k, v in attention.items() if k.startswith("index_")}
    assert count(indexer) == 2048 * (1024 + 64 + 16) + 128 == 2_261_120
    assert count(attention) - count(indexer) == 18_874_368 + 256
    moe = shapes["layer_3"]["moe"]
    assert count(moe["router"]) == 262_144
    assert count(moe) - count(moe["router"]) == 75_497_472
    assert count(shapes["layer_3"]) == 96_899_456
    assert count(shapes["embed_tokens"]) + count(shapes["lm_head"]) == \
        77_791_232
    # 10.5 GB at 16 bytes a parameter, 11.9 GB at 18, of the chip's 16.91
    assert 16 * count(shapes) < 10.6e9 and 18 * count(shapes) < 11.9e9
    per_token = keye_flops.dense_weights_per_token(cfg)
    held = 6 * 3 * 2048 * 768 * 8 * 16 / 128     # 16 of 128 -> 1 expert
    assert abs(held / per_token - 0.145) < 0.005
    # per selected pair: 4 x 128 a query head forward, x 3 with the backward
    assert keye_flops.select_attention_flops(cfg, 10, False) == \
        4 * 6 * 32 * 128 * 10
    assert keye_flops.select_attention_flops(cfg, 10) == \
        12 * 6 * 32 * 128 * 10
    assert keye_flops.index_flops(cfg, 10) == 6 * 6 * 16 * 64 * 10
    assert keye_flops.indexer_loss_flops(cfg, 10) == 2 * 6 * 32 * 128 * 10
    assert keye_flops.moe_expert_flops(cfg, 1) == 6 * 3 * 2048 * 768
    # the mask operand is among the bytes: 3 kernels x 4 B x 512 a slot
    assert keye_flops.select_attention_bytes(cfg, 1) == 6 * (
        2 * 128 * (2 * 32 + 2 * 4 + 3 * 32 + 4 * 4) + 3 * 4 * 512)
    # a full row: 14,336 of 16,384 queries have more earlier tokens than K
    assert keye_flops.selected_pairs(16384, 2048) == SELECTED
    assert keye_flops.band_pairs(16384, 0) == FULL
    assert SELECTED / FULL == pytest.approx(0.2344, abs=1e-4)
    assert sum(1 for t in range(16384) if t + 1 > 2048) == 14336
    step = keye_flops.train_flops(cfg, 32768, 2 * FULL, 2 * SELECTED)
    assert step == pytest.approx(
        6 * per_token * 32768 + 6 * (14 * 32 * 128 * 2 * SELECTED
                                     + 6 * 16 * 64 * 2 * FULL))
    # and the program's own estimate for a full row is the same arithmetic
    from bert_pytorch_tpu.config import KeyeConfig
    from bert_pytorch_tpu.models import keye

    program = KeyeConfig.from_dict(
        {k: v for k, v in cfg.items() if k != "remat_policy"})
    assert keye.train_flops_per_row(program, 16384) == pytest.approx(
        keye_flops.train_flops(cfg, 16384, FULL, SELECTED), rel=1e-9)


@pytest.mark.parametrize("topk", [1, 5, 16, 64])
def test_selected_pairs_against_a_brute_force_count(topk):
    """The family's count of the pairs a step's queries select from its
    segment ids, per document sum of min(topk, position + 1), against
    counting them one by one; and what the family writes beside them."""
    from benchmark.families import keye as family

    rng = np.random.default_rng(topk)
    seg = np.zeros((2, 3, 64), np.int32)
    for row in seg.reshape(-1, 64):
        cuts = np.sort(rng.choice(np.arange(1, 60), 3, replace=False))
        for g, (a, b) in enumerate(zip([0, *cuts[:-1]], cuts)):
            row[a:b] = g + 1                    # a padded tail after cuts[-1]
    want = 0
    for row in seg.reshape(-1, 64):
        start = 0
        for i in range(64):
            if i and row[i] != row[i - 1]:
                start = i
            want += int(row[i] > 0) * min(topk, i - start + 1)
    assert family.document_pairs(seg, topk) == want
    found = spec.find_cell(MANIFEST, CELL, ROOT)
    cell = dict(found, config=dict(found["config"], sa_config=dict(
        found["config"]["sa_config"], topk=topk)))
    scalars = {7: {"moe_l0_dropped": 0, "dsa_l0_kb0": want - 3,
                   "dsa_l0_kb1": 3, "dsa_l1_kb0": want,
                   "dsa_l0_candidates_lo": 5}}
    extras = family.window_extras({7: seg, 8: seg}, scalars, cell)
    assert extras["causal_pairs"][7] == family.document_pairs(seg, 0)
    assert extras["selected_pairs"] == {7: want, 8: want}
    assert extras["selected_by_program"] == {7: 2 * want}
    assert extras["dropped_pairs"] == 0


@pytest.mark.parametrize("flops", [lm_flops, kimi_flops, smallthinker_flops,
                                   laguna_flops, keye_flops],
                         ids=["lfm2-arithmetic", "kimi-arithmetic",
                              "smallthinker-arithmetic", "laguna-arithmetic",
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
                                 "traced_first_step": 7, "steps": 16,
                                 "slot_tokens": 16 * 32768}}}
    value = read(ctx, **metric.get("args", {}))
    if name == "unscoped_share.keye.train":
        assert value == 100.0       # nothing there is under the LM list
    else:
        assert value is None


def test_the_select_roofline_wants_kernels_pairs_and_the_arithmetic():
    """Each thing the reader needs, taken away in turn, leaves the metric
    out: a run of a family that runs `flash_sel_*` kernels under another
    family's arithmetic, or with no count of the selected pairs."""
    metric = spec.load_layer_metric("flash_select_roofline.keye", ROOT)
    read = spec.load_reader(metric["reader"], ROOT)
    trace = {"by_scope": {"jit(s)/attention/attn_core/flash_sel_fwd/"
                          "pallas_call": 0.5}, "busy_s": 1.0, "steps": 1}
    window = {"traced_first_step": 7, "steps": 16, "slot_tokens": 16 * 32768,
              "selected_pairs": {"7": 2 * SELECTED}}

    def value(flops=keye_flops, window=window, trace=trace):
        return read({"trace": trace, "chips": 1, "flops": flops,
                     "peaks": {"flops_per_s_bf16": 1e14,
                               "hbm_bytes_per_s": 1e12},
                     "cell": spec.find_cell(MANIFEST, CELL, ROOT),
                     "record": {"window": window}}, **metric["args"])

    cfg = spec.find_cell(MANIFEST, CELL, ROOT)["config"]
    assert value() == pytest.approx(100 * keye_flops.select_attention_flops(
        cfg, 2 * SELECTED) / 1e14 / 0.5)
    assert value(flops=laguna_flops) is None
    assert value(window={k: v for k, v in window.items()
                         if k != "selected_pairs"}) is None
    assert value(window=dict(window, selected_pairs={"9": 1})) is None
    assert value(trace=dict(trace, by_scope={"flash_fwd/pallas_call": 1.0})) \
        is None
    # where the bytes bound is the larger one it is the one used
    slow_hbm = read({"trace": trace, "chips": 1, "flops": keye_flops,
                     "peaks": {"flops_per_s_bf16": 1e18,
                               "hbm_bytes_per_s": 1e9},
                     "cell": spec.find_cell(MANIFEST, CELL, ROOT),
                     "record": {"window": window}}, **metric["args"])
    assert slow_hbm == pytest.approx(
        100 * keye_flops.select_attention_bytes(cfg, 32768) / 1e9 / 0.5)


def test_readers_on_a_run_of_the_family():
    cell = spec.find_cell(MANIFEST, CELL, ROOT)
    pre = "jit(train_step)/grad_accum/jvp(M)/decoder/layer_1/"
    back = ("jit(train_step)/grad_accum/transpose(jvp(M))/decoder/checkpoint/"
            "rematted_computation/layer_2/")
    trace = {"by_scope": {
        pre + "attention/attn_core/flash_sel_fwd/pallas_call": 0.1,
        back + "attention/attn_core/flash_sel_bwd_dq/pallas_call": 0.1,
        pre + "attention/dot_general": 0.03,
        pre + "attention/rotary/mul": 0.04,
        pre + "attention/indexer/dot_general": 0.02,
        pre + "attention/while/body/attention/indexer/dsa_index_fwd/"
        "pallas_call": 0.05,
        pre + "attention/while/body/attention/select/while/body/reduce": 0.06,
        pre + "attention/while/body/attention/indexer_loss/dsa_probs/"
        "pallas_call": 0.08,
        pre + "attention/while/body/attention/indexer_loss/dsa_index_bwd/"
        "pallas_call": 0.01,
        back + "attention/attention/indexer_loss/mul": 0.01,
        "ragged-dot-none": 0.04, pre + "moe/dispatch/sort": 0.01,
        pre + "moe/convert_element_type": 0.005,
        pre + "moe/router/dot": 0.02, pre + "moe/combine/scatter-add": 0.02,
        pre + "post_attention_layernorm/rmsnorm/rsqrt": 0.05,
        "jit(train_step)/grad_accum/jvp(loss)/while/body/checkpoint/"
        "lm_head/dot_general": 0.1,
        "jit(train_step)/grad_accum/jvp(loss)/while/body/checkpoint/loss/"
        "reduce": 0.09, "jit(train_step)/optimizer/mul": 0.07, "": 0.1},
        "busy_s": 1.0, "window_s": 1.0, "steps": 2}
    perf = [dict({"step": s}, **{f"moe_l{i}_pairs": 1000.0 * (s - 1)
                                 for i in range(6)},
                 moe_l0_load_max=300.0, moe_l0_load_mean=200.0,
                 moe_l1_load_max=250.0, moe_l1_load_mean=200.0,
                 dsa_selected_pairs=12.0 * SELECTED * (s - 1),
                 dsa_candidate_pairs=12.0 * FULL * (s - 1))
            for s in range(5, 12)]
    ctx = {"trace": trace, "chips": 1, "flops": keye_flops, "cell": cell,
           "peaks": {"flops_per_s_bf16": 1e14, "hbm_bytes_per_s": 1e12},
           "record": {"window": {
               "perf": perf, "traced_first_step": 7, "steps": 16,
               "slot_tokens": 16 * 32768,
               "causal_pairs": {"7": 2 * FULL, "8": 2 * FULL},
               "selected_pairs": {"7": 2 * SELECTED, "8": 2 * SELECTED}}}}
    got = spec.read_layer_metrics(MANIFEST, CELL, ctx)
    v = {k: m["value"] for k, m in got.items()}
    assert set(NEW_METRICS) <= set(v)
    assert v["indexer_share.keye.train"] == pytest.approx(7.0)
    assert v["select_share.keye.train"] == pytest.approx(6.0)
    assert v["indexer_loss_share.keye.train"] == pytest.approx(10.0)
    assert v["rotary_share.keye.train"] == pytest.approx(4.0)
    # the accepted metrics read all of it: everything sits under `attention`,
    # the kernels under its `attn_core`
    assert v["attention_share.train"] == pytest.approx(50.0)
    assert v["attention_core_share.train"] == pytest.approx(20.0)
    assert v["optimizer_share.train"] == pytest.approx(7.0)
    assert v["moe_share.keye.train"] == pytest.approx(9.5)
    # what no child scope claims: the projections and head norms directly
    # under `attention`, the routed layer's own slicing under `moe`
    assert v["attention_rest_share.keye.train"] == pytest.approx(3.0)
    assert v["moe_rest_share.keye.train"] == pytest.approx(0.5)
    assert v["moe_dispatch_share.keye.train"] == pytest.approx(3.0)
    assert v["rmsnorm_share.keye.train"] == pytest.approx(5.0)
    assert v["recompute_share.keye.train"] == pytest.approx(11.0)
    assert v["lm_head_share.keye.train"] == pytest.approx(19.0)
    assert v["unscoped_share.keye.train"] == pytest.approx(10.0)
    assert v["expert_load_max_over_mean.keye"] == pytest.approx(1.5)
    assert v["selected_pairs_share.keye"] == pytest.approx(
        100 * SELECTED / FULL)
    assert v["selected_pairs_share.keye"] == pytest.approx(23.44, abs=0.01)
    # 2 x 6,000 (token, held expert) pairs in steps 7-8 over 0.04 s
    assert v["moe_experts_roofline.keye"] == pytest.approx(
        100 * 6 * 12000 * 3 * 2048 * 768 / 1e14 / 0.04)
    # 6 layers x 32 heads x 4 x SELECTED pairs over the 0.2 s of flash_sel_*
    assert v["flash_select_roofline.keye"] == pytest.approx(
        100 * 12 * 6 * 32 * 128 * 4 * SELECTED / 1e14 / 0.2)
    # the index scores, forward and backward, over every causal pair, and
    # the KL term's reading of the selected pairs, each over its kernels
    assert v["dsa_index_roofline.keye"] == pytest.approx(
        100 * 6 * 6 * 16 * 64 * 4 * FULL / 1e14 / 0.06)
    assert v["dsa_probs_roofline.keye"] == pytest.approx(
        100 * 2 * 6 * 32 * 128 * 4 * SELECTED / 1e14 / 0.08)


def _record(program, reference, near, selected=(10, 10), by_program=(20, 20)):
    steps = {"first_step": 5, "last_step": 6}
    return {"compare": {
        "experts": {"program": [], "reference": [], "near_ties": [],
                    "padding": []},
        "loss_terms": {
            "program": {"lm_loss": [10.0, 9.9], "indexer_kl": [1.0, 1.01]},
            "reference": {"lm_loss": [10.0, 9.9], "indexer_kl": [1.0, 1.0]}},
        "selection": {"program": program, "reference": reference,
                      "near_pairs": near}},
        "window": dict(steps, dropped_pairs=0,
                       selected_pairs={"5": selected[0], "6": selected[1]},
                       selected_by_program={"5": by_program[0],
                                            "6": by_program[1]})}


@pytest.mark.parametrize("case,bad", [
    ("sound", []),
    ("moved_blocks", ["selected_l1_step1_layer1"]),
    ("other_block_count", ["selected_l1_step2_layer0"]),
    ("over_the_share", ["selected_l1_step2_layer1"]),
    ("lost_pairs", ["dsa_selected_pairs"]),
    ("kl_off", ["indexer_kl_rel_step2"]),
])
def test_the_familys_decision_on_the_selection(case, bad):
    """Per followed step and layer the L1 gap of the selected pairs by key
    block has to stay under `select_gap_share` of the reference's near-tie
    pairs (a gap the count itself would let through is refused); over the
    window the program's count equals the harness's, times the layers, exactly;
    both loss terms are held to the reference's."""
    from benchmark.families import keye as family

    cell = {"config": {"num_experts_per_tok": 8, "num_hidden_layers": 2},
            "traffic": {"limits": {"loss_rel": 1e-4, "kl_rel": 0.02,
                                   "select_gap_share": 0.5}}}
    reference = [[[100, 50, 7], [90, 60, 7]], [[100, 50, 7], [90, 60, 7]]]
    program = json.loads(json.dumps(reference))
    near = [[4, 4], [4, 4]]
    record = _record(program, reference, near)
    if case == "sound":
        program[0][0] = [101, 49, 7]            # one flip: L1 2 <= 0.5 x 4
    elif case == "moved_blocks":
        program[0][1] = [80, 70, 7]                 # the wrong keys
    elif case == "over_the_share":
        program[1][1] = [92, 58, 7]                 # L1 4: under 4, over 2
    elif case == "other_block_count":
        program[1][0] = [100, 57]
    elif case == "lost_pairs":
        record = _record(program, reference, near, by_program=(20, 19))
    elif case == "kl_off":
        record["compare"]["loss_terms"]["program"]["indexer_kl"][1] = 1.05
    rows = {}
    family.decide(cell, record,
                  lambda name, what, value, limit, ok: rows.update(
                      {name: ok}))
    assert sorted(n for n, ok in rows.items() if not ok) == bad
    assert {"dropped_pairs", "dsa_selected_pairs", "lm_loss_rel_step1",
            "indexer_kl_rel_step2", "selected_l1_step2_layer1"} <= set(rows)


def test_family_keeps_the_tree_samples_the_indexer_and_plants_its_faults(
        monkeypatch):
    import jax

    from benchmark.families import keye as family
    from bert_pytorch_tpu.models import keye as program

    found = spec.find_cell(MANIFEST, CELL, ROOT)
    cfg = dict(found["config"], **found["traffic"]["rehearse"]["config"])
    sizes = family.sizes(cfg, found["traffic"])
    assert (sizes["heads"], sizes["kv_heads"], sizes["select"]) == (8, 1, 32)
    assert sizes["kinds"] == (("select", "moe"),) * 2
    params = family.weights({"seed": 2 ** 31 + 3}, sizes)
    leaf_norms, _, sample = family.adapter_functions(sizes)
    norms = leaf_norms(params)
    assert len(norms) == len(jax.tree.leaves(params))
    assert norms["['layer_1']['moe']['experts_w1']"].shape == (4,)
    assert norms["['layer_1']['attention']['index_k_norm']['bias']"].shape \
        == (1,)
    sampled = sample(params)
    assert sorted(sampled) == sorted(
        f"layer_{i}/{n}" for i in (0, 1)
        for n in ("attention/q_proj", "attention/out_proj/kernel",
                  "attention/index_q_proj", "attention/index_k_proj",
                  "moe/experts_w1", "moe/experts_w2", "moe/router"))
    assert sampled["layer_1/moe/experts_w1"].shape == (128, 64)  # expert 0
    assert sampled["layer_0/attention/index_k_proj"].shape == (128, 16)
    # the table's rows are unit, every other matrix N(0, init_range); the
    # LayerNorm starts at gain 1 and bias 0
    assert float(params["embed_tokens"].std()) == pytest.approx(1.0, abs=0.02)
    assert float(params["lm_head"].std()) == pytest.approx(
        cfg["initializer_range"], rel=0.05)
    norm = params["layer_0"]["attention"]["index_k_norm"]
    assert (norm["scale"] == 1).all() and not np.asarray(norm["bias"]).any()
    # the planted faults replace names the model module looks up
    attend, total = program.dot_product_attention, program.total_loss
    try:
        seen = {}
        monkeypatch.setattr(
            program, "dot_product_attention",
            lambda *a, **kw: seen.update(kw) or "out")
        family.weights({"seed": 1, "fault": "no_select"}, sizes)
        words = np.zeros((1, 1, 4, 4), np.int32)
        assert program.dot_product_attention(
            1, 2, 3, causal=True, select=(words, words)) == "out"
        assert seen["causal"] and len(seen["select"]) == 2
        assert all((np.asarray(x) == -1).all() for x in seen["select"])
        family.weights({"seed": 1, "fault": "no_indexer_loss"}, sizes)
        assert program.total_loss(3.0, 4.0) == 3.0 and total(3.0, 4.0) == 7.0
    finally:
        program.dot_product_attention, program.total_loss = attend, total
        program._bench_fault = None
    with pytest.raises(ValueError, match="unknown fault"):
        family._break_program("sideways")
    assert family.TOPK_KEY == "num_experts_per_tok"
    assert found["config"][family.TOPK_KEY] == 8
    # the followed steps' counters and terms, from a step's scalars
    scalars = {s: {"moe_l0_e0": 3.0, "moe_l0_e1": 4.0, "dsa_l0_kb0": 5.0,
                   "dsa_l0_kb1": 6.0, "dsa_l1_kb0": 7.0, "dsa_l1_kb1": 8.0,
                   "lm_loss": 10.0, "indexer_kl": 1.5} for s in (1, 2, 3)}
    got = family.followed_by_program(scalars, 2)
    assert got["block_pairs"] == [[[5, 6], [7, 8]]] * 2
    assert got["expert_counts"] == [[[3, 4]]] * 2
    assert got["lm_loss"] == [10.0, 10.0] and got["indexer_kl"] == [1.5, 1.5]
