"""The lfm2_moe cell of the benchmark on the CPU: the metrics that are its
own, its cut, the family's arithmetic (lm_flops), its adapter, and that the
readers it brought return None, and do not raise, on a run of a program
that lacks the family's scopes and counters (the parent commit's). Its
rehearsed runs are cases of test_bench_rehearse.py::test_rehearsed_run."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import lm_flops, spec  # noqa: E402

CELL = "lfm2-ep8-clm-8k-packed"
MANIFEST = spec.load_manifest(ROOT)
NEW_METRICS = ["moe_share.train", "moe_dispatch_share.train",
               "conv_share.train", "lm_head_share.train",
               "unscoped_share.lm.train", "expert_load_max_over_mean",
               "moe_experts_roofline", "flash_causal_roofline",
               "recompute_share.lm.train", "rmsnorm_share.train"]


def test_the_cells_own_metrics():
    """What test_bench_manifest.py asks of every cell holds for this one
    there; here, what is this cell's alone."""
    found = spec.find_cell(MANIFEST, CELL, ROOT)
    assert found["family"] == "lfm2_moe"
    assert found["traffic"]["limits"]["tie_tol"] > 0
    mine = [m["name"] for m in spec.metrics_of_cell(MANIFEST, CELL,
                                                    "per_layer")]
    assert set(NEW_METRICS) <= set(mine)
    # what reads BERT's heads, LayerNorm kernels, layer scan or scope list
    # is not asked of this cell
    assert not {"mlm_head_share.train", "layernorm_share.train",
                "scan_carry_share.train", "unscoped_share.train",
                "flash_roofline", "recompute_share.train"} & set(mine)


def test_configuration_states_the_cut():
    cfg = spec.find_cell(MANIFEST, CELL, ROOT)["config"]
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "lfm2-24b-a2b-ep8")
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == [
        "num_dense_layers", "num_experts", "num_hidden_layers", "vocab_size"]
    published = {"hidden_size": 2048, "intermediate_size": 11776,
                 "moe_intermediate_size": 1536, "num_attention_heads": 32,
                 "num_key_value_heads": 8, "num_experts_per_tok": 4,
                 "conv_L_cache": 3, "norm_eps": 1e-05,
                 "max_position_embeddings": 128000,
                 "routed_scaling_factor": 1}
    assert {k: cfg[k] for k in published} == published
    assert len(cfg["layer_types"]) == 40 and cfg["experts_total"] == 64
    kinds = lm_flops.layer_kinds(cfg)
    assert kinds == [("conv", "dense"), ("attention", "moe"),
                     ("conv", "moe"), ("conv", "moe"), ("conv", "moe")]
    for key in ("tie_word_embeddings", "expert_bias", "weights",
                "optimizer", "dtype", "packing"):
        assert cfg["assumed"][key]
    assert "8 chips share each layer" in cfg["layout"]


def test_parameter_count_and_flops_of_the_cut():
    cfg = spec.find_cell(MANIFEST, CELL, ROOT)["config"]
    from benchmark.reference import lfm2_moe_ref as ref

    shapes = ref.param_shapes(ref.sizes_from_config(cfg))
    import jax

    n = sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))
    assert 468e6 < n < 470e6            # ISSUE 26: 469 M parameters
    per_token = lm_flops.dense_weights_per_token(cfg)
    experts = 4 * 3 * 2048 * 1536 * 4 / 8       # 4 layers, 4 of 64 -> 1/2
    assert abs(experts / per_token - 0.10) < 0.01   # ~10 % of the products
    assert lm_flops.moe_expert_flops(cfg, 1000) == 6 * 1000 * 3 * 2048 * 1536
    # one attention layer, 32 heads of 64: 12 x 32 x 64 per causal pair
    assert lm_flops.causal_attention_flops(cfg, 10) == 12 * 32 * 64 * 10
    assert lm_flops.causal_attention_flops(cfg, 10, False) == 4 * 32 * 64 * 10


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_reader_finds_nothing_in_a_run_without_the_family(name):
    """The driver lays these files over the parent's checkout: a traced run
    of a program with none of the family's scopes, kernels or counters
    must leave the metric out, not raise."""
    metric = spec.load_layer_metric(name, ROOT)
    read = spec.load_reader(metric["reader"], ROOT)
    bert_trace = {"by_scope": {"jit(train_step)/bert/encoder/scan/dot": 1.0},
                  "busy_s": 1.0, "window_s": 1.0, "steps": 3}
    ctx = {"trace": bert_trace, "chips": 1, "flops": lm_flops,
           "peaks": lm_flops.peaks("TPU v5 lite"),
           "cell": spec.find_cell(MANIFEST, CELL, ROOT),
           "record": {"window": {"perf": [{"step": 7, "compiles": 9}],
                                 "traced_first_step": 7}}}
    value = read(ctx, **metric.get("args", {}))
    if name == "unscoped_share.lm.train":
        assert value == 100.0       # nothing there is under the LM list
    else:
        assert value is None


def test_readers_on_a_run_of_the_family():
    cell = spec.find_cell(MANIFEST, CELL, ROOT)
    pre = "jit(train_step)/grad_accum/jvp(M)/decoder/layer_1/"
    trace = {"by_scope": {
        pre + "moe/experts/silu": 0.05, "ragged-dot-none": 0.15,
        pre + "moe/dispatch/gather": 0.05, pre + "moe/combine/scatter": 0.05,
        pre + "moe/router/dot": 0.05, pre + "ffn_norm/rmsnorm/rsqrt": 0.05,
        pre + "conv/in_proj/dot": 0.1,
        "jit(train_step)/grad_accum/transpose(jvp(M))/decoder/checkpoint/"
        "rematted_computation/layer_2/conv/mul": 0.1,
        "jit(train_step)/decoder/lm_head/dot": 0.1,
        "jit(train_step)/grad_accum/jvp(loss)/reduce": 0.1,
        pre + "attention/flash_fwd/pallas_call": 0.1, "": 0.1},
        "busy_s": 1.0, "window_s": 1.0, "steps": 2}
    perf = [{"step": s, "moe_l0_pairs": 1000.0 * (s - 1),
             "moe_l0_load_max": 300.0, "moe_l0_load_mean": 200.0,
             "moe_l1_load_max": 250.0, "moe_l1_load_mean": 200.0}
            for s in range(5, 12)]
    ctx = {"trace": trace, "chips": 1, "flops": lm_flops, "cell": cell,
           "peaks": {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e12},
           "record": {"window": {"perf": perf, "traced_first_step": 7,
                                 "causal_pairs": {"7": 1e6, "8": 1e6}}}}
    got = spec.read_layer_metrics(
        {**MANIFEST, "per_layer": [m for m in MANIFEST["per_layer"]
                                   if m["name"] in NEW_METRICS]}, CELL, ctx)
    v = {k: m["value"] for k, m in got.items()}
    assert v["moe_share.train"] == pytest.approx(35.0)
    assert v["rmsnorm_share.train"] == pytest.approx(5.0)
    assert v["recompute_share.lm.train"] == pytest.approx(10.0)
    assert v["moe_dispatch_share.train"] == pytest.approx(10.0)
    assert v["conv_share.train"] == pytest.approx(20.0)
    assert v["lm_head_share.train"] == pytest.approx(20.0)
    assert v["unscoped_share.lm.train"] == pytest.approx(10.0)
    assert v["expert_load_max_over_mean"] == pytest.approx(1.5)
    # 2000 pairs in steps 7-8: 6 x 2000 x 3 x 2048 x 1536 over 0.2 s at 1e12
    assert v["moe_experts_roofline"] == pytest.approx(
        100 * 6 * 2000 * 3 * 2048 * 1536 / 1e12 / 0.2)
    assert v["flash_causal_roofline"] == pytest.approx(
        100 * 12 * 32 * 64 * 2e6 / 1e12 / 0.1)


def test_adapter_renames_every_leaf_and_keeps_norms():
    from benchmark.harness import lm_adapter
    from benchmark.reference import lfm2_moe_ref as ref

    cfg = dict(spec.find_cell(MANIFEST, CELL, ROOT)["config"])
    cfg.update(spec.find_cell(MANIFEST, CELL, ROOT)["traffic"]["rehearse"]
               ["config"])
    sizes = ref.sizes_from_config(cfg)
    params = ref.init_params(2 ** 31 + 3, sizes)
    tree = lm_adapter.to_program_tree(params)
    import jax

    assert len(jax.tree.leaves(tree)) == len(jax.tree.leaves(params))
    norms = lm_adapter.leaf_norms(tree)
    assert norms["['layer_1']['moe']['experts_w1']"].shape == (8,)
    assert norms["['layer_1']['moe']['router']"].shape == (1,)
    sampled = lm_adapter.sample_matrices(tree, sizes["kinds"])
    assert sorted(sampled) == sorted([
        "layer_0/conv/in_proj/kernel", "layer_0/conv/out_proj/kernel",
        "layer_1/attention/q_proj", "layer_1/attention/out_proj/kernel",
        "layer_0/mlp/w1/kernel", "layer_0/mlp/w2/kernel",
        "layer_1/moe/experts_w1", "layer_1/moe/experts_w2",
        "layer_1/moe/router", "layer_4/moe/experts_w1",
        "layer_4/moe/experts_w2", "layer_4/moe/router"])


# seed 1749203044 on the chip (PR 32), step 2, routed layer 2: the padding
# slot's 4th choice is held expert 5, 2.18e-3 above its 5th, and the program
# sent the step's padding slots to the other.
_GOT = [3306, 4112, 4097, 2958, 3880, 6555, 4613, 4316]
_WANT = [3297, 4111, 4113, 2962, 3879, 6831, 4622, 4322]
_AT5 = [0, 0, 0, 0, 0, 1, 0, 0]


@pytest.mark.parametrize("got,slots,at,near,ties,gap,ok", [
    (_GOT, 0, [0] * 8, 0, 300, 322, False),     # as the check read it then
    (_GOT, 276, _AT5, 0, 300, 46, True),        # the slots left out
    (_WANT, 276, _AT5, 0, 300, 0, True),        # the slots where they were
    # a lump that is no padding stays: it is not the slots' size ...
    ([a + 500 * b for a, b in zip(_WANT, _AT5)], 276, _AT5, 0, 300, 500,
     False),
    # ... nor may the slots sit at more experts than a token selects
    ([a + 276 for a in _WANT], 276, [0] * 8, 0, 300, 276 * 4, False),
    # slots within tie_tol of a tie are no near-tie TOKENS
    (_WANT[:7] + [_WANT[7] + 50], 276, _AT5, 1, 300, 50, False),
], ids=["slots-counted", "slots-left-out", "slots-in-place", "other-lump",
        "more-than-k", "near-ties-without-slots"])
def test_padding_slots_are_one_token(got, slots, at, near, ties, gap, ok):
    from benchmark.families import lfm2_moe

    seen = []
    record = {"window": {"dropped_pairs": 0}, "compare": {"experts": {
        "program": [[got]], "reference": [[_WANT]], "near_ties": [[ties]],
        "padding": [{"slots": slots, "counts": [at], "near_ties": [near]}]}}}
    lfm2_moe.decide(
        {"config": {"num_experts_per_tok": 4}}, record,
        lambda name, what, value, limit, fine: seen.append(
            (name, value, fine)))
    assert seen[0] == ("experts_l1_step1_layer0", gap, ok), seen
    assert seen[1] == ("dropped_pairs", 0, True)
