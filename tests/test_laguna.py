"""The laguna family (models/laguna.py; ops/decoder_ops.rotary at a given
table over a part of the head; the banded and causal flash kernels at groups
of 8 and 6; ops/moe.py's sigmoid router beside a shared expert) against its
plain reference (benchmark/reference/laguna_ref.py), on the CPU at toy widths
with seeded weights and a band of 12 on rows of 96, packed so that document
boundaries fall inside the band: logits, the loss and every gradient leaf;
the YaRN table at the published parameters against hand-computed values;
the dims a partial rotation leaves alone; the per-layer head counts as they
reach the attention call and the kernels; no leak across a document
boundary; the 8 ranks' shares adding up to the uncut layer; the family's
selection, its unknown-key and refusal messages."""

import importlib
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import laguna_ref as ref  # noqa: E402
from bert_pytorch_tpu.config import (LagunaConfig,  # noqa: E402
                                     load_model_config)
from bert_pytorch_tpu.models import decoder, laguna  # noqa: E402
from bert_pytorch_tpu.ops.attention import dot_product_attention  # noqa: E402
from bert_pytorch_tpu.ops.decoder_ops import (rotary,  # noqa: E402
                                              rotary_table)

# the published rotary groups (poolside/Laguna-XS.2 config.json)
ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1},
    "original_max_position_embeddings": 4096}
TOY = {
    "model_type": "laguna", "vocab_size": 2048, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 5,
    "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-6, "num_experts": 4, "experts_total": 16,
    "experts_held": [4, 8], "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 12,
    "rope_parameters": ROPE,
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention",
                    "full_attention"],
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
}
SEED = 2 ** 31 + 7
# three documents in a row, two of them longer than the band of 12, with
# boundaries (9, 70) inside a query's band, and a padded tail; then a row
# that is one document
CUTS = [[0, 9, 70, 90], [0, 96]]
KINDS = (("full", 6, "dense"), ("sliding", 8, "moe"), ("sliding", 8, "moe"),
         ("sliding", 8, "moe"), ("full", 6, "moe"))


def _packed(rows=2, s=96, vocab=2048, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(10, vocab, (rows, s)).astype(np.int32)
    seg = np.zeros((rows, s), np.int32)
    pos = np.zeros((rows, s), np.int32)
    for r, cuts in enumerate(CUTS[:rows]):
        for g, (a, b) in enumerate(zip(cuts[:-1], cuts[1:])):
            seg[r, a:b] = g + 1
            pos[r, a:b] = np.arange(b - a)
    return ids * (seg > 0), seg, pos


@pytest.fixture(scope="module")
def toy():
    cfg = LagunaConfig.from_dict(TOY).replace(
        dtype="float32", checkpoint_activations=True, attention_impl="xla")
    assert cfg.layer_kinds == KINDS
    sizes = ref.sizes_from_config(TOY)
    assert sizes["kinds"] == tuple((k, f) for k, _, f in KINDS)
    assert sizes["layer_heads"] == tuple(h for _, h, _ in KINDS)
    params = ref.init_params(SEED, sizes)
    model = laguna.LagunaForCausalLM(cfg, dtype=jnp.float32)
    ids, seg, pos = _packed()
    batch = {"input_ids": jnp.asarray(ids), "segment_ids": jnp.asarray(seg),
             "position_ids": jnp.asarray(pos)}
    return cfg, sizes, params, model, batch


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


def test_parameter_tree_is_the_references_and_no_gain_decays(toy):
    """The reference keeps its weights under the program's names, each
    layer's attention at its own head count; LAMB's no-decay list covers the
    norms' gains and the selection bias and nothing else of the family."""
    from bert_pytorch_tpu.optim.lamb import default_weight_decay_mask

    cfg, sizes, params, model, batch = toy
    init = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          *decoder.init_inputs(batch))
    shapes = jax.tree.map(jnp.shape, init["params"])
    assert shapes == jax.tree.map(jnp.shape, params)
    assert [shapes[f"layer_{i}"]["attention"]["q_proj"][1] // 16
            for i in range(5)] == [6, 8, 8, 8, 6]
    assert shapes["layer_1"]["attention"]["gate_proj"] == (64, 8)
    assert "mlp" in shapes["layer_0"] and "moe" not in shapes["layer_0"]
    assert shapes["layer_4"]["shared_expert"]["w1"]["kernel"] == (64, 32)
    mask = jax.tree_util.tree_flatten_with_path(
        default_weight_decay_mask(params))[0]
    for path, decays in mask:
        name = jax.tree_util.keystr(path)
        assert decays == (not name.endswith(("['scale']",
                                             "['expert_bias']"))), name
    assert sum(not decays for _, decays in mask) == 2 * 5 + 1 + 4


def test_logits_match_the_reference(toy):
    cfg, sizes, params, model, batch = toy
    row_forward = jax.jit(ref.row_forward, static_argnames=("sz",))
    with jax.default_matmul_precision("highest"):
        hidden, head, load, dropped = jax.jit(model.apply)(
            {"params": params}, *decoder.init_inputs(batch))
        logits = hidden @ head.T
        for r in range(2):
            want, counts, _ = row_forward(
                params, batch["input_ids"][r], batch["segment_ids"][r],
                sz=ref._Sizes(sizes))
            real = np.asarray(batch["segment_ids"][r] > 0)
            np.testing.assert_allclose(np.asarray(logits[r])[real],
                                       np.asarray(want)[real], atol=2e-6)
    assert load.shape == (4, 4) and not np.asarray(dropped).any()
    assert int(load.sum()) > 0


def test_loss_gradients_and_counts_match_the_reference(toy):
    cfg, sizes, params, model, batch = toy
    loss_fn = laguna.pretrain_loss_fn_builder(model)
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            loss_fn, has_aux=True))(params, batch, None)
    want, want_grads, counts, _ = ref.step_loss_and_grad(
        params, [batch], sizes)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    scalars = aux["scalars"]
    # the four routed layers' counters, over the held experts
    assert [[int(scalars[f"moe_l{i}_e{j}"]) for j in range(4)]
            for i in range(4)] == np.asarray(counts).tolist()
    assert "moe_l4_e0" not in scalars
    assert all(int(scalars[f"moe_l{i}_dropped"]) == 0 for i in range(4))
    assert int(scalars["moe_pairs_routed"]) == 2 * 96 * 4
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == len(jax.tree.leaves(want_grads))
    for (path, got), ref_leaf in zip(flat, jax.tree.leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['expert_bias']"):    # a buffer: no gradient
            assert not np.asarray(got).any() and \
                not np.asarray(ref_leaf).any(), name
        else:
            assert _rel(got, ref_leaf) < 2e-5, name


def test_the_rotation_kernels_match_the_reference(monkeypatch):
    """Heads of 128 under the test switch: q and k of both kinds of layer
    take ops/pallas/rotary.py's kernels (interpret mode), whole head and
    half a head under YaRN, read from the fused projection; loss and every
    gradient leaf against the reference."""
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    wide = dict(TOY, head_dim=128)
    cfg = LagunaConfig.from_dict(wide).replace(
        dtype="float32", checkpoint_activations=True, attention_impl="xla")
    sizes = ref.sizes_from_config(wide)
    params = ref.init_params(SEED, sizes)
    model = laguna.LagunaForCausalLM(cfg, dtype=jnp.float32)
    batch = {k: jnp.asarray(v) for k, v in zip(
        ("input_ids", "segment_ids", "position_ids"), _packed())}
    loss_fn = laguna.pretrain_loss_fn_builder(model)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    text = str(jax.make_jaxpr(grad_fn)(params, batch, None))
    # 5 layers x (q, k), forward and recomputed; the rule once each
    assert text.count("name=rotary_fwd") == 20
    assert text.count("name=rotary_bwd") == 10
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.jit(grad_fn)(params, batch, None)
    want, want_grads, _, _ = ref.step_loss_and_grad(params, [batch], sizes)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), ref_leaf in zip(flat, jax.tree.leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        if not name.endswith("['expert_bias']"):
            assert _rel(got, ref_leaf) < 2e-5, name


def test_yarn_table_at_the_published_parameters():
    """lo, hi, f_0, f_31 and c by hand: R = 64 of the head's 128 dims turn;
    dim(64) = 64 ln(4096 / (128 pi)) / (2 ln 500000) = 5.66 -> lo 5;
    dim(1) = 64 ln(4096 / (2 pi)) / (2 ln 500000) = 15.80 -> hi 16; pairs
    0-5 keep their frequency, pairs 16-31 are slowed 64-fold, a linear ramp
    between. The program's table and the reference's, each written on its
    own, agree with these and with each other."""
    ln = math.log(500000.0)
    assert 64 * math.log(4096 / (128 * math.pi)) / (2 * ln) == \
        pytest.approx(5.660, abs=1e-3)
    assert 64 * math.log(4096 / (2 * math.pi)) / (2 * ln) == \
        pytest.approx(15.802, abs=1e-3)
    inv_freq, rotated, factor = rotary_table(128, ROPE["full_attention"])
    assert (rotated, factor) == (64, 1.4158883083359672)
    assert factor == pytest.approx(0.1 * math.log(64) + 1)
    e = [500000.0 ** (-2 * i / 64) for i in range(32)]
    assert inv_freq.shape == (32,) and inv_freq[0] == 1.0
    np.testing.assert_allclose(inv_freq[:6], e[:6], rtol=1e-6)     # r = 0
    np.testing.assert_allclose(inv_freq[16:], np.array(e[16:]) / 64,
                               rtol=1e-6)                          # r = 1
    assert inv_freq[31] == pytest.approx(500000.0 ** (-62 / 64) / 64,
                                         rel=1e-6)
    # the ramp's first step: pair 6, r = 1/11
    assert inv_freq[6] == pytest.approx(e[6] / 64 / 11 + e[6] * 10 / 11,
                                        rel=1e-6)
    r, c, freqs = ref.rope_table(128, ROPE["full_attention"])
    assert (r, c) == (64, factor)
    np.testing.assert_allclose(freqs, inv_freq, rtol=1e-6)
    # the windowed layers: the whole head at theta 10,000, no factor
    inv_freq, rotated, factor = rotary_table(128, ROPE["sliding_attention"])
    assert (rotated, factor) == (128, 1.0)
    np.testing.assert_allclose(
        inv_freq, 10000.0 ** (-np.arange(0, 128, 2) / 128), rtol=1e-6)
    assert ref.rope_table(128, ROPE["sliding_attention"])[:2] == (128, 1.0)
    with pytest.raises(ValueError, match="rope_type"):
        rotary_table(128, {"rope_theta": 1e4, "rope_type": "linear"})


def test_rotation_turns_the_first_dims_and_leaves_the_rest():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 3, 128))
    pos = jnp.tile(jnp.arange(24)[None], (2, 1))
    inv_freq, rotated, factor = rotary_table(128, ROPE["full_attention"])
    got = rotary(x, pos, inv_freq=inv_freq, rotated=rotated, factor=factor)
    np.testing.assert_array_equal(np.asarray(got[..., 64:]),
                                  np.asarray(x[..., 64:]))
    # position 0 is turned by nothing: the factor alone
    np.testing.assert_allclose(got[:, 0, :, :64], factor * x[:, 0, :, :64],
                               rtol=1e-6)
    assert float(jnp.abs(got[:, 1:, :, :64] - factor * x[:, 1:, :, :64])
                 .max()) > 0.1
    want = ref._rope(x[0], pos[0], ref.rope_table(128,
                                                  ROPE["full_attention"]))
    np.testing.assert_allclose(got[0], want, atol=2e-6)
    # what the families before it pass is what they passed: theta alone is
    # the table theta^(-2i/D) over the whole head
    old = rotary(x, pos, 1500000.0)
    table = 1.0 / (1500000.0 ** (jnp.arange(0, 128, 2, dtype=jnp.float32)
                                 / 128))
    np.testing.assert_array_equal(
        np.asarray(old), np.asarray(rotary(x, pos, inv_freq=table)))
    text = str(jax.make_jaxpr(lambda x: rotary(x, pos, 1e6))(x))
    assert "slice" in text and text.count("concatenate") == 3


def test_per_layer_head_counts_and_tables_reach_the_attention_call(
        toy, monkeypatch):
    """Each layer calls attention with its own count of query heads over the
    2 key/value heads, the windowed layers under the band."""
    cfg, sizes, params, model, batch = toy
    seen = []

    def spy(q, k, v, **kw):
        seen.append((q.shape[2], k.shape[2], kw.get("window"),
                     kw["causal"]))
        return dot_product_attention(q, k, v, **kw)

    monkeypatch.setattr(laguna, "dot_product_attention", spy)
    plain = laguna.LagunaForCausalLM(
        cfg.replace(checkpoint_activations=False), dtype=jnp.float32)
    jax.eval_shape(plain.apply, {"params": params},
                   *decoder.init_inputs(batch))
    assert seen == [(6, 2, None, True), (8, 2, 12, True), (8, 2, 12, True),
                    (8, 2, 12, True), (6, 2, None, True)]


@pytest.mark.parametrize("group,window", [(8, 40), (6, 0)],
                         ids=["band-group-8", "full-group-6"])
def test_flash_kernels_at_the_familys_groups_in_interpret_mode(
        group, window, monkeypatch):
    """The bh-layout kernels as the two kinds of layer call them: a program
    owns the 8 query heads of a windowed layer's key/value head (band +
    segment ids in ONE call, a boundary at 150 inside the band of the rows
    after it) or the 6 of a full layer's; forward and all three gradients
    against the XLA path."""
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    fa = importlib.import_module(
        "bert_pytorch_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "DEFAULT_BLK_Q", 128)
    monkeypatch.setattr(fa, "DEFAULT_BLK_K", 128)
    s, d = 256, 64
    keys = jax.random.split(jax.random.PRNGKey(group), 4)
    q = jax.random.normal(keys[0], (1, s, group, d))
    k = jax.random.normal(keys[1], (1, s, 1, d))
    v = jax.random.normal(keys[2], (1, s, 1, d))
    seg = np.zeros((1, s), np.int32)
    seg[0, :150], seg[0, 150:230] = 1, 2        # and a padded tail
    seg = jnp.asarray(seg)
    weight = jax.random.normal(keys[3], (1, s, group, d)) * (
        seg > 0)[:, :, None, None]             # no loss term reads padding

    def loss(impl):
        def f(q, k, v):
            out = dot_product_attention(
                q, k, v, segment_ids=seg, impl=impl, causal=True,
                window=window or None)
            return jnp.sum(out * weight), out
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, got), got_grads = jax.jit(loss("pallas"))(q, k, v)
    (_, want), want_grads = jax.jit(loss("xla"))(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for a, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, w, atol=5e-5)
    lay = fa._layout(1, s, group, d, group, d, window)
    assert (lay.native, lay.heads_per_prog) == (False, group)
    text = str(jax.make_jaxpr(loss("pallas"))(q, k, v))
    prefix = "flash_win_" if window else "flash_"
    for name in ("fwd", "bwd_dq", "bwd_dkv"):
        assert prefix + name in text
    # at the cell's shape a program owns a whole group, and the dkv call's
    # panels stay under a core's 128 MiB
    for heads, band in ((64, 512), (48, 0)):
        lay = fa._layout(1, 16384, heads, 128, heads // 8, 128, band)
        assert lay.heads_per_prog == heads // 8
        asked = fa._long_seq_params(
            16384, lay.heads_per_prog * 128,
            lay.heads_per_prog * fa._panel_bytes(16384, 128, 128))
        assert asked["compiler_params"].vmem_limit_bytes == (
            heads // 8 * 8 + 16) * 2 ** 20


@pytest.mark.parametrize("kind", ["full", "sliding"])
def test_no_leak_across_a_document_boundary(toy, kind):
    """Changing the tokens of a row's second document moves nothing in the
    documents before and after it, to the bit, in either kind of layer: the
    band counts tokens of the query's own document, rotary positions restart
    with it, and the gate is a token's own."""
    cfg, sizes, params, model, batch = toy
    layer, heads = ("layer_1", 8) if kind == "sliding" else ("layer_0", 6)
    lp = params[layer]["attention"]
    seg, pos = batch["segment_ids"][:1], batch["position_ids"][:1]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 96, 64))
    other = x.at[:, 9:70].add(1.0)          # the second document
    run = jax.jit(lambda a: laguna.Attention(
        cfg, kind, heads, jnp.float32).apply({"params": lp}, a, seg, pos))
    a, b = run(x), run(other)
    assert float(jnp.abs(a - b)[:, 9:70].max()) > 1e-3
    assert float(jnp.abs(a - b)[:, :9].max()) == 0.0
    assert float(jnp.abs(a - b)[:, 70:].max()) == 0.0
    # and the band bites: a windowed layer without it reads something else
    if kind == "sliding":
        wide = jax.jit(laguna.Attention(
            cfg.replace(sliding_window=96), kind, heads, jnp.float32).apply)(
                {"params": lp}, x, seg, pos)
        assert float(jnp.abs(wide - a)[:, 30:70].max()) > 1e-4


def test_expert_parallel_shares_add_up_to_the_whole_layer():
    """The share ties to the model: the program's routed FFN, told which 2
    of the 16 experts it holds, for each of the 8 ranks: the partial sums
    added up, and the shared expert counted ONCE, equal the UNCUT
    reference's routed layer (sigmoid scores, the selection bias, the
    scaling factor of 2.5)."""
    whole = dict(TOY, num_experts=16, experts_held=[0, 16])
    sizes = ref.sizes_from_config(whole)
    lp = ref.init_params(SEED, sizes)["layer_1"]
    m = jax.random.normal(jax.random.PRNGKey(3), (1, 96, 64), jnp.float32)
    stacks = ("experts_w1", "experts_w3", "experts_w2")
    cfg = LagunaConfig.from_dict(dict(
        TOY, num_experts=2, experts_held=[0, 2])).replace(dtype="float32")

    @jax.jit
    def ranks(lp, m):
        total = laguna.DenseMLP(cfg, jnp.float32, 32).apply(
            {"params": lp["shared_expert"]}, m)[0]
        loads, drops = [], []
        for lo in range(0, 16, 2):
            share = dict(lp["moe"], **{n: lp["moe"][n][lo:lo + 2]
                                       for n in stacks})
            out, load, dropped = laguna.RoutedExperts(
                cfg.replace(experts_held=(lo, lo + 2)), jnp.float32).apply(
                    {"params": share}, m)
            total = total + out[0]
            loads.append(load)
            drops.append(dropped)
        return total, jnp.concatenate(loads), jnp.stack(drops)

    with jax.default_matmul_precision("highest"):
        want, counts, _ = jax.jit(
            lambda lp, m: ref._routed(m, lp, ref._Sizes(sizes), None, 0.0))(
                lp, m[0])
        total, loads, drops = ranks(lp, m)
    assert not np.asarray(drops).any()
    loads = np.asarray(loads).tolist()
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=5e-6)
    assert loads == np.asarray(counts).tolist() and sum(loads) == 96 * 4
    # the weights of a token's selected experts sum to the scaling factor
    experts, weights, _ = ref.route(m[0], lp["moe"], ref._Sizes(sizes))
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)


def test_model_config_family_selection_and_messages(tmp_path):
    def write(d):
        p = tmp_path / f"cfg{len(list(tmp_path.iterdir()))}.json"
        p.write_text(json.dumps(d))
        return str(p)

    cfg = load_model_config(write(dict(
        TOY, source="s", reduced={}, assumed={}, layout="l")))
    assert isinstance(cfg, LagunaConfig) and cfg.layer_kinds == KINDS
    assert (cfg.router_scores, cfg.expert_activation, cfg.norm_topk_prob,
            cfg.use_expert_bias, cfg.routed_scaling_factor) == (
                "sigmoid", "silu", True, True, 2.5)
    assert cfg.router_width == 16 and cfg.held_range == (4, 8)
    assert cfg.rope("full")["rope_type"] == "yarn"
    assert cfg.rope("sliding")["partial_rotary_factor"] == 1
    hash(cfg)       # a static field of the modules
    # a key the family does not know is refused by name, nested ones too
    with pytest.raises(ValueError, match="conv_L_cache"):
        load_model_config(write(dict(TOY, conv_L_cache=3)))
    bad = json.loads(json.dumps(ROPE))
    bad["full_attention"]["mscale"] = 1.0
    with pytest.raises(ValueError,
                       match=r"rope_parameters\.full_attention\.mscale"):
        load_model_config(write(dict(TOY, rope_parameters=bad)))
    with pytest.raises(ValueError, match="rope_parameters.chunked"):
        load_model_config(write(dict(TOY, rope_parameters=dict(
            ROPE, chunked={}))))
    with pytest.raises(ValueError, match="one entry"):
        load_model_config(write(dict(
            TOY, num_attention_heads_per_layer=[6, 8, 8])))
    with pytest.raises(ValueError, match="linear_attention"):
        load_model_config(write(dict(
            TOY, layer_types=["linear_attention"] * 5)))
    with pytest.raises(ValueError, match="experts_held"):
        load_model_config(write(dict(TOY, experts_held=[14, 18])))
    for change, what in (({"gating": False}, "gating"),
                         ({"attention_bias": True}, "attention_bias"),
                         ({"tie_word_embeddings": True}, "tie_word"),
                         ({"num_attention_heads_per_layer": [6, 7, 8, 8, 6]},
                          "multiple of num_key_value_heads"),
                         ({"shared_expert_intermediate_size": 64},
                          "shared_expert")):
        with pytest.raises(NotImplementedError, match=what):
            load_model_config(write(dict(TOY, **change)))
    # the benchmark's configuration is one the program reads: every width
    # as published, the leading dense layer and one whole period, an eighth
    # of the experts and of the rows
    real = load_model_config(os.path.join(
        ROOT, "benchmark", "configs", "laguna-xs2-33b-a3b-ep8.json"))
    assert real.layer_kinds == (
        ("full", 48, "dense"), ("sliding", 64, "moe"),
        ("sliding", 64, "moe"), ("sliding", 64, "moe"), ("full", 48, "moe"))
    assert real.held_range == (0, 32) and real.router_width == 256
    assert (real.hidden_size, real.num_key_value_heads, real.head_dim,
            real.intermediate_size, real.moe_intermediate_size,
            real.shared_expert_intermediate_size, real.num_experts_per_tok,
            real.sliding_window) == (2048, 8, 128, 8192, 512, 512, 8, 512)
    assert real.vocab_size * 8 == 100352 and real.remat_policy == "dense"


def test_the_decoder_families_refusal_names_the_family(tmp_path):
    import run_pretraining

    cfg_path = tmp_path / "toy.json"
    cfg_path.write_text(json.dumps(TOY))
    with pytest.raises(SystemExit) as e:
        run_pretraining.main([
            "--model_config_file", str(cfg_path), "--input_dir",
            str(tmp_path), "--output_dir", str(tmp_path / "out"),
            "--tensorboard", "off", "--kfac"])
    message = str(e.value)
    assert "'lfm2_moe', 'kimi_linear', 'smallthinker', 'laguna'" in message
    for flag in ("--kfac", "--stream_dir", "--stacked_params",
                 "--steps_per_loop"):
        assert flag in message
    cfg = load_model_config(str(cfg_path))
    # a full row's FLOPs by the family's own formula: each layer's heads
    e, d = 64, 16
    weights = 2048 * e + sum(
        e * (h + 4) * d + e * h + h * d * e for h in (6, 8, 8, 8, 6))
    weights += 3 * e * 96 + 4 * (e * 16 + 3 * e * 32 * (1 + 4 * 4 / 16))
    pairs = 2 * 6 * laguna.band_pairs(128, 0) + 3 * 8 * laguna.band_pairs(
        128, 12)
    assert laguna.train_flops_per_row(cfg, 128) == pytest.approx(
        6.0 * weights * 128 + 12.0 * d * pairs)
