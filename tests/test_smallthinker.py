"""The smallthinker family (models/smallthinker.py, the banded causal flash
kernels, ops/moe.py's softmax router and ReLU gate) against its plain
reference (benchmark/reference/smallthinker_ref.py), on the CPU at toy
widths with seeded weights and a band of 12 on rows of 96 so that it bites:
logits, the loss, every gradient leaf and one LAMB step over packed rows;
the band's kernels against the XLA path in interpret mode at group sizes 1,
4 and 7 across a document boundary; a band wider than the row is plain
causal; a tile pair wholly behind the band is skipped; the softmax over the
selected equals the renormalised full softmax; the router's gradient reaches
the layer's input; the expert-parallel shares add up to the uncut layer; the
entry point's family selection, counters and scopes."""

import importlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.families import smallthinker as bench_family  # noqa: E402
from benchmark.reference import smallthinker_ref as ref  # noqa: E402
from bert_pytorch_tpu.config import (SmallThinkerConfig,  # noqa: E402
                                     load_model_config)
from bert_pytorch_tpu.models import decoder, smallthinker  # noqa: E402
from bert_pytorch_tpu.ops import moe as moe_ops  # noqa: E402
from bert_pytorch_tpu.ops.attention import dot_product_attention  # noqa: E402

TOY = {
    "model_type": "smallthinker", "vocab_size": 2048, "hidden_size": 64,
    "head_dim": 16, "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "moe_ffn_hidden_size": 32,
    "moe_num_primary_experts": 4, "experts_total": 8, "experts_held": [2, 6],
    "moe_num_active_primary_experts": 3,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rope_layout": [0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1],
    "sliding_window_size": 12, "rope_theta": 1500000, "rope_scaling": None,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 16384,
    "tie_word_embeddings": False,
}
SEED = 2 ** 31 + 7
# three documents in a row, two of them longer than the band of 12, and a
# padded tail; then a row that is one document
CUTS = [[0, 9, 70, 90], [0, 96]]


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
    cfg = SmallThinkerConfig.from_dict(TOY).replace(
        dtype="float32", checkpoint_activations=True, attention_impl="xla")
    assert cfg.layer_kinds == ((0, False), (12, True), (12, True),
                               (12, True))
    sizes = ref.sizes_from_config(TOY)
    assert sizes["kinds"] == cfg.layer_kinds
    params = ref.init_params(SEED, sizes)
    model = smallthinker.SmallThinkerForCausalLM(cfg, dtype=jnp.float32)
    ids, seg, pos = _packed()
    batch = {"input_ids": jnp.asarray(ids), "segment_ids": jnp.asarray(seg),
             "position_ids": jnp.asarray(pos)}
    return cfg, sizes, params, model, batch


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


def test_parameter_tree_is_the_references_and_no_gain_decays(toy):
    """The reference keeps its weights under the program's names; LAMB's
    no-decay list covers the family's three kinds of norm gain and nothing
    else of it."""
    from bert_pytorch_tpu.optim.lamb import default_weight_decay_mask

    cfg, sizes, params, model, batch = toy
    init = model.init(jax.random.PRNGKey(0), *decoder.init_inputs(batch))
    assert (jax.tree.map(jnp.shape, init["params"])
            == jax.tree.map(jnp.shape, params))
    mask = jax.tree_util.tree_flatten_with_path(
        default_weight_decay_mask(params))[0]
    for path, decays in mask:
        name = jax.tree_util.keystr(path)
        assert decays == (not name.endswith("['scale']")), name
    assert sum(not decays for _, decays in mask) == 2 * 4 + 1


def test_logits_match_the_reference(toy):
    cfg, sizes, params, model, batch = toy
    with jax.default_matmul_precision("highest"):
        hidden, head, load, dropped = model.apply(
            {"params": params}, *decoder.init_inputs(batch))
        logits = hidden @ head.T
        for r in range(2):
            want, counts, _ = ref.row_forward(
                params, batch["input_ids"][r], batch["segment_ids"][r],
                ref._Sizes(sizes))
            real = np.asarray(batch["segment_ids"][r] > 0)
            np.testing.assert_allclose(np.asarray(logits[r])[real],
                                       np.asarray(want)[real], atol=2e-6)
    assert not np.asarray(dropped).any()
    assert int(load.sum()) > 0


def test_loss_gradients_and_counts_match_the_reference(toy):
    cfg, sizes, params, model, batch = toy
    loss_fn = smallthinker.pretrain_loss_fn_builder(model)
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            loss_fn, has_aux=True))(params, batch, None)
    want, want_grads, counts, _ = ref.step_loss_and_grad(
        params, [batch], sizes)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    scalars = aux["scalars"]
    assert [[int(scalars[f"moe_l{i}_e{j}"]) for j in range(4)]
            for i in range(4)] == np.asarray(counts).tolist()
    assert all(int(scalars[f"moe_l{i}_dropped"]) == 0 for i in range(4))
    assert int(scalars["moe_pairs_routed"]) == 2 * 96 * 3
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), ref_leaf in zip(flat, jax.tree.leaves(want_grads)):
        assert _rel(got, ref_leaf) < 2e-5, jax.tree_util.keystr(path)


def test_the_rotation_kernels_match_the_reference(monkeypatch):
    """Heads of 128 under the test switch: q and k of the banded layers take
    ops/pallas/rotary.py's kernels (interpret mode), read from the fused
    projection; loss and every gradient leaf against the reference."""
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    wide = dict(TOY, head_dim=128)
    cfg = SmallThinkerConfig.from_dict(wide).replace(
        dtype="float32", checkpoint_activations=True, attention_impl="xla")
    sizes = ref.sizes_from_config(wide)
    params = ref.init_params(SEED, sizes)
    model = smallthinker.SmallThinkerForCausalLM(cfg, dtype=jnp.float32)
    batch = {k: jnp.asarray(v) for k, v in zip(
        ("input_ids", "segment_ids", "position_ids"), _packed())}
    loss_fn = smallthinker.pretrain_loss_fn_builder(model)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    text = str(jax.make_jaxpr(grad_fn)(params, batch, None))
    # 3 banded layers x (q, k), forward and recomputed; the rule once each
    assert text.count("name=rotary_fwd") == 12
    assert text.count("name=rotary_bwd") == 6
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.jit(grad_fn)(params, batch, None)
    want, want_grads, _, _ = ref.step_loss_and_grad(params, [batch], sizes)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), ref_leaf in zip(flat, jax.tree.leaves(want_grads)):
        assert _rel(got, ref_leaf) < 2e-5, jax.tree_util.keystr(path)


def test_one_lamb_step_matches_the_reference(toy):
    import run_pretraining
    from bert_pytorch_tpu.optim import schedulers
    from bert_pytorch_tpu.training import build_pretrain_step
    from bert_pytorch_tpu.training.state import TrainState

    cfg, sizes, params, model, batch = toy
    schedule = schedulers.make_schedule("poly", 0.004, 100, warmup=0.1)
    tx = run_pretraining.make_optimizer("lamb", schedule)
    # the schedule's rate is 0 at count 0: start one step in
    state = TrainState(step=jnp.ones([], jnp.int32), params=params,
                       opt_state=tx.init(params))
    state = state.replace(opt_state=jax.tree.map(
        lambda x: x + 1 if x.dtype == jnp.int32 and x.ndim == 0 else x,
        state.opt_state))
    step = build_pretrain_step(
        model, tx, schedule=schedule, accum_steps=1,
        loss_fn_builder=smallthinker.pretrain_loss_fn_builder,
        keep_float32=smallthinker.keep_float32)
    stacked = {k: v[None] for k, v in batch.items()}
    with jax.default_matmul_precision("highest"):
        new, _ = jax.jit(step)(state, stacked, jax.random.PRNGKey(0))
    _, grads, _, _ = ref.step_loss_and_grad(params, [batch], sizes)
    mine = jax.tree.map(jnp.copy, params)
    want, _ = ref.lamb_step(mine, grads, {"count": 1, "mu": None,
                                          "nu": None}, 0.004, 100, 0.1)
    leaf_norms, leaf_diff_norms, _ = bench_family.adapter_functions(sizes)
    moved = leaf_diff_norms(new.params, params)
    gaps = leaf_diff_norms(new.params, want)
    for name, gap in gaps.items():
        assert gap.max() <= 1e-3 * moved[name].max() + 1e-7, name
        assert moved[name].min() > 0.0, name


def _band_case(group, s=256, hkv=2, d=64, cuts=(0, 150, 256), seed=0):
    h = hkv * group
    keys = jax.random.split(jax.random.PRNGKey(seed + group), 4)
    q = jax.random.normal(keys[0], (2, s, h, d))
    k = jax.random.normal(keys[1], (2, s, hkv, d))
    v = jax.random.normal(keys[2], (2, s, hkv, d))
    seg = np.zeros((2, s), np.int32)
    for g, (a, b) in enumerate(zip(cuts[:-1], cuts[1:])):
        seg[0, a:b] = g + 1
    seg[1, :100], seg[1, 100:230] = 1, 2        # and a padded tail
    seg = jnp.asarray(seg)
    weight = jax.random.normal(keys[3], (2, s, h, d)) * (
        seg > 0)[:, :, None, None]             # no loss term reads padding
    return q, k, v, seg, weight


@pytest.mark.parametrize("group", [1, 4, 7])
def test_banded_flash_matches_xla_in_interpret_mode(group, monkeypatch):
    """The causal bh-layout kernels with a band of 40 over 128 x 128 tiles,
    forward and all three gradients, two documents in a row whose boundary
    (150) and whose band both cross a tile's edge, for one, four and seven
    query heads to a key/value head; under their own kernel names."""
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    fa = importlib.import_module(
        "bert_pytorch_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "DEFAULT_BLK_Q", 128)
    monkeypatch.setattr(fa, "DEFAULT_BLK_K", 128)
    q, k, v, seg, weight = _band_case(group)

    def loss(impl):
        def f(q, k, v):
            out = dot_product_attention(q, k, v, segment_ids=seg, impl=impl,
                                        causal=True, window=40)
            return jnp.sum(out * weight), out
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, got), got_grads = loss("pallas")(q, k, v)
    (_, want), want_grads = loss("xla")(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for a, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, w, atol=5e-5)
    # the band bites: plain causal attention is something else
    plain = dot_product_attention(q, k, v, segment_ids=seg, impl="xla",
                                  causal=True)
    assert float(jnp.abs(plain - want).max()) > 0.1
    text = str(jax.make_jaxpr(loss("pallas"))(q, k, v))
    for name in ("flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv"):
        assert name in text, name
    assert not re.search(r"\bflash_(fwd|bwd)", text)
    assert fa._layout(2, 256, 2 * group, 64, group, 64, 40).native is False


def test_a_band_wider_than_the_row_is_plain_causal(monkeypatch):
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    fa = importlib.import_module(
        "bert_pytorch_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "DEFAULT_BLK_Q", 128)
    monkeypatch.setattr(fa, "DEFAULT_BLK_K", 128)
    q, k, v, seg, _ = _band_case(4)
    for impl in ("pallas", "xla"):
        wide = dot_product_attention(q, k, v, segment_ids=seg, impl=impl,
                                     causal=True, window=256)
        plain = dot_product_attention(q, k, v, segment_ids=seg, impl=impl,
                                      causal=True)
        np.testing.assert_array_equal(np.asarray(wide), np.asarray(plain))
    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, k, v, segment_ids=seg, impl="xla",
                              window=40)
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(q, k, v, None, seg, None, 0.0, True, False, 40)


def test_a_tile_pair_wholly_behind_the_band_is_skipped(monkeypatch):
    """By the test every kernel makes: at S = 16,384, a band of 4,096 and
    512 x 512 tiles 252 of the causal triangle's 528 pairs hold an allowed
    pair. And in the kernel: values that are NaN in the key blocks wholly
    behind the last query block's band would poison its rows through
    0 x NaN if those tiles ran masked; skipped, its rows are clean and
    equal the XLA path's. (A band of 100 over four blocks of 128 spans two
    of them: the call takes the banded form, whose grid never reaches the
    blocks behind the band; tests/test_flash_band.py poisons every side.)"""
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    fa = importlib.import_module(
        "bert_pytorch_tpu.ops.pallas.flash_attention")
    tiles = [(qi, j) for qi in range(32) for j in range(32)]
    live = lambda w: sum(bool(fa._causal_live(  # noqa: E731
        True, qi * 512, 512, j * 512, 512, w)) for qi, j in tiles)
    assert (live(0), live(4096)) == (528, 252)
    assert live(1) == 32 and live(16384) == 528
    assert fa._causal_live(False, 0, 512, 512, 512, 4096) is None

    monkeypatch.setattr(fa, "DEFAULT_BLK_Q", 128)
    monkeypatch.setattr(fa, "DEFAULT_BLK_K", 128)
    s, window = 512, 100
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (1, s, 4, 64))
    k = jax.random.normal(keys[1], (1, s, 2, 64))
    v = jax.random.normal(keys[2], (1, s, 2, 64))
    # the last q block (rows 384..511) reaches back to 384 - 99 = 285: key
    # blocks 0 and 1 (columns 0..255) lie wholly behind its band
    poisoned = v.at[:, :256].set(jnp.nan)
    got = fa.flash_attention(q, k, poisoned, None, None, None, 0.0, True,
                             True, window)
    want = dot_product_attention(q, k, v, impl="xla", causal=True,
                                 window=window)
    assert np.isfinite(np.asarray(got[:, 384:])).all()
    np.testing.assert_allclose(got[:, 384:], want[:, 384:], atol=2e-5)
    # a tile that runs partly masked does read them
    assert np.isnan(np.asarray(got[:, 256:384])).any()


def test_softmax_over_the_selected_is_the_renormalised_full_softmax():
    """ops/moe.route with scores="softmax": the weights are the full
    softmax over all experts renormalised over the selected (`norm_topk_prob`
    changes nothing), the selection the largest logits."""
    x = jax.random.normal(jax.random.PRNGKey(2), (50, 32))
    kernel = jax.random.normal(jax.random.PRNGKey(3), (32, 16))
    with jax.default_matmul_precision("highest"):
        logits = x @ kernel
        for norm_topk in (True, False):
            routing = moe_ops.route(x, kernel, None, 6, norm_topk, 1.0,
                                    "softmax")
            full = jax.nn.softmax(logits, axis=-1)
            picked = jnp.take_along_axis(full, routing.experts, axis=-1)
            np.testing.assert_allclose(
                routing.gates, picked / picked.sum(-1, keepdims=True),
                rtol=2e-6)
            np.testing.assert_array_equal(
                np.sort(np.asarray(routing.experts), axis=-1),
                np.sort(np.asarray(jax.lax.top_k(logits, 6)[1]), axis=-1))
            np.testing.assert_allclose(routing.gates.sum(-1), 1.0, rtol=1e-6)
        experts, gates, _ = ref.route(logits, {"topk": 6})
    np.testing.assert_array_equal(np.asarray(experts),
                                  np.asarray(routing.experts))
    np.testing.assert_allclose(gates, routing.gates, rtol=2e-6)
    with pytest.raises(ValueError, match="selection bias"):
        moe_ops.route(x, kernel, jnp.zeros((16,)), 6, True, 1.0, "softmax")
    # and what the two families before it pass is what they passed
    sig = moe_ops.route(x, kernel, None, 6, True, 2.0)
    scores = jnp.take_along_axis(jax.nn.sigmoid(logits), sig.experts, -1)
    np.testing.assert_allclose(
        sig.gates, 2.0 * scores / (scores.sum(-1, keepdims=True) + 1e-6),
        rtol=2e-6)


def test_the_router_reads_the_layers_input_and_its_gradient_reaches_it(toy):
    """The layer's output moves with the router's INPUT x where the
    experts' input m is held still, and d(output)/dx has a part that goes
    through the router alone."""
    cfg, sizes, params, model, batch = toy
    lp = params["layer_1"]["moe"]
    m = jax.random.normal(jax.random.PRNGKey(4), (1, 96, 64))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 96, 64))
    experts = smallthinker.RoutedExperts(cfg, jnp.float32)

    def out(m, x):
        return experts.apply({"params": lp}, m, router_input=x)[0]

    with jax.default_matmul_precision("highest"):
        base = out(m, x)
        assert float(jnp.abs(out(m, x + 1.0) - base).max()) > 1e-4
        own = experts.apply({"params": lp}, m)[0]       # lfm2's and kimi's
        np.testing.assert_array_equal(np.asarray(own),
                                      np.asarray(out(m, m)))
        weight = jax.random.normal(jax.random.PRNGKey(6), base.shape)
        gx = jax.grad(lambda x: jnp.sum(out(m, x) * weight))(x)
    assert float(jnp.abs(gx).max()) > 1e-6
    # the reference's layer says the same of the whole layer: its router's
    # weight takes a gradient through x's logits only
    want = ref.layer_forward(x[0], params["layer_1"],
                             batch["segment_ids"][0], batch["position_ids"][0],
                             12, True, ref._Sizes(sizes))[0]
    layer = smallthinker.DecoderLayer(cfg, 12, True, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": params["layer_1"]}, x,
                          batch["segment_ids"][:1],
                          batch["position_ids"][:1])[0]
    real = np.asarray(batch["segment_ids"][0] > 0)
    np.testing.assert_allclose(np.asarray(got[0])[real],
                               np.asarray(want)[real], atol=5e-6)


@pytest.mark.parametrize("n_shares", [8, 2], ids=["8x1", "2x4"])
def test_expert_parallel_shares_add_up_to_the_whole_layer(n_shares):
    """The share ties to the model: the program's routed FFN, told which
    experts it holds, for every share of the 8 experts: the partial sums
    added up equal the UNCUT reference's layer (router fed the layer's
    input, ReLU gate, softmax over the selected)."""
    whole = dict(TOY, moe_num_primary_experts=8, experts_held=[0, 8])
    sizes = ref.sizes_from_config(whole)
    lp = ref.init_params(SEED, sizes)["layer_1"]["moe"]
    m = jax.random.normal(jax.random.PRNGKey(3), (1, 96, 64), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 96, 64), jnp.float32)
    per = 8 // n_shares
    with jax.default_matmul_precision("highest"):
        r = jnp.matmul(x[0], lp["router"])
        want, counts, _ = ref._experts(m[0], r, lp, ref._Sizes(sizes), None,
                                       0.0)
        total, loads = 0.0, []
        for lo in range(0, 8, per):
            cfg = SmallThinkerConfig.from_dict(dict(
                whole, moe_num_primary_experts=per,
                experts_held=[lo, lo + per])).replace(dtype="float32")
            share = dict(lp, **{name: lp[name][lo:lo + per] for name in
                                ("experts_w1", "experts_w3", "experts_w2")})
            out, load, dropped = smallthinker.RoutedExperts(
                cfg, jnp.float32).apply({"params": share}, m,
                                        router_input=x)
            assert int(dropped) == 0
            total = total + out[0]
            loads += np.asarray(load).tolist()
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-6)
    assert loads == np.asarray(counts).tolist() and sum(loads) == 96 * 3


@pytest.mark.parametrize("window", [0, 12], ids=["full", "band"])
def test_no_leak_across_a_document_boundary(toy, window):
    """Changing the tokens of a row's second document moves nothing in the
    documents before and after it, to the bit, in either kind of layer: the
    band counts tokens of the query's own document."""
    cfg, sizes, params, model, batch = toy
    lp = params["layer_1" if window else "layer_0"]["attention"]
    seg, pos = batch["segment_ids"][:1], batch["position_ids"][:1]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 96, 64))
    other = x.at[:, 9:70].add(1.0)          # the second document
    run = lambda a: smallthinker.Attention(  # noqa: E731
        cfg, window, bool(window), jnp.float32).apply(
            {"params": lp}, a, seg, pos)
    a, b = run(x), run(other)
    assert float(jnp.abs(a - b)[:, 9:70].max()) > 1e-3
    assert float(jnp.abs(a - b)[:, :9].max()) == 0.0
    assert float(jnp.abs(a - b)[:, 70:].max()) == 0.0


def test_model_config_family_selection(tmp_path):
    def write(d):
        p = tmp_path / f"cfg{len(list(tmp_path.iterdir()))}.json"
        p.write_text(json.dumps(d))
        return str(p)

    cfg = load_model_config(write(dict(
        TOY, source="s", reduced={}, assumed={}, layout="l")))
    assert isinstance(cfg, SmallThinkerConfig)
    assert cfg.num_experts_per_tok == 3 and cfg.router_width == 8
    assert (cfg.router_scores, cfg.expert_activation) == ("softmax", "relu")
    assert not cfg.use_expert_bias and cfg.held_range == (2, 6)
    with pytest.raises(ValueError, match="conv_L_cache"):
        load_model_config(write(dict(TOY, conv_L_cache=3)))
    with pytest.raises(ValueError, match="one entry"):
        load_model_config(write(dict(TOY, rope_layout=[0, 1, 1])))
    with pytest.raises(NotImplementedError, match="apply_softmax"):
        load_model_config(write(dict(
            TOY, moe_primary_router_apply_softmax=False)))
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        load_model_config(write(dict(TOY, rope_scaling={"factor": 2})))
    with pytest.raises(ValueError, match="experts_held"):
        load_model_config(write(dict(TOY, experts_held=[6, 10])))
    # the benchmark's configuration is one the program reads: every width
    # as published, two whole periods, an eighth of the experts and rows
    real = load_model_config(os.path.join(
        ROOT, "benchmark", "configs", "smallthinker-21b-a3b-ep8.json"))
    assert real.layer_kinds == ((0, False), (4096, True), (4096, True),
                                (4096, True)) * 2
    assert real.held_range == (0, 8) and real.router_width == 64
    assert (real.hidden_size, real.num_attention_heads,
            real.num_key_value_heads, real.head_dim,
            real.moe_ffn_hidden_size, real.num_experts_per_tok) == (
                2560, 28, 4, 128, 768, 6)
    assert real.vocab_size * 8 == 151936
    # the other decoder families pass what they passed
    for name in ("lfm2-24b-a2b-ep8", "kimi-linear-48b-a3b-ep32"):
        other = load_model_config(os.path.join(
            ROOT, "benchmark", "configs", name + ".json"))
        assert (other.router_scores, other.expert_activation) == (
            "sigmoid", "silu")


def _shards(tmp_path, n=96, s=128):
    from benchmark.harness import corpus

    d = str(tmp_path / "data")
    corpus.write_shards(d, {"samples": n, "shards": 2, "lengths": {
        "kind": "lognormal", "median": 30, "sigma": 0.8, "min": 16,
        "max": s}}, s, 2048, 11)
    return d


def test_entry_point_trains_the_family_and_counts_its_work(tmp_path):
    import run_pretraining

    cfg_path = tmp_path / "toy.json"
    cfg_path.write_text(json.dumps(TOY))
    out = tmp_path / "out"
    run_pretraining.main([
        "--model_config_file", str(cfg_path), "--input_dir",
        _shards(tmp_path), "--output_dir", str(out), "--learning_rate",
        "0.004", "--warmup_proportion", "0.1", "--max_steps", "100",
        "--steps", "3", "--global_batch_size", "4", "--local_batch_size",
        "2", "--skip_checkpoint", "--log_freq", "1", "--tensorboard", "off",
        "--packing", "--packing_max_segments", "16", "--packing_lookahead",
        "8", "--checkpoint_activations", "--dtype", "float32"])
    records = [json.loads(ln) for ln in
               (out / "logfile.jsonl").read_text().splitlines()]
    train = [r for r in records if r.get("tag") == "train"]
    perf = [r for r in records if r.get("tag") == "perf"]
    assert len(train) == 3 and all(
        abs(r["step_loss"] - np.log(2048)) < 0.4 for r in train)
    last = perf[-1]
    # (token, expert) pairs of whole rows of 128 slots at 3 experts a token
    assert train[0]["moe_pairs_routed"] % (128 * 3) == 0
    # the record of step n counts through step n - 1
    for layer in range(4):
        assert last[f"moe_l{layer}_dropped"] == 0
        assert last[f"moe_l{layer}_pairs"] == sum(
            r[f"moe_l{layer}_e{e}"] for r in train[:-1] for e in range(4))
        assert 0 < last[f"moe_l{layer}_held_share"] < 1
    cfg = load_model_config(str(cfg_path))
    mine = smallthinker.train_flops_per_row(cfg, 128)
    assert last["model_flops_per_sec"] / last["seq_per_sec"] == \
        pytest.approx(mine, rel=1e-3)
    # a full layer's pairs and three banded layers'
    assert smallthinker.band_pairs(128, 0) == 128 * 129 // 2
    assert smallthinker.band_pairs(128, 12) == 78 + 116 * 12
    # the decoder families' one refusal names every one of them
    with pytest.raises(SystemExit, match="'kimi_linear', 'smallthinker'"):
        run_pretraining.main([
            "--model_config_file", str(cfg_path), "--input_dir",
            str(tmp_path / "data"), "--output_dir", str(out), "--kfac"])


def test_the_step_carries_both_attention_scopes_and_both_kernel_sets(
        toy, monkeypatch):
    """Every instruction of the compiled step sits under an entry of the
    benchmark's unscoped_share.smallthinker.train list; the two kinds of
    attention layer are told apart by scope (`attention/attention_window`,
    `attention/attention_full`, both under `attention`) and by kernel name;
    the experts' four scopes, the norms, the head and the loss are there."""
    import run_pretraining
    from bert_pytorch_tpu.optim import schedulers
    from bert_pytorch_tpu.training import build_pretrain_step
    from bert_pytorch_tpu.training.pretrain import LM_STEP_SCOPES
    from bert_pytorch_tpu.training.state import TrainState

    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           "unscoped_share.smallthinker.train.json")) as f:
        scopes = json.load(f)["args"]["scopes"]
    # the program's list, without the mixers this family has none of, plus
    # its two sub-scopes
    assert [s for s in scopes if not s.startswith("attention_")] == [
        s for s in LM_STEP_SCOPES if s not in ("kda", "conv", "mlp")]
    cfg, sizes, params, _, _ = toy
    model = smallthinker.SmallThinkerForCausalLM(
        cfg.replace(attention_impl="pallas"), dtype=jnp.float32)
    ids, seg, pos = _packed(s=128)
    batch = {"input_ids": jnp.asarray(ids), "segment_ids": jnp.asarray(seg),
             "position_ids": jnp.asarray(pos)}
    schedule = schedulers.make_schedule("poly", 0.004, 100, warmup=0.1)
    tx = run_pretraining.make_optimizer("lamb", schedule)
    state = TrainState(step=jnp.zeros([], jnp.int32), params=params,
                       opt_state=tx.init(params))
    step = build_pretrain_step(
        model, tx, schedule=schedule, accum_steps=2,
        grad_dtype=jnp.bfloat16,
        loss_fn_builder=smallthinker.pretrain_loss_fn_builder,
        keep_float32=smallthinker.keep_float32)
    stacked = {k: jnp.stack([v, v]) for k, v in batch.items()}
    text = jax.jit(step).lower(state, stacked,
                               jax.random.PRNGKey(0)).compile().as_text()
    known = re.compile(r"(?:^|[/(])(?:" + "|".join(map(re.escape, scopes))
                       + r")\)*(?:/|$)")
    names = {op.group(1) for op in re.finditer(r'op_name="([^"]*)"', text)
             if op.group(1).startswith("jit(")}
    assert not [n for n in names if not known.search(n)][:5]
    for scope in ("attention/attention_window", "attention/attention_full",
                  "moe/router", "moe/dispatch", "moe/experts", "moe/combine",
                  "rmsnorm", "lm_head", "loss", "optimizer", "param_cast",
                  "grad_accum"):
        parts = r"\)*/".join(map(re.escape, scope.split("/")))
        assert any(re.search(rf"[/(]{parts}\)*(?:/|$)", n) for n in names), \
            scope
    for kernel in ("flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv",
                   "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert any(re.search(rf"[/(]{kernel}\)*/", n) for n in names), kernel
    # the banded kernels under the windowed layers' scope, the plain ones
    # under the full layers'
    assert all("attention_window" in n for n in names if "flash_win_" in n)
    assert all("attention_full" in n for n in names
               if re.search(r"[/(]flash_(fwd|bwd)", n))
    # and every kernel, forward and backward (custom_vjp rules), under the
    # core's scope inside its layer's (STEP_SUBSCOPES)
    for kind in ("window", "full"):
        core = re.compile(rf"[/(]attention/attention_{kind}/attn_core\)*/")
        kernels = [n for n in names if re.search(r"[/(]flash_", n)
                   and f"attention_{kind}" in n]
        assert kernels and all(core.search(n) for n in kernels)
        assert any("transpose(" in n for n in kernels)
    assert smallthinker.keep_float32((jax.tree_util.DictKey("router"),))
    assert not smallthinker.keep_float32((jax.tree_util.DictKey("q_proj"),))
