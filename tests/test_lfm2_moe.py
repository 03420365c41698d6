"""The lfm2_moe family (models/lfm2_moe.py, ops/moe.py, ops/decoder_ops.py,
the causal grouped-head flash kernels) against its plain reference
(benchmark/reference/lfm2_moe_ref.py), on the CPU at toy widths with seeded
weights: forward, loss, gradients and one LAMB step over packed rows for a
stack that holds every kind of layer; the expert-parallel shares of one
routed layer adding up to the whole layer; a router that sends every token
to one held expert; no leak across a document boundary; the kernels against
the XLA path in interpret mode; the entry point's family selection."""

import importlib
import json
import os
import sys

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import adapter, lm_adapter  # noqa: E402
from benchmark.reference import lfm2_moe_ref as ref  # noqa: E402
from bert_pytorch_tpu.config import (BertConfig, Lfm2MoeConfig,  # noqa: E402
                                     load_model_config)
from bert_pytorch_tpu.models import decoder, lfm2_moe  # noqa: E402
from bert_pytorch_tpu.ops import moe as moe_ops  # noqa: E402
from bert_pytorch_tpu.ops.attention import dot_product_attention  # noqa: E402
from bert_pytorch_tpu.ops.decoder_ops import short_conv  # noqa: E402

TOY = {
    "model_type": "lfm2_moe", "vocab_size": 2048, "hidden_size": 64,
    "intermediate_size": 160, "moe_intermediate_size": 32,
    "num_hidden_layers": 4, "num_dense_layers": 1, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_experts": 4, "num_experts_per_tok": 2,
    "experts_total": 8, "experts_held": [2, 6],
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv"],
    "layers_kept": [0, 2, 3, 4], "conv_L_cache": 3, "conv_bias": False,
    "norm_eps": 1e-5, "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "max_position_embeddings": 128000,
}
SEED = 2 ** 31 + 5


def _packed(rows=2, s=128, vocab=2048, seed=0):
    """Two packed rows: three documents and a padded tail; one document."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(10, vocab, (rows, s)).astype(np.int32)
    seg = np.zeros((rows, s), np.int32)
    pos = np.zeros((rows, s), np.int32)
    for r, cuts in enumerate([[0, 40, 97, 120], [0, s]][:rows]):
        for g, (a, b) in enumerate(zip(cuts[:-1], cuts[1:])):
            seg[r, a:b] = g + 1
            pos[r, a:b] = np.arange(b - a)
    return ids * (seg > 0), seg, pos


@pytest.fixture(scope="module")
def toy():
    cfg = Lfm2MoeConfig.from_dict(TOY).replace(
        dtype="float32", checkpoint_activations=True)
    assert cfg.layer_kinds == (("conv", "dense"), ("attention", "moe"),
                               ("conv", "moe"), ("conv", "moe"))
    sizes = ref.sizes_from_config(TOY)
    params = ref.init_params(SEED, sizes)
    model = lfm2_moe.Lfm2MoeForCausalLM(cfg, dtype=jnp.float32)
    ids, seg, pos = _packed()
    batch = {"input_ids": jnp.asarray(ids), "segment_ids": jnp.asarray(seg),
             "position_ids": jnp.asarray(pos)}
    return cfg, sizes, params, model, batch


def test_parameter_tree_is_the_adapters(toy):
    cfg, sizes, params, model, batch = toy
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), *decoder.init_inputs(batch)))["params"]
    ours = lm_adapter.to_program_tree(params)
    assert jax.tree.structure(shapes) == jax.tree.structure(ours)
    assert all(a.shape == b.shape for a, b in zip(jax.tree.leaves(shapes),
                                                  jax.tree.leaves(ours)))


def test_forward_matches_the_reference(toy):
    cfg, sizes, params, model, batch = toy
    with jax.default_matmul_precision("highest"):
        logits, load, dropped = model.apply(
            {"params": lm_adapter.to_program_tree(params)},
            batch["input_ids"], batch["segment_ids"], batch["position_ids"])
        for r in range(2):
            want, counts, _ = ref.row_forward(
                params, batch["input_ids"][r], batch["segment_ids"][r],
                ref._Sizes(sizes))
            real = np.asarray(batch["segment_ids"][r]) > 0
            np.testing.assert_allclose(np.asarray(logits[r])[real],
                                       np.asarray(want)[real], atol=2e-5)
    assert int(jnp.sum(dropped)) == 0 and load.shape == (3, 4)


def _program_loss_and_grad(toy):
    cfg, sizes, params, model, batch = toy
    loss_fn = lfm2_moe.pretrain_loss_fn_builder(model)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn, has_aux=True)(
            lm_adapter.to_program_tree(params), batch, None)


def _reference_step(toy):
    cfg, sizes, params, model, batch = toy
    micro = {k: batch[k] for k in ("input_ids", "segment_ids")}
    return ref.step_loss_and_grad(params, [micro], sizes, tie_tol=1e-4)


def test_loss_and_expert_counts_match_the_reference(toy):
    (loss, aux), _ = _program_loss_and_grad(toy)
    want, _, counts, ties = _reference_step(toy)
    assert abs(float(loss) - float(want)) < 1e-5
    assert abs(float(want) - np.log(2048)) < 0.2
    got = [[int(aux["scalars"][f"moe_l{layer}_e{e}"]) for e in range(4)]
           for layer in range(3)]
    assert got == np.asarray(counts).tolist()
    assert int(aux["scalars"]["moe_pairs_routed"]) == 2 * 128 * 2
    assert all(int(aux["scalars"][f"moe_l{i}_dropped"]) == 0
               for i in range(3))


def test_gradients_match_the_reference_leaf_by_leaf(toy):
    (_, _), grads = _program_loss_and_grad(toy)
    _, want, _, _ = _reference_step(toy)
    want = lm_adapter.to_program_tree(want)
    norms = lm_adapter.leaf_norms(want)
    diff = lm_adapter.leaf_diff_norms(grads, want)
    worst = max(float(np.max(diff[k] / np.maximum(norms[k], 1e-12)))
                for k in diff if float(np.max(norms[k])) > 0)
    assert worst < 2e-5
    # the selection bias takes no gradient on either side
    key = "['layer_1']['moe']['expert_bias']"
    assert float(norms[key][0]) == 0.0 and float(
        lm_adapter.leaf_norms(grads)[key][0]) == 0.0


def test_one_lamb_step_matches_the_reference(toy):
    import run_pretraining
    from bert_pytorch_tpu.optim import schedulers

    cfg, sizes, params, model, batch = toy
    (_, _), grads = _program_loss_and_grad(toy)
    _, rgrads, _, _ = _reference_step(toy)
    schedule = schedulers.make_schedule("poly", 0.004, 100, warmup=0.0)
    tx = run_pretraining.make_optimizer("lamb", schedule)
    ours = lm_adapter.to_program_tree(params)
    updates, _ = tx.update(grads, tx.init(ours), ours)
    new = jax.tree.map(jnp.add, ours, updates)
    want, _ = ref.lamb_step(params, rgrads, ref.lamb_init(params), 0.004,
                            100, 0.0)
    want = lm_adapter.to_program_tree(want)
    moved = lm_adapter.leaf_diff_norms(want, ours)
    diff = lm_adapter.leaf_diff_norms(new, want)
    for k in diff:
        assert np.all(diff[k] <= 1e-4 * np.maximum(moved[k], 1e-9) + 1e-9), k
    # per-expert trust ratios, a bias that does not move, gains not decayed
    assert moved["['layer_1']['moe']['experts_w1']"].shape == (4,)
    assert float(moved["['layer_1']['moe']['expert_bias']"][0]) == 0.0
    assert float(lm_adapter.leaf_diff_norms(new, ours)
                 ["['layer_1']['moe']['expert_bias']"][0]) == 0.0


@pytest.mark.parametrize("n_shares", [8, 2], ids=["8x1", "2x4"])
def test_expert_parallel_shares_add_up_to_the_whole_layer(n_shares):
    """The program's routed layer, told which experts it holds, for every
    share of the 8 experts: the shares' partial sums add up to what the
    UNCUT reference gives for the whole layer."""
    whole = dict(TOY, num_experts=8, experts_held=[0, 8])
    sizes = ref.sizes_from_config(whole)
    lp = ref.init_params(SEED, sizes)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(3), (96, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, counts, _ = ref._experts(x, lp, ref._Sizes(sizes), None, 0.0)
        routing = moe_ops.route(x, lp["wg"], lp["b"], 2, True, 1.0)
        per = 8 // n_shares
        total, loads = 0.0, []
        for lo in range(0, 8, per):
            out, load, dropped = moe_ops.held_experts(
                x, routing, lp["ew1"][lo:lo + per], lp["ew3"][lo:lo + per],
                lp["ew2"][lo:lo + per], (lo, lo + per), 64)
            assert int(dropped) == 0
            total = total + out
            loads += np.asarray(load).tolist()
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-6)
    assert loads == np.asarray(counts).tolist() and sum(loads) == 96 * 2


@pytest.mark.parametrize("both_held", [False, True])
def test_every_token_to_one_held_expert_drops_nothing(both_held):
    """A router that sends every token to held expert 5 (and, `both_held`,
    its second choice to held expert 4): four times the even load, across
    several windows; every pair is computed."""
    t, e = 512, 64
    sizes = ref.sizes_from_config(dict(TOY, experts_total=16))
    lp = ref.init_params(SEED, sizes)["layers"][1]
    bias = jnp.zeros((16,)).at[5].set(10.0)
    if both_held:
        bias = bias.at[4].set(5.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (t, e), jnp.float32)
    routing = moe_ops.route(x, lp["wg"], bias, 2, True, 1.0)
    assert np.all(np.asarray(routing.experts)[:, 0] == 5)
    with jax.default_matmul_precision("highest"):
        out, load, dropped = jax.jit(
            lambda x, r: moe_ops.held_experts(
                x, r, lp["ew1"], lp["ew3"], lp["ew2"], (2, 6), 256))(
                    x, routing)
        want = jnp.zeros_like(x)
        for held in range(2, 6):
            gate = jnp.sum(jnp.where(routing.experts == held, routing.gates,
                                     0.0), -1)
            want = want + gate[:, None] * ref._swiglu(
                x, lp["ew1"][held - 2], lp["ew3"][held - 2],
                lp["ew2"][held - 2], None)
    assert int(dropped) == 0
    assert int(load[3]) == t                       # expert 5
    assert int(jnp.sum(load)) >= (2 * t if both_held else t)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)


def _loop_as_it_was(x, routing, w1, w3, w2, held, window_rows,
                    activation="silu"):
    """ops/moe.held_experts before its loop had a rule of its own, kept here
    as the statement the rule is held to: a `lax.scan` over ALL windows, a
    skipped one through the untaken branch of a `lax.cond`, each window
    rematerialised, and reverse mode left to JAX (which hands back zeros of
    the weights' size from every skipped window and sums them)."""
    lo, hi = held
    n_held = hi - lo
    t, k = routing.experts.shape
    pairs = t * k
    window_rows = min(int(window_rows), pairs)
    n_windows = -(-pairs // window_rows)
    expert = routing.experts.reshape(-1)
    local = jnp.where((expert >= lo) & (expert < hi), expert - lo, n_held)
    order = jnp.argsort(local, stable=True)
    sizes = jnp.sum(local[:, None] == jnp.arange(n_held)[None, :], axis=0,
                    dtype=jnp.int32)
    n_pairs = jnp.sum(sizes)
    pad = n_windows * window_rows - pairs
    tokens = jnp.pad((order // k).astype(jnp.int32), (0, pad))
    gates = jnp.pad(routing.gates.reshape(-1)[order], (0, pad))

    @jax.checkpoint
    def window(out, done, x, w1, w3, w2, tokens, gates, start):
        return jax.lax.cond(
            start < n_pairs,
            lambda: moe_ops._window(out, done, x, w1, w3, w2, tokens, gates,
                                    sizes, n_pairs, start, activation),
            lambda: (out, done))

    (out, done), _ = jax.lax.scan(
        lambda carry, inp: (window(*carry, x, w1, w3, w2, *inp), None),
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros([], jnp.int32)),
        (tokens.reshape(n_windows, window_rows),
         gates.reshape(n_windows, window_rows),
         jnp.arange(n_windows, dtype=jnp.int32) * window_rows))
    return out, sizes, n_pairs - done


# held experts 2..5 of 16, 256 tokens x 2 selections in windows of 128 rows:
# the selection bias says where the tokens go, and so how many windows live
_LOADS = {"even": ({}, 1), "one_held_expert": ({5: 10.0, 4: 5.0}, 4),
          "none_held": ({0: 10.0, 1: 5.0}, 1)}


def _routed_case(load, dtype=jnp.float32):
    t, e, window_rows = 256, 64, 128
    sizes = ref.sizes_from_config(dict(TOY, experts_total=16))
    lp = ref.init_params(SEED, sizes)["layers"][1]
    bias = jnp.zeros((16,))
    for expert, value in _LOADS[load][0].items():
        bias = bias.at[expert].set(value)
    x = jax.random.normal(jax.random.PRNGKey(1), (t, e), jnp.float32)
    weight = jnp.cos(jnp.arange(t * e, dtype=jnp.float32)).reshape(t, e)

    def loss(held_experts, activation, x, w1, w3, w2, kernel):
        routing = moe_ops.route(x, kernel, bias, 2, True, 1.0)
        out, load, dropped = held_experts(
            x.astype(dtype), routing, w1.astype(dtype), w3.astype(dtype),
            w2.astype(dtype), (2, 6), window_rows, activation)
        # through the gates too: the router's kernel takes their cotangent
        return jnp.sum(out * weight), (load, dropped)

    return loss, (x, lp["ew1"], lp["ew3"], lp["ew2"], lp["wg"]), window_rows


@pytest.mark.parametrize("activation", ["silu", "relu"])
@pytest.mark.parametrize("load", sorted(_LOADS))
def test_the_loops_rule_gives_the_gradients_of_the_loop_as_it_was(
        load, activation):
    """Value and gradients (x, w1, w3, w2 and, through the gates, the
    router's kernel) of held_experts, whose loop over windows has a
    hand-written rule, against the plain statement of that loop: bit for
    bit, with one live window and with several (the rule adds the live
    windows in the order reverse mode did, and the zeros of the skipped ones
    added nothing)."""
    loss, args, window_rows = _routed_case(load)
    with jax.default_matmul_precision("highest"):
        (got, (held, dropped)), g_got = jax.jit(jax.value_and_grad(
            lambda *a: loss(moe_ops.held_experts, activation, *a),
            argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
        (want, _), g_want = jax.jit(jax.value_and_grad(
            lambda *a: loss(_loop_as_it_was, activation, *a),
            argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    live = int(moe_ops.live_windows(jnp.sum(held), window_rows))
    assert live == _LOADS[load][1] and int(dropped) == 0
    assert (int(jnp.sum(held)) == 0) == (load == "none_held")
    assert float(got) == float(want)
    for name, a, b in zip(("x", "w1", "w3", "w2", "router"), g_got, g_want):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
        assert load == "none_held" or float(jnp.max(jnp.abs(a))) > 0, name


def _loops(jaxpr, found=None):
    """Every `while` equation of a jaxpr, nested ones too."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _loops(sub, found)
    return found


def _zero_fills(jaxpr, found=None):
    """Shapes of the arrays a jaxpr (and what it calls) fills with one
    value."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "broadcast_in_dim"
                and not eqn.invars[0].aval.shape):
            found.append(tuple(eqn.outvars[0].aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _zero_fills(sub, found)
    return found


def test_the_backward_loop_is_bound_by_data_and_fills_no_weights():
    """The differentiated layer is two loops (the windows forward, the
    earlier live windows backward), each bound by a value computed from the
    routing and not by a constant; no `cond` is left, so no window is
    walked that computes nothing; and the backward loop's body adds into
    its carry, under `moe/accumulate`, without filling an array of the
    weights' shape."""
    loss, args, _ = _routed_case("even", jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: loss(moe_ops.held_experts, "silu", *a)[0],
        argnums=(0, 1, 2, 3, 4)))(*args).jaxpr
    text = str(jaxpr)
    assert " cond[" not in text and "scan[" not in text
    loops = _loops(jaxpr)
    assert len(loops) == 2
    for eqn in loops:
        # fori_loop(lo, hi): the bound is among the loop's operands; a
        # bound known when tracing would sit in cond_jaxpr as a literal
        cond = eqn.params["cond_jaxpr"].jaxpr
        (lt,) = [e for e in cond.eqns if e.primitive.name == "lt"]
        assert not any(isinstance(v, jax.extend.core.Literal) for v in lt.invars)
    backward = loops[-1].params["body_jaxpr"].jaxpr
    sums = [e.primitive.name for e in backward.eqns
            if str(e.source_info.name_stack) == "moe/accumulate"]
    # x, w1, w3, w2 added, the window's gates written to their rows
    assert sums.count("add") >= 4 and "dynamic_update_slice" in sums
    # (the fills there are: a live window's own, of its rows and of the
    # tokens' shape: the base of its scatter of x's cotangent, as before)
    w1, w2 = args[1].shape, args[3].shape
    fills = _zero_fills(backward)
    assert fills and not [s for s in fills if s in (w1, w2)]
    # what the loop as it was made of the same layer: zeros of the weights'
    # shape out of every skipped window
    old = jax.make_jaxpr(jax.grad(
        lambda *a: loss(_loop_as_it_was, "silu", *a)[0],
        argnums=(0, 1, 2, 3, 4)))(*args).jaxpr
    assert [s for s in _zero_fills(old) if s in (w1, w2)]


def _two_documents(s=256, cut=100):
    seg = np.ones((1, s), np.int32)
    seg[0, cut:] = 2
    pos = np.concatenate([np.arange(cut), np.arange(s - cut)])[None, :]
    return jnp.asarray(seg), jnp.asarray(pos.astype(np.int32)), cut


@pytest.mark.parametrize("operator", ["conv", "attention-xla",
                                      "attention-flash"])
def test_no_leak_across_a_document_boundary(operator, monkeypatch):
    """Perturb document A: document B's outputs, and the gradients of a
    loss over B with respect to B's inputs, are bit-equal."""
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    seg, pos, cut = _two_documents()
    s = seg.shape[1]
    kx, kw, kp = jax.random.split(jax.random.PRNGKey(7), 3)
    if operator == "conv":
        x = jax.random.normal(kx, (1, s, 32), jnp.float32)
        w = jax.random.normal(kw, (32, 3), jnp.float32)

        def f(x):
            return short_conv(x, w, pos)
    else:
        x = jax.random.normal(kx, (1, s, 4 * 64), jnp.float32)
        impl = "xla" if operator.endswith("xla") else "pallas"

        def f(x):
            q = x.reshape(1, s, 4, 64)
            k = q[:, :, :2] * 0.5 + 0.1
            return dot_product_attention(
                q, k, q[:, :, 2:], segment_ids=seg, impl=impl,
                causal=True).reshape(1, s, -1)

    weight = jax.random.normal(kp, f(x).shape, jnp.float32)

    def loss_b(x):
        out = f(x)
        return jnp.sum((out * weight)[:, cut:]), out

    x2 = x.at[:, :cut].add(jax.random.normal(kp, x[:, :cut].shape))
    (_, out1), g1 = jax.value_and_grad(loss_b, has_aux=True)(x)
    (_, out2), g2 = jax.value_and_grad(loss_b, has_aux=True)(x2)
    assert np.array_equal(np.asarray(out1[:, cut:]), np.asarray(out2[:, cut:]))
    assert np.array_equal(np.asarray(g1[:, cut:]), np.asarray(g2[:, cut:]))
    assert not np.any(np.asarray(g1[:, :cut]))      # and B ignores A
    assert not np.array_equal(np.asarray(out1[:, :cut]),
                              np.asarray(out2[:, :cut]))


@pytest.mark.parametrize("s,h,hkv,segments,split", [
    (256, 4, 2, True, False), (256, 4, 1, False, False),
    (256, 4, 2, True, True), (384, 8, 2, True, True),
    (256, 4, 4, True, False), (768, 4, 2, True, True),
], ids=["one-tile-gqa2-packed", "one-tile-mqa", "split-gqa2-packed",
        "split-gqa4-packed", "fused-mha-packed",
        "split-blocks-of-128-with-unmasked-interior-tiles"])
def test_causal_grouped_flash_matches_xla_in_interpret_mode(
        s, h, hkv, segments, split, monkeypatch):
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    fa = importlib.import_module(
        "bert_pytorch_tpu.ops.pallas.flash_attention")
    # grouped heads take the split kernels at any length (a program owns a
    # key/value head's query heads); `split`: S x D over the fused
    # backward's bound, as long sequences are
    if split:
        monkeypatch.setattr(fa, "_FUSED_BWD_MAX_PANEL", 128 * 64)
    if s == 768:    # 6 x 6 tiles: documents long enough to hold whole tiles
        monkeypatch.setattr(fa, "DEFAULT_BLK_Q", 128)
        monkeypatch.setattr(fa, "DEFAULT_BLK_K", 128)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    b, d = 2, 64
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
    w = jax.random.normal(ks[3], (b, s, h, d), jnp.float32)
    seg = None
    if segments:
        rows = np.zeros((b, s), np.int32)
        rows[0, :s // 3] = 1
        rows[0, s // 3:s - 17] = 2
        rows[1, :s // 2 + 5] = 1
        rows[1, s // 2 + 5:] = 2
        seg = jnp.asarray(rows)
        w = w * (seg > 0)[:, :, None, None]

    def run(impl):
        def f(q, k, v):
            out = dot_product_attention(q, k, v, segment_ids=seg, impl=impl,
                                        causal=True)
            return jnp.sum(out * w), out
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    (_, out_x), grads_x = run("xla")
    (_, out_p), grads_p = run("pallas")
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                               atol=2e-6)
    for got, want in zip(grads_p, grads_x):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
    # causal: the first token of a row attends to itself alone
    np.testing.assert_allclose(
        np.asarray(out_p[:, 0]),
        np.asarray(jnp.repeat(v[:, 0], h // hkv, axis=1)), atol=1e-6)


def test_model_config_family_selection(tmp_path):
    def write(d):
        path = tmp_path / "cfg.json"
        path.write_text(json.dumps(d))
        return str(path)

    assert isinstance(load_model_config(write(TOY)), Lfm2MoeConfig)
    bert = {"hidden_size": 64, "num_attention_heads": 2,
            "num_hidden_layers": 2, "intermediate_size": 128,
            "vocab_size": 2048, "source": "x", "reduced": [],
            "assumed": {}, "layout": "whole"}
    assert isinstance(load_model_config(write(bert)), BertConfig)
    with pytest.raises(ValueError, match="unknown model_type 'mamba'"):
        load_model_config(write(dict(bert, model_type="mamba")))
    # another architecture's keys are not trimmed into a BERT
    with pytest.raises(ValueError, match="moe_intermediate_size"):
        load_model_config(write(dict(bert, moe_intermediate_size=32)))
    with pytest.raises(ValueError, match="num_shared_experts"):
        load_model_config(write(dict(TOY, num_shared_experts=1)))
    with pytest.raises(ValueError, match="experts_held"):
        load_model_config(write(dict(TOY, experts_held=[2, 7])))


def _shards(tmp_path, n=96, s=128):
    from benchmark.harness import corpus

    d = str(tmp_path / "data")
    corpus.write_shards(d, {"samples": n, "shards": 2, "lengths": {
        "kind": "lognormal", "median": 30, "sigma": 0.8, "min": 16,
        "max": s}}, s, 2048, 11)
    return d


def test_loader_yields_ids_segments_positions_without_masking(tmp_path):
    from pathlib import Path

    import h5py

    from bert_pytorch_tpu.data.sharded import (
        CLM_FIELDS, HostShardSampler, PretrainingDataLoader, ShardIndex)

    d = _shards(tmp_path)
    files = sorted(str(p) for p in Path(d).rglob("*.hdf5"))
    index = ShardIndex(files)
    loader = PretrainingDataLoader(
        index, HostShardSampler(len(index), world_size=1, rank=0, seed=3),
        batch_size=4, mask_token_index=103, max_pred_per_seq=20,
        masked_lm_prob=0.15, vocab_size=2048, seed=3, packing=True,
        packing_max_segments=16, packing_lookahead=8, objective="clm")
    batch = next(iter(loader))
    loader.close()
    assert sorted(batch) == sorted(CLM_FIELDS)
    seg, pos, ids = (batch[k] for k in ("segment_ids", "position_ids",
                                        "input_ids"))
    assert np.array_equal(batch["attention_mask"], (seg > 0).astype(np.int32))
    starts = np.diff(seg, axis=1, prepend=0) != 0
    assert np.all(pos[starts & (seg > 0)] == 0)
    assert (seg > 0).mean() > 0.8
    with h5py.File(files[0]) as f:
        source = {tuple(r[r > 0][:8]) for r in f["input_ids"][:]}
    with h5py.File(files[1]) as f:
        source |= {tuple(r[r > 0][:8]) for r in f["input_ids"][:]}
    first = ids[0][seg[0] == 1][:8]
    assert tuple(first) in source and 103 not in ids    # nothing masked


def test_entry_point_trains_the_family_and_counts_expert_load(tmp_path):
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
        abs(r["step_loss"] - np.log(2048)) < 0.3 for r in train)
    last = perf[-1]
    assert last["remat_saves_dense"] == 1
    for layer in range(3):
        assert last[f"moe_l{layer}_dropped"] == 0
        assert last[f"moe_l{layer}_pairs"] == sum(
            r[f"moe_l{layer}_e{e}"] for r in train[:-1] for e in range(4))
        assert 0.2 < last[f"moe_l{layer}_held_share"] < 0.8     # 4 of 8
        # half the experts held: a window is all the pairs, so the loop
        # runs it once a layer pass, whatever the routing
        windows = [r[f"moe_l{layer}_windows"] for r in train[:-1]]
        assert last[f"moe_l{layer}_windows"] == sum(windows)
        assert windows[0] >= 1 and set(windows) == {windows[0]}
        assert (last[f"moe_l{layer}_load_min"]
                <= last[f"moe_l{layer}_load_mean"]
                <= last[f"moe_l{layer}_load_max"])
    # the FLOPs of the MFU line are the family's, never BERT's formula
    from bert_pytorch_tpu.telemetry import flops_per_seq

    cfg = load_model_config(str(cfg_path))
    mine = lfm2_moe.train_flops_per_row(cfg, 128)
    assert mine == pytest.approx(
        6 * (2048 * 64 + 3 * 4 * 64 * 64 + 64 * 64 * 2 + 2 * 64 * 32 * 1
             + 3 * 64 * 160 + 3 * (64 * 8 + 3 * 64 * 32 * 2 * 4 / 8)) * 128
        + 6 * 4 * 16 * 128 * 128)
    assert flops_per_seq(cfg, 128, 2048, 20) != pytest.approx(mine, rel=0.01)
    assert last["model_flops_per_sec"] / last["seq_per_sec"] == \
        pytest.approx(mine, rel=1e-3)


def test_entry_point_refuses_an_unknown_family(tmp_path):
    import run_pretraining

    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(dict(TOY, model_type="retnet")))
    with pytest.raises(SystemExit, match="unknown model_type 'retnet'"):
        run_pretraining.main(["--model_config_file", str(cfg_path),
                              "--input_dir", str(tmp_path), "--output_dir",
                              str(tmp_path / "out"), "--tensorboard", "off"])


def test_every_instruction_of_the_step_is_under_an_lm_scope(toy):
    """LM_STEP_SCOPES accounts for the compiled decoder step as STEP_SCOPES
    does for BERT's, and the benchmark's unscoped_share.lm.train carries a
    copy of the list."""
    import re

    import run_pretraining
    from bert_pytorch_tpu.optim import schedulers
    from bert_pytorch_tpu.telemetry import HealthConfig, init_telemetry_state
    from bert_pytorch_tpu.training import build_pretrain_step
    from bert_pytorch_tpu.training.pretrain import (LM_STEP_SCOPES,
                                                    STEP_SCOPES, step_scope)
    from bert_pytorch_tpu.training.state import TrainState

    # lfm2's copy is the list without `kda`, which its model never opens
    # (the whole list: unscoped_share.kimi.train, tests/test_kimi_linear.py)
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           "unscoped_share.lm.train.json")) as f:
        assert tuple(json.load(f)["args"]["scopes"]) == tuple(
            s for s in LM_STEP_SCOPES if s != "kda")
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           "unscoped_share.train.json")) as f:
        assert tuple(json.load(f)["args"]["scopes"]) == STEP_SCOPES
    cfg, sizes, params, model, batch = toy
    schedule = schedulers.make_schedule("poly", 0.004, 100, warmup=0.1)
    tx = run_pretraining.make_optimizer("lamb", schedule)
    ours = lm_adapter.to_program_tree(params)
    state = TrainState(step=jnp.zeros([], jnp.int32), params=ours,
                       opt_state=tx.init(ours),
                       telemetry=init_telemetry_state())
    step = build_pretrain_step(
        model, tx, schedule=schedule, accum_steps=2,
        grad_dtype=jnp.bfloat16, health=HealthConfig(action="log"),
        loss_fn_builder=lfm2_moe.pretrain_loss_fn_builder,
        keep_float32=decoder.keep_float32)
    stacked = {k: jnp.stack([v, v]) for k, v in batch.items()}
    text = jax.jit(step).lower(state, stacked,
                               jax.random.PRNGKey(0)).compile().as_text()
    found = {}
    for op in re.finditer(r'op_name="([^"]*)"', text):
        # as tests/test_step_scopes.py: an op_name that does not start at
        # the jitted step is an argument's name or the scalar combiner of a
        # reduce, a sort or a scatter, never an operation of its own
        if op.group(1).startswith("jit("):
            found.setdefault(step_scope(op.group(1), LM_STEP_SCOPES),
                             set()).add(op.group(1))
    assert None not in found, sorted(found[None])[:5]
    for scope in ("rmsnorm", "moe", "conv", "attention", "mlp", "lm_head",
                  "loss", "optimizer", "param_cast", "grad_accum"):
        assert scope in found, scope
    for inner in ("router", "dispatch", "experts", "combine"):
        assert any(f"/moe/{inner}/" in name for name in found["moe"]), inner
    # the router is read in float32, everything else in the compute dtype
    assert decoder.keep_float32((jax.tree_util.DictKey("moe"),
                                 jax.tree_util.DictKey("router")))
