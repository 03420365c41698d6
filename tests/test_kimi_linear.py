"""The kimi_linear family (models/kimi_linear.py, ops/kda.py, the flash
kernels at keys of 192 and values of 128) against its plain reference
(benchmark/reference/kimi_linear_ref.py), on the CPU at toy widths with
seeded weights: every layer kind, the loss, the gradients and one LAMB step
over packed rows; the chunked recurrence against the token-by-token one at
two chunk sizes and a length no chunk divides; the expert-parallel shares of
a routed layer, with the shared expert counted once, adding up to the uncut
layer; no leak across a document boundary; the kernels against the XLA path
in interpret mode; the entry point's family selection and counters."""

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

from benchmark.harness import kimi_adapter  # noqa: E402
from benchmark.reference import kimi_linear_ref as ref  # noqa: E402
from bert_pytorch_tpu.config import (KimiLinearConfig,  # noqa: E402
                                     load_model_config)
from bert_pytorch_tpu.models import decoder, kimi_linear  # noqa: E402
from bert_pytorch_tpu.ops.attention import dot_product_attention  # noqa: E402
from bert_pytorch_tpu.ops.kda import (kda_scan, kernel_mode,  # noqa: E402
                                      unit_lower_inverse)

TOY = {
    "model_type": "kimi_linear", "vocab_size": 2048, "hidden_size": 64,
    "intermediate_size": 160, "moe_intermediate_size": 32,
    "num_hidden_layers": 5, "layers_kept": [1, 2, 3, 4, 5],
    "first_k_dense_replace": 1, "num_attention_heads": 2,
    "num_key_value_heads": 2, "kv_lora_rank": 32, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "mla_use_nope": True, "linear_attn_config": {
        "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4], "num_heads": 2,
        "head_dim": 16, "short_conv_kernel_size": 4},
    "num_experts": 4, "experts_total": 8, "experts_held": [2, 6],
    "num_experts_per_token": 2, "num_shared_experts": 1,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "kda_chunk_size": 16, "head_dim": 32,
    "rope_theta": 10000, "use_grouped_topk": True,
}
SEED = 2 ** 31 + 5
# three documents in a row: one shorter than a chunk of 16, one crossing
# several chunks, one to the padded tail; then a row that is one document
CUTS = [[0, 9, 97, 120], [0, 128]]


def _packed(rows=2, s=128, vocab=2048, seed=0):
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
    cfg = KimiLinearConfig.from_dict(TOY).replace(
        dtype="float32", checkpoint_activations=True)
    assert cfg.layer_kinds == (("kda", "dense"), ("kda", "moe"),
                               ("kda", "moe"), ("mla", "moe"), ("kda", "moe"))
    sizes = ref.sizes_from_config(TOY)
    assert sizes["kinds"] == cfg.layer_kinds
    params = ref.init_params(SEED, sizes)
    model = kimi_linear.KimiLinearForCausalLM(cfg, dtype=jnp.float32)
    ids, seg, pos = _packed()
    batch = {"input_ids": jnp.asarray(ids), "segment_ids": jnp.asarray(seg),
             "position_ids": jnp.asarray(pos)}
    return cfg, sizes, params, model, batch


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


def test_parameter_tree_is_the_references(toy):
    """The reference keeps its weights under the program's names: what the
    program initialises and what the benchmark hands it are one tree."""
    cfg, sizes, params, model, batch = toy
    init = model.init(jax.random.PRNGKey(0), *decoder.init_inputs(batch))
    assert (jax.tree.map(jnp.shape, init["params"])
            == jax.tree.map(jnp.shape, params))
    kda = params["layer_1"]["kda"]
    # the decay starts inside the chunked form's range, the gate open half
    assert 0 <= float(kda["A_log"].min()) and float(
        kda["A_log"].max()) < np.log(16)
    steps = jax.nn.softplus(kda["dt_bias"])
    assert 9e-4 < float(steps.min()) and float(steps.max()) < 0.101
    assert not kda["g_bias"].any() and (kda["o_norm"] == 1).all()
    a = ref.init_params(1, sizes)["layer_3"]["moe"]["expert_bias"]
    assert (a == params["layer_3"]["moe"]["expert_bias"]).all()     # one draw
    assert (a != params["layer_2"]["moe"]["expert_bias"]).any()


@pytest.mark.parametrize("kind", ["kda", "mla", "routed"])
def test_each_layer_kind_matches_the_reference(toy, kind):
    cfg, sizes, params, model, batch = toy
    sz = ref._Sizes(sizes)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(4), (2, 128, 64))
    seg, pos = batch["segment_ids"], batch["position_ids"]
    with jax.default_matmul_precision("highest"):
        if kind == "kda":
            lp = params["layer_1"]["kda"]
            got = kimi_linear.KimiDeltaAttention(cfg, jnp.float32).apply(
                {"params": lp}, x, seg, pos)
            want = [ref._kda(x[r], lp, pos[r], sz, None) for r in range(2)]
        elif kind == "mla":
            lp = params["layer_3"]["attention"]
            got = kimi_linear.LatentAttention(
                cfg.replace(attention_impl="xla"), jnp.float32).apply(
                    {"params": lp}, x, seg, pos)
            want = [ref._mla(x[r], lp, seg[r], sz, None) for r in range(2)]
        else:
            lp = params["layer_2"]
            layer = kimi_linear.DecoderLayer(cfg, "kda", "moe", jnp.float32)
            # the FFN half alone: y - h of a layer whose mixer is the
            # program's own
            y, load, dropped = layer.apply({"params": lp}, x, seg, pos)
            mixed = x + kimi_linear.KimiDeltaAttention(
                cfg, jnp.float32).apply({"params": lp["kda"]},
                                        ref._rms_norm(
                                            x, lp["input_norm"]["scale"],
                                            1e-5), seg, pos)
            got = y - mixed
            want, counts = [], 0
            for r in range(2):
                out, c, _ = ref._routed(ref._rms_norm(
                    mixed[r], lp["ffn_norm"]["scale"], 1e-5), lp, sz, None,
                    0.0)
                want.append(out)
                counts = counts + c
            assert np.asarray(load).tolist() == np.asarray(counts).tolist()
            assert int(dropped) == 0
    assert _rel(got, jnp.stack(want)) < 2e-5


def test_loss_gradients_and_counts_match_the_reference(toy):
    cfg, sizes, params, model, batch = toy
    loss_fn = kimi_linear.pretrain_loss_fn_builder(model)
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            loss_fn, has_aux=True))(params, batch, None)
    want, want_grads, counts, _ = ref.step_loss_and_grad(
        params, [batch], sizes)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    scalars = aux["scalars"]
    assert [[int(scalars[f"moe_l{i}_e{j}"]) for j in range(4)]
            for i in range(4)] == np.asarray(counts).tolist()
    # 4 KDA layers over the 2 x 128 slots that are no padding; a state
    # restarts at each of the 4 documents and 8 padding slots
    assert int(scalars["kda_tokens"]) == 4 * (256 - 8)
    assert "kda_chunks" not in scalars
    assert int(scalars["kda_resets"]) == 4 + 8
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), ref_leaf in zip(flat, jax.tree.leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['expert_bias']"):
            assert not got.any() and not ref_leaf.any()     # a buffer
        else:
            assert _rel(got, ref_leaf) < 2e-5, name


@pytest.mark.parametrize("width", [16, 128], ids=["xla-scans", "kernels"])
def test_kda_kernel_tokens_counts_the_scans_that_took_the_kernels(
        width, monkeypatch):
    """`kda_kernel_tokens` is `kda_tokens` where the scans walk their chunks
    with the Pallas kernels and 0 where they do not: under
    BPT_PALLAS_INTERPRET=1 a head width of 16 does not tile and keeps the
    XLA scans, 128 takes the kernels; without the variable both scan."""
    raw = dict(TOY, linear_attn_config=dict(TOY["linear_attn_config"],
                                            head_dim=width))
    cfg = KimiLinearConfig.from_dict(raw).replace(
        dtype="float32", checkpoint_activations=True)
    model = kimi_linear.KimiLinearForCausalLM(cfg, dtype=jnp.float32)
    ids, seg, pos = _packed(rows=1)
    batch = {"input_ids": jnp.asarray(ids), "segment_ids": jnp.asarray(seg),
             "position_ids": jnp.asarray(pos)}
    params = jax.jit(model.init)(jax.random.PRNGKey(0), batch["input_ids"],
                                 batch["segment_ids"],
                                 batch["position_ids"])["params"]
    loss_fn = kimi_linear.pretrain_loss_fn_builder(model)

    def scalars_and_calls():
        # the four KDA layers call ONE jitted `kda_fwd` (traced once a
        # signature: ops/pallas/kda._once_a_signature), by its name
        fn = lambda p: loss_fn(p, batch, None)  # noqa: E731
        text = str(jax.make_jaxpr(fn)(params))
        assert text.count("name=kda_fwd") == ("_fwd_block" in text)
        return (jax.jit(fn)(params)[1]["scalars"],
                len(re.findall(r"\b_fwd_block\b", text)) >= 4)

    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    scalars, calls = scalars_and_calls()
    assert int(scalars["kda_tokens"]) == 4 * (128 - 8)
    took = width == 128
    assert calls == took
    assert int(scalars["kda_kernel_tokens"]) == (
        int(scalars["kda_tokens"]) if took else 0)
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "0")
    scalars, calls = scalars_and_calls()
    assert not calls and int(scalars["kda_kernel_tokens"]) == 0


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
        loss_fn_builder=kimi_linear.pretrain_loss_fn_builder,
        keep_float32=kimi_linear.keep_float32)
    stacked = {k: v[None] for k, v in batch.items()}
    with jax.default_matmul_precision("highest"):
        new, _ = jax.jit(step)(state, stacked, jax.random.PRNGKey(0))
    _, grads, _, _ = ref.step_loss_and_grad(params, [batch], sizes)
    mine = jax.tree.map(jnp.copy, params)
    want, _ = ref.lamb_step(mine, grads, {"count": 1, "mu": None,
                                          "nu": None}, 0.004, 100, 0.1)
    moved = kimi_adapter.leaf_diff_norms(new.params, params)
    gaps = kimi_adapter.leaf_diff_norms(new.params, want)
    for name, gap in gaps.items():
        assert gap.max() <= 1e-3 * moved[name].max() + 1e-7, name
    # no decay, so no update where there is no gradient: the buffer stays
    assert float(moved["['layer_1']['moe']['expert_bias']"][0]) == 0.0
    for name in ("A_log", "dt_bias", "g_bias", "o_norm"):
        assert float(moved[f"['layer_1']['kda']['{name}']"][0]) > 0.0


@pytest.mark.parametrize("chunk,length,block,decay,heads,width", [
    (16, 200, 3, 0.5, 2, 16), (64, 333, 32, 0.5, 2, 16),
    (64, 128, 1, 0.5, 2, 16), (16, 64, 32, 0.5, 2, 16),
    (64, 256, 2, 2.0, 2, 16),
    # the Pallas kernels (ops/pallas/kda.py) in interpret mode, at a head
    # width that fills the lanes: documents shorter than a chunk and a
    # boundary inside one over five blocks of three chunks; a ragged length
    # in one block; eight heads a program, two programs, a chunk a block;
    # a chunk of eight tokens; strong decay over two blocks
    (16, 200, 3, 0.5, 2, 128), (64, 333, 32, 0.5, 2, 128),
    (64, 128, 1, 0.5, 16, 128), (8, 100, 4, 0.5, 2, 128),
    (64, 256, 2, 2.0, 2, 128)],
    ids=["c16-s200", "c64-s333", "c64-s128-block1", "c16-s64",
         "c64-strong-decay", "kernels-c16-s200", "kernels-c64-s333",
         "kernels-c64-s128-block1-h16", "kernels-c8-s100",
         "kernels-c64-strong-decay"])
def test_chunked_kda_matches_the_token_by_token_recurrence(
        chunk, length, block, decay, heads, width, monkeypatch):
    """ops/kda.py against the reference's recurrence, forward and the
    hand-written backward, over documents shorter than a chunk, across
    several chunks and blocks, and a padded tail of one-slot documents.
    Strong decay (up to e^-2 a token and more: a chunk's exp(G) exp(-G)
    products overflow above the diagonal, which the masks have to keep out
    of every cotangent; on the chip the first step read nan before they
    did, PR 33). At a head width of 128 under BPT_PALLAS_INTERPRET=1 the
    chunks of a block are walked by the kernels `kda_fwd` / `kda_bwd`, held
    to the XLA scans (their oracle: the same products in another order) as
    well as to the recurrence; at 16 the scans run whatever the variable
    says."""
    b, h, d = 2, heads, width
    keys = jax.random.split(jax.random.PRNGKey(chunk + length), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (b, length, h, d)))
    k = unit(jax.random.normal(keys[1], (b, length, h, d)))
    v = jax.random.normal(keys[2], (b, length, h, d))
    g = -decay * jax.nn.softplus(
        jax.random.normal(keys[3], (b, length, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, length, h)))
    weight = jax.random.normal(keys[5], (b, length, h, d))
    starts = np.zeros((b, length), bool)
    starts[0, [0, 5, min(5 + 3 * chunk + 7, length - 30), length - 20]] = True
    starts[0, length - 4:] = True
    starts[1, 0] = True
    starts = jnp.asarray(starts)

    def chunked(*x):
        return jnp.sum(weight * kda_scan(*x, starts, chunk=chunk,
                                         block=block, mm_dtype=jnp.float32))

    def stepwise(*x):
        return jnp.sum(weight * jnp.stack([
            ref._delta_rule(*(a[r] for a in x), starts[r])
            for r in range(b)]))

    def run(fn):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(fn, argnums=range(5))(q, k, v, g, beta)

    def kernels_traced():
        text = str(jax.make_jaxpr(jax.grad(chunked, argnums=range(5)))(
            q, k, v, g, beta))
        return {n: text.count(f"name={n}") for n in ("kda_fwd", "kda_bwd")}

    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    taken = kernel_mode(width, width, chunk, block)
    assert taken is (True if width == 128 else None)
    assert kernels_traced() == ({"kda_fwd": 1, "kda_bwd": 1} if taken
                                else {"kda_fwd": 0, "kda_bwd": 0})
    got, got_grads = run(chunked)
    want, want_grads = run(stepwise)
    assert float(got) == pytest.approx(float(want), rel=1e-5, abs=1e-4)
    for a, w in zip(got_grads, want_grads):
        assert bool(jnp.isfinite(a).all()) and _rel(a, w) < 2e-5
    if taken:
        monkeypatch.setenv("BPT_PALLAS_INTERPRET", "0")
        assert kernels_traced() == {"kda_fwd": 0, "kda_bwd": 0}
        scans, scans_grads = run(chunked)
        assert float(got) == pytest.approx(float(scans), rel=1e-6)
        for a, w in zip(got_grads, scans_grads):
            assert _rel(a, w) < 2e-6


def test_unit_lower_inverse_and_its_rule():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 16, 16)), -1)
    want = jnp.linalg.inv(jnp.eye(16) + a)
    np.testing.assert_allclose(unit_lower_inverse(a), want, atol=2e-4)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 16))
    got = jax.grad(lambda x: jnp.sum(w * unit_lower_inverse(x)))(a)
    auto = jax.grad(lambda x: jnp.sum(w * jnp.linalg.inv(jnp.eye(16) + x)))(a)
    np.testing.assert_allclose(got, auto, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("n_shares", [4, 2], ids=["4x2", "2x4"])
def test_expert_parallel_shares_add_up_to_the_whole_layer(n_shares):
    """The program's routed FFN (held experts + the shared expert), told
    which experts it holds, for every share of the 8 experts: the routed
    parts summed, with the shared expert (which every chip computes alike)
    counted ONCE, equal the UNCUT reference's layer."""
    whole = dict(TOY, num_experts=8, experts_held=[0, 8])
    sizes = ref.sizes_from_config(whole)
    lp = ref.init_params(SEED, sizes)["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 96, 64), jnp.float32)
    per = 8 // n_shares
    with jax.default_matmul_precision("highest"):
        want, counts, _ = ref._routed(x[0], lp, ref._Sizes(sizes), None, 0.0)
        shared = kimi_linear.DenseMLP(
            KimiLinearConfig.from_dict(whole), jnp.float32, 32).apply(
            {"params": lp["shared_expert"]}, x.astype(jnp.float32))
        total, loads = shared[0], []
        for lo in range(0, 8, per):
            cfg = KimiLinearConfig.from_dict(dict(
                whole, num_experts=per, experts_held=[lo, lo + per])
            ).replace(dtype="float32")
            share = dict(lp["moe"], **{
                name: lp["moe"][name][lo:lo + per]
                for name in ("experts_w1", "experts_w3", "experts_w2")})
            out, load, dropped = kimi_linear.RoutedExperts(
                cfg, jnp.float32).apply({"params": share}, x)
            assert int(dropped) == 0
            total = total + out[0]
            loads += np.asarray(load).tolist()
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-6)
    assert loads == np.asarray(counts).tolist() and sum(loads) == 96 * 2


@pytest.mark.parametrize("mixer", ["kda", "mla"])
def test_no_leak_across_a_document_boundary(toy, mixer):
    """Changing the tokens of a row's second document moves nothing in the
    documents before and after it. Attention and the convolutions: to the
    bit. The chunked recurrence: to rounding, because a chunk's cumulative
    log-decay runs over all its tokens and differences of it inside one
    document round with what the other document's tokens added."""
    cfg, sizes, params, model, batch = toy
    layer, name, cls = (("layer_1", "kda", kimi_linear.KimiDeltaAttention)
                        if mixer == "kda" else
                        ("layer_3", "attention", kimi_linear.LatentAttention))
    lp = params[layer][name]
    seg, pos = batch["segment_ids"][:1], batch["position_ids"][:1]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 128, 64))
    other = x.at[:, 9:97].add(1.0)          # the second document
    run = lambda a: cls(cfg.replace(attention_impl="xla"),  # noqa: E731
                        jnp.float32).apply({"params": lp}, a, seg, pos)
    a, b = run(x), run(other)
    assert float(jnp.abs(a - b)[:, 9:97].max()) > 1e-3
    limit = 1e-7 if mixer == "kda" else 0.0
    assert float(jnp.abs(a - b)[:, :9].max()) <= limit
    assert float(jnp.abs(a - b)[:, 97:].max()) <= limit


@pytest.mark.parametrize("s,split", [(256, True), (128, False)],
                         ids=["s256", "s128"])
def test_flash_at_keys_192_values_128_matches_xla_in_interpret_mode(
        s, split, monkeypatch):
    """The causal kernels with values of a width of their own (bh layout,
    split backward), forward and backward, packed rows."""
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    import importlib

    fa = importlib.import_module(
        "bert_pytorch_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "DEFAULT_BLK_Q", 128)
    monkeypatch.setattr(fa, "DEFAULT_BLK_K", 128)
    h, d, dv = 2, 192, 128
    keys = jax.random.split(jax.random.PRNGKey(s), 4)
    q = jax.random.normal(keys[0], (1, s, h, d))
    k = jax.random.normal(keys[1], (1, s, h, d))
    v = jax.random.normal(keys[2], (1, s, h, dv))
    cuts = [0, 100, 130, s - 26] if split else [0, s - 20]
    seg = np.zeros((1, s), np.int32)
    for g, (a, b) in enumerate(zip(cuts[:-1], cuts[1:])):
        seg[0, a:b] = g + 1
    seg = jnp.asarray(seg)
    weight = jax.random.normal(keys[3], (1, s, h, dv)) * (
        seg > 0)[:, :, None, None]         # no loss term reads padding

    def loss(impl):
        def f(q, k, v):
            out = dot_product_attention(q, k, v, segment_ids=seg, impl=impl,
                                        causal=True)
            assert out.shape == (1, s, h, dv)
            return jnp.sum(out * weight), out
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    (_, got), got_grads = loss("pallas")
    (_, want), want_grads = loss("xla")
    np.testing.assert_allclose(got, want, atol=2e-5)
    for a, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, w, atol=5e-5)
    assert fa._layout(1, s, h, d, 1, dv).native is False
    assert fa._layout(1, s, h, 128, 1, 128).native is True     # as before


def test_model_config_family_selection(tmp_path):
    def write(d):
        p = tmp_path / f"cfg{len(list(tmp_path.iterdir()))}.json"
        p.write_text(json.dumps(d))
        return str(p)

    cfg = load_model_config(write(dict(
        TOY, source="s", reduced={}, assumed={}, layout="l")))
    assert isinstance(cfg, KimiLinearConfig)
    assert cfg.kda_layers == (1, 2, 3, 5) and cfg.kda_head_dim == 16
    assert cfg.num_experts_per_tok == 2 and cfg.router_width == 8
    with pytest.raises(ValueError, match="conv_L_cache"):
        load_model_config(write(dict(TOY, conv_L_cache=3)))
    with pytest.raises(ValueError, match="linear_attn_config.window"):
        load_model_config(write(dict(TOY, linear_attn_config=dict(
            TOY["linear_attn_config"], window=4))))
    with pytest.raises(ValueError, match="exactly one"):
        load_model_config(write(dict(TOY, linear_attn_config=dict(
            TOY["linear_attn_config"], full_attn_layers=[]))))
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        load_model_config(write(dict(TOY, q_lora_rank=64)))
    with pytest.raises(NotImplementedError, match="mla_use_nope"):
        load_model_config(write(dict(TOY, mla_use_nope=False)))
    # the benchmark's configuration is one the program reads
    real = load_model_config(os.path.join(
        ROOT, "benchmark", "configs", "kimi-linear-48b-a3b-ep32.json"))
    assert real.layer_kinds == cfg.layer_kinds and real.held_range == (0, 8)
    assert (real.kda_head_dim, real.gate_rank, real.kda_chunk_size) == (
        128, 128, 64)


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
    # the record of step n counts through step n - 1: 4 KDA layers over
    # the step's tokens that are no padding (slots = routed pairs / top-2)
    for key in ("kda_tokens", "kda_resets"):
        assert last[key] == sum(r[key] for r in train[:-1]) > 0
    # head width 16 on the CPU: the XLA scans, and the record says so
    assert last["kda_kernel_tokens"] == 0
    assert all(r["kda_kernel_tokens"] == 0 for r in train)
    print(sorted(train[0]))
    slots = train[0]["moe_pairs_routed"] // 2
    assert 0.5 * 4 * slots < train[0]["kda_tokens"] < 4 * slots
    assert train[0]["kda_tokens"] % 4 == 0
    for layer in range(4):
        assert last[f"moe_l{layer}_dropped"] == 0
        assert last[f"moe_l{layer}_pairs"] == sum(
            r[f"moe_l{layer}_e{e}"] for r in train[:-1] for e in range(4))
    cfg = load_model_config(str(cfg_path))
    mine = kimi_linear.train_flops_per_row(cfg, 128)
    assert last["model_flops_per_sec"] / last["seq_per_sec"] == \
        pytest.approx(mine, rel=1e-3)
    # the second header counts, in the executable, the instructions under
    # every sub-scope the family's step is declared to open
    from bert_pytorch_tpu.training.pretrain import step_subscopes

    (said,) = [r["program_scopes"] for r in records
               if r.get("tag") == "header" and "program_scopes" in r]
    counts = dict(kv.split("=") for kv in said.split())
    assert list(counts) == list(step_subscopes("kimi_linear"))
    assert all(int(n) > 0 for n in counts.values())


def test_every_instruction_of_the_step_is_under_an_lm_scope(toy):
    """LM_STEP_SCOPES accounts for this family's compiled step too: the
    mixer's four parts under `kda`, the shared expert under `moe`; the
    benchmark's unscoped_share.kimi.train carries a copy of the list."""
    import re

    import run_pretraining
    from bert_pytorch_tpu.optim import schedulers
    from bert_pytorch_tpu.training import build_pretrain_step
    from bert_pytorch_tpu.training.pretrain import (LM_STEP_SCOPES,
                                                    step_scope)
    from bert_pytorch_tpu.training.state import TrainState

    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           "unscoped_share.kimi.train.json")) as f:
        assert tuple(json.load(f)["args"]["scopes"]) == LM_STEP_SCOPES
    cfg, sizes, params, model, batch = toy
    schedule = schedulers.make_schedule("poly", 0.004, 100, warmup=0.1)
    tx = run_pretraining.make_optimizer("lamb", schedule)
    state = TrainState(step=jnp.zeros([], jnp.int32), params=params,
                       opt_state=tx.init(params))
    step = build_pretrain_step(
        model, tx, schedule=schedule, accum_steps=2,
        grad_dtype=jnp.bfloat16,
        loss_fn_builder=kimi_linear.pretrain_loss_fn_builder,
        keep_float32=kimi_linear.keep_float32)
    stacked = {k: jnp.stack([v, v]) for k, v in batch.items()}
    text = jax.jit(step).lower(state, stacked,
                               jax.random.PRNGKey(0)).compile().as_text()
    found = {}
    for op in re.finditer(r'op_name="([^"]*)"', text):
        if op.group(1).startswith("jit("):
            found.setdefault(step_scope(op.group(1), LM_STEP_SCOPES),
                             set()).add(op.group(1))
    assert None not in found, sorted(found[None])[:5]
    for scope in ("kda", "rmsnorm", "moe", "attention", "mlp", "lm_head",
                  "loss", "optimizer", "param_cast", "grad_accum"):
        assert scope in found, scope
    for inner in ("conv", "gates", "scan", "out"):
        assert any(re.search(rf"[/(]kda/{inner}\)*/", name)
                   for name in found["kda"]), inner
    assert any("/moe/shared/" in name for name in found["moe"])
    # A_log and dt_bias are read in float32, like the router
    assert kimi_linear.keep_float32((jax.tree_util.DictKey("A_log"),))
    assert not kimi_linear.keep_float32((jax.tree_util.DictKey("q_proj"),))
