"""The keye family (models/keye.py; ops/sparse_index.py: index scores, the
exact selection of each query's best keys, then, after the main attention,
the KL term on that attention's own log-sum-exp and its gradient rule; the
flash kernels under a selection, `flash_sel_*`; ops/moe.py's
softmax router at top-8 of 128) against its plain reference
(benchmark/reference/keye_ref.py), on the CPU at toy widths with seeded
weights and a selection of 12 keys on rows of 96 that pack documents both
shorter (9) and longer (61, 20, 96) than the selection: logits, both loss
terms and every gradient leaf; each query selects exactly min(K, n_t) keys
of its own document, none later, ties to the lower key; the exact K-th
score by bisection against a sort; documents no longer than K give plain
causal attention to the bit; each loss term's gradient is zero where the
other's lives; the kernels in interpret mode against the XLA masked path at
a group of 8 with boundaries inside a tile; three equal position components
give the plain rotary table; the 8 ranks' shares adding up to the uncut
layer; the cut's parameter count; the family's selection, its unknown-key
and refusal messages."""

import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import keye_ref as ref  # noqa: E402
from bert_pytorch_tpu.config import (KeyeConfig,  # noqa: E402
                                     load_model_config)
from bert_pytorch_tpu.models import decoder, keye  # noqa: E402
from bert_pytorch_tpu.ops import sparse_index  # noqa: E402
from bert_pytorch_tpu.ops.attention import (dot_product_attention,  # noqa: E402
                                            unpack_select)
from bert_pytorch_tpu.ops.decoder_ops import rotary  # noqa: E402

TOPK = 12
TOY = {
    "model_type": "keye", "vocab_size": 2048, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 2,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "attention_bias": False, "hidden_act": "silu", "rms_norm_eps": 1e-6,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "moe_intermediate_size": 32, "norm_topk_prob": True, "num_experts": 4,
    "num_local_experts": 4, "experts_total": 16, "experts_held": [4, 8],
    "num_experts_per_tok": 4, "rope_theta": 10000000,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": TOPK},
    "sliding_window": None, "use_sliding_window": False,
    "tie_word_embeddings": False, "initializer_range": 0.125,
}
SEED = 2 ** 31 + 11
# three documents in a row, one shorter than the selection of 12 and two
# longer, and a padded tail; then a row that is one document
CUTS = [[0, 9, 70, 90], [0, 96]]
INDEXER = ("index_q_proj", "index_k_proj", "index_w_proj", "index_k_norm")


def _packed(rows=2, s=96, vocab=2048, seed=0, cuts=CUTS):
    rng = np.random.default_rng(seed)
    ids = rng.integers(10, vocab, (rows, s)).astype(np.int32)
    seg = np.zeros((rows, s), np.int32)
    pos = np.zeros((rows, s), np.int32)
    for r, row in enumerate(cuts[:rows]):
        for g, (a, b) in enumerate(zip(row[:-1], row[1:])):
            seg[r, a:b] = g + 1
            pos[r, a:b] = np.arange(b - a)
    return ids * (seg > 0), seg, pos


def _expected_pairs(seg, pos, k):
    return int(np.minimum(pos + 1, k)[seg > 0].sum())


def _candidates(picked):
    hi, lo = (int(x) for x in picked.candidates)
    return hi * sparse_index.COUNT_UNIT + lo


@pytest.fixture(scope="module")
def toy():
    cfg = KeyeConfig.from_dict(TOY).replace(
        dtype="float32", checkpoint_activations=True, attention_impl="xla")
    sizes = ref.sizes_from_config(TOY)
    params = ref.init_params(SEED, sizes)
    model = keye.KeyeForCausalLM(cfg, dtype=jnp.float32)
    ids, seg, pos = _packed()
    batch = {"input_ids": jnp.asarray(ids), "segment_ids": jnp.asarray(seg),
             "position_ids": jnp.asarray(pos)}
    return cfg, sizes, params, model, batch


@pytest.fixture()
def small_blocks(monkeypatch):
    """Tiles of 128, so that a row of 384 is three blocks a side."""
    fa = importlib.import_module(
        "bert_pytorch_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "DEFAULT_BLK_Q", 128)
    monkeypatch.setattr(fa, "DEFAULT_BLK_K", 128)
    return fa


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


def _is_indexer(path) -> bool:
    return any(name in jax.tree_util.keystr(path) for name in INDEXER)


def test_parameter_tree_is_the_references_and_no_gain_decays(toy):
    from bert_pytorch_tpu.optim.lamb import default_weight_decay_mask

    cfg, sizes, params, model, batch = toy
    init = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          *decoder.init_inputs(batch))
    shapes = jax.tree.map(jnp.shape, init["params"])
    assert shapes == jax.tree.map(jnp.shape, params)
    attention = shapes["layer_1"]["attention"]
    assert attention["index_q_proj"] == (64, 4 * 8)
    assert attention["index_k_proj"] == (64, 8)
    assert attention["index_w_proj"] == (64, 4)
    assert attention["index_k_norm"] == {"scale": (8,), "bias": (8,)}
    assert shapes["layer_0"]["moe"]["router"] == (64, 16)
    for path, decays in jax.tree_util.tree_flatten_with_path(
            default_weight_decay_mask(params))[0]:
        name = jax.tree_util.keystr(path)
        assert decays == (not name.endswith(("['scale']", "['bias']"))), name
    # the reference's LAMB leaves the same leaves undecayed
    assert ref.NO_DECAY == ("scale", "bias")


def test_the_cut_counts_659_million_parameters():
    """Counted again from the reference's shapes, and from the program's
    own: 6 layers of 96.9 M (attention 18.87, indexer 2.26, router 0.26, 16
    experts 75.50) and 1/8 of both tables."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "keye-vl2-30b-a3b-ep8.json")) as f:
        raw = json.load(f)
    sizes = ref.sizes_from_config(raw)
    assert ref.param_count(sizes) == 659_190_016
    layer = ref.param_count(dict(sizes, kinds=sizes["kinds"][:1])) \
        - ref.param_count(dict(sizes, kinds=()))
    assert layer == 96_899_456
    assert ref.param_count(dict(sizes, kinds=sizes["kinds"][:5])) \
        == 659_190_016 - layer
    cfg = load_model_config(os.path.join(
        ROOT, "benchmark", "configs", "keye-vl2-30b-a3b-ep8.json"))
    model = keye.KeyeForCausalLM(cfg)
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        *(jax.ShapeDtypeStruct((1, 512), jnp.int32),) * 3)
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 659_190_016
    # every published width, topk 2048, an eighth of the experts and rows
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.moe_intermediate_size,
            cfg.num_experts_per_tok, cfg.sa_topk, cfg.sa_indexer_num_heads,
            cfg.sa_indexer_head_dim) == (2048, 32, 4, 128, 768, 8, 2048, 16,
                                         64)
    assert cfg.held_range == (0, 16) and cfg.router_width == 128
    assert cfg.vocab_size * 8 == 151936 and cfg.remat_policy == "dense"
    assert cfg.num_hidden_layers == 6
    # at the cell's shape the selection packs into one word plane each way
    fa = importlib.import_module(
        "bert_pytorch_tpu.ops.pallas.flash_attention")
    assert fa.select_blocks(16384) == (512, 512, 1, 1)
    assert fa.select_blocks(32768) == (512, 512, 2, 2)


def test_logits_match_the_reference(toy):
    cfg, sizes, params, model, batch = toy
    row_forward = jax.jit(ref.row_forward, static_argnames=("sz",))
    with jax.default_matmul_precision("highest"):
        hidden, head, load, dropped, picked = jax.jit(model.apply)(
            {"params": params}, *decoder.init_inputs(batch))
        logits = hidden @ head.T
        for r in range(2):
            want, counts, _ = row_forward(
                params, batch["input_ids"][r], batch["segment_ids"][r],
                sz=ref._Sizes(sizes))
            real = np.asarray(batch["segment_ids"][r] > 0)
            np.testing.assert_allclose(np.asarray(logits[r])[real],
                                       np.asarray(want)[real], atol=5e-6)
    assert load.shape == (2, 4) and not np.asarray(dropped).any()
    want = _expected_pairs(np.asarray(batch["segment_ids"]),
                           np.asarray(batch["position_ids"]), TOPK)
    assert np.asarray(picked[1]).sum(-1).tolist() == [want, want]


def test_the_rotation_kernels_match_the_reference(monkeypatch):
    """Heads of 128 under the test switch: q and k take
    ops/pallas/rotary.py's kernels (interpret mode) on the float32 the head
    norms hand them, the index heads of 16 keep the plain function; both
    loss terms and every gradient leaf against the reference."""
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    wide = dict(TOY, head_dim=128, rope_scaling=dict(
        TOY["rope_scaling"], mrope_section=[16, 24, 24]))
    cfg = KeyeConfig.from_dict(wide).replace(
        dtype="float32", checkpoint_activations=True, attention_impl="xla")
    sizes = ref.sizes_from_config(wide)
    params = ref.init_params(SEED, sizes)
    model = keye.KeyeForCausalLM(cfg, dtype=jnp.float32)
    batch = {k: jnp.asarray(v) for k, v in zip(
        ("input_ids", "segment_ids", "position_ids"), _packed())}
    loss_fn = keye.pretrain_loss_fn_builder(model)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    text = str(jax.make_jaxpr(grad_fn)(params, batch, None))
    # 2 layers x (q, k), forward and recomputed; the rule once each
    assert text.count("name=rotary_fwd") == 8
    assert text.count("name=rotary_bwd") == 4
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(grad_fn)(params, batch, None)
    want, want_grads, details = ref.step_loss_and_grad(
        params, [batch], sizes, None, 0.01, 0.01)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    assert float(aux["means"]["indexer_kl"]) == pytest.approx(
        float(details["indexer_kl"]), rel=2e-5)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), ref_leaf in zip(flat, jax.tree.leaves(want_grads)):
        assert _rel(got, ref_leaf) < 3e-5, jax.tree_util.keystr(path)


def test_both_loss_terms_gradients_and_counts_match_the_reference(toy):
    cfg, sizes, params, model, batch = toy
    loss_fn = keye.pretrain_loss_fn_builder(model)
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            loss_fn, has_aux=True))(params, batch, None)
    want, want_grads, details = ref.step_loss_and_grad(
        params, [batch], sizes, None, 0.01, 0.01)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    means = aux["means"]
    assert float(means["lm_loss"]) == pytest.approx(
        float(details["lm_loss"]), rel=2e-6)
    assert float(means["indexer_kl"]) == pytest.approx(
        float(details["indexer_kl"]), rel=2e-5)
    assert float(means["indexer_kl"]) > 0.01    # two layers' KL, not noise
    assert float(loss) == pytest.approx(
        float(means["lm_loss"] + means["indexer_kl"]), rel=1e-6)
    scalars = aux["scalars"]
    assert [[int(scalars[f"moe_l{i}_e{j}"]) for j in range(4)]
            for i in range(2)] == np.asarray(
                details["expert_counts"]).tolist()
    assert [[int(scalars[f"dsa_l{i}_kb0"])] for i in range(2)] == np.asarray(
        details["block_pairs"]).tolist()
    assert "dsa_l0_kb1" not in scalars and "dsa_l2_kb0" not in scalars
    pairs = _expected_pairs(np.asarray(batch["segment_ids"]),
                            np.asarray(batch["position_ids"]), TOPK)
    real = int((np.asarray(batch["segment_ids"]) > 0).sum())
    assert int(scalars["dsa_tokens"]) == real
    causal = sum(n * (n + 1) // 2 for n in (9, 61, 20, 96))
    for i in range(2):
        assert int(scalars[f"dsa_l{i}_kb0"]) == pairs
        assert (int(scalars[f"dsa_l{i}_candidates_hi"]),
                int(scalars[f"dsa_l{i}_candidates_lo"])) == divmod(
                    causal, sparse_index.COUNT_UNIT)
    assert int(details["near_pairs"].sum()) > 0
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == len(jax.tree.leaves(want_grads))
    for (path, got), ref_leaf in zip(flat, jax.tree.leaves(want_grads)):
        assert float(jnp.abs(ref_leaf).max()) > 0, path
        assert _rel(got, ref_leaf) < 3e-5, jax.tree_util.keystr(path)


@pytest.mark.parametrize("term", ["lm_loss", "indexer_kl"])
def test_each_loss_term_reaches_its_own_parameters_only(toy, term,
                                                        monkeypatch):
    """jax.grad(L_LM) is zero on the indexer's leaves and jax.grad(L_I) is
    zero on every other: the indexer's input is detached, the selection is
    discrete, the KL term's target is detached."""
    cfg, sizes, params, model, batch = toy
    monkeypatch.setattr(
        keye, "total_loss",
        (lambda lm, kl: lm) if term == "lm_loss" else (lambda lm, kl: kl))
    grads = jax.jit(jax.grad(lambda p: keye.pretrain_loss_fn_builder(model)(
        p, batch, None)[0]))(params)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert sum(_is_indexer(p) for p, _ in flat) == 2 * 5
    for path, leaf in flat:
        moved = bool(np.asarray(leaf).any())
        assert moved == (_is_indexer(path) == (term == "indexer_kl")), \
            jax.tree_util.keystr(path)


def _index_inputs(b, s, heads=8, kv=1, d=16, j=4, di=8, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(keys[0], (b, s, j, di)),
            jax.random.normal(keys[1], (b, s, di)),
            jax.random.normal(keys[2], (b, s, j)) / (j * di) ** 0.5,
            jax.random.normal(keys[3], (b, s, heads, d)),
            jax.random.normal(keys[4], (b, s, kv, d)),
            jax.random.normal(keys[5], (b, s, kv, d)))


def _layer_order(q_idx, k_idx, w_idx, q, k, v, seg, topk, impl="auto"):
    """The three calls of models/keye.Attention, in its order: (the
    selection, the context, the log-sum-exp, the KL sum)."""
    picked = sparse_index.index_select(q_idx, k_idx, w_idx, seg, topk, impl)
    ctx, lse = dot_product_attention(
        q, k, v, segment_ids=seg, impl=impl, causal=True,
        select=(picked.by_q, picked.by_k), with_lse=True)
    return picked, ctx, lse, sparse_index.index_kl(
        q_idx, k_idx, w_idx, q, k, lse, picked.by_q, impl)


def _dense_kl(q_idx, k_idx, w_idx, q, k, dense):
    """sum_t KL_t of one row written out over the whole (S, S) matrix:
    `dense` the selected pairs, the target's softmax taken here."""
    h, hkv = q.shape[1], k.shape[1]
    scores = sparse_index.index_scores(q_idx, k_idx, w_idx)
    s = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, h // hkv, axis=1),
                   preferred_element_type=jnp.float32) / q.shape[-1] ** 0.5
    p = jnp.where(dense, jax.nn.softmax(jnp.where(dense, s, -1e30), -1),
                  0.0).mean(0)
    log_pi = jax.nn.log_softmax(jnp.where(dense, scores, -1e30), -1)
    live = dense & (p > 0)
    return jnp.sum(jnp.where(
        live, p * (jnp.log(jnp.where(live, p, 1.0)) - log_pi), 0.0))


# rows of 384 = three tiles of 128: boundaries inside a tile (40, 300, 350),
# documents shorter (40, 30) and longer (260, 50, 384) than the 48 selected
CUTS_384 = [[0, 40, 300, 350, 380], [0, 384]]


@pytest.mark.parametrize("k", [48, 200])
def test_every_query_selects_its_best_keys_exactly(small_blocks, k):
    """Exactly min(K, n_t) keys a query, all of its own document, none
    later, and they are the K largest index scores (the reference's own
    top-k says which); both packings say the same; the counters add up."""
    k = 48
    _, seg, pos = _packed(2, 384, cuts=CUTS_384)
    q_idx, k_idx, w_idx, _, _, _ = _index_inputs(2, 384)
    picked = jax.jit(sparse_index.index_select, static_argnums=(4,))(
        q_idx, k_idx, w_idx, jnp.asarray(seg), k)
    assert picked.by_q.shape == (2, 1, 384, 128)
    assert picked.by_k.shape == (2, 1, 128, 384)
    dense = np.asarray(unpack_select(picked.by_q))
    by_k = np.asarray(picked.by_k)[:, 0]                    # (B, 128, S)
    other = np.stack([(by_k >> i) & 1 for i in range(3)], axis=1).reshape(
        2, 384, 384).astype(bool)
    np.testing.assert_array_equal(dense, other)
    np.testing.assert_array_equal(dense.sum(-1),
                                  np.minimum(pos + 1, k) * (seg > 0))
    same = (seg[:, :, None] == seg[:, None, :]) & (seg[:, :, None] > 0)
    causal = np.tril(np.ones((384, 384), bool))
    assert not (dense & ~(same & causal)).any()
    for b in range(2):
        scores = jnp.concatenate([sparse_index.index_scores(
            q_idx[b, i:i + 128], k_idx[b], w_idx[b, i:i + 128])
            for i in range(0, 384, 128)])
        want, _ = ref.select_rows(scores, jnp.asarray(same[b] & causal), k)
        np.testing.assert_array_equal(dense[b], np.asarray(want))
    assert int(picked.block_pairs.sum()) == _expected_pairs(seg, pos, k)
    assert _candidates(picked) == int((same & causal).sum())
    np.testing.assert_array_equal(
        np.asarray(picked.block_pairs),
        dense.reshape(2, 384, 3, 128).sum(axis=(0, 1, 3)))
    # and the KL pass's own unpacking of a chunk's words is the dense one
    for i in range(3):
        np.testing.assert_array_equal(
            np.asarray(unpack_select(
                picked.by_q[:, :, i * 128:(i + 1) * 128], 384)),
            dense[:, i * 128:(i + 1) * 128])


def test_equal_scores_go_to_the_lower_key():
    """Index weights of zero make every score 0: the selection is then the
    document's FIRST K keys, in the program and in the reference."""
    scores = jnp.zeros((6, 20))
    allowed = jnp.tril(jnp.ones((20, 20), bool))[14:]
    got = sparse_index.select_keys(scores, allowed, 5)
    want = np.zeros((6, 20), bool)
    want[:, :5] = True
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        np.asarray(ref.select_rows(scores, allowed, 5)[0]), want)
    # ties AT the K-th only: two keys above it, three tied for one place
    scores = jnp.asarray([[0.5, 2.0, 0.5, 3.0, 0.5, -1.0]])
    got = sparse_index.select_keys(scores, jnp.ones((1, 6), bool), 3)
    assert np.asarray(got).tolist() == [[True, True, False, True, False,
                                         False]]


@pytest.mark.parametrize("k", [1, 7, 64, 200])
def test_the_kth_largest_by_bisection_is_the_sorts(k):
    rng = np.random.default_rng(k)
    x = rng.standard_normal((9, 200)).astype(np.float32)
    x[0, :50] = 0.0
    x[1, :50] = -0.0
    x[2] = np.round(x[2], 1)                    # many duplicates
    x[3] *= 1e-30
    keys = sparse_index.ordered_bits(jnp.asarray(x))
    order = np.argsort(np.asarray(keys), axis=-1)
    np.testing.assert_array_equal(                 # the bits sort as floats
        np.take_along_axis(x + 0.0, order, -1), np.sort(x + 0.0, axis=-1))
    want = np.sort(np.asarray(keys), axis=-1)[:, -k]
    got = jax.jit(sparse_index.kth_largest, static_argnums=(1,))(keys, k)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(            # and `jax.lax.top_k`'s k-th
        np.asarray(jax.lax.top_k(keys, k)[0][:, -1]), want)
    # fewer than k keys above 0: nothing bounds the row
    few = jnp.where(jnp.arange(200) < k - 1, keys, jnp.uint32(0))
    assert not np.asarray(sparse_index.kth_largest(few, k)).any()


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_the_kl_terms_rule_is_the_gradient_of_its_forward(small_blocks, impl,
                                                          monkeypatch):
    """The layer's order (selection, main attention, then `index_kl` on the
    attention's log-sum-exp) and `index_kl`'s custom rule (the gradients
    taken in the forward pass): `kl_sum` and its gradients with respect to
    the indexer's three outputs against jax.grad of the KL written out
    densely, which takes its own softmax; in plain XLA and with every kernel
    of the layer (`flash_sel_fwd`, `dsa_index_fwd`, `dsa_probs`,
    `dsa_index_bwd`) in interpret mode."""
    if impl == "interpret":
        monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    k = 48
    _, seg, _ = _packed(1, 384, cuts=CUTS_384)
    seg = jnp.asarray(seg)
    q_idx, k_idx, w_idx, q, kk, v = _index_inputs(1, 384, heads=4, kv=2,
                                                  d=128, j=2, di=64, seed=3)
    impl = "xla" if impl == "xla" else "auto"

    def rule(q_idx, k_idx, w_idx):
        return _layer_order(q_idx, k_idx, w_idx, q, kk, v, seg, k, impl)[3]

    text = str(jax.make_jaxpr(rule)(q_idx, k_idx, w_idx))
    assert ("dsa_probs" in text) == ("flash_sel_fwd" in text) == (
        impl == "auto")
    dense = unpack_select(sparse_index.index_select(
        q_idx, k_idx, w_idx, seg, k, impl).by_q)[0]

    def plain(q_idx, k_idx, w_idx):
        return _dense_kl(q_idx[0], k_idx[0], w_idx[0], q[0], kk[0], dense)

    value, grads = jax.jit(jax.value_and_grad(rule, argnums=(0, 1, 2)))(
        q_idx, k_idx, w_idx)
    want_value, want = jax.jit(jax.value_and_grad(
        plain, argnums=(0, 1, 2)))(q_idx, k_idx, w_idx)
    assert float(value) == pytest.approx(float(want_value), rel=1e-5)
    assert float(value) > 0
    for got, w in zip(grads, want):
        assert _rel(got, w) < 1e-4
    # scaled by the cotangent
    doubled = jax.grad(lambda *a: 2.0 * rule(*a), argnums=2)(
        q_idx, k_idx, w_idx)
    np.testing.assert_allclose(doubled, 2.0 * grads[2], rtol=1e-6)
    # q, k and the log-sum-exp are data: no gradient reaches them
    picked = sparse_index.index_select(q_idx, k_idx, w_idx, seg, k, impl)
    lse = dot_product_attention(
        q, kk, v, segment_ids=seg, impl=impl, causal=True,
        select=(picked.by_q, picked.by_k), with_lse=True)[1]
    for g in jax.grad(lambda q, kk, lse: sparse_index.index_kl(
            q_idx, k_idx, w_idx, q, kk, lse, picked.by_q, impl),
            argnums=(0, 1, 2))(q, kk, lse):
        assert not np.asarray(g).any()


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_documents_no_longer_than_the_selection_attend_plainly(
        small_blocks, impl, monkeypatch):
    """Where every document of a row has at most K tokens every candidate
    is selected, and the layer's attention is the plain causal attention's:
    to the bit, the XLA path and the kernels' tile bodies alike."""
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    cuts = [[0, 40, 88, 130, 200, 256]]
    _, seg, _ = _packed(1, 256, cuts=cuts)
    seg = jnp.asarray(seg)
    q_idx, k_idx, w_idx, q, kk, v = _index_inputs(1, 256, seed=5)
    picked = sparse_index.index_select(q_idx, k_idx, w_idx, seg, 70)
    assert int(picked.block_pairs.sum()) == _candidates(picked)
    attend = jax.jit(lambda **kw: dot_product_attention(
        q, kk, v, segment_ids=seg, impl=impl, causal=True, **kw))
    np.testing.assert_array_equal(
        np.asarray(attend(select=(picked.by_q, picked.by_k))),
        np.asarray(attend()))


def test_flash_select_kernels_against_the_xla_path(small_blocks,
                                                   monkeypatch):
    """`flash_sel_fwd`, `flash_sel_bwd_dq`, `flash_sel_bwd_dkv` in interpret
    mode: a program owns the EIGHT query heads of a key/value head, segment
    ids in the same call (boundaries inside a tile, a padded tail), three
    tiles a side; forward and all three gradients against the XLA masked
    path."""
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    fa = small_blocks
    s, d = 384, 64
    _, seg, _ = _packed(2, s, cuts=CUTS_384)
    seg = jnp.asarray(seg)
    q_idx, k_idx, w_idx, q, kk, v = _index_inputs(2, s, heads=8, kv=1, d=d,
                                                  seed=8)
    picked = sparse_index.index_select(q_idx, k_idx, w_idx, seg, 48)
    select = (picked.by_q, picked.by_k)
    weight = jax.random.normal(jax.random.PRNGKey(9), q.shape) * (
        seg > 0)[:, :, None, None]             # no loss term reads padding

    def loss(impl):
        def f(q, k, v):
            out = dot_product_attention(q, k, v, segment_ids=seg, impl=impl,
                                        causal=True, select=select)
            return jnp.sum(out * weight), out
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, got), got_grads = jax.jit(loss("pallas"))(q, kk, v)
    (_, want), want_grads = jax.jit(loss("xla"))(q, kk, v)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for a, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, w, atol=5e-5)
    # and the selection bites: plain causal attention reads something else
    plain = dot_product_attention(q, kk, v, segment_ids=seg, impl="xla",
                                  causal=True)
    assert float(jnp.abs(plain - want).max()) > 1e-2
    lay = fa._layout(2, s, 8, d, 8, d, 0, True)
    assert (lay.native, lay.heads_per_prog) == (False, 8)
    text = str(jax.make_jaxpr(loss("pallas"))(q, kk, v))
    for name in ("flash_sel_fwd", "flash_sel_bwd_dq", "flash_sel_bwd_dkv"):
        assert name in text
    # more than 32 tiles a side: more than one plane of words
    assert fa.select_blocks(16384) == (128, 128, 4, 4)
    with pytest.raises(ValueError, match="packed by q block"):
        fa.flash_select_attention(q, kk, v, seg, picked.by_k, picked.by_q,
                                  True)
    with pytest.raises(ValueError, match="select= needs causal"):
        dot_product_attention(q, kk, v, select=select)


def test_index_kernels_against_their_plain_forms(small_blocks, monkeypatch):
    """ops/pallas/sparse_index.py's kernels in interpret mode, through
    `index_select`, the main attention and `index_kl` as the layer calls
    them (index heads of 64, main heads of 128: the shapes the kernels
    take): the same selection to the bit, the KL sum and all three gradients
    against the plain-XLA passes; rows with document boundaries inside a
    tile and a padded tail."""
    _, seg, _ = _packed(2, 384, cuts=CUTS_384)
    seg = jnp.asarray(seg)
    q_idx, k_idx, w_idx, q, kk, v = _index_inputs(2, 384, heads=4, kv=2,
                                                  d=128, j=2, di=64, seed=4)

    def run(impl):
        def f(a, b, c):
            picked, _, _, kl_sum = _layer_order(a, b, c, q, kk, v, seg, 48,
                                                impl)
            return kl_sum, picked
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))(q_idx, k_idx, w_idx)

    traced = lambda: str(jax.make_jaxpr(lambda: _layer_order(  # noqa: E731
        q_idx, k_idx, w_idx, q, kk, v, seg, 48))())
    (want_kl, want), want_grads = run("xla")
    assert "dsa_probs" not in traced()                  # no TPU, no kernels
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    text = traced()
    # the scores once for the selection and once more for the KL term
    assert text.count("name=dsa_index_fwd") == 2 * 2
    assert text.count("name=dsa_probs") == 2
    (got_kl, got), got_grads = run("auto")
    np.testing.assert_array_equal(np.asarray(got.by_q),
                                  np.asarray(want.by_q))
    np.testing.assert_array_equal(np.asarray(got.by_k),
                                  np.asarray(want.by_k))
    assert float(got_kl) == pytest.approx(float(want_kl), rel=1e-5)
    for a, w in zip(got_grads, want_grads):
        assert _rel(a, w) < 1e-5
    # toy widths (index heads of 8) stay in plain XLA
    from bert_pytorch_tpu.ops.pallas import sparse_index as ker
    assert not ker.supported(128, 128, 8, 16)


@pytest.mark.parametrize("chunk", [0, 1, 2])
def test_one_walk_probs_on_the_forward_kernels_log_sum_exp(
        small_blocks, chunk, monkeypatch):
    """`dsa_probs` in interpret mode, fed the log-sum-exp that
    `flash_select_attention` hands out for the same q, k and selection,
    against the plain-XLA `mean_probs`, which takes its own softmax: tiles
    of 128, two documents in a chunk (boundaries at 40, 300, 350), a padded
    tail (380..383: rows that select nothing read zeros), key blocks after
    the chunk's own zeros. ONE walk: the call's grid has one axis, and no
    scratch carries a normaliser from step to step."""
    from bert_pytorch_tpu.ops.pallas import sparse_index as ker

    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    fa = small_blocks
    _, seg, _ = _packed(2, 384, cuts=CUTS_384)
    seg = jnp.asarray(seg)
    q_idx, k_idx, w_idx, q, kk, v = _index_inputs(2, 384, heads=4, kv=2,
                                                  d=128, j=2, di=64, seed=6)
    picked = sparse_index.index_select(q_idx, k_idx, w_idx, seg, 48)
    _, lse = fa.flash_select_attention(q, kk, v, seg, picked.by_q,
                                       picked.by_k, True)
    assert lse.shape == (2, 4, 384) and lse.dtype == jnp.float32
    dense = unpack_select(picked.by_q)
    rows = slice(chunk * 128, (chunk + 1) * 128)
    for b in range(2):
        got = ker.mean_probs(jnp.int32(chunk), q[b, rows], kk[b],
                             lse[b][:, rows], picked.by_q[b][:, rows], 128,
                             True)
        want = sparse_index.mean_probs(q[b, rows], kk[b], dense[b, rows])
        np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-5)
        assert not np.asarray(got)[:, (chunk + 1) * 128:].any()
        real = np.asarray(seg[b, rows]) > 0
        np.testing.assert_allclose(np.asarray(got).sum(-1), real, atol=1e-5)
        # the XLA form on the same log-sum-exp is the same numbers
        np.testing.assert_allclose(
            sparse_index.mean_probs(q[b, rows], kk[b], dense[b, rows],
                                    lse[b][:, rows]), want, atol=1e-6,
            rtol=1e-5)
    jaxpr = jax.make_jaxpr(lambda: ker.mean_probs(
        jnp.int32(chunk), q[0, rows], kk[0], lse[0][:, rows],
        picked.by_q[0][:, rows], 128, True))()
    call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "dsa_probs"
    mapping = call.params["grid_mapping"]
    assert mapping.grid == (3,) and mapping.num_scratch_operands == 0


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_attention_hands_out_its_log_sum_exp_only_with_a_selection(
        small_blocks, impl, monkeypatch):
    """`dot_product_attention(..., with_lse=True)`: a pair (context, lse
    (B, H, S) float32) with `select=`, the kernel's residual or the XLA
    path's `logsumexp` (equal on the real rows); refused without a
    selection; a bare array wherever it is not asked for, so that no other
    family's call changes."""
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    _, seg, _ = _packed(2, 384, cuts=CUTS_384)
    seg = jnp.asarray(seg)
    q_idx, k_idx, w_idx, q, kk, v = _index_inputs(2, 384, heads=8, kv=1,
                                                  d=64, seed=8)
    picked = sparse_index.index_select(q_idx, k_idx, w_idx, seg, 48)
    select = (picked.by_q, picked.by_k)
    attend = lambda **kw: dot_product_attention(  # noqa: E731
        q, kk, v, segment_ids=seg, impl=impl, causal=True, **kw)
    bare = attend(select=select)
    assert isinstance(bare, jax.Array) and bare.shape == q.shape
    assert isinstance(attend(), jax.Array)
    ctx, lse = attend(select=select, with_lse=True)
    np.testing.assert_array_equal(np.asarray(ctx), np.asarray(bare))
    assert lse.shape == (2, 8, 384) and lse.dtype == jnp.float32
    with pytest.raises(ValueError, match="with_lse=True .* needs select="):
        attend(with_lse=True)
    # the log-sum-exp of the scaled scores over each query's selected keys
    dense = unpack_select(picked.by_q)
    s = jnp.einsum("bqhd,bkd->bhqk", q, kk[:, :, 0]) / 8.0
    want = jax.nn.logsumexp(jnp.where(dense[:, None], s, -1e30), axis=-1)
    real = np.broadcast_to((np.asarray(seg) > 0)[:, None, :], lse.shape)
    np.testing.assert_allclose(np.asarray(lse)[real],
                               np.asarray(want)[real], atol=2e-5)
    if impl == "pallas":
        # the kernel's residual is data, not a second differentiable
        # output: a cotangent given to it is dropped
        grad = jax.grad(lambda q: dot_product_attention(
            q, kk, v, segment_ids=seg, impl=impl, causal=True, select=select,
            with_lse=True)[1].sum())(q)
        assert not np.asarray(grad).any()


def _mrope_angles(positions, inv_freq, section):
    """Multimodal rotary angles: `positions` (3, B, S), the temporal, height
    and width components of every token; frequency pair i turns by the
    component its section names (`section`: how many consecutive pairs take
    each component, 16 + 24 + 24 of 64). -> (B, S, len(inv_freq)). The
    program has no such function: on text the three components are the
    position inside the document, which is why the layer calls
    ops/decoder_ops.rotary."""
    inv_freq = jnp.asarray(inv_freq, jnp.float32)
    component = jnp.repeat(jnp.arange(len(section)), jnp.asarray(section),
                           total_repeat_length=inv_freq.shape[0])
    chosen = jnp.take(positions.astype(jnp.float32), component, axis=0)
    return jnp.moveaxis(chosen, 0, -1) * inv_freq


def test_three_equal_position_components_give_the_plain_table():
    """`mrope_section` [16, 24, 24] over 64 frequency pairs: on text the
    temporal, height and width components are one position, and the angles
    are the plain rotate-half table's; with components that differ, pair i
    turns by its section's."""
    theta, d = 1e7, 128
    inv_freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    pos = jnp.asarray(np.arange(40)[None] % 17)
    same = _mrope_angles(jnp.stack([pos] * 3), inv_freq, (16, 24, 24))
    plain = pos.astype(jnp.float32)[:, :, None] * jnp.asarray(
        inv_freq, jnp.float32)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(plain))
    mixed = _mrope_angles(jnp.stack([pos, 2 * pos, 3 * pos]), inv_freq,
                          (16, 24, 24))
    np.testing.assert_allclose(mixed[..., :16], plain[..., :16])
    np.testing.assert_allclose(mixed[..., 16:40], 2 * plain[..., 16:40],
                               rtol=1e-6)
    np.testing.assert_allclose(mixed[..., 40:], 3 * plain[..., 40:], rtol=1e-6)
    # the layer's rotation is that table, cos and sin of it
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 2, d))
    cos = jnp.concatenate([jnp.cos(same)] * 2, -1)[:, :, None]
    sin = jnp.concatenate([jnp.sin(same)] * 2, -1)[:, :, None]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    np.testing.assert_allclose(rotary(x, pos, theta), x * cos + half * sin,
                               atol=1e-6)
    with pytest.raises(NotImplementedError, match="mrope_section"):
        KeyeConfig.from_dict(dict(TOY, rope_scaling={
            "mrope_section": [2, 3, 4]}))


def test_no_leak_across_a_document_boundary(toy):
    """Changing the tokens of a row's second document moves nothing in the
    documents before and after it, to the bit: candidates, selection,
    rotary positions and the KL term's target are a document's own."""
    cfg, sizes, params, model, batch = toy
    lp = params["layer_0"]["attention"]
    seg, pos = batch["segment_ids"][:1], batch["position_ids"][:1]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 96, 64))
    other = x.at[:, 9:70].add(1.0)          # the second document
    run = jax.jit(lambda a: keye.Attention(cfg, jnp.float32).apply(
        {"params": lp}, a, seg, pos)[0])
    a, b = run(x), run(other)
    assert float(jnp.abs(a - b)[:, 9:70].max()) > 1e-3
    assert float(jnp.abs(a - b)[:, :9].max()) == 0.0
    assert float(jnp.abs(a - b)[:, 70:].max()) == 0.0
    assert float(jnp.abs(a)[:, 90:].max()) == 0.0      # padding: nothing


def test_expert_parallel_shares_add_up_to_the_whole_layer():
    """The share ties to the model: the program's routed FFN, told which 2
    of the 16 experts it holds, for each of the 8 ranks: the partial sums
    added up equal the UNCUT reference's routed layer (softmax over the 4
    selected of 16 logits, no shared expert)."""
    whole = dict(TOY, num_experts=16, num_local_experts=16,
                 experts_held=[0, 16])
    sizes = ref.sizes_from_config(whole)
    lp = ref.init_params(SEED, sizes)["layer_1"]["moe"]
    m = jax.random.normal(jax.random.PRNGKey(3), (1, 96, 64), jnp.float32)
    stacks = ("experts_w1", "experts_w3", "experts_w2")
    cfg = KeyeConfig.from_dict(dict(
        TOY, num_experts=2, num_local_experts=2,
        experts_held=[0, 2])).replace(dtype="float32")

    @jax.jit
    def ranks(lp, m):
        total, loads, drops = 0.0, [], []
        for lo in range(0, 16, 2):
            share = dict(lp, **{n: lp[n][lo:lo + 2] for n in stacks})
            out, load, dropped = keye.RoutedExperts(
                cfg.replace(experts_held=(lo, lo + 2)), jnp.float32).apply(
                    {"params": share}, m)
            total = total + out[0]
            loads.append(load)
            drops.append(dropped)
        return total, jnp.concatenate(loads), jnp.stack(drops)

    with jax.default_matmul_precision("highest"):
        want, counts, _ = jax.jit(lambda lp, m: ref._experts(
            m, lp, ref._Sizes(sizes), None, 0.0))(lp, m[0])
        total, loads, drops = ranks(lp, m)
    assert not np.asarray(drops).any()
    loads = np.asarray(loads).tolist()
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=5e-6)
    assert loads == np.asarray(counts).tolist() and sum(loads) == 96 * 4
    # the weights of a token's selected experts sum to 1
    r = jnp.matmul(m[0], lp["router"], precision="highest")
    np.testing.assert_allclose(ref.route(r, ref._Sizes(sizes))[1].sum(-1),
                               1.0, rtol=1e-5)


def test_a_step_carries_both_loss_terms_and_the_selections_counters(toy):
    """training/pretrain.build_pretrain_step averages a loss's `means` over
    the micro-batches as it averages the loss, and sums the scalars;
    telemetry/expert_load.py turns those into the cumulative [perf] fields."""
    import run_pretraining
    from bert_pytorch_tpu.models.families import FAMILIES
    from bert_pytorch_tpu.optim import schedulers
    from bert_pytorch_tpu.training import build_pretrain_step
    from bert_pytorch_tpu.training.state import TrainState

    cfg, sizes, params, model, batch = toy
    sched = schedulers.make_schedule("poly", 0.004, 100, warmup=0.1)
    tx = run_pretraining.make_optimizer("lamb", sched)
    state = TrainState(step=jnp.zeros([], jnp.int32), params=params,
                       opt_state=tx.init(params))
    step = build_pretrain_step(model, tx, schedule=sched, accum_steps=2,
                               **FAMILIES["keye"].step_kwargs)
    stacked = {k: jnp.stack([v, v[::-1]]) for k, v in batch.items()}
    _, metrics = jax.jit(step)(state, stacked, jax.random.PRNGKey(0))
    vals = {k: float(v) for k, v in metrics.items()}
    assert vals["loss"] == pytest.approx(
        vals["lm_loss"] + vals["indexer_kl"], rel=1e-6)
    assert 0.01 < vals["indexer_kl"] < vals["lm_loss"]
    pairs = _expected_pairs(np.asarray(batch["segment_ids"]),
                            np.asarray(batch["position_ids"]), TOPK)
    assert vals["dsa_l0_kb0"] == vals["dsa_l1_kb0"] == 2 * pairs
    counters = FAMILIES["keye"].make_counters()
    counters.update(vals)
    counters.update(vals)
    fields = counters.fields()
    assert fields["dsa_selected_pairs"] == 2 * 2 * 2 * pairs
    causal = sum(n * (n + 1) // 2 for n in (9, 61, 20, 96))
    assert fields["dsa_candidate_pairs"] == 2 * 2 * 2 * causal
    assert fields["dsa_l1_kb0"] == 2 * 2 * pairs
    # the KL term's mean over the real tokens seen: the layers' add up to L_I
    assert fields["dsa_l0_kl"] + fields["dsa_l1_kl"] == pytest.approx(
        vals["indexer_kl"], rel=1e-5)
    assert fields["moe_l1_pairs"] > 0 and "dsa_tokens" not in fields


def test_model_config_family_selection_and_messages(tmp_path):
    def write(d):
        p = tmp_path / f"cfg{len(list(tmp_path.iterdir()))}.json"
        p.write_text(json.dumps(d))
        return str(p)

    cfg = load_model_config(write(dict(
        TOY, source="s", reduced={}, assumed={}, layout="l", published={})))
    assert isinstance(cfg, KeyeConfig)
    assert (cfg.router_scores, cfg.expert_activation, cfg.use_expert_bias,
            cfg.routed_scaling_factor) == ("softmax", "silu", False, 1.0)
    assert cfg.router_width == 16 and cfg.held_range == (4, 8)
    assert (cfg.sa_topk, cfg.sa_indexer_num_heads, cfg.sa_indexer_head_dim,
            cfg.mrope_section) == (12, 4, 8, (2, 3, 3))
    hash(cfg)       # a static field of the modules
    # a key the family does not know is refused by name, nested ones too
    with pytest.raises(ValueError, match="conv_L_cache"):
        load_model_config(write(dict(TOY, conv_L_cache=3)))
    with pytest.raises(ValueError, match=r"sa_config\.block_topk"):
        load_model_config(write(dict(TOY, sa_config=dict(
            TOY["sa_config"], block_topk=4))))
    with pytest.raises(ValueError, match=r"rope_scaling\.factor"):
        load_model_config(write(dict(TOY, rope_scaling=dict(
            TOY["rope_scaling"], factor=4.0))))
    with pytest.raises(ValueError, match="experts_held"):
        load_model_config(write(dict(TOY, experts_held=[14, 18])))
    for change, what in (
            ({"attention_bias": True}, "attention_bias"),
            ({"tie_word_embeddings": True}, "tie_word"),
            ({"mlp_only_layers": [0]}, "mlp_only_layers"),
            ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
            ({"use_sliding_window": True}, "use_sliding_window"),
            ({"num_local_experts": 8}, "num_local_experts"),
            ({"num_attention_heads": 7}, "multiple of num_key_value_heads"),
            ({"sa_config": dict(TOY["sa_config"], indexer_num_kv_heads=2)},
             "indexer_num_kv_heads"),
            # the tiles are the kernels': a chunk size the program would
            # not run at is refused, not run at 512 in silence
            ({"sa_config": dict(TOY["sa_config"], q_chunk_size=256)},
             "q_chunk_size / kv_chunk_size other than 512"),
            ({"sa_config": dict(TOY["sa_config"], kv_chunk_size=1024)},
             "q_chunk_size / kv_chunk_size other than 512")):
        with pytest.raises(NotImplementedError, match=what):
            load_model_config(write(dict(TOY, **change)))


@pytest.mark.parametrize("rows", [1, 16, 64])
def test_candidate_pairs_of_a_step_that_would_wrap_int32(rows):
    """A 16,384-token row holds 134,225,920 causal pairs: 16 rows a step
    pass 2**31. The device sums the count's two halves (int32, as the step
    sums every scalar) and the host joins them."""
    from bert_pytorch_tpu.models.families import FAMILIES
    from bert_pytorch_tpu.ops.pallas.flash_attention import (DEFAULT_BLK_K,
                                                             DEFAULT_BLK_Q)

    row = 16384 * 16385 // 2
    halves = np.asarray(divmod(row, sparse_index.COUNT_UNIT), np.int32)
    hi, lo = np.sum(np.stack([halves] * rows), axis=0, dtype=np.int32)
    counters = FAMILIES["keye"].make_counters()
    for _ in range(2):
        counters.update({"dsa_tokens": rows * 16384,
                         "dsa_l0_candidates_hi": hi,
                         "dsa_l0_candidates_lo": lo,
                         "dsa_l0_kb0": 7, "dsa_l0_kb1": 5})
    fields = counters.fields()
    assert fields["dsa_candidate_pairs"] == 2 * rows * row
    assert fields["dsa_selected_pairs"] == 2 * 12
    assert (rows * row >= 2 ** 31) == (rows >= 16)
    # the chunk whose count is split is the kernels' q block
    assert KeyeConfig._SA_CHUNK == DEFAULT_BLK_Q == DEFAULT_BLK_K


def test_the_decoder_families_refusal_names_the_family(tmp_path):
    import run_pretraining

    cfg_path = tmp_path / "toy.json"
    cfg_path.write_text(json.dumps(TOY))
    with pytest.raises(SystemExit) as e:
        run_pretraining.main([
            "--model_config_file", str(cfg_path), "--input_dir",
            str(tmp_path), "--output_dir", str(tmp_path / "out"),
            "--tensorboard", "off", "--steps_per_loop", "4"])
    message = str(e.value)
    assert ("'lfm2_moe', 'kimi_linear', 'smallthinker', 'laguna', 'keye'"
            in message)
    cfg = load_model_config(str(cfg_path))
    # a full row's FLOPs by the family's own formula
    e_, d, s = 64, 16, 128
    layer = (e_ * (8 + 4) * d + 8 * d * e_ + e_ * (4 * 8 + 8 + 4) + e_ * 16
             + 3 * e_ * 32 * 4 * 4 / 16)
    selected = sum(min(TOPK, t + 1) for t in range(s))
    assert sparse_index.full_row_selected_pairs(s, TOPK) == selected
    assert sparse_index.full_row_selected_pairs(16384, 2048) == 31_458_304
    assert keye.train_flops_per_row(cfg, s) == pytest.approx(
        6.0 * (2048 * e_ + 2 * layer) * s
        + 2 * (14.0 * 8 * d * selected + 6.0 * 4 * 8 * s * (s + 1) // 2))
