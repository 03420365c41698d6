"""Sequence-packing tests: the first-fit packer, segment-aware flash
attention parity (both kernel layouts + both backwards + the XLA fallback)
against a block-diagonal dense reference, the bit-exact no-cross-
contamination contract, packed-vs-unpacked loss equality, and StepWatch's
real-token accounting."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bert_pytorch_tpu.data import packing as P

SEQ = 32


# -- first-fit packer -------------------------------------------------------

def test_first_fit_hand_checked_layout():
    # capacity 10, 3 bins: first-fit in arrival order, no sorting
    bins = P.first_fit([6, 5, 4, 3, 2, 9], n_bins=3, capacity=10,
                       max_segments=4)
    # 6->bin0; 5->bin1; 4->bin0 (6+4=10); 3->bin1 (5+3=8); 2->bin1 (10);
    # 9->bin2
    assert bins == [[0, 2], [1, 3, 4], [5]]


def test_first_fit_respects_max_segments():
    bins = P.first_fit([1, 1, 1, 1], n_bins=2, capacity=10, max_segments=2)
    assert bins == [[0, 1], [2, 3]]


def test_first_fit_oversize_raises():
    with pytest.raises(ValueError):
        P.first_fit([11], n_bins=1, capacity=10, max_segments=2)


def test_first_fit_unplaceable_examples_left_out():
    bins = P.first_fit([10, 10, 10], n_bins=2, capacity=10, max_segments=2)
    assert bins == [[0], [1]]  # example 2 fits nowhere — stays pending


def _example_batch(lens, seed=0):
    rng = np.random.RandomState(seed)
    n = len(lens)
    ids = np.zeros((n, SEQ), np.int32)
    tok = np.zeros((n, SEQ), np.int32)
    am = np.zeros((n, SEQ), np.int32)
    lab = np.full((n, SEQ), -1, np.int32)
    for i, L in enumerate(lens):
        ids[i, :L] = rng.randint(5, 64, L)
        ids[i, 0] = 1
        ids[i, L - 1] = 2
        tok[i, L // 2:L] = 1
        am[i, :L] = 1
        mpos = rng.choice(np.arange(1, L - 1), 2, replace=False)
        lab[i, mpos] = ids[i, mpos]
        ids[i, mpos] = 3
    return {"input_ids": ids, "token_type_ids": tok, "attention_mask": am,
            "masked_lm_labels": lab,
            "next_sentence_labels": rng.randint(0, 2, (n,)).astype(np.int32)}


def test_pack_examples_fields():
    lens = [10, 14, 8, 20]
    ex = _example_batch(lens)
    bins = P.first_fit(P.example_lengths(ex["attention_mask"]), 2, SEQ, 4)
    out = P.pack_examples(ex, bins, SEQ, 4)
    assert out["input_ids"].shape == (2, SEQ)
    assert out["next_sentence_labels"].shape == (2, 4)
    for b, members in enumerate(bins):
        seg = out["segment_ids"][b]
        assert int((seg > 0).sum()) == sum(lens[i] for i in members)
        np.testing.assert_array_equal(out["attention_mask"][b], seg > 0)
        for g, ei in enumerate(members):
            idxs = np.nonzero(seg == g + 1)[0]
            L = lens[ei]
            assert len(idxs) == L and (np.diff(idxs) == 1).all()
            # tokens, token types and labels ride across verbatim
            np.testing.assert_array_equal(out["input_ids"][b, idxs],
                                          ex["input_ids"][ei, :L])
            np.testing.assert_array_equal(out["token_type_ids"][b, idxs],
                                          ex["token_type_ids"][ei, :L])
            np.testing.assert_array_equal(out["masked_lm_labels"][b, idxs],
                                          ex["masked_lm_labels"][ei, :L])
            # per-segment position reset + NSP slot
            np.testing.assert_array_equal(out["position_ids"][b, idxs],
                                          np.arange(L))
            assert out["nsp_positions"][b, g] == idxs[0]
            assert (out["next_sentence_labels"][b, g]
                    == ex["next_sentence_labels"][ei])
        # empty slots carry the -1 ignore label
        assert (out["next_sentence_labels"][b, len(members):] == -1).all()


# -- segment-aware flash attention ------------------------------------------

def _packed_qkv(b=2, s=256, h=2, d=64, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.array(rng.randn(b, s, h, d).astype(np.float32)) * 0.5
    seg = np.zeros((b, s), np.int32)
    # segments deliberately spanning the 128-wide tile boundaries
    seg[0, :100] = 1
    seg[0, 100:180] = 2
    seg[0, 180:230] = 3
    seg[1, :60] = 1
    seg[1, 60:200] = 2  # row 1 has a pad tail from 200
    return mk(), mk(), mk(), jnp.array(seg)


def _dense_block_diag(q, k, v, seg):
    """Dense reference: additive block-diagonal mask, fp32 softmax — the
    exact mirror of the in-kernel masking (same -1e30 constant)."""
    from bert_pytorch_tpu.ops.attention import make_segment_attention_bias

    d = q.shape[-1]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                    k.astype(jnp.float32)) / jnp.sqrt(d)
    sc = sc + make_segment_attention_bias(seg)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


@pytest.mark.parametrize("layout,bwd,skip", [
    ("native", "fused", "1"),
    ("native", "fused", "0"),
    ("bh", "fused", "1"),
    ("bh", "split", "1"),
])
def test_flash_segments_match_dense_reference(layout, bwd, skip,
                                              monkeypatch,
                                              force_flash_path):
    """Packed forward/backward vs the block-diagonal dense reference, on
    every kernel path: native + bh layouts, fused + split backwards, block
    skipping on and off. 128-wide blocks force multi-tile rows so the
    skip/cond path genuinely executes. Pad positions (segment 0) are
    excluded: their outputs are unspecified (zero when a tile is skipped,
    uniform-softmax garbage when not) and carry no loss or gradient."""
    fa = importlib.import_module(
        'bert_pytorch_tpu.ops.pallas.flash_attention')

    force_flash_path(layout, bwd)
    monkeypatch.setenv("FLASH_SEG_SKIP", skip)
    monkeypatch.setattr(fa, "DEFAULT_BLK_Q", 128)
    monkeypatch.setattr(fa, "DEFAULT_BLK_K", 128)

    q, k, v, seg = _packed_qkv()
    valid = jnp.array(np.asarray(seg) > 0)

    got = fa.flash_attention(q, k, v, segment_ids=seg, interpret=True)
    want = _dense_block_diag(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(got)[np.asarray(valid)],
                               np.asarray(want)[np.asarray(valid)],
                               rtol=2e-5, atol=2e-5)

    def loss_flash(q, k, v):
        out = fa.flash_attention(q, k, v, segment_ids=seg, interpret=True)
        return jnp.sum(jnp.where(valid[..., None, None], out, 0.0) ** 2)

    def loss_ref(q, k, v):
        out = _dense_block_diag(q, k, v, seg)
        return jnp.sum(jnp.where(valid[..., None, None], out, 0.0) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_flash_segments_with_dropout_layout_parity(monkeypatch,
                                                   force_flash_path):
    """Dropout + segments: native and bh layouts draw identical keep-masks
    (cross-layout bit-parity contract), so outputs agree to float tolerance
    and zero patterns exactly on valid positions."""
    fa = importlib.import_module(
        'bert_pytorch_tpu.ops.pallas.flash_attention')

    monkeypatch.setattr(fa, "DEFAULT_BLK_Q", 128)
    monkeypatch.setattr(fa, "DEFAULT_BLK_K", 128)
    q, k, v, seg = _packed_qkv()
    seed = jnp.array(11, jnp.int32)
    valid = np.asarray(seg) > 0

    outs = {}
    for layout in ("native", "bh"):
        force_flash_path(layout)
        outs[layout] = np.asarray(fa.flash_attention(
            q, k, v, segment_ids=seg, dropout_seed=seed, dropout_rate=0.3,
            interpret=True))
    np.testing.assert_allclose(outs["native"][valid], outs["bh"][valid],
                               rtol=1e-6, atol=1e-6)


def test_flash_segments_no_cross_contamination_bit_identical(
        monkeypatch, force_flash_path):
    """Perturbing every token of segment 1 leaves segments 2 and 3 of the
    same row BIT-identical — cross-segment probabilities are exact fp32
    zeros, not merely small."""
    fa = importlib.import_module(
        'bert_pytorch_tpu.ops.pallas.flash_attention')

    monkeypatch.setattr(fa, "DEFAULT_BLK_Q", 128)
    monkeypatch.setattr(fa, "DEFAULT_BLK_K", 128)
    for layout in ("native", "bh"):
        force_flash_path(layout)
        q, k, v, seg = _packed_qkv()
        seg_np = np.asarray(seg)
        q2 = q.at[0, :100].add(1.0)
        k2 = k.at[0, :100].add(-0.5)
        a = np.asarray(fa.flash_attention(q, k, v, segment_ids=seg,
                                          interpret=True))
        b = np.asarray(fa.flash_attention(q2, k2, v, segment_ids=seg,
                                          interpret=True))
        other = (seg_np[0] > 1)
        assert (a[0, other] == b[0, other]).all()
        # the untouched row is untouched
        assert (a[1] == b[1]).all()


def test_xla_fallback_matches_flash_segments():
    """dot_product_attention(impl='xla') with segment_ids — the parity
    fallback every non-TPU path uses — against the flash kernel in
    interpret mode."""
    from bert_pytorch_tpu.ops import attention
    fa = importlib.import_module(
        'bert_pytorch_tpu.ops.pallas.flash_attention')

    q, k, v, seg = _packed_qkv()
    valid = np.asarray(seg) > 0
    xla = np.asarray(attention.dot_product_attention(
        q, k, v, segment_ids=seg, impl="xla"))
    flash = np.asarray(fa.flash_attention(q, k, v, segment_ids=seg,
                                          interpret=True))
    np.testing.assert_allclose(xla[valid], flash[valid],
                               rtol=2e-5, atol=2e-5)


def test_pad_rows_zeroed_on_every_path(monkeypatch, force_flash_path):
    """Pad (segment-0) positions produce EXACT-zero attention outputs on
    every forward path — both kernel layouts, skip on and off, and the XLA
    fallback — so downstream consumers of full (B, S, E) hidden states
    (K-FAC factor taps) see identical pad activations regardless of kernel
    configuration."""
    from bert_pytorch_tpu.ops import attention

    fa = importlib.import_module(
        'bert_pytorch_tpu.ops.pallas.flash_attention')
    monkeypatch.setattr(fa, "DEFAULT_BLK_Q", 128)
    monkeypatch.setattr(fa, "DEFAULT_BLK_K", 128)
    q, k, v, seg = _packed_qkv()
    pad = np.asarray(seg) == 0
    assert pad.any()
    for layout in ("native", "bh"):
        for skip in ("1", "0"):
            force_flash_path(layout)
            monkeypatch.setenv("FLASH_SEG_SKIP", skip)
            out = np.asarray(fa.flash_attention(q, k, v, segment_ids=seg,
                                                interpret=True))
            assert (out[pad] == 0.0).all(), (layout, skip)
    out = np.asarray(attention.dot_product_attention(
        q, k, v, segment_ids=seg, impl="xla"))
    assert (out[pad] == 0.0).all()


@pytest.mark.slow  # re-tiered out of tier-1's 870s wall-clock budget
def test_packed_forward_backward_on_seq_sharded_mesh():
    """Packing on a data x seq mesh — the composition that raised
    NotImplementedError through round 10. A packed forward+backward
    through the FULL model on the sharded mesh must match the unsharded
    packed reference (loss to the test_packed_loss_equals_unpacked pin,
    grads close), and rewriting segment 1's tokens must leave the other
    segments' MLM logits BIT-identical on the sharded path too."""
    from bert_pytorch_tpu.models import losses
    from bert_pytorch_tpu.parallel import mesh as mesh_lib

    cfg, model = _tiny_model(attention_impl="ring")
    ex, pk = _packed_equivalents()
    # batch 2 (identical rows) so the data axis has something to shard
    pk = {k: np.concatenate([v, v]) for k, v in pk.items()}
    ids, tok, am = (jnp.asarray(pk[k]) for k in
                    ("input_ids", "token_type_ids", "attention_mask"))
    packed_kw = dict(position_ids=jnp.asarray(pk["position_ids"]),
                     segment_ids=jnp.asarray(pk["segment_ids"]),
                     nsp_positions=jnp.asarray(pk["nsp_positions"]))
    params = model.init(jax.random.PRNGKey(0), ids, tok, am)["params"]

    def loss_fn(params, input_ids):
        ml, nl = model.apply({"params": params}, input_ids, tok, am,
                             deterministic=True, **packed_kw)
        return losses.pretraining_loss(
            ml, jnp.asarray(pk["masked_lm_labels"]), nl,
            jnp.asarray(pk["next_sentence_labels"]))

    # unsharded packed reference: impl='ring' without a mesh is the exact
    # dense path
    want, wgrads = jax.value_and_grad(loss_fn)(params, ids)

    mesh = mesh_lib.make_mesh({"data": 2, "seq": 4})
    with mesh, mesh_lib.logical_rules():
        got, ggrads = jax.value_and_grad(loss_fn)(params, ids)
    assert float(got) == pytest.approx(float(want), abs=2e-5)
    for a, b in zip(jax.tree.leaves(wgrads), jax.tree.leaves(ggrads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)

    # bit-exact no-contamination ON the sharded mesh: rewrite segment 1
    def mlm(input_ids):
        with mesh, mesh_lib.logical_rules():
            ml, _ = model.apply({"params": params}, input_ids, tok, am,
                                deterministic=True, **packed_kw)
        return np.asarray(ml)

    seg = np.asarray(pk["segment_ids"])
    ids2 = pk["input_ids"].copy()
    ids2[seg == 1] = 7
    ml_a, ml_b = mlm(ids), mlm(jnp.asarray(ids2))
    other = seg > 1
    np.testing.assert_array_equal(ml_a[other], ml_b[other])
    assert not np.allclose(ml_a[seg == 1], ml_b[seg == 1])


# -- model + loss -----------------------------------------------------------

def _tiny_model(**over):
    from bert_pytorch_tpu.config import BertConfig
    from bert_pytorch_tpu.models import BertForPreTraining

    kw = dict(vocab_size=64, hidden_size=32, num_hidden_layers=2,
              num_attention_heads=4, intermediate_size=64,
              max_position_embeddings=64, next_sentence=True,
              hidden_dropout_prob=0.0,
              attention_probs_dropout_prob=0.0,
              fused_ops=False, attention_impl="xla", dtype="float32")
    kw.update(over)
    cfg = BertConfig(**kw)
    return cfg, BertForPreTraining(cfg, dtype=jnp.float32)


def _packed_equivalents(lens=(10, 14, 8), max_segments=4, seed=0):
    ex = _example_batch(list(lens), seed=seed)
    bins = P.first_fit(P.example_lengths(ex["attention_mask"]), 1, SEQ,
                       max_segments)
    assert bins == [list(range(len(lens)))]  # all fit one row
    return ex, P.pack_examples(ex, bins, SEQ, max_segments)


def test_packed_loss_equals_unpacked():
    """The hand-checkable loss contract: one packed row of 3 examples (2
    masked tokens each) produces EXACTLY the unpacked batch's MLM+NSP loss,
    which (equal mask counts) also equals the mean of the per-example
    losses."""
    from bert_pytorch_tpu.models import losses

    cfg, model = _tiny_model()
    ex, pk = _packed_equivalents()
    ids, tok, am = (jnp.asarray(ex[k]) for k in
                    ("input_ids", "token_type_ids", "attention_mask"))
    params = model.init(jax.random.PRNGKey(0), ids, tok, am)["params"]

    ml, nl = model.apply({"params": params}, ids, tok, am,
                         deterministic=True)
    unpacked = float(losses.pretraining_loss(
        ml, jnp.asarray(ex["masked_lm_labels"]), nl,
        jnp.asarray(ex["next_sentence_labels"])))

    per_example = []
    for i in range(ids.shape[0]):
        mli, nli = model.apply({"params": params}, ids[i:i + 1],
                               tok[i:i + 1], am[i:i + 1],
                               deterministic=True)
        per_example.append(float(losses.pretraining_loss(
            mli, jnp.asarray(ex["masked_lm_labels"][i:i + 1]), nli,
            jnp.asarray(ex["next_sentence_labels"][i:i + 1]))))

    mlp, nlp = model.apply(
        {"params": params}, jnp.asarray(pk["input_ids"]),
        jnp.asarray(pk["token_type_ids"]),
        jnp.asarray(pk["attention_mask"]), deterministic=True,
        position_ids=jnp.asarray(pk["position_ids"]),
        segment_ids=jnp.asarray(pk["segment_ids"]),
        nsp_positions=jnp.asarray(pk["nsp_positions"]))
    assert nlp.shape == (1, 4, 2)  # per-segment NSP logits
    packed = float(losses.pretraining_loss(
        mlp, jnp.asarray(pk["masked_lm_labels"]), nlp,
        jnp.asarray(pk["next_sentence_labels"])))

    assert packed == pytest.approx(unpacked, abs=2e-5)
    assert packed == pytest.approx(np.mean(per_example), abs=2e-5)


def test_packed_model_no_cross_contamination_bit_identical():
    """End-to-end through the full model (XLA attention path): perturbing
    segment 1's tokens leaves segment 2/3 MLM logits and their NSP logits
    bit-identical."""
    cfg, model = _tiny_model()
    ex, pk = _packed_equivalents()
    params = model.init(
        jax.random.PRNGKey(0), jnp.asarray(ex["input_ids"]),
        jnp.asarray(ex["token_type_ids"]),
        jnp.asarray(ex["attention_mask"]))["params"]

    def run(input_ids):
        return model.apply(
            {"params": params}, jnp.asarray(input_ids),
            jnp.asarray(pk["token_type_ids"]),
            jnp.asarray(pk["attention_mask"]), deterministic=True,
            position_ids=jnp.asarray(pk["position_ids"]),
            segment_ids=jnp.asarray(pk["segment_ids"]),
            nsp_positions=jnp.asarray(pk["nsp_positions"]))

    ids2 = pk["input_ids"].copy()
    seg = pk["segment_ids"][0]
    ids2[0, seg == 1] = 7  # rewrite every token of segment 1
    ml_a, nsp_a = run(pk["input_ids"])
    ml_b, nsp_b = run(ids2)
    other = np.asarray(seg) > 1
    assert (np.asarray(ml_a)[0, other] == np.asarray(ml_b)[0, other]).all()
    # segment 1's NSP slot changes; segments 2 and 3 stay bit-identical.
    # (Empty slots gather row position 0 — segment 1's [CLS] — by design;
    # their label is -1 so the loss never reads them.)
    n_real = int(np.asarray(seg).max())
    assert (np.asarray(nsp_a)[0, 1:n_real]
            == np.asarray(nsp_b)[0, 1:n_real]).all()
    assert not (np.asarray(nsp_a)[0, 0] == np.asarray(nsp_b)[0, 0]).all()


@pytest.mark.parametrize("policy", [None, "nothing"])
def test_packed_model_remat_variants(policy):
    """The segment threading survives nn.remat (static_argnums shifted to
    4) under the policy that saves the projections and the one that saves
    nothing: logits AND gradients are the plain stacked model's."""
    from bert_pytorch_tpu.models import BertForPreTraining
    from tests.test_model import assert_remat_matches

    _, pk = _packed_equivalents()
    cfg, _ = _tiny_model(hidden_dropout_prob=0.1,
                         attention_probs_dropout_prob=0.1)
    assert_remat_matches(
        BertForPreTraining, cfg, policy,
        tuple(jnp.asarray(pk[k]) for k in
              ("input_ids", "token_type_ids", "attention_mask")),
        position_ids=jnp.asarray(pk["position_ids"]),
        segment_ids=jnp.asarray(pk["segment_ids"]),
        nsp_positions=jnp.asarray(pk["nsp_positions"]))


def test_packed_model_unstacked_variant():
    """... and the unstacked per-layer encoder: the same logits as the
    plain stacked forward."""
    ex, pk = _packed_equivalents()
    args = dict(deterministic=True,
                position_ids=jnp.asarray(pk["position_ids"]),
                segment_ids=jnp.asarray(pk["segment_ids"]),
                nsp_positions=jnp.asarray(pk["nsp_positions"]))
    ids, tok, am = (jnp.asarray(pk[k]) for k in
                    ("input_ids", "token_type_ids", "attention_mask"))

    cfg, base = _tiny_model()
    params = base.init(jax.random.PRNGKey(0), ids, tok, am)["params"]
    want_ml, want_nsp = base.apply({"params": params}, ids, tok, am, **args)

    from bert_pytorch_tpu.models import BertForPreTraining
    from bert_pytorch_tpu.models.pretrained import unstack_layer_tree

    unstacked = BertForPreTraining(cfg.replace(stacked_params=False),
                                   dtype=jnp.float32)
    got_ml, got_nsp = unstacked.apply(
        {"params": unstack_layer_tree(params)}, ids, tok, am, **args)
    np.testing.assert_allclose(np.asarray(got_ml), np.asarray(want_ml),
                               rtol=1e-5, atol=1e-5)


def test_pretrain_step_accepts_packed_batch():
    """build_pretrain_step threads the packed fields end to end: one
    optimizer step over a packed batch runs, updates params, and reports
    finite metrics (the K-FAC builder shares the same _packed_kwargs
    plumbing)."""
    import optax

    from bert_pytorch_tpu.training.pretrain import (build_pretrain_step,
                                                    stack_microbatches)

    cfg, model = _tiny_model()
    ex, pk = _packed_equivalents()
    batch = stack_microbatches(pk, 1)
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    params = model.init(
        jax.random.PRNGKey(0), batch["input_ids"][0],
        batch["token_type_ids"][0], batch["attention_mask"][0])["params"]
    tx = optax.sgd(1e-2)

    from bert_pytorch_tpu.training.state import TrainState

    state = TrainState(step=jnp.zeros([], jnp.int32), params=params,
                       opt_state=tx.init(params))
    step = build_pretrain_step(model, tx, accum_steps=1, max_predictions=8)
    new_state, metrics = jax.jit(step)(state, batch, jax.random.PRNGKey(1))
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["mlm_accuracy"]) >= 0.0
    # params moved
    leaf = jax.tree.leaves(params)[0]
    new_leaf = jax.tree.leaves(new_state.params)[0]
    assert not np.allclose(np.asarray(leaf), np.asarray(new_leaf))


# -- telemetry --------------------------------------------------------------

def test_stepwatch_real_tokens_and_pad_fraction():
    from bert_pytorch_tpu.telemetry.stepwatch import StepWatch

    t = [0.0]
    sw = StepWatch(flops_per_step=1e9, seqs_per_step=4, seq_len=128,
                   peak_flops=1e12, log_freq=2, time_fn=lambda: t[0])
    # two steps, 4 rows x 128 slots each = 1024 slot tokens, 768 real
    sw.note_tokens(384)
    t[0] += 1.0
    assert sw.step_done() is None
    sw.note_tokens(384)
    t[0] += 1.0
    rec = sw.step_done()
    assert rec is not None
    assert rec["real_tokens_per_sec"] == pytest.approx(768 / 2.0)
    assert rec["packing_efficiency"] == pytest.approx(768 / 1024)
    assert rec["pad_fraction"] == pytest.approx(1 - 768 / 1024)
    # tokens_per_sec still counts slots — the hardware-occupancy number
    assert rec["tokens_per_sec"] == pytest.approx(4 * 128 * 2 / 2.0)
    # without note_tokens the fields stay absent (pre-round-9 records)
    sw2 = StepWatch(flops_per_step=1e9, seqs_per_step=4, seq_len=128,
                    peak_flops=1e12, log_freq=1, time_fn=lambda: t[0])
    t[0] += 1.0
    rec2 = sw2.step_done()
    assert "pad_fraction" not in rec2 and "real_tokens_per_sec" not in rec2


def test_packing_efficiency_helper():
    seg = np.array([[1, 1, 2, 0], [1, 0, 0, 0]])
    assert P.packing_efficiency(seg) == pytest.approx(4 / 8)
