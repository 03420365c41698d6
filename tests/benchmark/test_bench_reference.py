"""The plain reference against the program's model at a tiny size (CPU):
they agree in float32, the comparison fails when the model side computes
in a lower precision, and the lower-precision control (the reference in
fp8) moves the compared numbers by more than the sound side does."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import adapter  # noqa: E402
from benchmark.reference import bert_ref  # noqa: E402

CFG = {"vocab_size": 500, "hidden_size": 128, "num_hidden_layers": 2,
       "num_attention_heads": 2, "intermediate_size": 256,
       "max_position_embeddings": 64, "type_vocab_size": 2,
       "hidden_dropout_prob": 0.0, "attention_probs_dropout_prob": 0.0,
       "next_sentence": True, "fused_ops": False}
SIZES = bert_ref.sizes_from_config(CFG, 128)       # 512 vocabulary rows
HEADS, MAX_PRED, F32_TOL = 2, 6, 2e-4


def _batch(seed, packed):
    rng = np.random.RandomState(seed)
    b, s = 4, 32
    ids = rng.randint(5, 500, (b, s)).astype(np.int32)
    types = rng.randint(0, 2, (b, s)).astype(np.int32)
    labels = np.full((b, s), -1, np.int32)
    for r in range(b):
        for p in rng.choice(np.arange(1, 20), 4, replace=False):
            labels[r, p] = rng.randint(5, 500)
    if not packed:
        mask = np.ones((b, s), np.int32)
        mask[:, 26:] = 0
        ids[:, 26:] = 0
        return {"input_ids": ids, "token_type_ids": types,
                "attention_mask": mask, "masked_lm_labels": labels,
                "next_sentence_labels": rng.randint(0, 2, b).astype(np.int32)}
    # two documents (12 and 14 tokens) and 6 pad slots per row
    seg = np.array([1] * 12 + [2] * 14 + [0] * 6, np.int32)
    pos = np.array(list(range(12)) + list(range(14)) + [0] * 6, np.int32)
    ids[:, 26:] = 0
    labels[:, 26:] = -1
    nsp = np.full((b, 8), -1, np.int32)
    nsp[:, :2] = rng.randint(0, 2, (b, 2))
    cls = np.zeros((b, 8), np.int32)
    cls[:, 1] = 12
    return {"input_ids": ids, "token_type_ids": types,
            "attention_mask": (seg > 0).astype(np.int32)[None].repeat(b, 0),
            "segment_ids": seg[None].repeat(b, 0),
            "position_ids": pos[None].repeat(b, 0),
            "masked_lm_labels": labels, "next_sentence_labels": nsp,
            "nsp_positions": cls}


def _program_loss_and_grads(params, batch, dtype, micro_key=None, **cfg):
    """The program's loss and gradients; with `micro_key`, a training
    forward at dropout 0.1 drawing from that micro-batch key."""
    from bert_pytorch_tpu.config import BertConfig
    from bert_pytorch_tpu.models import BertForPreTraining
    from bert_pytorch_tpu.training.pretrain import _pretrain_loss_fn

    if micro_key is not None:
        cfg = dict(cfg, hidden_dropout_prob=0.1,
                   attention_probs_dropout_prob=0.1)
    cfg = BertConfig.from_dict(dict(CFG, vocab_size=SIZES["vocab_rows"],
                                    **cfg))
    model = BertForPreTraining(cfg, dtype=dtype)
    loss_fn = _pretrain_loss_fn(model, MAX_PRED)

    def loss(p):
        if dtype != jnp.float32:
            p = jax.tree.map(lambda x: x.astype(dtype), p)
        return loss_fn(p, {k: jnp.asarray(v) for k, v in batch.items()},
                       micro_key, deterministic=micro_key is None)[0]

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss))(params)


def _long(batch, seq):
    """`batch` (32 positions) tiled to `seq`, packed documents kept apart."""
    reps = seq // 32
    out = {k: (np.tile(v, (1, reps)) if v.ndim == 2 and v.shape[1] == 32
               else v) for k, v in batch.items()}
    if "segment_ids" in out:
        seg = out["segment_ids"].copy()
        for r in range(reps):
            blk = seg[:, r * 32:(r + 1) * 32]
            blk[blk > 0] += 2 * r
        out["segment_ids"] = seg
    return out


@pytest.fixture(scope="module")
def weights():
    ref = bert_ref.init_params(1234, SIZES)
    # biases and gains off their trivial initial values, so that every term
    # of the forward pass is exercised
    leaves, treedef = jax.tree.flatten(ref)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    ref = jax.tree.unflatten(treedef, [
        x + 0.02 * jax.random.normal(k, x.shape) for k, x in
        zip(keys, leaves)])
    return ref, adapter.to_program_tree(ref, HEADS)


@pytest.mark.parametrize("packed", [False, True],
                         ids=["one-document-rows", "packed-rows"])
def test_reference_agrees_with_the_program_in_float32(weights, packed):
    ref, prog = weights
    batch = _batch(3, packed)
    want_loss, want = bert_ref.step_loss_and_grad(ref, [batch], HEADS,
                                                  MAX_PRED)
    got_loss, got = _program_loss_and_grads(prog, batch, jnp.float32)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5 * float(want_loss)
    gap = adapter.worst_gap(
        adapter.leaf_norms(got),
        adapter.leaf_norms(adapter.to_program_tree(want, HEADS)))
    assert gap["gap"] < F32_TOL, gap


@pytest.mark.parametrize("packed,seq,cfg", [
    (False, 32, {}),
    (True, 32, {"checkpoint_activations": True}),
    (False, 32, {"fused_ops": True, "checkpoint_activations": True}),
    (False, 384, {"fused_ops": True, "max_position_embeddings": 384}),
    (True, 384, {"fused_ops": True, "max_position_embeddings": 384}),
], ids=["xla-rows", "xla-packed-remat", "fused-kernels-remat",
        "flash-rows", "flash-packed"])
def test_reference_draws_the_programs_dropout_masks(monkeypatch, packed,
                                                    seq, cfg):
    """A training forward at dropout 0.1: the reference, computing every
    site's seed and mask from the stated rule, lands on the program's loss
    and gradients to float32 noise, on the XLA paths, through the fused
    dropout-LN kernels and through the flash kernel (interpret mode), with
    and without remat. Without the masks the loss is 3e-3 away."""
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    sizes = bert_ref.sizes_from_config(dict(CFG, **cfg), 128)
    ref = bert_ref.init_params(1234, sizes)
    batch = _long(_batch(3, packed), seq) if seq > 32 else _batch(3, packed)
    step_key = jax.random.PRNGKey(99)
    flash = seq > 256
    seeds = bert_ref.dropout_seeds(step_key, 2, sizes["layers"], flash)
    second = jax.tree.map(lambda x: x[1:], seeds)
    want_loss, want = bert_ref.step_loss_and_grad(
        ref, [batch], HEADS, MAX_PRED, None, (0.1, 0.1, flash, second))
    plain_loss, _ = bert_ref.step_loss_and_grad(ref, [batch], HEADS,
                                                MAX_PRED)
    got_loss, got = _program_loss_and_grads(
        adapter.to_program_tree(ref, HEADS), batch, jnp.float32,
        jax.random.split(step_key, 2)[1], **cfg)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert abs(float(got_loss) - float(plain_loss)) > 1e-4 * float(want_loss)
    gap = adapter.worst_gap(
        adapter.leaf_norms(got),
        adapter.leaf_norms(adapter.to_program_tree(want, HEADS)))
    assert gap["gap"] < F32_TOL, gap
    diff = adapter.diff_gap(
        adapter.sample_matrices(got),
        adapter.sample_matrices(adapter.to_program_tree(want, HEADS)))
    assert diff < 1e-4, diff


@pytest.mark.parametrize("flash", [False, True], ids=["row-col", "flash"])
def test_dropout_masks_keep_nine_tenths_and_follow_the_key(flash):
    a = bert_ref.dropout_seeds(jax.random.PRNGKey(1), 4, 3, flash)
    b = bert_ref.dropout_seeds(jax.random.PRNGKey(1), 4, 3, flash)
    c = bert_ref.dropout_seeds(jax.random.PRNGKey(2), 4, 3, flash)
    assert a["probs"].shape == (4, 3) and a["emb"].shape == (4,)
    assert (np.asarray(a["probs"]) == np.asarray(b["probs"])).all()
    assert (np.asarray(a["probs"]) != np.asarray(c["probs"])).any()
    # every site of every layer and micro-batch has a seed of its own
    every = np.concatenate([np.asarray(v).ravel() for v in a.values()])
    assert len(set(every.tolist())) == every.size
    keep = (bert_ref._keep_flash if flash else bert_ref._keep_row_col)(
        a["probs"][0, 0], (4, 2, 64, 64), 0.1)
    assert keep.shape == (4, 2, 64, 64)
    assert float(keep.mean()) == pytest.approx(0.9, abs=0.01)
    other = (bert_ref._keep_flash if flash else bert_ref._keep_row_col)(
        a["probs"][0, 1], (4, 2, 64, 64), 0.1)
    assert float((keep == other).mean()) == pytest.approx(0.82, abs=0.02)


def test_sampled_matrices_and_the_norm_of_a_difference():
    ref = bert_ref.init_params(7, SIZES)
    tree = adapter.to_program_tree(ref, HEADS)
    sample = adapter.sample_matrices(tree)
    assert len(sample) == 4 * SIZES["layers"]      # 2 layers: first and last
    assert all(v.dtype == np.float32 for v in sample.values())
    assert adapter.diff_gap(sample, sample) == 0.0
    off = {k: v * 1.01 for k, v in sample.items()}
    assert adapter.diff_gap(off, sample) == pytest.approx(0.01, rel=1e-3)
    with pytest.raises(KeyError):
        adapter.diff_gap(dict(list(sample.items())[1:]), sample)
    key = jax.random.PRNGKey(5)
    assert adapter.key_data(key).dtype == np.uint32
    assert (adapter.key_data(jax.random.key(5))
            == adapter.key_data(key)).all()


def test_comparison_fails_when_the_model_side_is_cast_lower(weights):
    ref, prog = weights
    batch = _batch(4, False)
    _, want = bert_ref.step_loss_and_grad(ref, [batch], HEADS, MAX_PRED)
    _, got = _program_loss_and_grads(prog, batch, jnp.bfloat16)
    gap = adapter.worst_gap(
        adapter.leaf_norms(got),
        adapter.leaf_norms(adapter.to_program_tree(want, HEADS)))
    assert gap["gap"] > 3 * F32_TOL, gap


def test_fp8_control_moves_the_numbers_more_than_float32_noise(weights):
    ref, _ = weights
    batch = _batch(5, False)
    loss, want = bert_ref.step_loss_and_grad(ref, [batch], HEADS, MAX_PRED)
    ctl_loss, ctl = bert_ref.step_loss_and_grad(ref, [batch], HEADS,
                                                MAX_PRED, "fp8")
    gap = adapter.worst_gap(adapter.leaf_norms(ctl),
                            adapter.leaf_norms(want))
    assert gap["gap"] > 3 * F32_TOL, gap
    assert abs(float(ctl_loss) - float(loss)) > 1e-5 * float(loss)
    # the number the cells' limits separate the control by (here against
    # the float32 program's gradient):
    ref_prog = adapter.to_program_tree(want, HEADS)
    ctl_prog = adapter.to_program_tree(ctl, HEADS)
    _, got = _program_loss_and_grads(weights[1], batch, jnp.float32)
    # the norm of the gradient's difference over sampled encoder matrices
    want_sample = adapter.sample_matrices(ref_prog)
    sound = adapter.diff_gap(adapter.sample_matrices(got), want_sample)
    control = adapter.diff_gap(adapter.sample_matrices(ctl_prog),
                               want_sample)
    assert control > 0.03 and control > 30 * sound, (sound, control)


def test_lamb_step_follows_the_programs_optimizer(weights):
    from bert_pytorch_tpu.optim import schedulers
    from bert_pytorch_tpu.optim.lamb import (default_trust_batch_axes,
                                             default_weight_decay_mask, lamb)

    ref, prog = weights
    sched = schedulers.make_schedule("poly", 6e-3, 100, warmup=0.1)
    tx = lamb(sched, weight_decay=0.01,
              weight_decay_mask=default_weight_decay_mask,
              trust_batch_axes=default_trust_batch_axes)
    opt_p, opt_r = tx.init(prog), bert_ref.lamb_init(ref)
    for step in range(3):
        batch = _batch(10 + step, False)
        _, g_ref = bert_ref.step_loss_and_grad(ref, [batch], HEADS, MAX_PRED)
        g_prog = adapter.to_program_tree(g_ref, HEADS)
        updates, opt_p = tx.update(g_prog, opt_p, prog)
        prog = jax.tree.map(jnp.add, prog, updates)
        ref, opt_r = bert_ref.lamb_step(ref, g_ref, opt_r, 6e-3, 100, 0.1)
    moved = adapter.leaf_diff_norms(prog, weights[1])
    diff = adapter.leaf_diff_norms(prog, adapter.to_program_tree(ref, HEADS))
    for key in moved:
        assert (diff[key] <= 1e-3 * moved[key] + 1e-9).all(), key
    assert max(v.max() for v in moved.values()) > 0   # step 1 has lr 0


def test_weights_are_a_function_of_the_seed_alone():
    a = bert_ref.init_params(2**31 + 7, SIZES)
    b = bert_ref.init_params(2**31 + 7, SIZES)
    c = bert_ref.init_params(7, SIZES)
    assert (np.asarray(a["word"]) == np.asarray(b["word"])).all()
    assert (np.asarray(a["word"]) != np.asarray(c["word"])).any()
    assert float(jnp.std(a["layers"]["w1"])) == pytest.approx(0.02, rel=0.05)
    assert float(jnp.abs(a["layers"]["b1"]).max()) == 0.0
