"""Encoder parameter-layout tests: stacked (nn.scan, leading (L, ...) axis)
vs unstacked (per-layer encoder/layer_{i} modules, config.stacked_params=
False). Covers bit-exact conversion round trips in BOTH directions —
including LAMB moments and K-FAC factor state — forward/grad parity between
the two encoder builds, cross-layout checkpoint restore, and TF-checkpoint
import straight into the unstacked layout."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bert_pytorch_tpu.config import BertConfig
from bert_pytorch_tpu.models import BertForPreTraining, losses
from bert_pytorch_tpu.models.pretrained import (
    convert_tree_layout,
    stack_layer_tree,
    tree_layout,
    unstack_layer_tree,
)
from bert_pytorch_tpu.optim.lamb import (
    default_trust_batch_axes,
    default_weight_decay_mask,
    lamb,
)
from bert_pytorch_tpu.training import (
    CheckpointManager,
    TrainState,
    build_pretrain_step,
    make_sharded_state,
)
from bert_pytorch_tpu.training.pretrain import stack_microbatches
from bert_pytorch_tpu.training.state import unbox

TINY = BertConfig(
    vocab_size=128, hidden_size=32, num_hidden_layers=3,
    num_attention_heads=4, intermediate_size=64,
    max_position_embeddings=64, next_sentence=True,
    dtype="float32", fused_ops=False, attention_impl="xla",
    hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
)
UNSTACKED = TINY.replace(stacked_params=False)


def _inputs(batch=2, seq=16, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(5, TINY.vocab_size, (batch, seq)).astype(np.int32)
    types = rng.randint(0, 2, (batch, seq)).astype(np.int32)
    mask = np.ones((batch, seq), np.int32)
    return jnp.array(ids), jnp.array(types), jnp.array(mask)


def _init_params(cfg, seed=0):
    ids, types, mask = _inputs()
    model = BertForPreTraining(cfg, dtype=jnp.float32)
    params = unbox(model.init(jax.random.PRNGKey(seed), ids, types, mask)
                   ["params"])
    return model, params


def _assert_trees_equal(a, b, exact=True):
    assert (jax.tree_util.tree_structure(a)
            == jax.tree_util.tree_structure(b))
    if exact:
        jax.tree.map(lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)), a, b)
    else:
        jax.tree.map(lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-6, atol=1e-7), a, b)


def test_param_layout_roundtrip_bit_exact_both_directions():
    _, ps = _init_params(TINY)
    _, pu = _init_params(UNSTACKED)
    assert tree_layout(ps) == "stacked"
    assert tree_layout(pu) == "unstacked"

    # stacked -> unstacked: structure matches a fresh unstacked init
    conv = unstack_layer_tree(ps)
    assert (jax.tree_util.tree_structure(conv)
            == jax.tree_util.tree_structure(pu))
    # -> back: bit-exact
    _assert_trees_equal(stack_layer_tree(conv), ps)

    # unstacked -> stacked -> back: bit-exact the other way round
    conv2 = stack_layer_tree(pu)
    assert (jax.tree_util.tree_structure(conv2)
            == jax.tree_util.tree_structure(ps))
    _assert_trees_equal(unstack_layer_tree(conv2), pu)


def test_boxed_init_roundtrip_preserves_partition_metadata():
    """Converting the BOXED init tree must strip/restore the leading
    'layers' logical-axis name so sharding annotations stay valid."""
    ids, types, mask = _inputs()
    boxed_s = BertForPreTraining(TINY, dtype=jnp.float32).init(
        jax.random.PRNGKey(0), ids, types, mask)["params"]
    boxed_u = BertForPreTraining(UNSTACKED, dtype=jnp.float32).init(
        jax.random.PRNGKey(0), ids, types, mask)["params"]
    conv = unstack_layer_tree(boxed_s)
    # structure equality covers the partition names (they live in the
    # pytree treedef of flax's Partitioned boxes)
    assert (jax.tree_util.tree_structure(conv)
            == jax.tree_util.tree_structure(boxed_u))
    back = stack_layer_tree(conv)
    assert (jax.tree_util.tree_structure(back)
            == jax.tree_util.tree_structure(boxed_s))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), boxed_s, back)


def test_forward_and_grad_parity_between_layouts():
    """Same weights through both encoder builds: identical forward, grads
    equal to float tolerance (the unrolled Python loop and the unrolled
    scan may schedule reductions differently)."""
    ids, types, mask = _inputs()
    m_s, ps = _init_params(TINY)
    m_u = BertForPreTraining(UNSTACKED, dtype=jnp.float32)
    pu = unstack_layer_tree(ps)

    out_s, nsp_s = m_s.apply({"params": ps}, ids, types, mask)
    out_u, nsp_u = m_u.apply({"params": pu}, ids, types, mask)
    np.testing.assert_array_equal(np.asarray(out_s), np.asarray(out_u))
    np.testing.assert_array_equal(np.asarray(nsp_s), np.asarray(nsp_u))

    labels = np.full((2, 16), -1, np.int32)
    labels[0, 3], labels[1, 5] = 7, 11
    labels = jnp.array(labels)
    nsl = jnp.array([0, 1], np.int32)

    def make_loss(model):
        def loss(p):
            ml, nl = model.apply({"params": p}, ids, types, mask)
            return losses.pretraining_loss(ml, labels, nl, nsl)
        return loss

    gs = jax.grad(make_loss(m_s))(ps)
    gu = jax.grad(make_loss(m_u))(pu)
    _assert_trees_equal(stack_layer_tree(gu), gs, exact=False)


def test_train_step_parity_between_layouts_on_mesh():
    """One jitted LAMB train step per layout on the 8-device CPU mesh:
    losses match and the updated params agree (converted for comparison).
    Exercises the logical-rule resolution without the 'layers' axis and the
    per-layer trust ratios of the unstacked path."""
    from bert_pytorch_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.make_mesh()
    rng = np.random.RandomState(3)
    gb, seq = 16, 16
    ids = rng.randint(5, TINY.vocab_size, (gb, seq)).astype(np.int32)
    labels = np.full((gb, seq), -1, np.int32)
    for b in range(gb):
        p = rng.randint(1, seq - 1)
        labels[b, p] = ids[b, p]
    batch = stack_microbatches({
        "input_ids": ids,
        "token_type_ids": np.zeros((gb, seq), np.int32),
        "attention_mask": np.ones((gb, seq), np.int32),
        "masked_lm_labels": labels,
        "next_sentence_labels": rng.randint(0, 2, (gb,)).astype(np.int32),
    }, 1)
    batch = {k: jnp.asarray(v) for k, v in batch.items()}

    results = {}
    for name, cfg in (("stacked", TINY), ("unstacked", UNSTACKED)):
        model = BertForPreTraining(cfg, dtype=jnp.float32)
        tx = lamb(1e-3, weight_decay=0.01,
                  weight_decay_mask=default_weight_decay_mask,
                  trust_batch_axes=default_trust_batch_axes)
        step_fn = build_pretrain_step(model, tx)

        def init_fn(r, model=model):
            return model.init(r, batch["input_ids"][0],
                              batch["token_type_ids"][0],
                              batch["attention_mask"][0])

        with mesh_lib.logical_rules():
            state, _ = make_sharded_state(jax.random.PRNGKey(0), init_fn,
                                          tx, mesh=mesh)
        if name == "unstacked":
            # same starting weights as the stacked run, converted
            state = TrainState(step=state.step,
                               params=unstack_layer_tree(
                                   results["stacked"][2]),
                               opt_state=convert_tree_layout(
                                   results["stacked"][3], stacked=False))
        start_params, start_opt = state.params, state.opt_state
        with mesh, mesh_lib.logical_rules():
            state, metrics = jax.jit(step_fn)(state, batch,
                                              jax.random.PRNGKey(1))
        results[name] = (float(metrics["loss"]), state.params,
                         start_params, start_opt)

    loss_s, new_s = results["stacked"][0], results["stacked"][1]
    loss_u, new_u = results["unstacked"][0], results["unstacked"][1]
    np.testing.assert_allclose(loss_u, loss_s, rtol=1e-6)
    _assert_trees_equal(stack_layer_tree(new_u), new_s, exact=False)


def test_optimizer_state_conversion_roundtrip():
    _, ps = _init_params(TINY)
    tx = lamb(1e-3, weight_decay_mask=default_weight_decay_mask,
              trust_batch_axes=default_trust_batch_axes)
    opt = tx.init(ps)
    # put nonzero content into the moments so the test is not vacuous
    grads = jax.tree.map(lambda p: jnp.full_like(p, 0.01), ps)
    _, opt = tx.update(grads, opt, ps)

    down = convert_tree_layout(opt, stacked=False)
    assert tree_layout(down.mu) == "unstacked"
    _assert_trees_equal(convert_tree_layout(down, stacked=True), opt)


@pytest.mark.slow  # re-tiered out of tier-1's 870s wall-clock budget
def test_kfac_state_conversion_and_unstacked_step():
    """K-FAC taps/factors work per layer under the unstacked layout, and a
    stacked KFACState converts to the unstacked tap-tree structure and back
    bit-exact."""
    from bert_pytorch_tpu.optim.kfac import KFAC, KFACConfig
    from bert_pytorch_tpu.training import init_kfac_state
    from bert_pytorch_tpu.training.pretrain import build_kfac_pretrain_step

    ids, types, mask = _inputs()
    rng = np.random.RandomState(5)
    labels = np.full((2, 16), -1, np.int32)
    labels[0, 3], labels[1, 5] = 7, 11
    batch = stack_microbatches({
        "input_ids": np.asarray(ids),
        "token_type_ids": np.asarray(types),
        "attention_mask": np.asarray(mask),
        "masked_lm_labels": labels,
        "next_sentence_labels": rng.randint(0, 2, (2,)).astype(np.int32),
    }, 1)
    batch = {k: jnp.asarray(v) for k, v in batch.items()}

    start = {}  # same starting weights for both layouts
    states = {}
    for name, cfg in (("stacked", TINY), ("unstacked", UNSTACKED)):
        model = BertForPreTraining(cfg.replace(kfac_taps=True),
                                   dtype=jnp.float32)
        kfac = KFAC(KFACConfig(learning_rate=1e-3))
        tx = lamb(1e-3, weight_decay_mask=default_weight_decay_mask,
                  trust_batch_axes=default_trust_batch_axes)

        def init_fn(r, model=model):
            return model.init(r, ids, types, mask)

        state, _ = make_sharded_state(jax.random.PRNGKey(0), init_fn, tx)
        if name == "stacked":
            start["params"] = state.params
            start["opt"] = state.opt_state
        else:
            state = TrainState(
                step=state.step,
                params=unstack_layer_tree(start["params"]),
                opt_state=convert_tree_layout(start["opt"], stacked=False))
        state, pert = init_kfac_state(model, kfac, state,
                                      (ids, types, mask))
        step_fn = build_kfac_pretrain_step(model, tx, kfac, pert,
                                           accum_steps=1)
        new_state, metrics = jax.jit(step_fn)(state, batch,
                                              jax.random.PRNGKey(2))
        assert np.isfinite(float(metrics["loss"]))
        states[name] = new_state

    # the two runs optimize the same function: same loss trajectory start
    # and the stacked KFACState converts to the unstacked structure + back
    kstate_s = states["stacked"].precond_state
    kstate_u = states["unstacked"].precond_state
    down = convert_tree_layout(kstate_s, stacked=False)
    assert (jax.tree_util.tree_structure(down.factors)
            == jax.tree_util.tree_structure(kstate_u.factors))
    _assert_trees_equal(convert_tree_layout(down, stacked=True), kstate_s)
    # factor values agree between the natively-unstacked run and the
    # converted stacked run (same taps, different tree shapes)
    _assert_trees_equal(down.factors, kstate_u.factors, exact=False)


@pytest.mark.parametrize("save_layout", ["stacked", "unstacked"])
def test_checkpoint_cross_layout_restore(tmp_path, save_layout):
    """A checkpoint written under either layout resumes bit-exact into a
    model built with the other (restore_either_layout)."""
    cfg = TINY if save_layout == "stacked" else UNSTACKED
    model, params = _init_params(cfg)
    tx = lamb(1e-3, weight_decay_mask=default_weight_decay_mask,
              trust_batch_axes=default_trust_batch_axes)
    state = TrainState(step=jnp.asarray(7, jnp.int32), params=params,
                       opt_state=tx.init(params))

    mgr = CheckpointManager(str(tmp_path / "ckpts"))
    mgr.save(7, state, extra={"epoch": 1})
    mgr.wait()

    # same-layout restore still works through the tolerant entry point
    same = jax.eval_shape(lambda: state)
    restored, extra, step = mgr.restore_either_layout(same)
    assert step == 7 and extra["epoch"] == 1
    _assert_trees_equal(restored.params, state.params)

    # cross-layout: abstract template in the OTHER layout
    other = convert_tree_layout(state, stacked=(save_layout == "unstacked"))
    abstract = jax.eval_shape(lambda: other)
    restored2, _, _ = mgr.restore_either_layout(abstract)
    assert (tree_layout(restored2.params)
            == ("unstacked" if save_layout == "stacked" else "stacked"))
    _assert_trees_equal(restored2.params, other.params)
    _assert_trees_equal(restored2.opt_state, other.opt_state)
    mgr.close()


def test_tf_conversion_emits_unstacked_layout():
    """convert_tf_to_flax targets whichever layout the config asks for, and
    the two results are each other's conversions."""
    from bert_pytorch_tpu.models import convert_tf_to_flax
    from tests.test_pretrained import CFG, make_tf_vars

    tf_vars = make_tf_vars()
    got_s = convert_tf_to_flax(tf_vars, CFG)
    got_u = convert_tf_to_flax(tf_vars, CFG.replace(stacked_params=False))
    assert tree_layout(got_s) == "stacked"
    assert tree_layout(got_u) == "unstacked"
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), unstack_layer_tree(got_s), got_u)

    # the unstacked tree drops straight into the unstacked model
    model = BertForPreTraining(CFG.replace(stacked_params=False),
                               dtype=jnp.float32)
    ids, types, mask = _inputs()
    want = unbox(model.init(jax.random.PRNGKey(0),
                            jnp.asarray(np.asarray(ids) % CFG.vocab_size),
                            types, mask)["params"])
    assert (jax.tree_util.tree_structure(jax.tree.map(np.shape, got_u))
            == jax.tree_util.tree_structure(jax.tree.map(np.shape, want)))


@pytest.mark.parametrize("policy", [None, "dense", "nothing"])
def test_unstacked_remat_matches_no_remat(policy):
    from tests.test_model import assert_remat_matches

    assert_remat_matches(BertForPreTraining, UNSTACKED, policy, _inputs())
