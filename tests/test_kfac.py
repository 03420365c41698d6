"""K-FAC tests: factor statistics against hand computation, Cholesky inverse
correctness, preconditioning math on a single linear layer, kl_clip, and the
full tapped-BERT K-FAC train step reducing loss."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bert_pytorch_tpu.config import BertConfig
from bert_pytorch_tpu.models import BertForPreTraining
from bert_pytorch_tpu.optim.kfac import (
    KFAC,
    KFACConfig,
    KFACState,
    _chol_inverse,
)
from bert_pytorch_tpu.optim.lamb import default_weight_decay_mask, lamb
from bert_pytorch_tpu.optim import schedulers
from bert_pytorch_tpu.training import (
    init_kfac_state,
    make_sharded_state,
)
from bert_pytorch_tpu.training.pretrain import (
    build_kfac_pretrain_step,
    stack_microbatches,
)

KFAC_TINY = BertConfig(
    vocab_size=128, hidden_size=32, num_hidden_layers=2,
    num_attention_heads=4, intermediate_size=64,
    max_position_embeddings=64, next_sentence=True,
    dtype="float32", fused_ops=False, attention_impl="xla",
    hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
    kfac_taps=True,
)


def test_chol_inverse():
    rng = np.random.RandomState(0)
    m = rng.randn(16, 16).astype(np.float32)
    spd = m @ m.T + 16 * np.eye(16, dtype=np.float32)
    inv = _chol_inverse(jnp.array(spd))
    np.testing.assert_allclose(np.asarray(inv @ spd), np.eye(16),
                               rtol=1e-3, atol=1e-3)


def test_compute_stats_matches_manual():
    kfac = KFAC(KFACConfig())
    rng = np.random.RandomState(0)
    B, S, DIN, DOUT = 4, 8, 16, 12
    a = rng.randn(B, S, DIN).astype(np.float32)
    g = rng.randn(B, S, DOUT).astype(np.float32)
    acts = {"site": (jnp.array(a),)}          # sown values are 1-tuples
    perts = {"site": jnp.array(g)}
    stats = kfac.compute_stats(acts, perts)["site"]

    rows = B * S
    a2 = np.concatenate([a.reshape(rows, DIN), np.ones((rows, 1))], axis=1)
    want_A = a2.T @ a2 / rows
    g2 = g.reshape(rows, DOUT)
    want_G = g2.T @ g2 * rows
    np.testing.assert_allclose(np.asarray(stats["A"]), want_A, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(stats["G"]), want_G, rtol=1e-4)


def test_compute_stats_stacked_layers():
    kfac = KFAC(KFACConfig())
    rng = np.random.RandomState(0)
    L, B, S, DIN, DOUT = 3, 2, 4, 8, 6
    a = rng.randn(L, B, S, DIN).astype(np.float32)
    g = rng.randn(L, B, S, DOUT).astype(np.float32)
    stats = kfac.compute_stats({"x": (jnp.array(a),)},
                               {"x": jnp.array(g)})["x"]
    assert stats["A"].shape == (L, DIN + 1, DIN + 1)
    assert stats["G"].shape == (L, DOUT, DOUT)
    # layer 1 matches the per-layer manual computation
    rows = B * S
    a1 = np.concatenate([a[1].reshape(rows, DIN), np.ones((rows, 1))], axis=1)
    np.testing.assert_allclose(np.asarray(stats["A"][1]), a1.T @ a1 / rows,
                               rtol=1e-4)


def test_precondition_identity_factors_is_firstorder():
    """With A=G=I inverses, preconditioning only applies the kl_clip scale."""
    cfg = KFACConfig(kl_clip=1e9)  # effectively no clip
    kfac = KFAC(cfg)
    din, dout = 8, 6
    rng = np.random.RandomState(0)
    kg = jnp.array(rng.randn(din, dout).astype(np.float32))
    bg = jnp.array(rng.randn(dout).astype(np.float32))
    grads = {"site": {"kernel": kg, "bias": bg}}
    state = KFACState(
        factors={"site": {"A": jnp.zeros((din + 1, din + 1)),
                          "G": jnp.zeros((dout, dout))}},
        inverses={"site": {"A": jnp.eye(din + 1, dtype=jnp.float32),
                           "G": jnp.eye(dout, dtype=jnp.float32)}},
        count=jnp.zeros([], jnp.int32))
    out = kfac.precondition(state, grads, lr=1.0)
    np.testing.assert_allclose(np.asarray(out["site"]["kernel"]),
                               np.asarray(kg), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out["site"]["bias"]),
                               np.asarray(bg), rtol=1e-5)


def test_kl_clip_scales_down():
    cfg = KFACConfig(kl_clip=1e-4)
    kfac = KFAC(cfg)
    din, dout = 4, 4
    grads = {"site": {"kernel": jnp.full((din, dout), 10.0),
                      "bias": jnp.full((dout,), 10.0)}}
    state = KFACState(
        factors={"site": {"A": jnp.zeros((din + 1, din + 1)),
                          "G": jnp.zeros((dout, dout))}},
        inverses={"site": {"A": jnp.eye(din + 1), "G": jnp.eye(dout)}},
        count=jnp.zeros([], jnp.int32))
    out = kfac.precondition(state, grads, lr=1.0)
    # nu = sqrt(kl_clip / (lr^2 * sum(pre*grad))) = sqrt(1e-4 / 2000) << 1
    want_nu = np.sqrt(1e-4 / (10.0 * 10.0 * (16 + 4)))
    np.testing.assert_allclose(np.asarray(out["site"]["kernel"][0, 0]),
                               10.0 * want_nu, rtol=1e-4)


def test_kfac_preconditioning_whitens_single_layer():
    """For a pure linear regression layer, K-FAC's F^{-1} g should equal the
    Gauss-Newton direction for correlated inputs (up to damping)."""
    rng = np.random.RandomState(0)
    N, DIN, DOUT = 4096, 8, 4
    # strongly correlated inputs
    mix = rng.randn(DIN, DIN).astype(np.float32)
    a = (rng.randn(N, DIN).astype(np.float32) @ mix)
    g = rng.randn(N, DOUT).astype(np.float32) / N  # mean-loss scale

    kfac = KFAC(KFACConfig(damping=1e-4, kl_clip=1e9, stat_decay=0.0,
                           inverse_dtype=jnp.float32))
    acts = {"lin": (jnp.array(a).reshape(1, N, DIN),)}
    perts = {"lin": jnp.array(g).reshape(1, N, DOUT)}
    stats = kfac.compute_stats(acts, perts)
    state = kfac.init(acts, perts)
    state, _ = kfac.step(state, stats, {"lin": {
        "kernel": jnp.zeros((DIN, DOUT)), "bias": jnp.zeros((DOUT,))}}, 1.0)

    # preconditioned grad of W_grad: A^-1 Wg G^-1
    Wg = jnp.array(rng.randn(DIN, DOUT).astype(np.float32))
    bgr = jnp.array(rng.randn(DOUT).astype(np.float32))
    out = kfac.precondition(state, {"lin": {"kernel": Wg, "bias": bgr}}, 1.0)

    rows = N
    a_aug = np.concatenate([a, np.ones((N, 1), np.float32)], 1)
    A = a_aug.T @ a_aug / rows * (1.0)  # stat_decay 0 -> factors == stats
    G = (g.T @ g) * rows
    tr_a = np.trace(A) / A.shape[0]
    tr_g = np.trace(G) / G.shape[0]
    pi = np.sqrt(tr_a / tr_g)
    lam = np.sqrt(1e-4)
    A_inv = np.linalg.inv(A + lam * pi * np.eye(DIN + 1))
    G_inv = np.linalg.inv(G + lam / pi * np.eye(DOUT))
    aug = np.concatenate([np.asarray(Wg), np.asarray(bgr)[None]], 0)
    want = A_inv @ aug @ G_inv
    np.testing.assert_allclose(np.asarray(out["lin"]["kernel"]), want[:-1],
                               rtol=2e-2, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out["lin"]["bias"]), want[-1],
                               rtol=2e-2, atol=1e-4)


def _kfac_setup(accum=1, cfg=None, mesh=None):
    """One K-FAC BERT training setup; with `mesh`, the state is sharded
    under it and the batch is placed per its data sharding — the
    hyperparameters are defined exactly once so mesh/no-mesh runs are
    comparable."""
    from bert_pytorch_tpu.parallel import mesh as mesh_lib

    model = BertForPreTraining(cfg if cfg is not None else KFAC_TINY,
                               dtype=jnp.float32)
    sched = schedulers.poly_warmup_schedule(0.02, total_steps=100, warmup=0.1)
    tx = lamb(sched, weight_decay=0.01,
              weight_decay_mask=default_weight_decay_mask)
    kfac = KFAC(KFACConfig(inv_interval=2, factor_interval=1,
                           stat_decay=0.5, damping=0.003, kl_clip=0.001,
                           learning_rate=sched,
                           inverse_dtype=jnp.float32))

    rng = np.random.RandomState(0)
    B, S = 8, 16
    ids = rng.randint(5, 128, (B, S)).astype(np.int32)
    labels = np.full((B, S), -1, np.int32)
    for b in range(B):
        p = rng.randint(1, S - 1, 2)
        labels[b, p] = ids[b, p]
        ids[b, p] = 3
    batch = stack_microbatches({
        "input_ids": ids,
        "token_type_ids": np.zeros((B, S), np.int32),
        "attention_mask": np.ones((B, S), np.int32),
        "masked_lm_labels": labels,
        "next_sentence_labels": rng.randint(0, 2, (B,)).astype(np.int32),
    }, accum)

    init_fn = lambda r: model.init(r, jnp.asarray(batch["input_ids"][0]),
                                   jnp.asarray(batch["token_type_ids"][0]),
                                   jnp.asarray(batch["attention_mask"][0]))
    if mesh is not None:
        with mesh_lib.logical_rules():
            state, _ = make_sharded_state(jax.random.PRNGKey(0), init_fn, tx,
                                          mesh=mesh)
        batch = mesh_lib.host_to_device_batch(mesh, batch)
    else:
        state, _ = make_sharded_state(jax.random.PRNGKey(0), init_fn, tx)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
    state, pert_template = init_kfac_state(
        model, kfac, state, (batch["input_ids"][0],
                             batch["token_type_ids"][0],
                             batch["attention_mask"][0]))
    step_fn = build_kfac_pretrain_step(model, tx, kfac, pert_template,
                                       schedule=sched, accum_steps=accum)
    return model, kfac, step_fn, state, batch


def test_kfac_bert_step_runs_and_reduces_loss():
    _, kfac, step_fn, state, batch = _kfac_setup()
    jit_step = jax.jit(step_fn, donate_argnums=(0,))
    losses = []
    for i in range(8):
        state, metrics = jit_step(state, batch, jax.random.PRNGKey(i))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses
    assert int(state.precond_state.count) == 8
    # factors actually accumulated (non-zero after EMA updates)
    a_leaf = jax.tree.leaves(state.precond_state.factors)[0]
    assert float(jnp.abs(a_leaf).sum()) > 0


@pytest.mark.slow  # re-tiered out of tier-1's 870s wall-clock budget
def test_kfac_step_invariant_to_data_sharding():
    """Multi-chip K-FAC correctness: the factor statistics contract over the
    batch dimension, which is sharded under SPMD — XLA must turn the local
    a^T a partial products into a global psum, so an 8-way data mesh on the
    same global batch must produce the same factors and the same parameter
    update as a single device (the reference allreduced factors explicitly
    through its comm backend; here the collective falls out of the einsum's
    sharding)."""
    from bert_pytorch_tpu.parallel import mesh as mesh_lib
    import contextlib

    def run(mesh_shape):
        mesh = (mesh_lib.make_mesh(mesh_shape)
                if mesh_shape is not None else None)
        _, _, step_fn, state, batch = _kfac_setup(mesh=mesh)
        jit_step = jax.jit(step_fn, donate_argnums=(0,))
        ctx = (contextlib.nullcontext() if mesh is None
               else contextlib.ExitStack())
        with ctx as stack:
            if mesh is not None:
                stack.enter_context(mesh)
                stack.enter_context(mesh_lib.logical_rules())
            for i in range(3):
                state, metrics = jit_step(state, batch, jax.random.PRNGKey(i))
            jax.block_until_ready(state.params)
        return state, float(metrics["loss"])

    state_1, loss_1 = run(None)
    state_8, loss_8 = run({"data": 8, "fsdp": 1, "model": 1, "seq": 1})

    assert abs(loss_1 - loss_8) < 1e-4, (loss_1, loss_8)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(state_1.params)[0],
            jax.tree_util.tree_flatten_with_path(state_8.params)[0]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
            err_msg=f"params diverge at {jax.tree_util.keystr(pa)}")
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(state_1.precond_state.factors)[0],
            jax.tree_util.tree_flatten_with_path(state_8.precond_state.factors)[0]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
            err_msg=f"factors diverge at {jax.tree_util.keystr(pa)}")


def test_kfac_taps_present_only_when_enabled():
    model_on = BertForPreTraining(KFAC_TINY, dtype=jnp.float32)
    v = model_on.init(jax.random.PRNGKey(0), jnp.ones((2, 8), jnp.int32),
                      jnp.zeros((2, 8), jnp.int32), jnp.ones((2, 8), jnp.int32))
    assert "perturbations" in v
    sites = jax.tree.leaves(v["perturbations"])
    # qkv, attn output, mlp in, mlp out (stacked over layers) + pooler dense
    # and NSP head (unstacked) — reference preconditioned every supported
    # layer minus its skip-list (run_pretraining.py:311-345)
    assert len(sites) == 6
    flat = {"/".join(str(k.key) for k in p): x.shape
            for p, x in jax.tree_util.tree_flatten_with_path(
                v["perturbations"])[0]}
    assert any("pooler" in k for k in flat), flat
    assert any("cls_seq_relationship" in k for k in flat), flat

    model_off = BertForPreTraining(KFAC_TINY.replace(kfac_taps=False),
                                   dtype=jnp.float32)
    v2 = model_off.init(jax.random.PRNGKey(0), jnp.ones((2, 8), jnp.int32),
                        jnp.zeros((2, 8), jnp.int32),
                        jnp.ones((2, 8), jnp.int32))
    assert "perturbations" not in v2


@pytest.mark.parametrize("policy", [
    # re-tiered out of tier-1's 870s wall-clock budget
    pytest.param("nothing", marks=pytest.mark.slow),
    # the default under --checkpoint_activations: the qkv and mlp_output
    # taps' perturb sits just before a saved value
    "dense"])
def test_kfac_taps_under_remat(policy):
    """sow/perturb taps re-fire during nn.remat's recomputed forward:
    K-FAC under activation checkpointing must produce the same loss, grads,
    factor statistics and updated params as the un-rematted model (the
    reference ran K-FAC and checkpointing together,
    run_pretraining.py:257-258,311-345)."""
    def one_step(remat):
        cfg = KFAC_TINY.replace(checkpoint_activations=remat,
                                remat_policy=policy,
                                hidden_dropout_prob=0.0,
                                attention_probs_dropout_prob=0.0)
        _, _, step_fn, state, batch = _kfac_setup(accum=2, cfg=cfg)
        state, metrics = jax.jit(step_fn)(state, batch, jax.random.PRNGKey(1))
        return state, metrics

    s0, m0 = one_step(False)
    s1, m1 = one_step(True)
    assert float(m0["loss"]) == pytest.approx(float(m1["loss"]), abs=1e-6)
    fd = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                      s0.precond_state.factors, s1.precond_state.factors)
    # recomputed forwards can fuse differently; anything beyond fp32
    # round-off noise means a tap mis-fired under remat
    assert max(jax.tree.leaves(fd)) < 1e-6, "factor stats differ under remat"
    pd = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                      s0.params, s1.params)
    assert max(jax.tree.leaves(pd)) < 1e-6, "params diverged under remat"


# --- coalesced factor reductions (--coalesce_reductions, round 15) -------


def _bucketed_setup(factor_bucket_bytes, sync_freq=1, coalesce_norms=True):
    """The kfac_zero1_dp8_bucketed wiring at test scale: zero1 plan +
    NormReducer + bucketed KFAC, exactly as run_pretraining/graphcheck
    build it."""
    from bert_pytorch_tpu.optim.lamb import default_trust_batch_axes
    from bert_pytorch_tpu.parallel import mesh as mesh_lib
    from bert_pytorch_tpu.parallel.coalesce import NormReducer
    from bert_pytorch_tpu.parallel.zero import make_zero1_plan

    mesh = mesh_lib.make_mesh()  # data=8
    model = BertForPreTraining(KFAC_TINY, dtype=jnp.float32)
    sched = schedulers.poly_warmup_schedule(1e-3, total_steps=100,
                                            warmup=0.1)
    rng = np.random.RandomState(0)
    B, S = 16, 16
    ids = rng.randint(5, 128, (B, S)).astype(np.int32)
    labels = np.full((B, S), -1, np.int32)
    for b in range(B):
        for p in rng.choice(np.arange(1, S - 1), 4, replace=False):
            labels[b, p] = ids[b, p]
            ids[b, p] = 3
    batch_np = stack_microbatches({
        "input_ids": ids,
        "token_type_ids": np.zeros((B, S), np.int32),
        "attention_mask": np.ones((B, S), np.int32),
        "masked_lm_labels": labels,
        "next_sentence_labels": rng.randint(0, 2, (B,)).astype(np.int32),
    }, 1)

    def init_fn(r):
        return model.init(r, jnp.asarray(batch_np["input_ids"][0]),
                          jnp.asarray(batch_np["token_type_ids"][0]),
                          jnp.asarray(batch_np["attention_mask"][0]))

    tx = lamb(sched, weight_decay=0.01,
              weight_decay_mask=default_weight_decay_mask,
              trust_batch_axes=default_trust_batch_axes)
    with mesh_lib.logical_rules():
        state, shardings = make_sharded_state(
            jax.random.PRNGKey(0), init_fn, tx, mesh=mesh, zero1=True)
    plan = make_zero1_plan(state.params, shardings.params, mesh,
                           warn_skipped=False)
    reducer = None
    if coalesce_norms and factor_bucket_bytes is not None:
        reducer = NormReducer(plan.grad_shardings, mesh)
        tx = lamb(sched, weight_decay=0.01,
                  weight_decay_mask=default_weight_decay_mask,
                  trust_batch_axes=default_trust_batch_axes,
                  norm_reducer=reducer)
    kfac = KFAC(KFACConfig(learning_rate=sched), mesh=mesh,
                factor_bucket_bytes=factor_bucket_bytes,
                factor_sync_freq=sync_freq)
    state, pert = init_kfac_state(
        model, kfac, state,
        (batch_np["input_ids"][0], batch_np["token_type_ids"][0],
         batch_np["attention_mask"][0]))
    step = build_kfac_pretrain_step(
        model, tx, kfac, pert, schedule=sched, max_predictions=4,
        zero1=plan, norm_reducer=reducer)
    batch = mesh_lib.host_to_device_batch(mesh, batch_np)
    return (mesh, state, jax.jit(step, donate_argnums=(0,)), kfac, batch)


def test_kfac_bucketed_stats_unit_parity():
    """The eager core of the coalescing claim, at unit scale (no XLA BERT
    compile — tier-1 cheap): partial contraction + bucketed psum equals
    the plain reduced statistics (allclose — the plain path's global dot
    groups its summation differently), bucket GRANULARITY is value-free
    bit for bit (psum of a concatenation IS the concatenation of psums),
    and the bucket assignment is deterministic, in site order, recorded
    for the run header. The full train-step restatement (loss
    trajectories, compiled all-reduce <= half) runs as the slow-marked
    test below; the compiled-count criterion is ALSO enforced tier-1 by
    the checked-in kfac_zero1_dp8_bucketed budget
    (tests/test_sharding_rules.py::test_checked_in_report_verifies_cleanly).
    """
    from bert_pytorch_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.make_mesh()  # data=8
    rng = np.random.RandomState(0)
    B, S, DIN, DOUT, L = 16, 8, 16, 12, 2
    acts = {
        "site": (jnp.array(rng.randn(B, S, DIN).astype(np.float32)),),
        "layers": {"x": (jnp.array(
            rng.randn(L, B, S, DIN).astype(np.float32)),)},
    }
    perts = {
        "site": jnp.array(rng.randn(B, S, DOUT).astype(np.float32)),
        "layers": {"x": jnp.array(
            rng.randn(L, B, S, DOUT).astype(np.float32))},
    }
    plain = KFAC(KFACConfig()).compute_stats(acts, perts)

    def reduced(cap):
        k = KFAC(KFACConfig(), mesh=mesh, factor_bucket_bytes=cap)
        assert k.bucketed
        with mesh:
            partial = k.compute_stats(acts, perts)
            # every partial leaf grew the leading batch-shard axis and
            # compiled/executed ZERO collectives (pure local contraction)
            assert all(x.shape[0] == 8
                       for x in jax.tree.leaves(partial))
            return k, k._reduce_stats(partial)

    k_one, red_one = reduced(1)          # every factor its own bucket
    k_big, red_big = reduced(4 << 20)    # one coalesced bucket
    assert len(k_one.bucket_assignment) == 4  # A+G per site, 2 sites
    assert len(k_big.bucket_assignment) == 1
    assert k_big.bucket_assignment[0]["factors"][0].startswith("['layers']")
    for a, b in zip(jax.tree.leaves(red_one), jax.tree.leaves(red_big)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg="bucket granularity changed a reduced factor")
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(red_big)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_kfac_bucketed_reduction_parity():
    """The round-15 acceptance pin, three claims at sync_freq=1:

    1. BUCKETED vs UNBUCKETED reductions bit-identical: cap=1 byte gives
       every factor its own reduction (one psum per factor — the
       unbucketed layout) vs the default cap packing them into one
       bucket; params AND factor state bit-equal over 3 steps, because
       psum of a concatenation IS the concatenation of psums.
    2. vs the LEGACY program (factor_bucket_bytes=None — GSPMD's own
       per-site reductions, which replicate activations for some sites
       and therefore sum in a different grouping): loss trajectory equal
       step for step, factor state allclose at reduction-reorder
       tolerance. Deliberately not bit-equal: XLA replicates activations
       for some sites, a grouping no local contraction reproduces.
    3. the compiled all-reduce count of the bucketed program is <= HALF
       the legacy one (the collective_budget ceiling checked in for
       kfac_zero1_dp8_bucketed enforces the same on the production gate
       model).
    """
    from bert_pytorch_tpu.analysis import collective_counts
    from bert_pytorch_tpu.parallel import mesh as mesh_lib

    mesh, s_leg, step_leg, _, batch = _bucketed_setup(None)
    _, s_one, step_one, k_one, _ = _bucketed_setup(1)
    _, s_big, step_big, k_big, _ = _bucketed_setup(4 << 20)
    assert len(k_one.bucket_assignment) > 1  # per-factor reductions
    assert len(k_big.bucket_assignment) == 1  # one coalesced bucket
    counts = {}
    with mesh, mesh_lib.logical_rules():
        for name, st, fn in (("legacy", s_leg, step_leg),
                             ("bucketed", s_big, step_big)):
            counts[name] = collective_counts(
                fn.lower(st, batch, jax.random.PRNGKey(0))
                .compile().as_text())
        for i in range(3):
            s_leg, m_leg = step_leg(s_leg, batch, jax.random.PRNGKey(i))
            s_one, m_one = step_one(s_one, batch, jax.random.PRNGKey(i))
            s_big, m_big = step_big(s_big, batch, jax.random.PRNGKey(i))
            assert float(m_leg["loss"]) == float(m_big["loss"]), f"step {i}"
            assert float(m_one["loss"]) == float(m_big["loss"]), f"step {i}"
    assert counts["bucketed"]["all-reduce"] \
        <= counts["legacy"]["all-reduce"] // 2, counts
    # claim 1: bucket granularity cannot change a bit
    for what, ta, tb in (
            ("params", s_one.params, s_big.params),
            ("factors", s_one.precond_state.factors,
             s_big.precond_state.factors),
            ("inverses", s_one.precond_state.inverses,
             s_big.precond_state.inverses),
            ("mu", s_one.opt_state.mu, s_big.opt_state.mu)):
        for a, b in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{what}: bucket cap changed the update")
    # claim 2: vs legacy — reduction-reorder tolerance
    for a, b in zip(jax.tree.leaves(s_leg.precond_state.factors),
                    jax.tree.leaves(s_big.precond_state.factors)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)
    for a, b in zip(jax.tree.leaves(s_leg.params),
                    jax.tree.leaves(s_big.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)


def test_kfac_factor_sync_freq_skips_offstep_ema():
    """--kfac_factor_sync_freq=2: factors sync (reduce + EMA) on even
    counts only; the off step leaves the factor state bit-unchanged, the
    on step applies the bucketed reduction inside the cond's true
    branch. Eager at unit scale — the full-step restatement rides the
    slow parity test."""
    from bert_pytorch_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.make_mesh()  # data=8
    rng = np.random.RandomState(1)
    B, S, DIN, DOUT = 16, 4, 6, 5
    acts = {"x": (jnp.array(rng.randn(B, S, DIN).astype(np.float32)),)}
    perts = {"x": jnp.array(rng.randn(B, S, DOUT).astype(np.float32))}
    grads = {"x": {"kernel": jnp.array(
        rng.randn(DIN, DOUT).astype(np.float32)),
        "bias": jnp.array(rng.randn(DOUT).astype(np.float32))}}
    kfac = KFAC(KFACConfig(), mesh=mesh, factor_bucket_bytes=4 << 20,
                factor_sync_freq=2)
    with mesh:
        # tap name 'x' (no _tap suffix needed at unit scale): precondition
        # strips the suffix only when present
        state = kfac.init(acts, perts)
        stats = kfac.compute_stats(acts, perts)
        s1, _ = kfac.step(state, stats, grads, lr=1.0)   # count 0: sync
        s2, _ = kfac.step(s1, stats, grads, lr=1.0)      # count 1: skip
        s3, _ = kfac.step(s2, stats, grads, lr=1.0)      # count 2: sync
    f1 = jax.tree.leaves(jax.tree.map(np.asarray, s1.factors))
    f2 = jax.tree.leaves(jax.tree.map(np.asarray, s2.factors))
    f3 = jax.tree.leaves(jax.tree.map(np.asarray, s3.factors))
    for a, b in zip(f1, f2):
        np.testing.assert_array_equal(a, b)  # off step: EMA skipped
    assert any(not np.array_equal(a, b) for a, b in zip(f2, f3)), \
        "on step must update the factor EMA"
    assert int(s3.count) == 3


def test_kfac_bucketed_nondivisible_fallback_warns(capsys):
    """Rows that don't divide the batch-shard count cannot bucket: the
    instance falls back to the per-factor path with ONE loud warning
    naming the site, keeps producing REDUCED stats (training continues),
    and stays fallen back (the batch shape is fixed per run)."""
    from bert_pytorch_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.make_mesh()  # data=8
    kfac = KFAC(KFACConfig(), mesh=mesh, factor_bucket_bytes=4 << 20)
    assert kfac.bucketed
    rng = np.random.RandomState(0)
    B, S, DIN, DOUT = 12, 8, 16, 12  # 12 % 8 != 0
    acts = {"site": (jnp.array(rng.randn(B, S, DIN).astype(np.float32)),)}
    perts = {"site": jnp.array(rng.randn(B, S, DOUT).astype(np.float32))}
    stats = kfac.compute_stats(acts, perts)
    err = capsys.readouterr().err
    assert "WARNING: kfac: bucketed factor reductions DISABLED" in err
    assert "site" in err
    assert not kfac.bucketed
    # the fallback produced REDUCED stats identical to a plain instance's
    plain = KFAC(KFACConfig()).compute_stats(acts, perts)
    np.testing.assert_array_equal(np.asarray(stats["site"]["A"]),
                                  np.asarray(plain["site"]["A"]))
    # the warning is once-per-instance
    kfac.compute_stats(acts, perts)
    assert "DISABLED" not in capsys.readouterr().err


# -- bf16 factor statistics (--kfac_stats_dtype, round 16) ------------------


def test_kfac_bf16_stats_keep_f32_trajectory():
    """--kfac_stats_dtype bf16 halves the statistics bytes on the wire;
    this pins everything the thinning is NOT allowed to change:

    1. stats_dtype=None emits statistics in factor_dtype — the literal
       round-15 tree (bit for bit), so the default program cannot move
       (the compiled-identity half of that claim is the graphcheck
       budgets staying byte-identical).
    2. bf16 statistics land as bf16 arrays (the cast is on the wire, not
       cosmetic) and agree with the f32 statistics to bf16 rounding.
    3. The EMA accumulator never thins: factors driven by bf16 stats rest
       in f32 and track the f32-stats trajectory within bf16 rounding —
       no drift accumulation, because each step's error enters through a
       (1 - stat_decay)-weighted term.
    4. The bucketed reduction upcasts BEFORE summing: reduced factors of
       bf16 partials come back f32 and match the plain f32 reduction to
       input-rounding tolerance (no bf16 partial-sum cascade).
    """
    from bert_pytorch_tpu.parallel import mesh as mesh_lib

    rng = np.random.RandomState(3)
    B, S, DIN, DOUT, L = 16, 8, 16, 12, 2
    acts = {
        "site": (jnp.array(rng.randn(B, S, DIN).astype(np.float32)),),
        "layers": {"x": (jnp.array(
            rng.randn(L, B, S, DIN).astype(np.float32)),)},
    }
    perts = {
        "site": jnp.array(rng.randn(B, S, DOUT).astype(np.float32)),
        "layers": {"x": jnp.array(
            rng.randn(L, B, S, DOUT).astype(np.float32))},
    }
    k32 = KFAC(KFACConfig())
    kbf = KFAC(KFACConfig(stats_dtype=jnp.bfloat16))

    s32 = k32.compute_stats(acts, perts)
    sbf = kbf.compute_stats(acts, perts)
    sdefault = KFAC(KFACConfig(stats_dtype=None)).compute_stats(acts, perts)
    for a, b in zip(jax.tree.leaves(s32), jax.tree.leaves(sdefault)):
        assert a.dtype == b.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s32), jax.tree.leaves(sbf)):
        assert b.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a),
                                   np.asarray(b, dtype=np.float32),
                                   rtol=2e-2, atol=2e-2)

    # 3-step factor EMA, each step on a fresh stats draw
    f32 = jax.tree.map(lambda s: jnp.zeros_like(s), s32)
    fbf = jax.tree.map(
        lambda s: jnp.zeros_like(s, dtype=jnp.float32), sbf)
    for i in range(3):
        scale = 1.0 + 0.25 * i
        a_i = jax.tree.map(lambda x: x * scale, acts)
        f32 = k32._update_factors(f32, k32.compute_stats(a_i, perts))
        fbf = kbf._update_factors(fbf, kbf.compute_stats(a_i, perts))
    for a, b in zip(jax.tree.leaves(f32), jax.tree.leaves(fbf)):
        assert b.dtype == jnp.float32, "bf16 stats thinned the EMA rest"
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-2)

    mesh = mesh_lib.make_mesh()  # data=8
    kb32 = KFAC(KFACConfig(), mesh=mesh, factor_bucket_bytes=4 << 20)
    kbbf = KFAC(KFACConfig(stats_dtype=jnp.bfloat16), mesh=mesh,
                factor_bucket_bytes=4 << 20)
    assert kb32.bucketed and kbbf.bucketed
    with mesh:
        red32 = kb32._reduce_stats(kb32.compute_stats(acts, perts))
        redbf = kbbf._reduce_stats(kbbf.compute_stats(acts, perts))
    for a, b in zip(jax.tree.leaves(red32), jax.tree.leaves(redbf)):
        assert b.dtype == jnp.float32, "reduction failed to upcast"
        # the contraction of bf16-rounded inputs cancels on the small
        # off-diagonal entries, so the bound is relative to the factor's
        # SCALE (its largest entry), not elementwise — a bf16 partial-sum
        # cascade would blow through this by orders of magnitude
        a, b = np.asarray(a), np.asarray(b)
        assert np.max(np.abs(a - b)) <= 3e-2 * np.max(np.abs(a)) + 1e-6, (
            np.max(np.abs(a - b)), np.max(np.abs(a)))


@pytest.mark.slow
def test_kfac_zero1_rs_bit_identical():
    """--zero1_rs under the full K-FAC step: the psum_scatter gradient
    exit vs the rs_mode='allreduce' arm of the SAME shard_map program —
    params/mu/nu/loss bit-identical over 3 steps while the HLO trades
    all-reduces for reduce-scatters at an unchanged all-gather count.
    This is the budget-combo kfac_zero1_rs_dp8's value-level complement:
    graphcheck pins the counts, this pins that the cheaper program is the
    same training run. (The factor-statistics psums are untouched by the
    rs rewrite — they live outside the shard_map region — which is why
    bucketed K-FAC composes with rs at all.)"""
    from bert_pytorch_tpu.analysis import collective_counts
    from bert_pytorch_tpu.optim.lamb import default_trust_batch_axes
    from bert_pytorch_tpu.parallel import mesh as mesh_lib
    from bert_pytorch_tpu.parallel.coalesce import NormReducer
    from bert_pytorch_tpu.parallel.zero import make_zero1_plan

    mesh = mesh_lib.make_mesh()  # data=8
    model = BertForPreTraining(KFAC_TINY, dtype=jnp.float32)
    sched = schedulers.poly_warmup_schedule(1e-3, total_steps=100,
                                            warmup=0.1)
    rng = np.random.RandomState(0)
    B, S = 16, 16
    ids = rng.randint(5, 128, (B, S)).astype(np.int32)
    labels = np.full((B, S), -1, np.int32)
    for b in range(B):
        p = rng.randint(1, S - 1, 2)
        labels[b, p] = ids[b, p]
        ids[b, p] = 3
    sample = stack_microbatches({
        "input_ids": ids,
        "token_type_ids": np.zeros((B, S), np.int32),
        "attention_mask": np.ones((B, S), np.int32),
        "masked_lm_labels": labels,
        "next_sentence_labels": rng.randint(0, 2, (B,)).astype(np.int32),
    }, 1)
    init_fn = lambda r: model.init(
        r, jnp.asarray(sample["input_ids"][0]),
        jnp.asarray(sample["token_type_ids"][0]),
        jnp.asarray(sample["attention_mask"][0]))

    def make(rs_mode):
        with mesh_lib.logical_rules():
            state, shardings = make_sharded_state(
                jax.random.PRNGKey(0), init_fn, tx=lamb(
                    sched, weight_decay=0.01,
                    weight_decay_mask=default_weight_decay_mask,
                    trust_batch_axes=default_trust_batch_axes),
                mesh=mesh, zero1=True, zero1_params=True)
        plan = make_zero1_plan(state.params, shardings.params, mesh,
                               gather_on_use=True, reduce_scatter=True,
                               warn_skipped=False)
        plan = plan._replace(rs_mode=rs_mode)
        reducer = NormReducer(plan.grad_shardings, mesh)
        tx = lamb(sched, weight_decay=0.01,
                  weight_decay_mask=default_weight_decay_mask,
                  trust_batch_axes=default_trust_batch_axes,
                  norm_reducer=reducer)
        kfac = KFAC(KFACConfig(learning_rate=sched), mesh=mesh,
                    factor_bucket_bytes=4 << 20)
        st, pert = init_kfac_state(
            model, kfac, state,
            (sample["input_ids"][0], sample["token_type_ids"][0],
             sample["attention_mask"][0]))
        step = build_kfac_pretrain_step(
            model, tx, kfac, pert, schedule=sched, max_predictions=4,
            zero1=plan, norm_reducer=reducer)
        return st, jax.jit(step, donate_argnums=(0,))

    batch = mesh_lib.host_to_device_batch(mesh, sample)
    states, steps, counts, losses = {}, {}, {}, {}
    with mesh, mesh_lib.logical_rules():
        for mode in ("scatter", "allreduce"):
            st, fn = make(mode)
            compiled = fn.lower(st, batch, jax.random.PRNGKey(0)).compile()
            counts[mode] = collective_counts(compiled.as_text())
            states[mode], steps[mode] = st, fn
        for i in range(3):
            for mode in states:
                states[mode], m = steps[mode](states[mode], batch,
                                              jax.random.PRNGKey(i))
                losses.setdefault(mode, []).append(float(m["loss"]))

    assert counts["scatter"]["reduce-scatter"] > 0, counts["scatter"]
    assert counts["allreduce"]["reduce-scatter"] == 0, counts["allreduce"]
    assert counts["scatter"]["all-reduce"] < \
        counts["allreduce"]["all-reduce"], counts
    assert counts["scatter"]["all-gather"] == \
        counts["allreduce"]["all-gather"], counts

    assert losses["scatter"] == losses["allreduce"], losses
    sc, ar = states["scatter"], states["allreduce"]
    for what, a_tree, b_tree in (
            ("params", sc.params, ar.params),
            ("mu", sc.opt_state.mu, ar.opt_state.mu),
            ("nu", sc.opt_state.nu, ar.opt_state.nu),
            ("factors", sc.precond_state.factors,
             ar.precond_state.factors)):
        for a, b in zip(jax.tree.leaves(a_tree), jax.tree.leaves(b_tree)):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{what} not bit-identical after 3 steps")
