"""ZeRO-1 optimizer-state sharding (parallel/zero.py) on the 8-device CPU
mesh: spec derivation units, sharded-vs-replicated update parity (params
bit-close over multiple steps, trust ratios preserved), moments born AND
kept sharded, checkpoint round-trip of sharded moments, the promoted
zero-reshard compile gate (2x2 mesh), the overlap flag pack, and the
dryrun's known-noise stderr filter."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from bert_pytorch_tpu.config import BertConfig
from bert_pytorch_tpu.models import BertForPreTraining
from bert_pytorch_tpu.optim import schedulers
from bert_pytorch_tpu.optim.lamb import (default_trust_batch_axes,
                                         default_weight_decay_mask, lamb)
from bert_pytorch_tpu.parallel import mesh as mesh_lib
from bert_pytorch_tpu.parallel.zero import (assert_moments_sharded,
                                            make_zero1_plan, zero1_spec)
from bert_pytorch_tpu.training import (CheckpointManager,
                                       build_pretrain_step,
                                       make_sharded_state)
from bert_pytorch_tpu.training.pretrain import stack_microbatches

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY = BertConfig(
    vocab_size=128, hidden_size=32, num_hidden_layers=2,
    num_attention_heads=4, intermediate_size=64,
    max_position_embeddings=64, next_sentence=True,
    dtype="float32", fused_ops=False, attention_impl="xla",
    hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
)


def _batch(global_batch=16, seq=16, vocab=128, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(5, vocab, (global_batch, seq)).astype(np.int32)
    labels = np.full((global_batch, seq), -1, np.int32)
    for b in range(global_batch):
        for p in rng.randint(1, seq - 1, (2,)):
            labels[b, p] = ids[b, p]
            ids[b, p] = 3
    return stack_microbatches({
        "input_ids": ids,
        "token_type_ids": np.zeros((global_batch, seq), np.int32),
        "attention_mask": np.ones((global_batch, seq), np.int32),
        "masked_lm_labels": labels,
        "next_sentence_labels": rng.randint(0, 2, (global_batch,)).astype(
            np.int32),
    }, 1)


def _tx():
    sched = schedulers.poly_warmup_schedule(1e-3, total_steps=100, warmup=0.1)
    return lamb(sched, weight_decay=0.01,
                weight_decay_mask=default_weight_decay_mask,
                trust_batch_axes=default_trust_batch_axes), sched


def _setup(mesh, zero1):
    model = BertForPreTraining(TINY, dtype=jnp.float32)
    tx, sched = _tx()
    sample = _batch()
    init_fn = lambda r: model.init(
        r, jnp.asarray(sample["input_ids"][0]),
        jnp.asarray(sample["token_type_ids"][0]),
        jnp.asarray(sample["attention_mask"][0]))
    with mesh_lib.logical_rules():
        state, shardings = make_sharded_state(
            jax.random.PRNGKey(0), init_fn, tx, mesh=mesh, zero1=zero1)
    plan = (make_zero1_plan(state.params, shardings.params, mesh)
            if zero1 else None)
    step_fn = build_pretrain_step(model, tx, schedule=sched, zero1=plan)
    return state, plan, jax.jit(step_fn, donate_argnums=(0,))


# --- spec derivation units ---------------------------------------------


def test_zero1_spec_picks_largest_divisible_dim():
    mesh = mesh_lib.make_mesh()  # data=8
    assert zero1_spec((64, 16), P(None, None), mesh) == P("data", None)
    # dim0 not divisible by 8 -> falls to dim1
    assert zero1_spec((12, 32), P(None, None), mesh) == P(None, "data")
    # nothing divisible -> unchanged
    assert zero1_spec((3, 5), P(None, None), mesh) == P(None, None)
    # scalar untouched
    assert zero1_spec((), P(), mesh) == P()


def test_zero1_spec_composes_with_existing_axes():
    mesh = mesh_lib.make_mesh({"data": 2, "fsdp": 4})
    # a FREE dim that divides is preferred over stacking onto the fsdp dim
    # (an everything-sharded grad layout costs involuntary reshards against
    # the batch-sharded backward residuals)
    assert zero1_spec((64, 8), P("fsdp", None), mesh) == P("fsdp", "data")
    # no free dim divides -> data stacks onto the already-sharded dim
    assert zero1_spec((64, 3), P("fsdp", None), mesh) == \
        P(("fsdp", "data"), None)
    # axis already used anywhere -> unchanged
    assert zero1_spec((64, 8), P("data", None), mesh) == P("data", None)
    # size-1 mesh axes occupying an entry count as free (nothing is
    # actually sharded there), so the biggest dim still wins
    mesh_dp = mesh_lib.make_mesh()  # data=8, fsdp/model size 1
    got = zero1_spec((64, 8), P(("model", "fsdp"), None), mesh_dp)
    assert got == P(("model", "fsdp", "data"), None)


def test_make_zero1_plan_none_when_trivial():
    one = mesh_lib.make_mesh({"data": 1, "fsdp": 8})
    params = {"w": jnp.zeros((16, 16))}
    from jax.sharding import NamedSharding

    base = {"w": NamedSharding(one, P(None, None))}
    assert make_zero1_plan(params, base, one) is None
    assert make_zero1_plan(params, base, None) is None


def test_zero1_spec_prime_and_odd_dims_fall_back():
    """Leaves with no evenly-divisible dim keep their base spec — a ragged
    split would cost GSPMD padding every step, and the small leaves this
    hits (norm scales, odd biases) are cheap to keep replicated."""
    mesh = mesh_lib.make_mesh()  # data=8
    # primes and odds against n=8: nothing divides -> unchanged
    assert zero1_spec((7, 13), P(None, None), mesh) == P(None, None)
    assert zero1_spec((17,), P(None), mesh) == P(None)
    assert zero1_spec((3, 3, 5), P(None, None, None), mesh) == \
        P(None, None, None)
    # mixed: the odd dim is skipped, the divisible one takes the split
    assert zero1_spec((7, 24), P(None, None), mesh) == P(None, "data")
    # divisible by a FACTOR of n but not n itself (4 % 8): no ragged split
    assert zero1_spec((4, 3), P(None, None), mesh) == P(None, None)


def test_zero1_spec_stacking_needs_joint_divisibility():
    """Stacking data onto an fsdp-sharded dim requires divisibility by the
    JOINT factor (fsdp * data), not just data — otherwise fall back."""
    mesh = mesh_lib.make_mesh({"data": 2, "fsdp": 4})
    # 12 % (4*2) != 0: cannot stack onto the fsdp dim; 5 is indivisible
    # by 2 -> whole leaf falls back to base
    assert zero1_spec((12, 5), P("fsdp", None), mesh) == P("fsdp", None)
    # 16 % (4*2) == 0: stacking is legal when no free dim divides
    assert zero1_spec((16, 5), P("fsdp", None), mesh) == \
        P(("fsdp", "data"), None)


def test_zero1_spec_vocab_dim_never_double_stacks_over_free_dim():
    """The tied-embedding shape: vocab dim already (model, fsdp)-sharded.
    With ANY divisible free dim present, data must land there — an
    everything-on-one-dim grad layout costs involuntary reshards against
    the batch-sharded backward residuals (the round-7 reshard gate)."""
    mesh = mesh_lib.make_mesh({"data": 2, "fsdp": 2, "model": 2})
    # 64 divides the joint (model*fsdp*data) factor, so stacking WOULD be
    # legal — but the divisible free dim must win
    got = zero1_spec((64, 16), P(("model", "fsdp"), None), mesh)
    assert got == P(("model", "fsdp"), "data")


# --- parity + sharded state --------------------------------------------


def test_zero1_parity_and_moments_stay_sharded(tmp_path):
    """Same grads through the replicated and the ZeRO-1-sharded LAMB update
    on the 8-way data mesh: params bit-close after several steps (trust
    ratios are a function of the update, so parity of params across steps
    implies per-tensor/per-layer ratios matched), moments genuinely sharded
    before and after stepping, and the sharded moments survive a checkpoint
    round-trip."""
    mesh = mesh_lib.make_mesh()  # data=8
    state_r, _, step_r = _setup(mesh, zero1=False)
    state_z, plan, step_z = _setup(mesh, zero1=True)
    assert plan is not None

    # EVERY planned moment leaf born sharded (per-leaf plan walk, not a
    # spot check — partial replication must fail)
    assert_moments_sharded(state_z.opt_state.mu, plan, "at init")
    assert_moments_sharded(state_z.opt_state.nu, plan, "at init (nu)")
    # the replicated arm really is replicated (the contrast under test)
    emb_r = state_r.opt_state.mu["bert"]["embeddings"]["word_embeddings"][
        "embedding"]
    assert emb_r.sharding.is_fully_replicated

    batch = mesh_lib.host_to_device_batch(mesh, _batch())
    with mesh, mesh_lib.logical_rules():
        for i in range(4):
            state_r, m_r = step_r(state_r, batch, jax.random.PRNGKey(i))
            state_z, m_z = step_z(state_z, batch, jax.random.PRNGKey(i))
    np.testing.assert_allclose(float(m_r["loss"]), float(m_z["loss"]),
                               rtol=1e-6)
    for a, b in zip(jax.tree.leaves(state_r.params),
                    jax.tree.leaves(state_z.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-7)
    # moments numerically identical too (mu/nu are linear in the grads; the
    # only difference is reduction order) and still sharded after stepping
    for a, b in zip(jax.tree.leaves(state_r.opt_state.mu),
                    jax.tree.leaves(state_z.opt_state.mu)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-8)
    assert_moments_sharded(state_z.opt_state.mu, plan, "post-step")
    emb2 = state_z.opt_state.mu["bert"]["embeddings"]["word_embeddings"][
        "embedding"]

    # checkpoint round-trip of the SHARDED moments: orbax restores into the
    # zero1 layout from the abstract template's shardings
    mgr = CheckpointManager(str(tmp_path / "ckpts"), max_to_keep=2)
    assert mgr.save(4, state_z, extra={"epoch": 0})
    mgr.wait()
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        state_z)
    restored, extra, step = mgr.restore(abstract)
    assert step == 4 and extra["epoch"] == 0
    r_emb = restored.opt_state.mu["bert"]["embeddings"]["word_embeddings"][
        "embedding"]
    assert r_emb.sharding == emb2.sharding
    for a, b in zip(jax.tree.leaves(state_z.opt_state.mu),
                    jax.tree.leaves(restored.opt_state.mu)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and training continues identically from the restored sharded state
    with mesh, mesh_lib.logical_rules():
        cont, _ = step_z(state_z, batch, jax.random.PRNGKey(9))
        cont_r, _ = step_z(restored, batch, jax.random.PRNGKey(9))
    for a, b in zip(jax.tree.leaves(cont.params),
                    jax.tree.leaves(cont_r.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


# --- gather-on-use ZeRO-1 (--zero1_overlap, round 11) -------------------


@pytest.mark.slow  # both arms: tier-1's 870s budget; the compiled
# collective structure stays tier-1-pinned via the graph-budget gate
@pytest.mark.parametrize(
    "stacked",
    [True,
     # the unstacked arm re-proves the same claims at per-layer scatter
     # granularity — an extra XLA compile, so (like the fsdp/rs siblings
     # below) it rides outside tier-1's wall-clock budget
     pytest.param(False, marks=pytest.mark.slow)],
    ids=["stacked", "unstacked"])
def test_zero1_overlap_bit_identical(stacked):
    """gather_on_use=True must be the SAME training run as the round-7
    path — params, mu, nu, and loss bit-identical over several steps —
    while the params genuinely rest in the 1/N shard layout between steps
    and the step's all-gather count stays flat (the gathers MOVED from
    trailing the update to leading the forward; none were added). Both
    encoder layouts, because the per-leaf gather granularity differs:
    whole (L, ...) stacks vs per-layer kernels."""
    from bert_pytorch_tpu.analysis import collective_counts

    cfg = TINY if stacked else TINY.replace(stacked_params=False)
    mesh = mesh_lib.make_mesh()  # data=8
    model = BertForPreTraining(cfg, dtype=jnp.float32)
    tx, sched = _tx()
    sample = _batch()
    init_fn = lambda r: model.init(
        r, jnp.asarray(sample["input_ids"][0]),
        jnp.asarray(sample["token_type_ids"][0]),
        jnp.asarray(sample["attention_mask"][0]))

    def make(overlap):
        with mesh_lib.logical_rules():
            state, shardings = make_sharded_state(
                jax.random.PRNGKey(0), init_fn, tx, mesh=mesh, zero1=True,
                zero1_params=overlap)
        plan = make_zero1_plan(state.params, shardings.params, mesh,
                               gather_on_use=overlap)
        assert plan is not None and plan.gather_on_use == overlap
        step = build_pretrain_step(model, tx, schedule=sched, zero1=plan)
        return state, jax.jit(step, donate_argnums=(0,))

    s_base, step_base = make(False)
    s_ovl, step_ovl = make(True)

    # the feature's storage claim: params born (and kept) shard-resident
    n_sharded = sum(1 for l in jax.tree.leaves(s_ovl.params)
                    if not l.sharding.is_fully_replicated)
    assert n_sharded >= 10, f"only {n_sharded} param leaves rest sharded"

    batch = mesh_lib.host_to_device_batch(mesh, _batch())
    gathers = {}
    with mesh, mesh_lib.logical_rules():
        for name, st, fn in (("base", s_base, step_base),
                             ("ovl", s_ovl, step_ovl)):
            # one compile serves both the HLO inspection and the run; the
            # counter is the analyzer's (shared with the graphcheck budget
            # pass), not a per-test regex
            compiled = fn.lower(st, batch, jax.random.PRNGKey(0)).compile()
            gathers[name] = collective_counts(
                compiled.as_text())["all-gather"]
        for i in range(3):
            s_base, m_b = step_base(s_base, batch, jax.random.PRNGKey(i))
            s_ovl, m_o = step_ovl(s_ovl, batch, jax.random.PRNGKey(i))
            assert float(m_b["loss"]) == float(m_o["loss"]), f"step {i}"

    assert gathers["ovl"] == gathers["base"], (
        f"overlap program changed the all-gather count: {gathers} — the "
        "gathers must MOVE (update tail -> point of use), not multiply")

    for tree_b, tree_o, what in (
            (s_base.params, s_ovl.params, "params"),
            (s_base.opt_state.mu, s_ovl.opt_state.mu, "mu"),
            (s_base.opt_state.nu, s_ovl.opt_state.nu, "nu")):
        for a, b in zip(jax.tree.leaves(tree_b), jax.tree.leaves(tree_o)):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{what} not bit-identical after 3 steps")
    # ...and the overlap params STILL rest sharded after stepping
    n_sharded = sum(1 for l in jax.tree.leaves(s_ovl.params)
                    if not l.sharding.is_fully_replicated)
    assert n_sharded >= 10


# --- reduce-scatter gradients (--zero1_rs, round 16) --------------------


def test_zero1_rs_plan_validation_and_scatter_dims():
    """The rs plan's guard rails: reduce_scatter refuses without
    gather_on_use (the region consumes replicated params and emits
    sharded grads) and on any mesh with a second non-trivial axis (inside
    shard_map every axis is manual — a model-sharded forward would
    silently compute garbage). scatter_dims reads the appended-axis
    derivation back per leaf: the dim carrying plan.axis, None for
    divisibility-fallback leaves."""
    from jax.sharding import NamedSharding

    from bert_pytorch_tpu.parallel.zero import rs_supported, scatter_dims

    mesh = mesh_lib.make_mesh()  # data=8, other axes trivial
    params = {"big": jnp.zeros((64, 16)), "odd": jnp.zeros((7, 13))}
    base = {k: NamedSharding(mesh, P(None, None)) for k in params}
    with pytest.raises(ValueError, match="gather_on_use"):
        make_zero1_plan(params, base, mesh, reduce_scatter=True,
                        warn_skipped=False)

    mixed = mesh_lib.make_mesh({"data": 2, "model": 4})
    base_m = {k: NamedSharding(mixed, P(None, None)) for k in params}
    assert rs_supported(mesh) and not rs_supported(mixed)
    with pytest.raises(ValueError, match="data-only"):
        make_zero1_plan(params, base_m, mixed, gather_on_use=True,
                        reduce_scatter=True, warn_skipped=False)

    plan = make_zero1_plan(params, base, mesh, gather_on_use=True,
                           reduce_scatter=True, warn_skipped=False)
    assert plan.reduce_scatter and plan.rs_mode == "scatter"
    dims = dict(zip(sorted(params), scatter_dims(plan)))
    assert dims["big"] == 0        # (64, 16): data landed on dim 0
    assert dims["odd"] is None     # prime dims: replicated fallback


@pytest.mark.slow  # both arms: tier-1's 870s budget; the compiled
# collective structure stays tier-1-pinned via the graph-budget gate
@pytest.mark.parametrize(
    "stacked",
    [True,
     # the unstacked arm re-proves the claims at per-layer scatter
     # granularity and adds the legacy-GSPMD reference arm — two more XLA
     # compiles, so it rides outside tier-1's wall-clock budget
     pytest.param(False, marks=pytest.mark.slow)],
    ids=["stacked", "unstacked"])
def test_zero1_rs_bit_identical(stacked):
    """--zero1_rs: the shard_map region whose gradients exit through
    psum_scatter vs the SAME region with rs_mode='allreduce' (psum +
    slice-own-shard — the 2x-bytes pattern the path exists to kill):
    params, mu, nu, loss and grad_norm BIT-identical over 3 steps, while
    the compiled HLO swaps all-reduces for reduce-scatters (counted via
    the shared analyzer, same as the graphcheck zero1_rs_dp8 budget). The
    legacy GSPMD lowering (slow arm) agrees to reduction-reorder
    tolerance only — GSPMD regroups sums on its own, which is exactly why
    the exact parity gate is scatter-vs-allreduce, not scatter-vs-legacy."""
    from bert_pytorch_tpu.analysis import collective_counts

    cfg = TINY if stacked else TINY.replace(stacked_params=False)
    mesh = mesh_lib.make_mesh()  # data=8
    model = BertForPreTraining(cfg, dtype=jnp.float32)
    sample = _batch()
    init_fn = lambda r: model.init(
        r, jnp.asarray(sample["input_ids"][0]),
        jnp.asarray(sample["token_type_ids"][0]),
        jnp.asarray(sample["attention_mask"][0]))

    def make(mode):
        tx, sched = _tx()
        with mesh_lib.logical_rules():
            state, shardings = make_sharded_state(
                jax.random.PRNGKey(0), init_fn, tx, mesh=mesh, zero1=True,
                zero1_params=True)
        plan = make_zero1_plan(state.params, shardings.params, mesh,
                               gather_on_use=True,
                               reduce_scatter=mode is not None,
                               warn_skipped=False)
        assert plan is not None
        if mode is not None:
            plan = plan._replace(rs_mode=mode)
        step = build_pretrain_step(model, tx, schedule=sched,
                                   max_predictions=4, zero1=plan)
        return state, jax.jit(step, donate_argnums=(0,))

    modes = ("scatter", "allreduce") + (() if stacked else (None,))
    states, steps, counts, metrics = {}, {}, {}, {}
    batch = mesh_lib.host_to_device_batch(mesh, _batch())
    with mesh, mesh_lib.logical_rules():
        for mode in modes:
            st, fn = make(mode)
            compiled = fn.lower(st, batch, jax.random.PRNGKey(0)).compile()
            counts[mode] = collective_counts(compiled.as_text())
            states[mode], steps[mode] = st, fn
        for i in range(3):
            for mode in states:
                states[mode], m = steps[mode](states[mode], batch,
                                              jax.random.PRNGKey(i))
                metrics.setdefault(mode, []).append(
                    (float(m["loss"]), float(m["grad_norm"])))

    # the structural claim: grads leave through reduce-scatter, and the
    # all-reduces that carried them are gone — not merely renamed
    assert counts["scatter"]["reduce-scatter"] > 0, counts["scatter"]
    assert counts["allreduce"]["reduce-scatter"] == 0, counts["allreduce"]
    assert counts["scatter"]["all-reduce"] < \
        counts["allreduce"]["all-reduce"], (counts["scatter"],
                                            counts["allreduce"])
    # ...at an unchanged all-gather count (the params path is untouched)
    assert counts["scatter"]["all-gather"] == \
        counts["allreduce"]["all-gather"]

    # the value claim: same training run, bit for bit
    assert metrics["scatter"] == metrics["allreduce"]
    for what, sel in (("params", lambda s: s.params),
                      ("mu", lambda s: s.opt_state.mu),
                      ("nu", lambda s: s.opt_state.nu)):
        for a, b in zip(jax.tree.leaves(sel(states["scatter"])),
                        jax.tree.leaves(sel(states["allreduce"]))):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{what} not bit-identical after 3 steps")
    # params still rest 1/N-sharded (the gather-on-use contract rs rides)
    n_sharded = sum(1 for leaf in jax.tree.leaves(states["scatter"].params)
                    if not leaf.sharding.is_fully_replicated)
    assert n_sharded >= 10
    if None in states:
        for a, b in zip(jax.tree.leaves(states[None].params),
                        jax.tree.leaves(states["scatter"].params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6)


# --- fsdp gather-on-use (--fsdp_overlap, round 15) ----------------------


@pytest.mark.slow  # both arms: tier-1's 870s budget; the compiled
# collective structure stays tier-1-pinned via the graph-budget gate
@pytest.mark.parametrize(
    "stacked",
    [True,
     # the unstacked arm re-proves the same claims at per-layer gather
     # granularity — two more XLA compiles, so it rides outside tier-1's
     # wall-clock budget (same split as the graph-gate's slow full run)
     pytest.param(False, marks=pytest.mark.slow)],
    ids=["stacked", "unstacked"])
def test_fsdp_overlap_bit_identical(stacked):
    """The fsdp-axis restatement of the zero1 overlap contract: the
    BLOCKING layout (same per-leaf gather nodes fused behind one
    whole-tree barrier — FSDP-without-prefetch semantics) and the
    OVERLAP layout (independent per-leaf barriers the scheduler can
    interleave) must be the SAME training run — loss and params
    bit-identical over several steps — with the compiled all-gather
    count flat between them (the gathers change dependence structure,
    not count). Versus the no-plan program (GSPMD's implicit
    re-materialization, which may sink gathers into contracting-dim
    matmuls) the explicit layouts agree to reduction-reorder tolerance —
    pinned allclose, deliberately not bit-equal. Both encoder layouts:
    whole-(L,...)-stack gathers vs per-layer-kernel gathers."""
    from bert_pytorch_tpu.analysis import collective_counts
    from bert_pytorch_tpu.parallel.zero import make_fsdp_plan

    cfg = TINY if stacked else TINY.replace(stacked_params=False)
    mesh = mesh_lib.make_mesh({"fsdp": 8})
    model = BertForPreTraining(cfg, dtype=jnp.float32)
    tx, sched = _tx()
    sample = _batch()
    init_fn = lambda r: model.init(
        r, jnp.asarray(sample["input_ids"][0]),
        jnp.asarray(sample["token_type_ids"][0]),
        jnp.asarray(sample["attention_mask"][0]))

    def make(mode):
        with mesh_lib.logical_rules():
            state, shardings = make_sharded_state(
                jax.random.PRNGKey(0), init_fn, tx, mesh=mesh)
        plan = None
        if mode is not None:
            plan = make_fsdp_plan(state.params, shardings.params, mesh,
                                  blocking=(mode == "blocking"))
            assert plan is not None and plan.axis == "fsdp"
            assert plan.gather_on_use and \
                plan.blocking_gather == (mode == "blocking")
        step = build_pretrain_step(model, tx, schedule=sched, zero1=plan)
        return state, jax.jit(step, donate_argnums=(0,))

    states, steps, gathers = {}, {}, {}
    batch = mesh_lib.host_to_device_batch(mesh, _batch())
    # the implicit-GSPMD reference arm is compiled once, in the SLOW
    # (unstacked) variant only — the allclose claim is layout-independent
    # and every extra XLA compile is real tier-1 wall time; the tier-1
    # stacked arm pins the bit-identity + flat-gather-count core
    modes = ("blocking", "overlap") + (() if stacked else (None,))
    with mesh, mesh_lib.logical_rules():
        for mode in modes:
            st, fn = make(mode)
            compiled = fn.lower(st, batch, jax.random.PRNGKey(0)).compile()
            gathers[mode] = collective_counts(
                compiled.as_text())["all-gather"]
            states[mode], steps[mode] = st, fn
        # params genuinely rest fsdp-sharded in every mode
        n_sharded = sum(
            1 for leaf in jax.tree.leaves(states["overlap"].params)
            if not leaf.sharding.is_fully_replicated)
        assert n_sharded >= 8, f"only {n_sharded} param leaves sharded"
        for i in range(3):
            for mode in states:
                states[mode], _m = steps[mode](states[mode], batch,
                                               jax.random.PRNGKey(i))

    assert gathers["overlap"] == gathers["blocking"], (
        f"overlap changed the all-gather count: {gathers} — per-leaf "
        "barriers must re-schedule the same gathers, not multiply them")
    for a, b in zip(jax.tree.leaves(states["blocking"].params),
                    jax.tree.leaves(states["overlap"].params)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg="blocking vs overlap not bit-identical after 3 steps")
    if None in states:
        # explicit-gather vs implicit-GSPMD: reduction-reorder tolerance
        for a, b in zip(jax.tree.leaves(states[None].params),
                        jax.tree.leaves(states["overlap"].params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6)
    # ...and the overlap params still rest sharded after stepping
    n_sharded = sum(1 for leaf in jax.tree.leaves(states["overlap"].params)
                    if not leaf.sharding.is_fully_replicated)
    assert n_sharded >= 8


def test_coalesced_norms_bit_identical():
    """--coalesce_reductions on the plain ZeRO-1 step: LAMB's per-tensor
    trust norms, the pre-normalization global norm and the logged
    grad_norm route through bucketed reductions (parallel/coalesce.py) —
    params, mu, nu and the loss trajectory BIT-identical to the
    per-tensor program (same local reduce, same per-element cross-device
    sum)."""
    from bert_pytorch_tpu.parallel.coalesce import NormReducer

    mesh = mesh_lib.make_mesh()  # data=8
    model = BertForPreTraining(TINY, dtype=jnp.float32)
    sample = _batch()
    init_fn = lambda r: model.init(
        r, jnp.asarray(sample["input_ids"][0]),
        jnp.asarray(sample["token_type_ids"][0]),
        jnp.asarray(sample["attention_mask"][0]))

    def make(coalesce):
        tx, sched = _tx()
        with mesh_lib.logical_rules():
            state, shardings = make_sharded_state(
                jax.random.PRNGKey(0), init_fn, tx, mesh=mesh, zero1=True)
        plan = make_zero1_plan(state.params, shardings.params, mesh,
                               warn_skipped=False)
        reducer = None
        if coalesce:
            from bert_pytorch_tpu.optim.lamb import (
                default_trust_batch_axes, default_weight_decay_mask, lamb)

            reducer = NormReducer(plan.grad_shardings, mesh)
            tx = lamb(sched, weight_decay=0.01,
                      weight_decay_mask=default_weight_decay_mask,
                      trust_batch_axes=default_trust_batch_axes,
                      norm_reducer=reducer)
        step = build_pretrain_step(model, tx, schedule=sched, zero1=plan,
                                   norm_reducer=reducer)
        return state, jax.jit(step, donate_argnums=(0,)), reducer

    s_base, step_base, _ = make(False)
    s_co, step_co, reducer = make(True)
    batch = mesh_lib.host_to_device_batch(mesh, _batch())
    # (the compiled all-reduce REDUCTION is enforced elsewhere — the
    # checked-in kfac_zero1_dp8_bucketed budget and the slow kfac parity
    # test count it; re-compiling both programs here just for the count
    # would double this test's tier-1 wall time)
    with mesh, mesh_lib.logical_rules():
        for i in range(3):
            s_base, m_b = step_base(s_base, batch, jax.random.PRNGKey(i))
            s_co, m_c = step_co(s_co, batch, jax.random.PRNGKey(i))
            assert float(m_b["loss"]) == float(m_c["loss"]), f"step {i}"
            assert float(m_b["grad_norm"]) == float(m_c["grad_norm"])
    for what, ta, tb in ((("params"), s_base.params, s_co.params),
                         ("mu", s_base.opt_state.mu, s_co.opt_state.mu),
                         ("nu", s_base.opt_state.nu, s_co.opt_state.nu)):
        for a, b in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{what} not bit-identical with coalesced norms")
    # the deterministic bucket assignment is recorded for the run header
    summary = reducer.summary()
    assert summary is not None and summary["groups"], summary
    assert summary["groups"][0]["axes"] == ["data"]


def test_zero1_replicated_leaf_warning_and_plan_field(capsys):
    """The round-15 silent-skip bugfix: leaves the appended-axis
    derivation leaves on their base layout are (a) recorded on the plan
    (run_pretraining exports the count as bert_zero1_replicated_leaves)
    and (b) named in ONE counted warning — a layout regression can no
    longer hide in a quiet fallback."""
    mesh = mesh_lib.make_mesh()  # data=8
    # one shardable leaf, one prime-sized leaf the derivation must skip
    from jax.sharding import NamedSharding

    params = {"big": jnp.zeros((64, 16)), "odd": jnp.zeros((7, 13))}
    base = {"big": NamedSharding(mesh, P(None, None)),
            "odd": NamedSharding(mesh, P(None, None))}
    plan = make_zero1_plan(params, base, mesh)
    err = capsys.readouterr().err
    assert plan is not None
    assert len(plan.replicated_leaves) == 1
    assert "odd" in plan.replicated_leaves[0]
    assert "[7, 13]" in plan.replicated_leaves[0]
    assert "WARNING: zero1[data]: 1 param leaves" in err
    assert "odd" in err
    # warn_skipped=False silences the print but keeps the record
    plan2 = make_zero1_plan(params, base, mesh, warn_skipped=False)
    assert capsys.readouterr().err == ""
    assert plan2.replicated_leaves == plan.replicated_leaves


# --- the promoted zero-reshard gate (tier-1) ----------------------------


def test_no_involuntary_reshard_on_2x2_mesh(capfd):
    """The dryrun's `spmd_involuntary_reshard_warnings=0` gate as a pytest:
    compile (don't just trace) the production train step — gathered MLM
    head, NSP, ZeRO-1 sharded LAMB — under a 2x2 (data x model) CPU mesh
    and assert XLA's SPMD partitioner emitted zero 'Involuntary full
    rematerialization' warnings, so sharding regressions fail CI and not
    only the standalone dryrun.

    The mesh is data x model (DP+TP), the combination where every
    annotated tensor has a consistent home; data x fsdp at this tiny size
    is a known pre-existing GSPMD tension (fsdp serves both the batch axes
    and the vocab/embed param axes, so (B, .., V)-shaped loss tensors have
    two irreconcilable preferred layouts on a 4-device mesh) — the
    production 4-axis mesh {data,fsdp,model} stays gated at zero by the
    driver's dryrun, which this test complements, not replaces."""
    import __graft_entry__ as graft

    # the gate greps for a literal XLA log message; keep the canary that
    # the installed XLA still contains those bytes (fail-open protection)
    graft._assert_reshard_gate_alive()

    mesh = mesh_lib.make_mesh({"data": 2, "model": 2},
                              devices=jax.devices()[:4])
    state, plan, _ = _setup(mesh, zero1=True)
    assert plan is not None
    model = BertForPreTraining(TINY, dtype=jnp.float32)
    tx, sched = _tx()
    step_fn = build_pretrain_step(model, tx, schedule=sched, zero1=plan,
                                  max_predictions=4)
    batch = mesh_lib.host_to_device_batch(mesh, _batch())
    capfd.readouterr()  # drop anything buffered before the compile
    with mesh, mesh_lib.logical_rules():
        state, metrics = jax.jit(step_fn, donate_argnums=(0,))(
            state, batch, jax.random.PRNGKey(0))
        assert np.isfinite(float(metrics["loss"]))
    err = capfd.readouterr().err
    n = err.count(graft._RESHARD_WARNING)
    assert n == 0, (
        f"{n} involuntary-reshard warning(s) compiling the 2x2-mesh ZeRO-1 "
        f"step:\n{err[-2000:]}")


# --- overlap flag pack + noise filter -----------------------------------


def test_overlap_flag_pack_env_semantics():
    from bert_pytorch_tpu.parallel.xla_flags import (OVERLAP_FLAG_PACK,
                                                     apply_overlap_flags,
                                                     overlap_flags_active)

    env = {}
    added = apply_overlap_flags(env)
    assert added == list(OVERLAP_FLAG_PACK)
    assert overlap_flags_active(env)
    # idempotent
    assert apply_overlap_flags(env) == []
    # an operator's explicit polarity wins over the pack
    env2 = {"LIBTPU_INIT_ARGS":
            "--xla_tpu_enable_async_collective_fusion=false"}
    added2 = apply_overlap_flags(env2)
    assert "--xla_tpu_enable_async_collective_fusion=true" not in added2
    assert ("--xla_tpu_enable_async_collective_fusion=false"
            in env2["LIBTPU_INIT_ARGS"])
    assert overlap_flags_active(env2)


def test_filter_known_noise_keeps_signal():
    import __graft_entry__ as graft

    spam = ("E0803 02:23:37 25287 cpu_aot_loader.cc:210] Loading XLA:CPU "
            "AOT result. Target machine feature +prefer-no-gather ...\n")
    signal_line = "dryrun_multichip spmd_involuntary_reshard_warnings=0\n"
    warn = f"blah {graft._RESHARD_WARNING} of op %foo\n"
    out = graft.filter_known_noise(spam * 40 + warn + signal_line)
    assert "cpu_aot_loader.cc" not in out
    assert signal_line in out
    assert warn in out  # the gate's warning text is NEVER filtered
    assert "filtered 40 known-noise" in out
    # clean streams pass through untouched
    assert graft.filter_known_noise(signal_line) == signal_line
