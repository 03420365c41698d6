"""Jitted pretraining step: forward, loss, grad, accumulation, update.

The reference split this across forward_backward_pass / take_optimizer_step
with DDP no_sync() gymnastics to suppress NCCL allreduce during accumulation
(run_pretraining.py:395-451, :525-535). Under SPMD there is nothing to
suppress: microbatches accumulate grads inside a `lax.scan` carry, and the
single grad (p)sum the compiler inserts happens once per optimization step by
construction. The whole step — N microbatch fwd/bwd, optimizer, schedule — is
one XLA program; donation makes it in-place.

Batch layout contract: every array arrives shaped (accum_steps, micro_batch,
...). accum_steps == 1 is the plain path (no scan). Loss is averaged over
microbatches (reference pre-divided by accumulation count,
run_pretraining.py:436 — same result, computed exactly).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from bert_pytorch_tpu.analysis.hlo import scope_pattern
from bert_pytorch_tpu.models import losses
from bert_pytorch_tpu.models.bert import REMAT_AUTO_ORDER
from bert_pytorch_tpu.telemetry.health import (HealthConfig,
                                               global_norm_f32,
                                               health_signals, health_update,
                                               is_sticky_metric,
                                               select_state)
from bert_pytorch_tpu.training.state import TrainState

Batch = Dict[str, jax.Array]

# The account of the compiled step: every operation of jit(train_step)
# belongs to the FIRST entry of this list that is a component of its
# `op_name` path (jax.named_scope and flax module names; a transform's
# wrapper, as in `jvp(loss)`, does not hide the name). So `attention`
# inside `encoder` is `attention`, what is left of `encoder` is the layer
# scan's own slicing and stacking (the benchmark's scan_carry_share.train),
# what is left of `bert` is model glue (the attention bias), and what is
# left of `grad_accum` is the micro-batch scan: its carry's adds, the
# batch slicing, the final division. `rematted_computation` cuts across
# the list (a recomputed attention operation is `attention`), so it is
# not in it. The benchmark's unscoped_share.train carries a copy of this
# list as its argument (tests/test_step_scopes.py holds the two equal), and
# the same test compiles both step builders and finds every instruction
# that has an op_name under one of these.
STEP_SCOPES = (
    "attention", "mlp", "mlm_head", "nsp_head", "pooler", "embeddings",
    "loss", "optimizer", "grad_norm", "health", "param_cast", "metrics",
    "encoder", "bert", "grad_accum",
)
# The same account for a step of the decoder families (models/lfm2_moe.py,
# models/kimi_linear.py, models/smallthinker.py, models/laguna.py), whose
# models open other scopes, all under `decoder`: `kda` first, then `rmsnorm` (the q/k norms
# inside `attention` are norms), `mlp` the dense FFN only. The last two
# entries are not scopes of the program: XLA:TPU lowers `lax.ragged_dot`
# (ops/moe.py's grouped products) to kernels of its own whose `op_name` is
# the compiler's and carries no scope. The benchmark's
# unscoped_share.kimi.train carries a copy of the list, and
# unscoped_share.lm.train of the list without `kda` (lfm2 has none).
LM_STEP_SCOPES = (
    "kda", "rmsnorm", "moe", "conv", "attention", "mlp", "lm_head", "loss",
    "embeddings", "optimizer", "grad_norm", "health", "param_cast",
    "metrics", "decoder", "grad_accum",
    "ragged-dot-none", "ragged-dot-metadata",
)
# The second level of the account: under an entry of the two lists above (or
# a path under one), the children the program opens and a metric of the
# benchmark reads, each with the families (config.MODEL_FAMILIES' keys) whose
# step opens it. A child's path is `parent/child`, the components next to
# each other in the `op_name` as benchmark/readers/scope_sum_share.under
# wants them: smallthinker and laguna open `attn_core` under their two kinds
# of layer, so those are parents here. What a parent holds beside its children belongs
# to the parent alone (under `attention`: BERT's LayerNorm kernels, lfm2's
# and kimi's projections and q/k norms; under `kda/scan`: the chunk-major
# transposes, the outer scan's slices and the walk over a block's chunks;
# under `moe`: the held experts' casts, the loops' slices of the sorted
# pairs and `moe/accumulate`, which only a second live window's backward
# pass runs: ops/moe._live_windows). tests/test_step_scopes.py finds
# every child in each family's compiled step, forward and backward;
# `program_scopes` in the run's second header counts them in the executable
# (run_pretraining.py); docs/OBSERVABILITY.md draws the tree.
_FLAT_ATTENTION = ("bert", "lfm2_moe", "kimi_linear", "keye")
_BY_KIND_ATTENTION = ("smallthinker", "laguna")
_ROUTED_FAMILIES = ("lfm2_moe", "kimi_linear", "smallthinker", "laguna",
                    "keye")
STEP_SUBSCOPES = {
    # ops/attention.dot_product_attention: q, k, v in, context out
    "attention": {"attn_core": _FLAT_ATTENTION,
                  "qkv": ("bert",), "output": ("bert",),
                  # a layer's whole attention, by kind
                  "attention_window": _BY_KIND_ATTENTION,
                  "attention_full": _BY_KIND_ATTENTION,
                  # models/laguna.py: the rotation of q and k (a part of the
                  # head, at a table of its kind; ops/decoder_ops.rotary, on
                  # a TPU the kernels of ops/pallas/rotary.py) and the
                  # per-head gate on the context, beside the kind's
                  # projections and kernels
                  "rotary": ("laguna", "keye"), "gate": ("laguna",),
                  # models/keye.py and ops/sparse_index.py: the learned
                  # index's projections and scores, the exact selection of
                  # each query's keys, and the KL term the index learns from
                  # (forward and backward)
                  "indexer": ("keye",), "select": ("keye",),
                  "indexer_loss": ("keye",)},
    # models/smallthinker.py: the banded layers alone rotate q and k
    "attention/attention_window": {"attn_core": _BY_KIND_ATTENTION,
                                   "rotary": ("smallthinker",)},
    "attention/attention_full": {"attn_core": _BY_KIND_ATTENTION},
    # models/lfm2_moe.ShortConv: `mix` is what is no projection
    "conv": {"in_proj": ("lfm2_moe",), "mix": ("lfm2_moe",),
             "out_proj": ("lfm2_moe",)},
    # models/kimi_linear.py's mixer; ops/kda.py opens what is under `scan`
    "kda": {"conv": ("kimi_linear",), "gates": ("kimi_linear",),
            "scan": ("kimi_linear",), "out": ("kimi_linear",)},
    "kda/scan": {"prepare": ("kimi_linear",)},
    "kda/scan/prepare": {"inverse": ("kimi_linear",)},
    # ops/moe.py; kimi_linear's and laguna's shared expert beside the routed
    "moe": {"router": _ROUTED_FAMILIES, "dispatch": _ROUTED_FAMILIES,
            "experts": _ROUTED_FAMILIES, "combine": _ROUTED_FAMILIES,
            "shared": ("kimi_linear", "laguna")},
}


# Declared paths that hold no operation of the backward pass: the selection
# is discrete (no cotangent passes through it) and what it packs is kept
# for the backward kernels (models/keye.REMAT_POLICIES), not made again.
FORWARD_ONLY_SUBSCOPES = ("attention/select",)


def step_subscopes(family: Optional[str] = None) -> Tuple[str, ...]:
    """The paths of STEP_SUBSCOPES (`parent/child`) that `family`'s step
    opens; every path where `family` is None."""
    return tuple(f"{parent}/{child}"
                 for parent, children in STEP_SUBSCOPES.items()
                 for child, families in children.items()
                 if family is None or family in families)


_SCOPE_PATTERNS = {
    scopes: tuple((name, scope_pattern(name)) for name in scopes)
    for scopes in (STEP_SCOPES, LM_STEP_SCOPES)}


def step_scope(op_name: str, scopes=STEP_SCOPES) -> Optional[str]:
    """The entry of `scopes` (STEP_SCOPES or LM_STEP_SCOPES) an operation's
    `op_name` belongs to (first match), or None."""
    for name, pattern in _SCOPE_PATTERNS[scopes]:
        if pattern.search(op_name):
            return name
    return None


def _apply_health(health: Optional[HealthConfig], state: TrainState,
                  loss, grads, grad_norm, params, opt_state, metrics,
                  precond_state=None):
    """Shared health-pack tail for both step builders: non-finite signals,
    EMA/z-score/drift update, and — under action='skip' — the in-graph
    state guard. Returns (params, opt_state, precond_state, telemetry).

    The skip select must live IN the compiled step: the host reads metrics
    one step late (the non-blocking readback contract), so by the time it
    could react, a poisoned update would already be applied. Step-count
    semantics of a skip: TrainState.step (and so the LOGGED learning_rate
    metric, and the K-FAC builder's schedule argument) still advances, but
    the reverted opt_state includes the optimizer's internal count — the
    optax schedule the update actually consumes counts only APPLIED steps,
    exactly as if the poisoned batch never reached the optimizer. After k
    skips the applied lr therefore trails the logged one by k schedule
    steps; with rare skips (the intended regime) the drift is noise, and it
    is the price of keeping the skip bit-exact.
    """
    if health is None:
        return params, opt_state, precond_state, state.telemetry
    with jax.named_scope("health"):
        hmetrics, bad = health_signals(loss, grads, grad_norm)
        if health.action == "skip":
            params = select_state(bad, state.params, params)
            opt_state = select_state(bad, state.opt_state, opt_state)
            if precond_state is not None:
                precond_state = select_state(bad, state.precond_state,
                                             precond_state)
            hmetrics["skipped_nonfinite"] = bad.astype(jnp.int32)
        telemetry, ema_metrics = health_update(health, state.telemetry,
                                               grad_norm, bad, params)
    metrics.update(hmetrics)
    metrics.update(ema_metrics)
    return params, opt_state, precond_state, telemetry


def inject_nonfinite(params: Any, bad) -> Any:
    """Fault-injection drill (--inject_nonfinite_step, tools/replay.py):
    when `bad` is true, set one element of the first encoder kernel — in
    canonical (sorted-path) order that is layer 0's attention output
    projection, in either parameter layout — to NaN, so a real NaN
    propagates attention -> loss -> gradients exactly the way a hardware
    or data blowup would, and the whole alarm -> flight-recorder ->
    replay -> bisect pipeline can be exercised end to end on a live run.
    Because the poison is a pure function of the traced step counter it
    replays deterministically from the recorded manifest. Compiled in
    only when the flag is set; `bad` false is an exact no-op value-wise.
    """
    done = [False]

    def maybe(path, leaf):
        keys = "/".join(str(getattr(p, "key", p)) for p in path)
        if done[0] or "encoder" not in keys or "kernel" not in keys \
                or not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        done[0] = True
        flat = jnp.asarray(leaf).reshape(-1)  # tolerate numpy leaves (replay)
        flat = flat.at[0].set(jnp.where(bad,
                                        jnp.asarray(jnp.nan, leaf.dtype),
                                        flat[0]))
        return flat.reshape(leaf.shape)

    return jax.tree_util.tree_map_with_path(maybe, params)


def _param_caster(grad_dtype, keep_float32: Optional[Callable] = None):
    """tree-cast fp params to grad_dtype (bf16 grads against fp32 masters,
    the apex-O2-equivalent scheme); identity when grad_dtype is None.
    `keep_float32(path)` names leaves that are read as they are (a
    family's float32 router)."""
    def cast(params):
        if grad_dtype is None:
            return params
        if keep_float32 is not None:
            return jax.tree_util.tree_map_with_path(
                lambda path, p: p.astype(grad_dtype)
                if jnp.issubdtype(p.dtype, jnp.floating)
                and not keep_float32(path) else p, params)
        return jax.tree.map(
            lambda p: p.astype(grad_dtype)
            if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
    return cast


def _accum_zeros(gparams, accum_steps: int):
    """Gradient-accumulator init: carry dtype follows the per-micro grad
    dtype up to depth 128 — worst-case bf16 accumulation rounding
    (~sqrt(N)*2^-9, ~2% relative at N=128) stays far below microbatch
    gradient noise, matching the reference's apex-O2 fp16 accumulation at
    its typical depths (run_pretraining.py:438-448). Beyond 128 the carry
    switches to fp32: the bf16 ulp approaches a whole microbatch
    contribution (catastrophic at N>~500) and the fp32 carry's constant
    extra traffic is amortized by the long scan."""
    deep = accum_steps > 128
    return jax.tree.map(
        lambda p: jnp.zeros(
            p.shape, jnp.float32
            if deep and jnp.issubdtype(p.dtype, jnp.floating) else p.dtype),
        gparams)


# global_norm with fp32 leaf upcast (bf16 sums of millions of squares
# misreport the norm) — single implementation shared with the health pack
# so the logged grad_norm and param_norm can never diverge in method
_global_norm_f32 = global_norm_f32


@jax.named_scope("grad_norm")
def _grad_norm(grads, norm_reducer):
    """The logged gradient norm of both step builders."""
    return (norm_reducer.global_norm_f32(grads) if norm_reducer is not None
            else _global_norm_f32(grads))


def gather_masked_labels(masked_lm_labels: jax.Array, max_predictions: int
                         ) -> Tuple[jax.Array, jax.Array]:
    """(B, S) dense labels (-1 = unmasked) -> ((B, P) positions, (B, P)
    labels) with the masked positions first in original order.

    Rows with fewer than P masked tokens fill the tail with positions whose
    gathered label is -1, which the loss ignores — the gathered path then
    computes the exact same CE as the dense path. P must be >= the data
    pipeline's max_predictions_per_seq or excess masked positions silently
    drop out of the loss.
    """
    unmasked = masked_lm_labels == -1
    positions = jnp.argsort(unmasked, axis=-1, stable=True)
    positions = positions[:, :max_predictions].astype(jnp.int32)
    labels = jnp.take_along_axis(masked_lm_labels, positions, axis=-1)
    return positions, labels


def _gathered_labels(mlm_labels: jax.Array,
                     max_predictions: Optional[int]):
    """(labels, masked positions or None, masked positions dropped) as the
    gathered MLM head wants them; the dense labels, None and 0 without a
    `max_predictions`. The labels' side of the `loss` scope."""
    if max_predictions is None:
        return mlm_labels, None, jnp.zeros([], jnp.int32)
    with jax.named_scope("loss"):
        dense_total = jnp.sum(mlm_labels != -1).astype(jnp.int32)
        masked_positions, mlm_labels = gather_masked_labels(
            mlm_labels, max_predictions)
        # rows with > max_predictions masks lose the excess; surface it
        dropped = dense_total - jnp.sum(mlm_labels != -1).astype(jnp.int32)
    return mlm_labels, masked_positions, dropped


def _packed_kwargs(batch: Batch) -> Dict[str, Any]:
    """The packed-sequence fields (data/packing.py batch contract), passed
    through to the model only when the loader emitted them — an unpacked
    batch traces the exact pre-packing program."""
    return {k: batch[k] for k in ("position_ids", "segment_ids",
                                  "nsp_positions") if k in batch}


def _pretrain_loss_fn(model, max_predictions: Optional[int] = None
                      ) -> Callable:
    def loss_fn(params, batch: Batch, dropout_rng,
                deterministic: bool = False) -> Tuple[jax.Array, Dict]:
        mlm_labels, masked_positions, dropped = _gathered_labels(
            batch["masked_lm_labels"], max_predictions)
        mlm_logits, nsp_logits = model.apply(
            {"params": params},
            batch["input_ids"],
            batch.get("token_type_ids"),
            batch.get("attention_mask"),
            deterministic=deterministic,
            masked_positions=masked_positions,
            rngs=None if deterministic else {"dropout": dropout_rng},
            **_packed_kwargs(batch),
        )
        with jax.named_scope("loss"):
            loss = losses.pretraining_loss(
                mlm_logits, mlm_labels,
                nsp_logits, batch.get("next_sentence_labels"))
            correct, total = losses.mlm_accuracy(mlm_logits, mlm_labels)
        return loss, {"mlm_correct": correct, "mlm_total": total,
                      "mlm_dropped": dropped}

    return loss_fn


@jax.named_scope("optimizer")
def _zero1_update(tx, grads, state, zero1):
    """The optimizer tail shared by both step builders, with the optional
    ZeRO-1 sharding constraints (parallel/zero.py) around it.

    With a Zero1Plan: the post-accumulation gradient is constrained into its
    shard layout (GSPMD lowers the batch psum to a reduce-scatter), the
    moments/update compute shard-local against the sharded-at-init opt_state,
    and the updated params are constrained back to their train-step layout
    (the all-gather). Without a plan this is exactly the old update.

    gather_on_use plans instead leave the updated params IN the shard
    layout: the all-gather moves to the start of the next step
    (_use_params), where it overlaps forward compute instead of trailing
    the update as a barrier. state.params arrive shard-resident there
    (make_sharded_state(zero1_params=True)), so apply_updates is
    shard-local end to end.

    Bit-identity between the two modes is a PROGRAM-STRUCTURE property,
    not a given — a reduction's rounding depends on its grouping, and
    GSPMD regroups freely when the two programs differ anywhere. Three
    deliberate symmetries hold it (each was empirically necessary; drop
    one and the paths drift ~1e-9/step):
      1. the params handed to tx.update are constrained to the SHARD
         layout in both modes (free local slice vs no-op), so LAMB's
         trust-ratio norms reduce in the same partial+psum order;
      2. the updated params are pinned to the SHARD layout in both modes
         right after apply_updates — the non-overlap mode then appends
         its trailing all-gather as a pure output-layout materialization,
         the only node the two programs do not share;
      3. the point-of-use gather node exists in both modes too
         (_use_params), a no-op re-statement in the non-overlap one.
    Net collective count is identical (one gather per planned leaf per
    step, verified against the compiled HLO in tests/test_zero1.py);
    only WHERE it sits differs — trailing the update (a barrier with no
    compute left to hide it) vs leading the forward (interleavable)."""
    if zero1 is not None:
        grads = jax.lax.with_sharding_constraint(grads, zero1.grad_shardings)
        norm_params = jax.lax.with_sharding_constraint(
            state.params, zero1.grad_shardings)
    else:
        norm_params = state.params
    updates, opt_state = tx.update(grads, state.opt_state, norm_params)
    if zero1 is not None:
        updates = jax.lax.with_sharding_constraint(
            updates, zero1.grad_shardings)
    params = optax.apply_updates(state.params, updates)
    if zero1 is not None:
        params = jax.lax.with_sharding_constraint(
            params, zero1.grad_shardings)
        if not zero1.gather_on_use:
            params = jax.lax.with_sharding_constraint(
                params, zero1.param_shardings)
    return params, opt_state, grads


@jax.named_scope("param_cast")
def _use_params(state, zero1, cast_params, nan_inject_step=None):
    """The params the forward/backward consume: cast to the grad dtype and —
    for a gather-on-use Zero1Plan — re-constrained from the 1/N resting
    layout to the train-step layout, leaf by leaf (parallel/zero.py
    gather_params). Cast-then-gather order matters for traffic, not values:
    the all-gather then moves the bf16 copy (half the bytes of the fp32
    masters) while the masters stay shard-resident for the update. With
    grad_dtype=None the cast is identity and the gather moves fp32 —
    exactly what the non-overlap path's end-of-step gather moved.
    `nan_inject_step` is the fault-injection drill (inject_nonfinite)."""
    gparams = cast_params(state.params)
    if zero1 is not None:
        from bert_pytorch_tpu.parallel.zero import gather_params

        # BOTH modes get the same per-leaf constraint node: in overlap mode
        # it is the all-gather from the 1/N resting layout, in the baseline
        # it is a no-op re-statement of the layout the params already rest
        # in. Keeping the node in both programs is what makes them the SAME
        # program to the SPMD partitioner (modulo the resting layout), and
        # therefore bit-identical — with the node present on one side only,
        # GSPMD partitions the backward's wgrad reductions differently and
        # the paths drift ~1e-9/step.
        gparams = gather_params(gparams, zero1)
    if nan_inject_step is not None:
        gparams = inject_nonfinite(
            gparams, state.step + 1 == nan_inject_step)
    return gparams


def _build_rs_micro(model, zero1, max_predictions=None,
                    kfac=None, zeros_perts=None):
    """One-microbatch fwd/bwd inside an EXPLICIT shard_map region whose
    gradients leave through `psum_scatter` — the --zero1_rs path.

    The legacy lowering all-reduces every full gradient and only then
    slices out the shard the ZeRO-1 update consumes: 2x the bytes the
    update needs. Here each grad leaf exits the region through
    psum_scatter on the dim the appended-axis derivation gave plan.axis
    (parallel/zero.scatter_dims — literally parallel/rules.appended_dim
    over the SAME specs that built plan.grad_shardings, so the scatter,
    the layout the moments rest in, and the sharding_rules pass all read
    one derivation), landing each device exactly its shard and nothing
    else. Leaves the divisibility fallback left replicated exit via
    plain psum.

    Value-parity design (each point was empirically necessary):
    - the masked-token / NSP counts are label-only, so they are psum'd
      BEFORE the differentiated function: the backward stays psum-free,
      and dividing the LOCAL nll sums by the GLOBAL counts seeds every
      position's cotangent with the baseline's exact 1/count;
    - the logged loss is psum(local sums)/count — the same
      sum-then-divide grouping GSPMD lowers losses.pretraining_loss to,
      so the metric is bit-identical to the legacy path;
    - model.apply runs under nn.logical_axis_rules(()): inside shard_map
      every mesh axis is manual, so the model's with_logical_constraint
      annotations must dissolve (the data-only-mesh guard in
      make_zero1_plan is what makes that safe — nothing was
      model/seq-sharded to begin with);
    - plan.rs_mode="allreduce" swaps each psum_scatter for
      psum + slice-own-shard — the 2x-bytes pattern this path exists to
      kill, kept because it is the SAME program modulo the reduction op
      and therefore bit-identical, which is what lets
      tests/test_zero1.py pin scatter-vs-allreduce parity EXACTLY (the
      legacy GSPMD program reassociates reductions on its own and is
      only comparable to tolerance);
    - dropout draws from fold_in(rng, axis_index): valid training (each
      device gets independent bits) but not bit-matched to the legacy
      path's global-shape masks — parity gates run with dropout 0, where
      the rng folds prune away entirely.

    With `kfac` (must be bucketed — factor_bucket_bytes set), the region
    also returns K-FAC factor statistics: kfac.local_partial_stats' local
    contractions exit with their leading partial axis mapped back onto
    the batch axes, exactly the layout `kfac.step`'s coalesced
    _reduce_stats consumes. `zeros_perts` is the zero perturbation tree
    (an explicit shard_map operand, replicated).

    Returns one_micro with the step builders' usual signature:
    (params, micro, rng) -> (loss, aux, grads[, stats]).
    """
    import flax.linen as nn
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map
    from bert_pytorch_tpu.parallel import rules as rules_lib
    from bert_pytorch_tpu.parallel import zero as zero_lib

    if kfac is not None and not kfac.bucketed:
        raise ValueError(
            "zero1 reduce_scatter + K-FAC requires bucketed factor "
            "reductions (factor_bucket_bytes): the region emits PARTIAL "
            "factor statistics only _reduce_stats knows how to consume")

    mesh = next(s.mesh for s in jax.tree.leaves(zero1.grad_shardings)
                if isinstance(s, NamedSharding))
    axis = zero1.axis
    ax_entry = rules_lib.batch_axes(mesh)
    n_shards = int(mesh.shape[axis])
    sdims = zero_lib.scatter_dims(zero1)
    grad_specs = jax.tree.map(
        lambda s: s.spec if isinstance(s, NamedSharding) else P(),
        zero1.grad_shardings)
    rep = P()

    def reduce_grads(grads):
        flat, tdef = jax.tree_util.tree_flatten(grads)
        out = []
        for g, d in zip(flat, sdims):
            if d is None:
                out.append(jax.lax.psum(g, axis))
            elif zero1.rs_mode == "allreduce":
                full = jax.lax.psum(g, axis)
                shard = g.shape[d] // n_shards
                start = jax.lax.axis_index(axis) * shard
                out.append(jax.lax.dynamic_slice_in_dim(
                    full, start, shard, d))
            else:
                out.append(jax.lax.psum_scatter(
                    g, axis, scatter_dimension=d, tiled=True))
        return jax.tree_util.tree_unflatten(tdef, out)

    def prep_labels(micro):
        return _gathered_labels(micro["masked_lm_labels"], max_predictions)

    @jax.named_scope("loss")
    def global_counts(mlm_labels, nsp_labels):
        # label-only, psum'd OUTSIDE the differentiated function — exact
        # int sums, and the backward never sees a collective
        c_mlm = jnp.maximum(
            jax.lax.psum(jnp.sum(mlm_labels != -1), ax_entry), 1)
        c_nsp = (jnp.maximum(
            jax.lax.psum(jnp.sum(nsp_labels != -1), ax_entry), 1)
            if nsp_labels is not None else None)
        return c_mlm, c_nsp

    @jax.named_scope("loss")
    def terms_to_loss(mlm_logits, nsp_logits, mlm_labels, nsp_labels,
                      c_mlm, c_nsp):
        (mlm_sum, _), nsp = losses.pretraining_loss_terms(
            mlm_logits, mlm_labels, nsp_logits, nsp_labels)
        lloc = mlm_sum / c_mlm
        nsp_sum = jnp.zeros([], jnp.float32)
        if nsp is not None:
            nsp_sum = nsp[0]
            lloc = lloc + nsp_sum / c_nsp
        correct, total = losses.mlm_accuracy(mlm_logits, mlm_labels)
        return lloc, mlm_sum, nsp_sum, correct, total

    @jax.named_scope("loss")
    def metric_loss(mlm_sum, nsp_sum, nsp_labels, c_mlm, c_nsp):
        loss = jax.lax.psum(mlm_sum, ax_entry) / c_mlm
        if nsp_labels is not None:
            loss = loss + jax.lax.psum(nsp_sum, ax_entry) / c_nsp
        return loss

    def local_micro(params, micro, rng):
        mlm_labels, masked_positions, dropped = prep_labels(micro)
        nsp_labels = micro.get("next_sentence_labels")
        c_mlm, c_nsp = global_counts(mlm_labels, nsp_labels)
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))

        def local_loss(p):
            with nn.logical_axis_rules(()):
                mlm_logits, nsp_logits = model.apply(
                    {"params": p}, micro["input_ids"],
                    micro.get("token_type_ids"),
                    micro.get("attention_mask"),
                    deterministic=False,
                    masked_positions=masked_positions,
                    rngs={"dropout": rng},
                    **_packed_kwargs(micro))
            lloc, mlm_sum, nsp_sum, correct, total = terms_to_loss(
                mlm_logits, nsp_logits, mlm_labels, nsp_labels,
                c_mlm, c_nsp)
            return lloc, (mlm_sum, nsp_sum, correct, total)

        (_, (mlm_sum, nsp_sum, correct, total)), grads = \
            jax.value_and_grad(local_loss, has_aux=True)(params)
        loss = metric_loss(mlm_sum, nsp_sum, nsp_labels, c_mlm, c_nsp)
        aux = {"mlm_correct": jax.lax.psum(correct, ax_entry),
               "mlm_total": jax.lax.psum(total, ax_entry),
               "mlm_dropped": jax.lax.psum(dropped, ax_entry)}
        return loss, aux, reduce_grads(grads)

    def local_micro_kfac(params, perts, micro, rng):
        mlm_labels, masked_positions, _ = prep_labels(micro)
        nsp_labels = micro.get("next_sentence_labels")
        c_mlm, c_nsp = global_counts(mlm_labels, nsp_labels)
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))

        def local_loss(p, pe):
            with nn.logical_axis_rules(()):
                (mlm_logits, nsp_logits), mut = model.apply(
                    {"params": p, "perturbations": pe},
                    micro["input_ids"], micro.get("token_type_ids"),
                    micro.get("attention_mask"),
                    deterministic=False,
                    masked_positions=masked_positions,
                    rngs={"dropout": rng}, mutable=["kfac_in"],
                    **_packed_kwargs(micro))
            lloc, mlm_sum, nsp_sum, correct, total = terms_to_loss(
                mlm_logits, nsp_logits, mlm_labels, nsp_labels,
                c_mlm, c_nsp)
            return lloc, (mlm_sum, nsp_sum, correct, total,
                          mut["kfac_in"])

        (_, (mlm_sum, nsp_sum, correct, total, acts)), \
            (pgrads, pert_grads) = jax.value_and_grad(
                local_loss, argnums=(0, 1), has_aux=True)(params, perts)
        with jax.named_scope("optimizer/kfac"):
            stats = kfac.local_partial_stats(acts, pert_grads)
        loss = metric_loss(mlm_sum, nsp_sum, nsp_labels, c_mlm, c_nsp)
        aux = {"mlm_correct": jax.lax.psum(correct, ax_entry),
               "mlm_total": jax.lax.psum(total, ax_entry)}
        return loss, aux, reduce_grads(pgrads), stats

    def _stats_probe(params, micro, rng):
        # shapes only (jax.eval_shape): the stats tree STRUCTURE and per-
        # leaf ranks the region's out_specs need. Collective-free and
        # traced OUTSIDE shard_map on global shapes — ranks match the
        # local ones, and record_norms=False keeps the (8x-wrong) global
        # row counts out of the normalization bookkeeping.
        mlm_labels, masked_positions, _ = prep_labels(micro)

        def local_loss(p, pe):
            (mlm_logits, nsp_logits), mut = model.apply(
                {"params": p, "perturbations": pe},
                micro["input_ids"], micro.get("token_type_ids"),
                micro.get("attention_mask"),
                deterministic=False, masked_positions=masked_positions,
                rngs={"dropout": rng}, mutable=["kfac_in"],
                **_packed_kwargs(micro))
            return losses.pretraining_loss(
                mlm_logits, mlm_labels, nsp_logits,
                micro.get("next_sentence_labels")), mut["kfac_in"]

        (_, acts), (_, pert_grads) = jax.value_and_grad(
            local_loss, argnums=(0, 1), has_aux=True)(params, zeros_perts)
        return kfac.local_partial_stats(acts, pert_grads,
                                        record_norms=False)

    def one_micro(params, micro, rng):
        p_specs = jax.tree.map(lambda _: rep, params)
        m_specs = jax.tree.map(
            lambda v: P(ax_entry, *([None] * (v.ndim - 1))), micro)
        if kfac is None:
            fn = shard_map(
                local_micro, mesh=mesh,
                in_specs=(p_specs, m_specs, rep),
                out_specs=(rep, {"mlm_correct": rep, "mlm_total": rep,
                                 "mlm_dropped": rep}, grad_specs),
                check_vma=False)
            return fn(params, micro, rng)
        # perturbation taps are activation-shaped: batch rides dim 0, or
        # dim 1 under the nn.scan-stacked encoder ([L, B, ...] 'layers'
        # leaves) — enter the region sliced like the microbatch so the
        # in-model `x + perturb` sees local shapes
        def pe_spec(path, v):
            keys = [getattr(k, "key", str(k)) for k in path]
            if "layers" in keys:
                return P(None, ax_entry, *([None] * (v.ndim - 2)))
            return P(ax_entry, *([None] * (v.ndim - 1)))

        pe_specs = jax.tree_util.tree_map_with_path(pe_spec, zeros_perts)
        stats_struct = jax.eval_shape(_stats_probe, params, micro, rng)
        s_specs = jax.tree.map(
            lambda sd: P(ax_entry, *([None] * (sd.ndim - 1))),
            stats_struct)
        fn = shard_map(
            local_micro_kfac, mesh=mesh,
            in_specs=(p_specs, pe_specs, m_specs, rep),
            out_specs=(rep, {"mlm_correct": rep, "mlm_total": rep},
                       grad_specs, s_specs),
            check_vma=False)
        return fn(params, zeros_perts, micro, rng)

    return one_micro


def build_pretrain_step(
    model,
    tx: optax.GradientTransformation,
    schedule: Optional[optax.Schedule] = None,
    accum_steps: int = 1,
    loss_fn_builder: Optional[Callable] = None,
    max_predictions: Optional[int] = None,
    grad_dtype: Optional[Any] = None,
    zero1: Optional[Any] = None,
    health: Optional[HealthConfig] = None,
    nan_inject_step: Optional[int] = None,
    norm_reducer: Optional[Any] = None,
    keep_float32: Optional[Callable] = None,
) -> Callable[[TrainState, Batch, jax.Array], Tuple[TrainState, Dict]]:
    """Returns train_step(state, batch, rng) -> (state, metrics).

    `loss_fn_builder(model)` -> loss_fn(params, micro, rng, deterministic)
    -> (loss, aux) replaces the built-in MLM + NSP loss (the decoder
    families: models/lfm2_moe.pretrain_loss_fn_builder). A dict under
    aux["scalars"] is summed over the step's micro-batches and returned
    among the metrics as it is (a family's counters); one under
    aux["means"] is averaged over them, as the loss is (the terms of a loss
    that has several: models/keye.py's `lm_loss` and `indexer_kl`).
    `keep_float32`: see _param_caster.

    `schedule` is only consulted for the lr metric (the optimizer owns its
    own schedule). `max_predictions` (pretraining only; ignored when a custom
    loss_fn_builder is given) turns on the gathered MLM head: logits are
    computed for at most that many masked positions per sequence instead of
    the full (B, S, V) tensor. For K-FAC use build_kfac_pretrain_step.

    `grad_dtype` (e.g. jnp.bfloat16): compute the forward/backward against a
    params copy cast to this dtype, so gradients — including the encoder
    grad buffers, the dominant non-matmul HBM traffic at BERT-Large scale —
    live in the compute dtype instead of fp32. (Under the stacked layout
    those buffers are the scan's (L, ...) stacks filled by
    dynamic_update_slice; under config.stacked_params=False they are
    per-layer leaves written directly — either way this halves their
    bytes.) The fp32 master params still receive the update (the optimizer
    upcasts); the reference's apex-O2 path likewise kept fp16 grads against
    fp32 masters. None = grads in param dtype (fp32).

    The accumulation scan below is layout-agnostic: the carry mirrors
    whatever pytree the grads arrive as (stacked (L, ...) leaves or
    per-layer subtrees), so both encoder layouts share this step builder
    unchanged.

    `zero1` (a parallel.zero.Zero1Plan, from make_zero1_plan): shard the
    optimizer update ZeRO-1-style over the data axis — reduce-scatter the
    accumulated gradient, update 1/N of the moments/params per chip,
    all-gather the result. Requires state built with
    make_sharded_state(zero1=True) so the moments' storage layout matches.
    LAMB trust-ratio semantics are unchanged: the per-tensor/per-layer norm
    reductions are global-view, so GSPMD adds the scalar cross-shard psums
    (parity: tests/test_zero1.py). A plan with gather_on_use=True
    (--zero1_overlap) additionally keeps the params shard-resident between
    steps and re-gathers them per-leaf at the point of use — bit-identical
    values, overlap-schedulable gathers; requires
    make_sharded_state(zero1_params=True).

    `health` (telemetry/health.HealthConfig): compile the in-graph health
    pack into the step — non-finite counts for loss and per-group grads,
    grad-norm EMA/z-score spike flag, param-norm drift, all returned in
    `metrics`; with health.action='skip' a non-finite step leaves params /
    optimizer state bit-identical. Requires state.telemetry populated
    (telemetry.init_telemetry_state()); the returned state carries the
    updated TelemetryState.

    `nan_inject_step` (fault-injection drill): poison layer 0's attention
    output kernel with one NaN on exactly that global step (state.step+1
    numbering, like the logged metrics) — see inject_nonfinite. None (the
    default) compiles nothing extra.

    `norm_reducer` (parallel/coalesce.NormReducer built from the plan's
    grad layout): route the logged grad_norm's cross-device reductions
    through the bucketed path — one vector all-reduce per axis group
    instead of one scalar per leaf, bit-identical value. Pass the same
    instance to lamb(norm_reducer=...) so the whole step shares one
    deterministic bucket assignment. None = the per-leaf program,
    byte-identical to round 15.
    """
    rs = zero1 is not None and getattr(zero1, "reduce_scatter", False)
    if loss_fn_builder is None:
        loss_fn = _pretrain_loss_fn(model, max_predictions)
    else:
        if rs:
            raise ValueError(
                "zero1 reduce_scatter supports only the built-in "
                "pretraining loss: the shard_map region owns the loss "
                "decomposition (losses.pretraining_loss_terms), so a "
                "custom loss_fn_builder cannot ride it")
        loss_fn = loss_fn_builder(model)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    cast_params = _param_caster(grad_dtype, keep_float32)

    if rs:
        one_micro = _build_rs_micro(model, zero1, max_predictions)

        def aux_of(params, micro: Batch, rng):
            return one_micro(params, micro, rng)[1]
    else:
        def one_micro(params, micro: Batch, rng):
            (loss, aux), grads = grad_fn(params, micro, rng)
            return loss, aux, grads

        def aux_of(params, micro: Batch, rng):
            # the forward pass alone says what aux holds; tracing the
            # gradient too for its shapes would double the step's tracing
            return loss_fn(params, micro, rng)[1]

    def train_step(state: TrainState, batch: Batch, rng: jax.Array):
        gparams = _use_params(state, zero1, cast_params, nan_inject_step)
        with jax.named_scope("grad_accum"):
            loss, aux, grads = accumulate(gparams, batch, rng)
        params, opt_state, grads = _zero1_update(tx, grads, state, zero1)
        grad_norm = _grad_norm(grads, norm_reducer)

        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
        }
        params, opt_state, _, telemetry = _apply_health(
            health, state, loss, grads, grad_norm, params, opt_state,
            metrics)
        with jax.named_scope("metrics"):
            new_state = state.replace(step=state.step + 1, params=params,
                                      opt_state=opt_state,
                                      telemetry=telemetry)
            if "mlm_correct" in aux and "mlm_total" in aux:
                metrics["mlm_accuracy"] = (
                    aux["mlm_correct"] / jnp.maximum(aux["mlm_total"], 1))
            if "mlm_dropped" in aux:
                # masked positions beyond max_predictions lose supervision;
                # a nonzero value means the data pipeline and step config
                # disagree
                metrics["mlm_dropped"] = aux["mlm_dropped"]
            metrics.update(aux.get("scalars", {}))
            metrics.update(aux.get("means", {}))
            if schedule is not None:
                metrics["learning_rate"] = schedule(state.step)
        return new_state, metrics

    def accumulate(gparams, batch: Batch, rng: jax.Array):
        """(loss, aux, grads) averaged over the micro-batches; everything
        here that no inner scope claims is the `grad_accum` scope."""
        rngs = jax.random.split(rng, accum_steps)
        if accum_steps == 1:
            micro = jax.tree.map(lambda x: x[0], batch)
            loss, aux, grads = one_micro(gparams, micro, rngs[0])
        else:
            zeros = _accum_zeros(gparams, accum_steps)

            def body(carry, inp):
                grads_acc, loss_acc, aux_acc = carry
                micro, r = inp
                loss, aux, grads = one_micro(gparams, micro, r)
                carry = (
                    jax.tree.map(lambda a, g: a + g.astype(a.dtype),
                                 grads_acc, grads),
                    loss_acc + loss,
                    jax.tree.map(jnp.add, aux_acc, aux),
                )
                return carry, None

            micro0 = jax.tree.map(lambda x: x[0], batch)
            aux_shape = jax.eval_shape(aux_of, gparams, micro0, rngs[0])
            aux_zeros = jax.tree.map(
                lambda sd: jnp.zeros(sd.shape, sd.dtype), aux_shape)
            init = (zeros, jnp.zeros([], jnp.float32), aux_zeros)
            (grads, loss, aux), _ = jax.lax.scan(body, init, (batch, rngs))
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            loss = loss / accum_steps
            if "means" in aux:
                aux = dict(aux, means=jax.tree.map(
                    lambda v: v / accum_steps, aux["means"]))
        return loss, aux, grads

    return train_step


def chain_steps(step_fn: Callable, n_steps: int,
                per_step_batch: bool = False) -> Callable:
    """Wrap a train step into a device-side n-step loop (one host dispatch).

    chained(state, batch, rng) runs `step_fn` n_steps times. With
    per_step_batch=True, `batch` carries a leading (n_steps, ...) axis of
    fresh data per inner step (run_pretraining's --steps_per_loop path);
    with False, the single (accum, micro, ...) batch is reused every step
    (bench steady-state). The per-step rng derives from fold_in(rng, i).
    Returns (state, metrics_of_last_step) — except health/anomaly flags
    (telemetry.health.STICKY_METRIC_KEYS), which are max-accumulated across
    the inner steps so a NaN or spike in ANY of them survives to the one
    readback the host gets per loop.

    This is the TPU-idiomatic "host out of the loop" structure: the host
    only feeds data and reads metrics every n_steps, so per-step dispatch
    latency amortizes away.
    """
    if n_steps == 1:
        return step_fn

    def chained(state, batch, rng):
        def select(i):
            return (jax.tree.map(lambda x: x[i], batch) if per_step_batch
                    else batch)

        def body(i, carry):
            state, prev_metrics = carry
            state, metrics = step_fn(state, select(i),
                                     jax.random.fold_in(rng, i))
            for k in metrics:
                if is_sticky_metric(k) and k in prev_metrics:
                    metrics[k] = jnp.maximum(metrics[k], prev_metrics[k])
            return state, metrics

        # one real step builds the metrics pytree structure for the carry
        carry = step_fn(state, select(0), jax.random.fold_in(rng, 0))
        return jax.lax.fori_loop(1, n_steps, body, carry)

    return chained


class StepProgram:
    """AOT dispatch wrapper around a built train step — the lowering hook
    the static graph analyzer (bert_pytorch_tpu/analysis, tools/
    graphcheck.py) and the program-fingerprint plumbing hang off.

    jit-and-call hides the executable: once `jitted(args)` has compiled,
    there is no public route back to the HLO the run is actually
    executing. This wrapper makes the compile explicit — the first
    dispatch lowers and compiles (one XLA compile, same cost jit would
    have paid) and keeps the jax.stages.Compiled object, so
    `as_text()` / `fingerprint()` can report the live program's structure.
    Dispatches whose avals/shardings do not match the compiled signature
    (tail chunks, sharding drift on an uncommitted input) fall back to the
    plain jit cache — exactly the behavior the entry points had before,
    verified cheap because AOT argument validation raises BEFORE any
    donation or execution happens.

    The wrapped callable is positional-arity-agnostic: train steps call it
    as (state, batch, rng), the serving engine's bucketed inference
    forwards as (params, batch) — same AOT lifecycle either way
    (serving/engine.py compiles one StepProgram per sequence-length
    bucket so steady-state traffic never recompiles).
    """

    def __init__(self, step_fn: Callable, donate_state: bool = True):
        self.jitted = jax.jit(step_fn,
                              donate_argnums=(0,) if donate_state else ())
        self.lowered = None
        self.compiled = None
        self._aot_broken = False

    def lower(self, *args):
        """Trace only (cheap); keeps the lowered StableHLO for the dtype
        lint."""
        self.lowered = self.jitted.lower(*args)
        return self.lowered

    def compile(self, *args):
        """Lower (if needed) + XLA-compile; keeps the Compiled object."""
        if args or self.lowered is None:
            self.lower(*args)
        self.compiled = self.lowered.compile()
        return self.compiled

    def __call__(self, *args):
        if self.compiled is None and not self._aot_broken:
            try:
                # a program the caller has lowered already (the entry point
                # times lowering as a set-up span) is not lowered again
                self.compile(*(() if self.lowered is not None else args))
            except Exception as e:
                # fall back to plain jit, but never silently: a broken AOT
                # compile also means no program fingerprint for this run's
                # headers/bundles — the operator should see why
                import sys

                print(f"WARNING: StepProgram AOT compile failed "
                      f"({type(e).__name__}: {e}); dispatching through "
                      "the jit cache — program fingerprint unavailable",
                      file=sys.stderr)
                self._aot_broken = True
        if self.compiled is not None:
            try:
                return self.compiled(*args)
            except (ValueError, TypeError):
                # aval/sharding mismatch — raised during argument
                # validation, before donation or execution, so retrying
                # through the jit cache is safe (and compiles the new
                # signature exactly as the pre-wrapper code did)
                pass
        return self.jitted(*args)

    def as_text(self) -> Optional[str]:
        return self.compiled.as_text() if self.compiled is not None else None

    def peak_bytes(self) -> int:
        """The compiler's own statement of the program's peak (arguments,
        outputs and temporaries alive together at the worst point of its
        schedule); 0 where nothing AOT-compiled or the backend states
        none."""
        if self.compiled is None:
            return 0
        try:
            return int(self.compiled.memory_analysis().peak_memory_in_bytes)
        except Exception:
            return 0

    def fingerprint(self, scopes=()) -> Optional[Dict[str, Any]]:
        """Structural identity (collective counts + donation hash) of the
        compiled program, or None if nothing AOT-compiled (fallback mode).
        `scopes` (step_subscopes(family)): also the instructions under each.
        """
        if self.compiled is None:
            return None
        from bert_pytorch_tpu.analysis.hlo import program_fingerprint

        return program_fingerprint(self.compiled, scopes)


def resolve_remat_policy(build: Callable[[str], StepProgram], args,
                         bytes_limit: Optional[int],
                         candidates: Tuple[str, ...] = REMAT_AUTO_ORDER,
                         log: Callable[[str], None] = print):
    """remat_policy="auto", decided from what can be observed: compile the
    step under each candidate in turn and take the first whose peak, as the
    compiler states it, the device holds. `build(policy)` returns the
    StepProgram of the step built with that policy, `args` are the step's
    (state, batch, rng) or their avals, `bytes_limit` the device's memory
    (telemetry.hbm_snapshot()["hbm_bytes_limit"]; None where the backend
    states none, and then the first candidate is taken). A candidate is
    passed over where its peak is above the limit or its compile fails for
    memory (the TPU compiler refuses a program it cannot place); the last
    is taken whatever it needs. Returns (policy, program): the program is
    compiled, so the loop that runs it compiles nothing more.
    """
    for policy in candidates:
        last = policy == candidates[-1]
        program = build(policy)
        try:
            program.compile(*args)
        except jax.errors.JaxRuntimeError as e:
            if last or "RESOURCE_EXHAUSTED" not in str(e):
                raise
            log(f"remat_policy auto: '{policy}' does not compile for memory "
                f"({str(e).splitlines()[0][:200]})")
            continue
        peak = program.peak_bytes()
        if last or not bytes_limit or peak <= bytes_limit:
            return policy, program
        log(f"remat_policy auto: '{policy}' peaks at {peak} bytes, over "
            f"the device's {bytes_limit}")


def step_input_expectations(abstract_state, state, batch, mesh,
                            zero1: bool = False,
                            zero1_params: bool = False,
                            n_leading: int = 1,
                            kfac_shard_axes=None):
    """(expected shardings, rule labels) for EVERY input leaf of a
    compiled train step's (state, batch, rng) argument tuple, flat in
    tree_leaves order — the `sharding_rules` static-analysis contract
    (analysis/passes.py; tools/graphcheck.py feeds this into
    program_report and the pass verifies each compiled in-sharding
    against it). Everything is DERIVED from the logical-axis-rules table
    (parallel/rules.py), never hand-written per leaf:

    - TrainState leaves: rules.train_state_expectations — params and
      moments through the logical annotations, plus the ZeRO-1 appended
      axis (zero1) and the --zero1_overlap resting layout (zero1_params);
    - K-FAC precond leaves (state.precond_state is not None):
      optim/kfac.state_shardings placements — stacked factor/inverse
      leaves the table distributes carry their L-axis spec; leaves the
      table deliberately leaves unplaced (2D sites, non-divisible
      stacks) carry NO expectation, because their in-sharding is GSPMD's
      choice rather than a rule;
    - batch leaves: the table's 'data' rule with `n_leading` unsharded
      leading axes (the (accum, micro, ...) contract);
    - the rng key: no expectation (pruned from the program entirely when
      dropout is off).

    `abstract_state` is training/state.abstract_train_state's tree;
    `state` the built TrainState (for the precond structure); `batch`
    the device batch dict; `kfac_shard_axes` the KFAC instance's
    configured axes when it deviates from the table's KFAC_SHARD_AXES
    default — the expectations must mirror the derivation that actually
    placed the state.
    """
    from jax.sharding import NamedSharding

    from bert_pytorch_tpu.optim import kfac as kfac_lib
    from bert_pytorch_tpu.parallel import rules as rules_lib

    expected, labels = rules_lib.train_state_expectations(
        abstract_state, mesh, zero1=zero1, zero1_params=zero1_params)
    if state.precond_state is not None:
        axes = (tuple(kfac_shard_axes) if kfac_shard_axes is not None
                else rules_lib.KFAC_SHARD_AXES)
        kfac_axes = "+".join(axes)
        for sh in kfac_lib.state_shardings(state.precond_state, mesh,
                                           axes):
            expected.append(sh)
            labels.append(f"kfac_stacked[{kfac_axes}]" if sh is not None
                          else "kfac_unplaced")
    n_batch = len(jax.tree_util.tree_leaves(batch))
    batch_sh = NamedSharding(mesh, rules_lib.batch_spec(n_leading, mesh))
    batch_label = "batch(" + "+".join(rules_lib.batch_axes(mesh)) + ")"
    expected += [batch_sh] * n_batch
    labels += [batch_label] * n_batch
    expected.append(None)
    labels.append("rng")
    return expected, labels


def init_kfac_state(model, kfac, state, sample_inputs: Tuple):
    """Attach a freshly-initialized KFACState to `state`.

    Shapes come from eval_shape only — no forward pass runs. `sample_inputs`
    is one microbatch's (input_ids, token_type_ids, attention_mask). Returns
    (new_state, pert_template); pert_template is what
    build_kfac_pretrain_step needs. Single source of truth for the tap-shape
    bootstrap used by run_pretraining, the multi-chip dryrun, and the tests.
    """
    from bert_pytorch_tpu.training.state import TrainState

    ids, types, mask = (jnp.asarray(x) for x in sample_inputs)
    variables = jax.eval_shape(
        lambda r: model.init(r, ids, types, mask), jax.random.PRNGKey(0))
    pert_template = jax.tree.map(
        lambda sd: jnp.zeros(sd.shape, sd.dtype), variables["perturbations"])
    acts_shape = jax.eval_shape(
        lambda p, pe: model.apply(
            {"params": p, "perturbations": pe}, ids, types, mask,
            mutable=["kfac_in"])[1]["kfac_in"],
        state.params, pert_template)
    acts0 = jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype), acts_shape,
                         is_leaf=lambda x: hasattr(x, "shape"))
    new_state = TrainState(step=state.step, params=state.params,
                           opt_state=state.opt_state,
                           precond_state=kfac.init(acts0, pert_template))
    return new_state, pert_template


def build_kfac_pretrain_step(
    model,
    tx: optax.GradientTransformation,
    kfac,
    pert_template: Any,
    schedule: Optional[optax.Schedule] = None,
    accum_steps: int = 1,
    max_predictions: Optional[int] = None,
    grad_dtype: Optional[Any] = None,
    zero1: Optional[Any] = None,
    health: Optional[HealthConfig] = None,
    nan_inject_step: Optional[int] = None,
    norm_reducer: Optional[Any] = None,
):
    """K-FAC variant of the train step (model built with
    config.kfac_taps=True; `kfac` is optim.kfac.KFAC; `pert_template` the
    'perturbations' collection from model.init on a microbatch).

    Order matches the reference's take_optimizer_step (run_pretraining.py:
    395-407): factor stats from this step's fwd/bwd -> preconditioner ->
    optimizer on the preconditioned grads. TrainState.precond_state carries
    the KFACState pytree so it checkpoints/restores with everything else.

    `zero1` shards the trailing LAMB update exactly as in
    build_pretrain_step; the constraint lands AFTER kfac.step because
    preconditioning contracts the full grad tensors against the factor
    inverses (sharding its input would force a gather inside the
    preconditioner instead of a reduce-scatter into the optimizer).

    `health` as in build_pretrain_step; under action='skip' the K-FAC
    factor/inverse state is guarded too — a poisoned batch's NaN statistics
    must not survive in the preconditioner. `nan_inject_step` as in
    build_pretrain_step (the fault-injection drill covers the K-FAC path
    too — its factor statistics are exactly the kind of state a NaN
    poisons silently).
    """
    def loss_fn(params, perts, micro: Batch, rng):
        mlm_labels, masked_positions, _ = _gathered_labels(
            micro["masked_lm_labels"], max_predictions)
        (mlm_logits, nsp_logits), mut = model.apply(
            {"params": params, "perturbations": perts},
            micro["input_ids"], micro.get("token_type_ids"),
            micro.get("attention_mask"),
            deterministic=False, masked_positions=masked_positions,
            rngs={"dropout": rng},
            mutable=["kfac_in"],
            **_packed_kwargs(micro))
        with jax.named_scope("loss"):
            loss = losses.pretraining_loss(
                mlm_logits, mlm_labels,
                nsp_logits, micro.get("next_sentence_labels"))
            correct, total = losses.mlm_accuracy(mlm_logits, mlm_labels)
        return loss, ({"mlm_correct": correct, "mlm_total": total},
                      mut["kfac_in"])

    grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)
    zeros_perts = jax.tree.map(jnp.zeros_like, pert_template)

    # factor statistics are unaffected by bf16 grads (compute_stats
    # upcasts to fp32)
    cast_params = _param_caster(grad_dtype)

    rs = zero1 is not None and getattr(zero1, "reduce_scatter", False)
    if rs:
        one_micro = _build_rs_micro(model, zero1, max_predictions,
                                    kfac=kfac, zeros_perts=zeros_perts)
    else:
        def one_micro(params, micro, rng):
            (loss, (aux, acts)), (pgrads, pert_grads) = grad_fn(
                params, zeros_perts, micro, rng)
            with jax.named_scope("optimizer/kfac"):
                stats = kfac.compute_stats(acts, pert_grads)
            return loss, aux, pgrads, stats

    def train_step(state: TrainState, batch: Batch, rng: jax.Array):
        gparams = _use_params(state, zero1, cast_params, nan_inject_step)
        with jax.named_scope("grad_accum"):
            loss, aux, grads, stats = accumulate(gparams, batch, rng)
        with jax.named_scope("optimizer/kfac"):
            lr = (schedule(state.step) if schedule is not None
                  else kfac.config.learning_rate)
            if rs:
                # preconditioning contracts FULL grad tensors against the
                # factor inverses; the region's grads arrive reduce-
                # scattered, so gather them at the point of use (same per-
                # leaf all-gather economics as gather_on_use params) —
                # _zero1_update re-pins the preconditioned output to the
                # shard layout
                grads = jax.lax.with_sharding_constraint(
                    grads, zero1.param_shardings)
            kstate, grads = kfac.step(state.precond_state, stats, grads, lr)
        params, opt_state, grads = _zero1_update(tx, grads, state, zero1)
        grad_norm = _grad_norm(grads, norm_reducer)
        with jax.named_scope("metrics"):
            metrics = {
                "loss": loss,
                "grad_norm": grad_norm,
                "mlm_accuracy": (aux["mlm_correct"]
                                 / jnp.maximum(aux["mlm_total"], 1)),
            }
        params, opt_state, kstate, telemetry = _apply_health(
            health, state, loss, grads, grad_norm, params, opt_state,
            metrics, precond_state=kstate)
        with jax.named_scope("metrics"):
            new_state = state.replace(step=state.step + 1, params=params,
                                      opt_state=opt_state,
                                      precond_state=kstate,
                                      telemetry=telemetry)
            if schedule is not None:
                metrics["learning_rate"] = schedule(state.step)
        return new_state, metrics

    def accumulate(gparams, batch: Batch, rng: jax.Array):
        """(loss, aux, grads, factor statistics) averaged over the
        micro-batches, as in build_pretrain_step."""
        rngs = jax.random.split(rng, accum_steps)
        if accum_steps == 1:
            micro = jax.tree.map(lambda x: x[0], batch)
            loss, aux, grads, stats = one_micro(gparams, micro, rngs[0])
        else:
            def body(carry, inp):
                g_acc, s_acc, loss_acc, c_acc, t_acc = carry
                micro, r = inp
                loss, aux, g, s = one_micro(gparams, micro, r)
                return (jax.tree.map(lambda a, g_: a + g_.astype(a.dtype),
                                     g_acc, g),
                        jax.tree.map(jnp.add, s_acc, s),
                        loss_acc + loss,
                        c_acc + aux["mlm_correct"],
                        t_acc + aux["mlm_total"]), None

            zeros_g = _accum_zeros(gparams, accum_steps)
            micro0 = jax.tree.map(lambda x: x[0], batch)
            stats_shape = jax.eval_shape(
                lambda p, m, r: one_micro(p, m, r)[3],
                gparams, micro0, rngs[0])
            zeros_s = jax.tree.map(
                lambda sd: jnp.zeros(sd.shape, sd.dtype), stats_shape)
            init = (zeros_g, zeros_s, jnp.zeros([], jnp.float32),
                    jnp.zeros([], jnp.int32), jnp.zeros([], jnp.int32))
            (grads, stats, loss, correct, total), _ = jax.lax.scan(
                body, init, (batch, rngs))
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            stats = jax.tree.map(lambda s: s / accum_steps, stats)
            loss = loss / accum_steps
            aux = {"mlm_correct": correct, "mlm_total": total}
        return loss, aux, grads, stats

    return train_step


def build_debug_forward(model, max_predictions: Optional[int] = None
                        ) -> Callable:
    """Forward probe for tools/replay.py --bisect: fwd(params, micro, rng)
    -> (loss, taps) runs ONE microbatch's forward exactly as the train
    step's loss_fn would — same masked-position gathering, same packed-
    field threading (_packed_kwargs), same dropout rng plumbing — on a
    model built with config.debug_taps=True, returning the 'debug_taps'
    collection (embeddings / per-layer attention & mlp / pooler / heads)
    alongside the loss. Sharing this preprocessing with _pretrain_loss_fn
    is what keeps bisect from ever drifting from what training computed.
    `rng` is the per-microbatch key, i.e. jax.random.split(step_rng,
    accum_steps)[i] for microbatch i — the same derivation the step uses.
    """

    def fwd(params, micro: Batch, rng):
        mlm_labels = micro["masked_lm_labels"]
        masked_positions = None
        if max_predictions is not None:
            masked_positions, mlm_labels = gather_masked_labels(
                mlm_labels, max_predictions)
        (mlm_logits, nsp_logits), mut = model.apply(
            {"params": params},
            micro["input_ids"], micro.get("token_type_ids"),
            micro.get("attention_mask"),
            deterministic=False, masked_positions=masked_positions,
            rngs={"dropout": rng},
            mutable=["debug_taps"],
            **_packed_kwargs(micro))
        loss = losses.pretraining_loss(
            mlm_logits, mlm_labels,
            nsp_logits, micro.get("next_sentence_labels"))
        return loss, mut.get("debug_taps", {})

    return fwd


def build_eval_step(model, loss_fn_builder: Callable = _pretrain_loss_fn):
    """eval_step(params, batch) -> metrics; batch unstacked (no accum axis).
    Uses the same loss_fn_builder contract as build_pretrain_step
    (loss_fn(params, batch, rng, deterministic) -> (loss, aux))."""
    loss_fn = loss_fn_builder(model)

    def eval_step(params, batch: Batch):
        dummy_rng = jax.random.PRNGKey(0)
        loss, aux = loss_fn(params, batch, dummy_rng, deterministic=True)
        metrics = {"loss": loss}
        if "mlm_total" in aux:
            metrics["mlm_accuracy"] = (
                aux["mlm_correct"] / jnp.maximum(aux["mlm_total"], 1))
        return metrics

    return eval_step


def stack_microbatches(batch: Dict[str, Any], accum_steps: int
                       ) -> Dict[str, Any]:
    """Host-side: (B, ...) numpy batch -> (accum, B/accum, ...). The loader
    delivers flat per-host batches; this reshapes for the scan contract."""
    import numpy as np

    def split(x):
        x = np.asarray(x)
        if x.shape[0] % accum_steps:
            raise ValueError(
                f"batch dim {x.shape[0]} not divisible by accum {accum_steps}")
        return x.reshape(accum_steps, x.shape[0] // accum_steps, *x.shape[1:])

    return {k: split(v) for k, v in batch.items()}
