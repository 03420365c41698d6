"""ZeRO-1 optimizer-state sharding over the data-parallel mesh axis.

Under pure data parallelism every chip holds a full replica of the LAMB
moments and redundantly executes the full once-per-step update — an HBM
floor whose cost is not measured on this runtime. This module is the
TPU-native analog of the reference's apex `DistributedFusedLAMB` /
K-FAC HYBRID_OPT distributed-optimizer ownership (run_pretraining.py:325-327):
each data-parallel chip owns 1/N of every moment tensor and computes only its
shard of the update.

Mechanically this is three sharding constraints, not a rewrite — GSPMD keeps
the global-view semantics and inserts the collectives:

  1. moments are *born* sharded (training/state.make_sharded_state(zero1=True)
     overrides the opt_state storage shardings with `zero1_shardings`);
  2. the post-accumulation gradient is constrained to the same shard layout
     (training/pretrain.py), so the compiler lowers the gradient sum to a
     reduce-scatter instead of an all-reduce;
  3. the updated params are constrained back to their train-step layout,
     which becomes the all-gather of the 1/N-sized updates.

Same bytes on the wire as an all-reduce (reduce-scatter + all-gather), 1/N
optimizer-state read/write and update FLOPs per chip. LAMB's trust-ratio
norms need no hand-written psum in this formulation: the per-tensor /
per-layer reductions in optim/lamb.py are written against the global shapes,
and the partitioner inserts the (scalar-sized) cross-shard reductions where a
tensor is split — parity is asserted in tests/test_zero1.py.

Spec derivation: for each moment/grad leaf, `zero1_spec` appends the shard
axis to the dimension with the largest *per-shard* extent whose size divides
evenly, composing with whatever fsdp/model sharding the logical rules already
placed (a dim sharded 4-way over fsdp can additionally split over data). A
leaf with no evenly-divisible free dim stays on its base sharding — small
(E,)-norm params are replicated anyway by DEFAULT_LOGICAL_AXIS_RULES, and a
ragged split would cost GSPMD padding on every step. Since round 15 the
derivation itself lives in parallel/rules.py (shard_append_spec /
shard_append_tree) — the one logical-axis-rules table the static
`sharding_rules` gate verifies compiled programs against; the wrappers
here keep the ZeRO-1-named API the training code and tests use.
"""

from __future__ import annotations

import sys
from typing import Any, NamedTuple, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from bert_pytorch_tpu.parallel import rules as rules_lib


class Zero1Plan(NamedTuple):
    """Shardings a train step needs to run the ZeRO-1 update.

    grad_shardings: param-shaped tree — the shard layout for the reduced
        gradient, the moments, and the per-shard update (the reduce-scatter
        output layout).
    param_shardings: param-shaped tree — the params' train-step layout (the
        all-gather target after the update).
    axis: the mesh axis the update is sharded over.
    replicated_leaves: paths of param leaves the spec derivation left on
        their base layout (no evenly-divisible dim — the divisibility
        fallback). Expected for tiny (E,)-norm params; a LARGE leaf here
        is a layout regression, which is why make_zero1_plan warns loudly
        naming them and run_pretraining exports the count as the
        `bert_zero1_replicated_leaves` gauge.
    gather_on_use: False (the round-7 path) leaves the updated params in
        their train-step layout at the END of the step — one block of
        all-gathers after the optimizer, with no compute left to hide them
        behind. True (--zero1_overlap) keeps the params in their 1/N shard
        layout inside the state and re-constrains them leaf-by-leaf at the
        START of the step, right where the forward consumes them — the
        gathers become per-leaf (per-layer under the unstacked encoder
        layout) ops the latency-hiding scheduler can interleave with
        embedding/encoder compute instead of a post-update barrier. Values
        are bit-identical either way — guaranteed by the deliberate
        program-structure symmetries in training/pretrain.py _zero1_update
        (see its docstring), not by hand-waving about all-gathers moving
        bytes; only the collective schedule changes. Requires state built
        with make_sharded_state(zero1_params=True) so the resting params
        match the shard layout.
    """

    grad_shardings: Any
    param_shardings: Any
    axis: str = "data"
    gather_on_use: bool = False
    replicated_leaves: Tuple[str, ...] = ()
    # fsdp plans only: True = the point-of-use gathers are fused behind
    # ONE whole-tree optimization_barrier (every forward op waits on every
    # gather — the blocking layout), False = independent per-leaf barriers
    # the latency-hiding scheduler can interleave with forward compute.
    # Same gather nodes, same arithmetic, bit-identical values either way
    # (tests/test_zero1.py::test_fsdp_overlap_bit_identical); only the
    # schedulability changes — exactly the zero1_overlap trade restated
    # for the fsdp axis.
    blocking_gather: bool = False
    # --zero1_rs: the fwd/bwd runs inside an explicit shard_map region and
    # each grad leaf EXITS it through psum_scatter on its appended-axis dim
    # (scatter_dims below), landing directly in grad_shardings' layout —
    # no full-gradient all-reduce is ever materialized, so the wire moves
    # half the bytes of the all-reduce-then-slice lowering. Requires
    # gather_on_use (the update is shard-local either way; the params'
    # point-of-use gathers are the return path) and a data-only mesh
    # (rs_supported) — inside shard_map every mesh axis is manual, so a
    # model/seq-sharded forward would need its own collective rewrite.
    reduce_scatter: bool = False
    # Which collective carries each sharded grad leaf out of the shard_map
    # region: "scatter" (psum_scatter — the real path) or "allreduce"
    # (psum + slice of own shard — the 2x-bytes pattern this plan exists
    # to kill, kept as a test arm because it is the SAME program modulo
    # the reduction op and therefore bit-identical on CPU/TPU, which is
    # what lets tests pin rs-vs-allreduce parity exactly rather than
    # allclose; the legacy GSPMD path reassociates sums on its own and is
    # only comparable to tolerance).
    rs_mode: str = "scatter"


def zero1_spec(shape, base_spec: PartitionSpec, mesh: Mesh,
               axis: str = "data") -> PartitionSpec:
    """base_spec with `axis` added on the best-splittable dim of `shape`
    — the rules table's appended-axis derivation
    (parallel/rules.shard_append_spec holds the logic and the free-dim-
    first / divisibility-fallback rationale); this wrapper keeps the
    ZeRO-1-named API."""
    return rules_lib.shard_append_spec(shape, base_spec, mesh, axis)


def zero1_shardings(abstract_tree: Any, base_shardings: Any, mesh: Mesh,
                    axis: str = "data") -> Any:
    """Tree of NamedShardings with the ZeRO-1 axis applied per leaf
    (parallel/rules.shard_append_tree). `abstract_tree` supplies shapes
    (ShapeDtypeStructs or concrete arrays), `base_shardings` the matching
    NamedSharding tree (e.g. from nn.logical_to_mesh_sharding).
    Non-NamedSharding leaves and scalars pass through untouched, so this
    maps safely over a whole opt_state — LAMB's step count keeps its
    replicated placement."""
    return rules_lib.shard_append_tree(abstract_tree, base_shardings,
                                       mesh, axis)


def plan_expected_shardings(plan: Zero1Plan) -> list:
    """Flat expected-sharding list for a param-shaped tree under `plan`:
    the grad/moment sharding where the plan actually shards the leaf, None
    (no expectation) where it does not — the `expected` contract of
    analysis/hlo.sharding_leaves, shared by assert_moments_sharded and
    tools/graphcheck.py."""
    return [
        g if (isinstance(g, NamedSharding) and isinstance(p, NamedSharding)
              and g.spec != p.spec) else None
        for g, p in zip(jax.tree.leaves(plan.grad_shardings),
                        jax.tree.leaves(plan.param_shardings))]


def assert_moments_sharded(moments: Any, plan: Zero1Plan,
                           where: str = "") -> None:
    """Assert EVERY moment leaf the plan shards is actually non-replicated.

    An any()-style spot check would pass when a stray constraint (or a
    GSPMD branch merge — the K-FAC lax.cond case) replicates a subset of
    leaves, silently losing most of the 1/N state win; this walks the plan
    so exactly the leaves whose grad spec differs from their param spec are
    required to stay sharded. `moments` is any param-shaped tree (mu or
    nu). Since round 13 this is one instance of the general
    unexpected-replication pass (bert_pytorch_tpu/analysis) — the same
    rule tools/graphcheck.py applies to the whole compiled program's
    inputs.
    """
    from bert_pytorch_tpu.analysis.hlo import sharding_leaves
    from bert_pytorch_tpu.analysis.passes import replication_findings

    leaves = sharding_leaves(moments, expected=plan_expected_shardings(plan))
    bad = replication_findings(leaves, rule="zero1_moments")
    assert not bad, f"zero1 moments replicated {where}:\n" + "\n".join(
        str(f) for f in bad)


def placement_bytes(tree: Any) -> dict:
    """{device id: bytes of `tree` resident on that device}, summed over
    every leaf's addressable shards — where the state actually sits, not
    what a sharding spec promises. A replicated tree shows its full size on
    each device; a ZeRO-1 state about 1/N of it."""
    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in getattr(leaf, "addressable_shards", ()):
            dev = int(shard.device.id)
            out[dev] = out.get(dev, 0) + int(shard.data.nbytes)
    return dict(sorted(out.items()))


def _gather_leaf(p, p_sh: NamedSharding):
    """One leaf's gather-on-use constraint, with an IDENTITY backward.

    with_sharding_constraint's transpose re-applies the forward sharding to
    the cotangent — here that would pin the parameter cotangent to the
    GATHERED layout, forcing the batch grad-sum into an all-reduce that is
    only sliced back down at the zero1 grad constraint. The baseline path
    has no such pin: its cotangent reaches the grad constraint unconstrained
    and the sum lowers straight to a reduce-scatter. The custom VJP passes
    the cotangent through untouched, so the overlap path's backward is the
    SAME program as the baseline's — which is also what makes the two paths
    bit-identical (same reduction order), not just close."""

    @jax.custom_vjp
    def g(x):
        return _materialized(x)

    def _materialized(x):
        # The optimization_barrier pins the GATHERED value as a real
        # intermediate: without it the partitioner may sink the gather
        # into a consuming matmul whose contracting dim the shard layout
        # splits (pooler/MLM-transform kernels under the unstacked
        # layout), computing partial-matmul + psum — a different
        # accumulation grouping than the baseline's local matmul, i.e. an
        # ulp-level fork. Both modes get the same barrier (a no-op cost
        # on an already-gathered value), so both consume a materialized
        # replicated operand and partition identically downstream.
        return jax.lax.optimization_barrier(
            jax.lax.with_sharding_constraint(x, p_sh))

    def fwd(x):
        return _materialized(x), None

    def bwd(_, ct):
        return (ct,)

    g.defvjp(fwd, bwd)
    return g(p)


def _gather_tree_blocking(leaves, shardings):
    """The blocking counterpart of the per-leaf gather: the same
    with_sharding_constraint per leaf, but ONE optimization_barrier over
    the whole gathered tuple — every consumer of any param now depends on
    every gather, so the scheduler cannot start forward compute until the
    last gather lands (torch-FSDP-without-prefetch semantics). The joint
    identity-backward custom VJP keeps the gradient program untouched,
    exactly like _gather_leaf. Same arithmetic, same nodes, bit-identical
    values to the per-leaf mode; only the dependence structure differs."""

    @jax.custom_vjp
    def g(*xs):
        return _materialized(*xs)

    def _materialized(*xs):
        constrained = [jax.lax.with_sharding_constraint(x, s)
                       for x, s in zip(xs, shardings)]
        out = jax.lax.optimization_barrier(tuple(constrained))
        return tuple(out)

    def fwd(*xs):
        return _materialized(*xs), None

    def bwd(_, cts):
        return tuple(cts)

    g.defvjp(fwd, bwd)
    return g(*leaves)


def gather_params(params: Any, plan: Zero1Plan) -> Any:
    """Re-constrain shard-resident params to their train-step layout,
    LEAF BY LEAF — the gather-on-use half of plan.gather_on_use.

    Each leaf gets its own with_sharding_constraint, so each all-gather is
    an independent node whose only consumer is that parameter's first use:
    under the unstacked encoder layout that is one gather per layer per
    kernel, which the scheduler can prefetch behind the previous layer's
    forward compute; under the stacked layout the (L, ...) scan stacks
    gather as whole leaves (the scan consumes the full stack), still split
    by kernel kind (qkv vs mlp vs norms) rather than fused into one
    end-of-step barrier. Leaves whose grad spec equals their param spec
    (nothing was sharded) pass through without a constraint op. The
    backward is identity per leaf (_gather_leaf), so the gradient program
    is the baseline path's bit for bit.

    plan.blocking_gather=True (the fsdp plans' blocking reference layout)
    routes the same constraint set through ONE whole-tree barrier instead
    — see _gather_tree_blocking."""
    flat, treedef = jax.tree_util.tree_flatten(params)
    g_flat = jax.tree.leaves(plan.grad_shardings)
    p_flat = jax.tree.leaves(plan.param_shardings)
    needs = [
        isinstance(g, NamedSharding) and isinstance(p, NamedSharding)
        and g.spec != p.spec
        for g, p in zip(g_flat, p_flat)]
    if plan.blocking_gather:
        idx = [i for i, n in enumerate(needs) if n]
        gathered = _gather_tree_blocking(
            [flat[i] for i in idx], [p_flat[i] for i in idx])
        out = list(flat)
        for i, x in zip(idx, gathered):
            out[i] = x
        return jax.tree_util.tree_unflatten(treedef, out)
    out = [_gather_leaf(x, p) if n else x
           for x, n, p in zip(flat, needs, p_flat)]
    return jax.tree_util.tree_unflatten(treedef, out)


def _skipped_leaf_paths(params_like: Any, param_shardings: Any,
                        grads: Any) -> Tuple[str, ...]:
    """Paths (with shapes) of the leaves the appended-axis derivation left
    on their base layout — the divisibility fallback's output, surfaced so
    a layout regression (a LARGE leaf silently falling back) cannot
    hide."""
    flat = jax.tree_util.tree_flatten_with_path(params_like)[0]
    g_leaves = jax.tree.leaves(grads)
    p_leaves = jax.tree.leaves(param_shardings)
    out = []
    for (path, leaf), g, p in zip(flat, g_leaves, p_leaves):
        if isinstance(g, NamedSharding) and isinstance(p, NamedSharding) \
                and g.spec == p.spec:
            shape = tuple(getattr(leaf, "shape", ()) or ())
            out.append(f"{jax.tree_util.keystr(path)}{list(shape)}")
    return tuple(out)


def warn_replicated_leaves(leaves: Tuple[str, ...], axis: str,
                           axis_size: int, stream=None) -> None:
    """One counted warning naming every leaf the ZeRO-1 derivation left
    replicated (the silent-skip the round-15 bugfix surfaces). Expected
    for (E,)-norm scales and odd biases; anything big in this list means
    the free-dim-first derivation regressed. run_pretraining additionally
    exports the count as the `bert_zero1_replicated_leaves` gauge."""
    if not leaves:
        return
    stream = stream or sys.stderr
    names = list(leaves)
    shown = names[:12] + ([f"... +{len(names) - 12} more"]
                          if len(names) > 12 else [])
    print(f"WARNING: zero1[{axis}]: {len(names)} param leaves have "
          f"no dim divisible by {axis_size} and stay on their base "
          f"layout (replicated w.r.t. the {axis} axis): "
          + ", ".join(shown), file=stream)


def rs_supported(mesh: Optional[Mesh], axis: str = "data") -> bool:
    """True when the mesh shape admits the shard_map reduce-scatter region:
    a non-trivial `axis` and every OTHER axis trivial. Inside shard_map all
    mesh axes are manual, so a model/seq-sharded forward would silently
    compute garbage without its own collective rewrite — refuse instead."""
    if mesh is None or mesh.shape.get(axis, 1) <= 1:
        return False
    return all(n == 1 for a, n in mesh.shape.items() if a != axis)


def scatter_dims(plan: Zero1Plan) -> list:
    """Per-leaf psum_scatter dimension for the plan's grad tree (flat,
    tree.leaves order): the dim the appended-axis derivation gave to
    plan.axis (parallel/rules.appended_dim — the SAME derivation that
    built grad_shardings, so the scatter provably lands each shard in the
    layout the moments rest in), or None for leaves the divisibility
    fallback left on their base layout (those exit via plain psum)."""
    out = []
    for g, p in zip(jax.tree.leaves(plan.grad_shardings),
                    jax.tree.leaves(plan.param_shardings)):
        if isinstance(g, NamedSharding) and isinstance(p, NamedSharding) \
                and g.spec != p.spec:
            out.append(rules_lib.appended_dim(p.spec, g.spec, plan.axis))
        else:
            out.append(None)
    return out


def make_zero1_plan(params_like: Any, param_shardings: Any,
                    mesh: Optional[Mesh], axis: str = "data",
                    gather_on_use: bool = False,
                    reduce_scatter: bool = False,
                    warn_skipped: bool = True
                    ) -> Optional[Zero1Plan]:
    """Build the Zero1Plan a train step consumes, or None when sharding the
    update cannot help (no mesh / trivial axis / nothing splittable).

    `params_like` is the (unboxed) param tree — concrete arrays or abstract
    shapes — and `param_shardings` its NamedSharding tree; the grad/moment
    specs derived here are identical to what make_sharded_state(zero1=True)
    chose for the moments, because mu/nu share their param's shape and base
    spec (flax metadata propagates through tx.init's zeros_like).

    Leaves the derivation leaves on their base layout (nothing divides)
    are recorded in plan.replicated_leaves and warned about loudly
    (warn_skipped=False silences the print for derivation-only callers;
    the list is always populated).
    """
    if mesh is None:
        return None
    if mesh.shape.get(axis, 1) <= 1:
        return None
    grads = zero1_shardings(params_like, param_shardings, mesh, axis)
    changed = any(
        isinstance(g, NamedSharding) and isinstance(p, NamedSharding)
        and g.spec != p.spec
        for g, p in zip(jax.tree.leaves(grads),
                        jax.tree.leaves(param_shardings)))
    if not changed:
        return None
    if reduce_scatter:
        if not gather_on_use:
            raise ValueError(
                "zero1 reduce_scatter requires gather_on_use: the shard_map "
                "region consumes replicated params and emits sharded grads, "
                "so the params must rest sharded and gather at point of use")
        if not rs_supported(mesh, axis):
            raise ValueError(
                f"zero1 reduce_scatter needs a data-only mesh (axis "
                f"'{axis}' > 1, every other axis == 1); got "
                f"{dict(mesh.shape)}")
    plan = Zero1Plan(grad_shardings=grads, param_shardings=param_shardings,
                     axis=axis, gather_on_use=gather_on_use,
                     replicated_leaves=_skipped_leaf_paths(
                         params_like, param_shardings, grads),
                     reduce_scatter=reduce_scatter)
    if warn_skipped:
        warn_replicated_leaves(plan.replicated_leaves, axis,
                               int(mesh.shape.get(axis, 1)))
    return plan


def make_fsdp_plan(params_like: Any, param_shardings: Any,
                   mesh: Optional[Mesh], zero1: bool = False,
                   blocking: bool = False,
                   warn_skipped: bool = True) -> Optional[Zero1Plan]:
    """Gather-on-use plan for fsdp-RESIDENT params (--fsdp_overlap): the
    round-11 ZeRO-1 overlap pattern extended to the fsdp axis.

    Under plain fsdp the params already rest sharded (that is fsdp's
    memory win) and GSPMD inserts the point-of-use gathers implicitly —
    wherever (and fused however) the partitioner likes. This plan makes
    each gather an EXPLICIT per-leaf node exactly like zero1_overlap:

    - grad_shardings = the storage layout the rules table prescribes
      (the fsdp-sharded base specs, plus the appended data axis when
      `zero1` — one derivation with make_sharded_state, so grads
      reduce-scatter into, and the update computes in, the layout the
      state actually rests in);
    - param_shardings = the USE layout: the storage spec with the fsdp
      axis stripped (parallel/rules.strip_axis_spec — whole over fsdp,
      still model-sharded where the table says so). gather_params
      constrains each leaf to it behind the identity-backward VJP +
      optimization_barrier, so each all-gather is an independent,
      overlap-schedulable node whose backward is untouched;
    - axis = 'fsdp'; gather_on_use is always True (there is no "params
      rest gathered" mode for fsdp — resting gathered would simply not
      be fsdp). `blocking` instead selects the BLOCKING reference
      layout: the same gather nodes fused behind one whole-tree barrier
      (every forward op waits on every gather) — what an FSDP
      implementation without prefetch does, and the baseline the
      overlap mode is measured and bit-parity-pinned against
      (tests/test_zero1.py::test_fsdp_overlap_bit_identical).

    The explicit gather-then-compute structure deliberately differs from
    the implicit-GSPMD no-plan program (which is free to sink gathers
    into contracting-dim matmuls as partial-matmul + psum — a different
    accumulation grouping): blocking and overlap share every node and
    are bit-identical to each other; versus the no-plan program the
    values agree to reduction-reorder tolerance only, which the test
    pins as allclose.

    With `zero1` the plan composes both overlaps (requires
    make_sharded_state(zero1=True, zero1_params=True) so params rest in
    the data-appended layout the post-update pin restores). Returns None
    when the mesh has no non-trivial fsdp axis or nothing is
    fsdp-sharded.
    """
    if mesh is None or mesh.shape.get("fsdp", 1) <= 1:
        return None
    rest = param_shardings
    if zero1:
        rest = zero1_shardings(params_like, param_shardings, mesh)
    use = rules_lib.strip_axis_tree(param_shardings, mesh)
    changed = any(
        isinstance(g, NamedSharding) and isinstance(p, NamedSharding)
        and g.spec != p.spec
        for g, p in zip(jax.tree.leaves(rest), jax.tree.leaves(use)))
    if not changed:
        return None
    skipped = (_skipped_leaf_paths(params_like, param_shardings, rest)
               if zero1 else ())
    plan = Zero1Plan(grad_shardings=rest, param_shardings=use,
                     axis="fsdp", gather_on_use=True,
                     replicated_leaves=skipped, blocking_gather=blocking)
    if warn_skipped and zero1:
        warn_replicated_leaves(skipped, "data",
                               int(mesh.shape.get("data", 1)))
    return plan
