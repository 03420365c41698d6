"""The logical-axis-rules table: ONE source of truth for every sharding
spec in the repo.

Before round 15 the specs lived scattered — the flax rules tuple in
parallel/mesh.py, the ZeRO-1 free-dim-first derivation in
parallel/zero.py, the K-FAC stacked-factor placement in optim/kfac.py,
the batch-input layout in mesh.batch_sharding, and the serving engine's
implicit single-device placement — which meant every collective
optimization had to reason about specs it could not see in one place.
This module is that one place:

- `BASE_RULES`: the logical-axis -> mesh-axis table (each entry carries
  the WHY next to the mapping). `resolve(mesh)` turns it into the
  flax-style pair list, applying any per-mesh-config override from
  `CONFIG_OVERRIDES` — dp-only, dp x fsdp, dp x mp, and dp x seq meshes
  all compose through the same table.
- derivation helpers every consumer routes through:
  `shard_append_spec` (the ZeRO-1 moment/grad layout — free-dim-first
  with a divisibility fallback, formerly parallel/zero.zero1_spec),
  `stacked_spec` (the K-FAC distributed-factor layout, formerly
  KFAC._stacked_sharding), `batch_spec` (the activation/input layout the
  step builders and the serving engine consume), and
  `train_state_expectations` (the full TrainState storage layout plus a
  per-leaf rule LABEL, consumed by training/state.make_sharded_state for
  construction and by tools/graphcheck.py's `sharding_rules` pass for
  verification — the same derivation on both sides is what makes the
  static check meaningful: any ad-hoc constraint site that diverges from
  the table shows up as a compiled in-sharding that the table did not
  derive).

The table is declarative and the check is static: tools/graphcheck.py
compiles every production program combo and verifies each input leaf's
compiled in-sharding against the spec derived here (docs/SHARDING.md is
the operator guide; docs/OBSERVABILITY.md "Static graph analysis" covers
the gate). Fingerprint neutrality of the round-15 refactor — every
pre-existing combo's collective counts + donation hash byte-identical —
is pinned in tests/test_sharding_rules.py.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

MESH_AXES = ("data", "fsdp", "model", "seq")

# a rule's mesh_axes: None (replicated), one axis name, or a tuple of
# axis names whose product shards the dimension
Axes = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class Rule:
    """One row of the table: a logical axis name (what model code
    annotates via nn.with_logical_partitioning) mapped to the mesh
    axis/axes that shard it, with the reason pinned next to the
    mapping."""

    logical: str
    mesh_axes: Axes
    note: str = ""


BASE_RULES: Tuple[Rule, ...] = (
    # -- params ------------------------------------------------------------
    Rule("vocab", ("model", "fsdp"),
         "embedding rows / MLM decoder cols: splitting the big (V, E) "
         "table on its vocab axis over BOTH model and fsdp keeps the ZeRO "
         "memory win while leaving the embed axis replicated — an "
         "embed-sharded table makes every lookup emit a "
         "replicate-then-repartition against the batch-sharded "
         "activations (SPMD 'involuntary full rematerialization')"),
    Rule("embed", "fsdp",
         "hidden dim of params -> ZeRO sharding"),
    Rule("mlp", "model",
         "FFN inner dim -> megatron column/row split"),
    Rule("heads", "model",
         "attention heads"),
    Rule("kv", None, "per-head dim stays whole"),
    Rule("embed_out", None, "output embed dim of row-split kernels"),
    Rule("embed_head", None,
         "embed-dim of the small post-pooler heads (pooler dense, "
         "NSP/classifier kernels): replicated — an fsdp-sharded "
         "contracting dim on a few-KB kernel forces GSPMD to reshard the "
         "batch-sharded (B, E) pooled activations embed-major, an "
         "involuntary full rematerialization on (data x fsdp) meshes "
         "(tests/test_zero1.py 2x2-mesh gate)"),
    Rule("norm", None,
         "(E,)-shaped norm scales/biases and the small "
         "position/token-type tables: sharding a few KB forces XLA into "
         "replicate-then-repartition transitions against the "
         "batch-sharded activations, so they stay replicated by design"),
    Rule("layers", None,
         "scan-stacked layer axis stays replicated. This logical axis "
         "only exists under the stacked layout (config.stacked_params="
         "True, where nn.scan prepends it via PARTITION_NAME); the "
         "unstacked per-layer layout has no leading L dim anywhere, so "
         "its leaves resolve through the remaining rules unchanged — "
         "same mesh placement per layer"),
    # -- activations -------------------------------------------------------
    Rule("data", ("data", "fsdp"),
         "batch shards over data AND fsdp (fsdp devices are data "
         "parallel for activations; only params/moments split on fsdp)"),
    Rule("seq", "seq",
         "sequence axis -> ring-attention seq sharding"),
    Rule("embed_act", None, "activation embed dim stays whole"),
)

# The named production config (round 15): the collective-time feature
# pack — packing + ring attention + ZeRO-1 overlap + fsdp gather-on-use
# — promoted to a first-class CONFIG_OVERRIDES entry so "what the
# production mesh runs" is a name in the rules table, not a flag recipe
# scattered across launch scripts. Its RULE rows are identical to
# BASE_RULES (empty override tuple: every production mesh composes
# through the base table); what the name carries is
# the feature set `production_features(mesh)` derives per mesh shape.
PRODUCTION_CONFIG = "production"

# Per-mesh-config overrides: config name (see `mesh_config`) -> extra
# Rule rows that REPLACE the base row for the same logical axis on that
# config only. The only named entry today is `production` (rule rows ==
# base — the override hook stays load-bearing for the ROADMAP item-1b
# sharded serving mesh and is exercised by tests/test_sharding_rules.py).
CONFIG_OVERRIDES: Dict[str, Tuple[Rule, ...]] = {
    PRODUCTION_CONFIG: (),
}

# K-FAC distributed factor ownership splits the stacked layer axis over
# these mesh axes (optim/kfac.py KFAC.shard_axes default) — part of the
# table so the audit/gate derivations and the live placement agree.
KFAC_SHARD_AXES: Tuple[str, ...] = ("data", "fsdp")

# The ZeRO-1 update shards over this axis (parallel/zero.Zero1Plan.axis
# default).
ZERO1_AXIS = "data"


def mesh_config(mesh=None) -> str:
    """Short name of a mesh's parallelism config: the non-trivial axes in
    MESH_AXES order, joined — 'dp', 'dp_fsdp', 'dp_mp', 'dp_seq',
    'dp_fsdp_mp', ... 'replicated' when every axis is trivial or there is
    no mesh. This is the CONFIG_OVERRIDES key."""
    if mesh is None:
        return "replicated"
    short = {"data": "dp", "fsdp": "fsdp", "model": "mp", "seq": "seq"}
    sizes = dict(mesh.shape)
    parts = [short[a] for a in MESH_AXES if sizes.get(a, 1) > 1]
    return "_".join(parts) if parts else "replicated"


def resolve(mesh=None, overrides: Optional[Dict[str, Tuple[Rule, ...]]]
            = None, config: Optional[str] = None
            ) -> Tuple[Tuple[str, Axes], ...]:
    """The flax-style ((logical, mesh_axes), ...) pair list for `mesh`:
    BASE_RULES with this mesh config's overrides applied row-by-row
    (an override row replaces the base row with the same logical name;
    a new logical name appends). mesh=None returns the base table —
    exactly the tuple parallel/mesh.DEFAULT_LOGICAL_AXIS_RULES re-exports
    for flax contexts that are mesh-agnostic. `config` selects a NAMED
    override entry (e.g. PRODUCTION_CONFIG) instead of the mesh-derived
    key — how run_pretraining resolves the rules when --mesh_config
    picked the production pack."""
    rows = list(BASE_RULES)
    table = CONFIG_OVERRIDES if overrides is None else overrides
    key = config if config is not None else mesh_config(mesh)
    for over in table.get(key, ()):
        for i, row in enumerate(rows):
            if row.logical == over.logical:
                rows[i] = over
                break
        else:
            rows.append(over)
    return tuple((r.logical, r.mesh_axes) for r in rows)


def production_features(mesh=None) -> Dict[str, bool]:
    """The feature set the `production` config turns on for THIS mesh —
    each entry only where the mesh shape can express it:

    - packing: always (unpadded rows are a pure win on any shape);
    - zero1 / zero1_overlap: the data axis is non-trivial (ZeRO-1 shards
      the update over `data`; overlap moves its all-gathers to the point
      of use);
    - fsdp_overlap: the fsdp axis is non-trivial (gather-on-use for
      fsdp-resident params — parallel/zero.make_fsdp_plan);
    - ring_attention: the seq axis is non-trivial (ops/ring_attention.py;
      the default attention impl already routes there — recorded so the
      resolved config names the whole composition).

    run_pretraining consumes this when --mesh_config resolves to
    `production`. The full composition's rate is not measured on this
    runtime: no benchmark cell has a multi-chip mesh yet."""
    sizes = dict(mesh.shape) if mesh is not None else {}
    data = sizes.get("data", 1) > 1
    return {
        "packing": True,
        "zero1": data,
        "zero1_overlap": data,
        "fsdp_overlap": sizes.get("fsdp", 1) > 1,
        "ring_attention": sizes.get("seq", 1) > 1,
    }


def production_qualifies(mesh=None) -> bool:
    """Does this mesh have any axis the production feature pack can use?
    (A single-device / replicated mesh gains nothing — --mesh_config=auto
    keeps the base config there.)"""
    if mesh is None:
        return False
    sizes = dict(mesh.shape)
    return any(sizes.get(a, 1) > 1 for a in ("data", "fsdp", "seq"))


def rule_for(logical: str, mesh=None) -> Axes:
    """The mesh axes the table assigns to one logical axis (None =
    replicated). Raises KeyError on an unknown logical name — a typo in
    a model annotation must not silently replicate."""
    for name, axes in resolve(mesh):
        if name == logical:
            return axes
    raise KeyError(f"no rule for logical axis {logical!r}")


# -- derivation: extra-axis append (the ZeRO-1 layout) -------------------------


def _entry_axes(entry) -> tuple:
    if entry is None:
        return ()
    if isinstance(entry, (tuple, list)):
        return tuple(entry)
    return (entry,)


def shard_append_spec(shape, base_spec, mesh, axis: str = ZERO1_AXIS):
    """base_spec with `axis` added on the best-splittable dim of `shape`
    — the ZeRO-1 moment/grad layout derivation (formerly
    parallel/zero.zero1_spec; zero.py now delegates here).

    Preference order: the largest UNSHARDED dim that divides evenly by
    the axis size; only if no free dim qualifies, stack onto an
    already-sharded dim (largest per-shard extent divisible by the extra
    factor). Free dims first is not just cosmetic — stacking `data` onto
    a dim another mesh axis already shards (e.g. the (model, fsdp)-
    sharded vocab dim of the tied embedding) creates a grad layout
    sharded over every axis at once, which the loss/backward residuals
    can only reach by involuntary full rematerialization (reshard gate,
    tests/test_zero1.py). Returns base_spec unchanged when the axis is
    trivial, already used, or nothing divides (the divisibility
    fallback — prime-sized leaves stay on their base layout instead of
    paying GSPMD ragged-split padding every step). `mesh` only needs a
    `.shape` mapping, so tests can probe prime shard counts without
    devices."""
    from jax.sharding import PartitionSpec

    n = mesh.shape.get(axis, 1) if hasattr(mesh.shape, "get") \
        else dict(mesh.shape)[axis]
    if n <= 1 or not shape:
        return base_spec
    entries = list(tuple(base_spec))
    entries += [None] * (len(shape) - len(entries))
    if any(axis in _entry_axes(e) for e in entries):
        return base_spec

    def shard_factor(entry) -> int:
        f = 1
        for a in _entry_axes(entry):
            f *= mesh.shape[a]
        return f

    best, best_local, best_free = -1, 0, False
    for d, size in enumerate(shape):
        cur = shard_factor(entries[d])
        if size == 0 or size % (cur * n):
            continue
        free = cur == 1
        local = size // cur  # per-shard extent before the new split
        if (free, local) > (best_free, best_local):
            best, best_local, best_free = d, local, free
    if best < 0:
        return base_spec
    prior = _entry_axes(entries[best])
    entries[best] = prior + (axis,) if prior else axis
    return PartitionSpec(*entries)


def shard_append_tree(abstract_tree: Any, base_shardings: Any, mesh,
                      axis: str = ZERO1_AXIS) -> Any:
    """Tree of NamedShardings with the appended axis applied per leaf
    (formerly parallel/zero.zero1_shardings — zero.py delegates here).
    `abstract_tree` supplies shapes (ShapeDtypeStructs or concrete
    arrays), `base_shardings` the matching NamedSharding tree.
    Non-NamedSharding leaves and scalars pass through untouched, so this
    maps safely over a whole opt_state — LAMB's step count keeps its
    replicated placement."""
    import jax
    from jax.sharding import NamedSharding

    def one(ab, sh):
        if not isinstance(sh, NamedSharding):
            return sh
        shape = getattr(ab, "shape", None)
        if not shape:
            return sh
        return NamedSharding(mesh, shard_append_spec(shape, sh.spec, mesh,
                                                     axis))

    return jax.tree.map(one, abstract_tree, base_shardings)


def appended_dim(base_spec, appended_spec, axis: str = ZERO1_AXIS
                 ) -> Optional[int]:
    """The dim index where shard_append_spec placed `axis` — i.e. the one
    entry of `appended_spec` that carries `axis` while the matching
    `base_spec` entry does not — or None for a leaf the divisibility
    fallback left on its base layout. This is the reduce-scatter
    dimension derivation: the ZeRO-1 rs gradient path psum-scatters each
    per-device gradient along exactly this dim, so the scattered local
    block lands in the SAME layout shard_append_spec derived for the
    moments (one derivation serving the plan construction, the scatter,
    and the sharding_rules pass)."""
    a_entries = list(tuple(appended_spec))
    b_entries = list(tuple(base_spec))
    b_entries += [None] * (len(a_entries) - len(b_entries))
    for d, (ae, be) in enumerate(zip(a_entries, b_entries)):
        if axis in _entry_axes(ae) and axis not in _entry_axes(be):
            return d
    return None


# -- derivation: axis strip (the fsdp gather-on-use USE layout) ----------------


FSDP_AXIS = "fsdp"


def strip_axis_spec(base_spec, axis: str = FSDP_AXIS):
    """base_spec with every occurrence of `axis` removed — the USE-layout
    derivation behind fsdp gather-on-use (--fsdp_overlap). Params REST in
    the table's storage layout (which shards their fsdp-ruled dims); at
    the point of use the forward wants them whole over fsdp, and this
    spec is the explicit per-leaf gather target parallel/zero.
    gather_params constrains to. Deriving it here (rather than in
    zero.py) keeps construction (make_sharded_state), the point-of-use
    gather, and the sharding_rules verification reading ONE source: the
    use layout is a pure function of the storage layout the table
    already owns. Entries that shard over `axis` jointly with other
    axes keep the others ((model, fsdp) vocab stays model-sharded at
    use — only the fsdp factor gathers)."""
    from jax.sharding import PartitionSpec

    if base_spec is None:
        return None
    out = []
    for entry in tuple(base_spec):
        axes = tuple(a for a in _entry_axes(entry) if a != axis)
        out.append(axes if len(axes) > 1 else (axes[0] if axes else None))
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def strip_axis_tree(base_shardings: Any, mesh,
                    axis: str = FSDP_AXIS) -> Any:
    """Tree of NamedShardings with `axis` stripped per leaf
    (strip_axis_spec) — the whole-params use layout an fsdp gather-on-use
    plan gathers to. Non-NamedSharding leaves pass through untouched."""
    import jax
    from jax.sharding import NamedSharding

    def one(sh):
        if not isinstance(sh, NamedSharding):
            return sh
        return NamedSharding(mesh, strip_axis_spec(sh.spec, axis))

    return jax.tree.map(one, base_shardings)


# -- derivation: stacked-layer-axis split (the K-FAC factor layout) ------------


def shard_count(mesh, axes: Sequence[str] = KFAC_SHARD_AXES) -> int:
    """Product of the named axes' sizes; missing axes count as 1 so
    custom meshes degrade to the replicated layout instead of raising."""
    if mesh is None:
        return 1
    sizes = dict(mesh.shape)
    return int(np.prod([sizes.get(a, 1) for a in axes]))


def stacked_spec(mesh, n_stacked: int,
                 axes: Sequence[str] = KFAC_SHARD_AXES):
    """NamedSharding splitting a leading stacked-layer axis of size
    `n_stacked` over `axes`, or None when there is no mesh / the axis
    does not divide evenly over the shards (uneven layouts are rejected
    by jax for donated/jitted state; a replicated fallback is always
    correct). Formerly KFAC._stacked_sharding — optim/kfac.py delegates
    here, and so do the shard-audit/gate expectations, which is what
    retires their private copies."""
    shards = shard_count(mesh, axes)
    if shards <= 1 or n_stacked % shards != 0:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(tuple(axes)))


# -- derivation: batch/activation layout ---------------------------------------


def batch_axes(mesh=None) -> Tuple[str, ...]:
    """The mesh axes the table assigns to the batch ('data' logical)
    axis."""
    return tuple(_entry_axes(rule_for("data", mesh)))


def batch_spec(n_leading: int = 1, mesh=None):
    """PartitionSpec for input batches: `n_leading` unsharded leading
    axes (accum, or steps+accum) before the batch axis, which rides the
    table's 'data' rule. n_leading=0 is a flat (batch, ...) array (the
    serving engine's bucketed forwards)."""
    from jax.sharding import PartitionSpec as P

    return P(*([None] * n_leading), batch_axes(mesh))


# -- derivation: whole-TrainState expectations ---------------------------------


def label_logical(spec) -> str:
    """Human label for a leaf's logical annotation: 'logical(vocab,embed)'
    with '-' for unsharded dims, 'replicated' when nothing is annotated."""
    entries = tuple(spec) if spec is not None else ()
    if not any(e is not None for e in entries):
        return "replicated"
    return "logical(" + ",".join(
        "-" if e is None else
        ("+".join(e) if isinstance(e, (tuple, list)) else str(e))
        for e in entries) + ")"


def is_spec_leaf(x) -> bool:
    from jax.sharding import PartitionSpec

    return x is None or isinstance(x, PartitionSpec)


def train_state_shardings(abstract_state: Any, mesh,
                          zero1: bool = False, zero1_params: bool = False,
                          table=None) -> Any:
    """The STORAGE NamedSharding tree the rules table prescribes for a
    TrainState (abstract, with flax Partitioned metadata still boxed —
    training/state.abstract_train_state builds one): logical annotations
    -> mesh axes via `resolve(mesh)`, then the ZeRO-1 appended axis on
    the moments (zero1=True) and on the resting params
    (zero1_params=True, the --zero1_overlap layout).
    training/state.make_sharded_state CONSTRUCTS the state from this
    derivation and tools/graphcheck.py VERIFIES compiled programs
    against it — one derivation, two consumers."""
    from flax import linen as nn

    rules = list(table) if table is not None else list(resolve(mesh))
    logical = nn.get_partition_spec(abstract_state)
    shardings = nn.logical_to_mesh_sharding(logical, mesh, rules)
    unboxed = _unbox(abstract_state)
    if zero1:
        shardings = shardings.replace(opt_state=shard_append_tree(
            unboxed.opt_state, shardings.opt_state, mesh))
    if zero1_params:
        shardings = shardings.replace(params=shard_append_tree(
            unboxed.params, shardings.params, mesh))
    return shardings


def train_state_expectations(abstract_state: Any, mesh,
                             zero1: bool = False,
                             zero1_params: bool = False,
                             table=None) -> Tuple[List[Any], List[str]]:
    """(expected shardings, rule labels), FLAT in tree_leaves order, for
    every leaf of a TrainState — the `sharding_rules` static-analysis
    contract (analysis/passes.py, tools/graphcheck.py). The expected
    sharding is exactly `train_state_shardings`; the label names the
    logical axes the table resolved plus any appended-axis derivation
    ('logical(-,embed)+zero1[data]'), so a gate finding can say WHICH
    rule the compiled program violated."""
    import jax
    from flax import linen as nn
    from jax.sharding import NamedSharding

    base = train_state_shardings(abstract_state, mesh, zero1=False,
                                 table=table)
    final = train_state_shardings(abstract_state, mesh, zero1=zero1,
                                  zero1_params=zero1_params, table=table)
    logical = nn.get_partition_spec(abstract_state)

    # flatten all three with None-as-leaf so the structural Nones
    # (TrainState.precond_state / .telemetry) line the trees up, then
    # drop them — program args flatten without them too
    none_leaf = {"is_leaf": lambda x: x is None}
    flat_logical = jax.tree.leaves(logical, is_leaf=is_spec_leaf)
    flat_base = jax.tree.leaves(base, **none_leaf)
    flat_final = jax.tree.leaves(final, **none_leaf)
    if not (len(flat_logical) == len(flat_base) == len(flat_final)):
        raise ValueError(
            f"rules: logical/base/final leaf counts diverge "
            f"({len(flat_logical)}/{len(flat_base)}/{len(flat_final)})")
    expected, labels = [], []
    for lg, b, f in zip(flat_logical, flat_base, flat_final):
        if b is None and f is None:
            continue  # structural None — not a program input leaf
        label = label_logical(lg)
        if isinstance(f, NamedSharding) and isinstance(b, NamedSharding) \
                and f.spec != b.spec:
            label += f"+zero1[{ZERO1_AXIS}]"
        expected.append(f)
        labels.append(label)
    return expected, labels


def _unbox(tree: Any) -> Any:
    """Local copy of training/state.unbox (strip flax Partitioned boxes)
    to keep the parallel package import-independent of training/."""
    import jax
    from flax import linen as nn

    return jax.tree.map(
        lambda x: x.unbox() if isinstance(x, nn.Partitioned) else x,
        tree,
        is_leaf=lambda x: isinstance(x, nn.Partitioned),
    )
