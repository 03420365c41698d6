"""K-FAC second-order preconditioning, in-framework and TPU-native.

The reference delegated K-FAC to the external `kfac_pytorch` library wired at
run_pretraining.py:311-345 (factor_decay 0.95, damping 0.003, kl_clip 0.001,
factor_update_freq 1, inv_update_freq 10, skip-list
['BertLMPredictionHead','embedding'], fp16 inverses, NCCL factor
communication). SURVEY §2.2/§2.3 requires it re-implemented in-framework.

TPU-native design (no hooks, no NCCL):
- **Taps, not hooks.** The model sows each encoder linear layer's input
  (collection 'kfac_in') and adds a flax `perturb` on its output; the grad of
  the loss w.r.t. the perturbation IS the layer's output gradient, obtained
  from the same backward pass as the parameter grads — no separate autograd
  machinery (reference lib attached fwd/bwd torch hooks).
- **Layer-stacked factors.** Encoder taps arrive stacked over the scanned
  layer axis (L, ...); factor statistics, EMA updates, Cholesky inverses, and
  preconditioning are vmapped over L — one XLA op per tap *site*, 24x fewer
  kernels than per-layer Python loops. Under the unstacked encoder layout
  (config.stacked_params=False) taps arrive per layer (one 2D site per
  layer_{i}); every code path below already handles both ranks — per-layer
  sites simply take the non-vmapped branch, and the L-axis distributed
  factor ownership does not apply (2D factors stay replicated; they are
  small). Checkpointed KFACState converts between layouts with
  models/pretrained.convert_tree_layout like every other state subtree.
- **Communication is compiled.** Activations/output-grads are batch-sharded;
  the (rows, in)^T @ (rows, in) factor contraction reduces over the sharded
  row axis, so XLA inserts the factor all-reduce over ICI automatically —
  the reference's explicit factor allreduce/HYBRID_OPT machinery dissolves.
- **Factored Tikhonov damping** (pi-correction) and kl_clip rescaling follow
  the standard K-FAC formulation the reference lib implements.
- Kernel and bias are preconditioned jointly via homogeneous-coordinate
  augmentation of A (append-1 activation column).

Scope parity note: taps cover the 96 encoder linears of BERT-Large (4 per
layer x 24) plus the pooler and NSP-head linears — every layer the reference
library preconditioned (it hooked all supported modules minus the skip-list,
run_pretraining.py:311-345). Embeddings and the MLM head are skipped per the
reference's skip-list.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct


@dataclasses.dataclass(frozen=True)
class KFACConfig:
    inv_interval: int = 10          # kfac_inv_interval (reference CLI :132)
    factor_interval: int = 1        # kfac_factor_interval (:134)
    stat_decay: float = 0.95        # kfac_stat_decay (:136)
    damping: float = 0.003          # kfac_damping (:138)
    kl_clip: float = 0.001          # kfac_kl_clip (:140)
    skip_layers: Tuple[str, ...] = ("cls_predictions", "embeddings")
    learning_rate: Union[float, Callable] = 1.0  # for kl_clip scaling
    factor_dtype: Any = jnp.float32
    inverse_dtype: Any = jnp.bfloat16  # reference used fp16 inverses
    # --kfac_stats_dtype: dtype of the per-microbatch factor STATISTICS —
    # the tensors the factor collectives move every factor_interval step.
    # bf16 halves that wire traffic (in bucketed mode the coalesced psums
    # genuinely move bf16 vectors); the EMA still accumulates in f32
    # (_update_factors upcasts into factor_dtype, and _reduce_stats
    # upcasts before the /rows normalization), which is what keeps the
    # trajectory within the f32-parity gate in tests/test_kfac.py.
    # None = factor_dtype (the exact round-15 program, bit for bit).
    stats_dtype: Any = None


@struct.dataclass
class KFACState:
    """factors/inverses are pytrees keyed like the tap tree; each leaf is a
    dict {'A': (..., in+1, in+1), 'G': (..., out, out)} with optional leading
    stacked-layer axes."""

    factors: Any
    inverses: Any
    count: jax.Array  # optimization steps seen


class KFAC:
    """Functional K-FAC: state in a pytree, all updates inside the jitted
    train step. Usage (training/pretrain.py wires this):

        kfac = KFAC(config)
        state0 = kfac.init(acts, pert_grads)
        stats  = kfac.compute_stats(acts, pert_grads)   # per microbatch
        new_state, grads = kfac.step(state, stats, grads, lr)
    """

    def __init__(self, config: KFACConfig, mesh=None,
                 shard_axes: Optional[Tuple[str, ...]] = None,
                 factor_bucket_bytes: Optional[int] = None,
                 factor_sync_freq: int = 1):
        """mesh + shard_axes turn on distributed factor/inverse ownership:
        every layer-stacked site (leaves with a leading L axis) stores its
        factors and inverses sharded over `shard_axes` on the L axis, the
        vmapped Cholesky inversion runs only on each device's L-shard, and
        preconditioning is computed shard-local before XLA re-gathers the
        preconditioned grads to the params' sharding. This is the TPU
        equivalent of the reference K-FAC's distributed inverse ownership
        (comm_method=HYBRID_OPT, grad_worker_fraction=0.5,
        run_pretraining.py:325-327) — except the collectives are compiled
        into the step instead of hand-scheduled NCCL broadcasts. mesh=None
        (single chip) keeps everything replicated. shard_axes defaults to
        the rules table's KFAC_SHARD_AXES (parallel/rules.py — the one
        logical-axis table every sharding derivation routes through).

        `factor_bucket_bytes` (--kfac_bucket_mb) turns on COALESCED
        factor reductions: compute_stats returns per-device PARTIAL
        factor contractions (a leading batch-shard axis, zero collectives
        — the same local matmul GSPMD's partial-dot lowering performs),
        and `step` reduces them in a handful of deterministic size-capped
        buckets (one psum per bucket) instead of one all-reduce per
        factor, dividing the compiled all-reduce count while keeping the
        update bit-identical at accum_steps=1 (same local contraction,
        same per-element cross-device sum, normalization after the
        reduction in both paths — tests/test_kfac.py pins it; at
        accum>1 the partial accumulation reorders the normalization,
        mathematically equal but not bit-equal). The assignment is
        recorded in `self.bucket_assignment` after the first trace (run
        headers log it). Batches whose global rows don't divide the
        batch-shard count fall back to the per-factor path with a loud
        warning.

        `factor_sync_freq` N>1 skips the factor-statistic reduction AND
        the EMA update on steps where count % N != 0 — the statistics are
        EMA-smoothed anyway, so syncing every step buys little once the
        factors have burned in; with bucketed stats the off-step skips
        the psums at runtime, not just the EMA. 1 (the default) compiles
        the exact freq-free program (parity-tested)."""
        from bert_pytorch_tpu.parallel import rules as rules_lib

        self.config = config
        self.mesh = mesh
        self.shard_axes = (tuple(shard_axes) if shard_axes is not None
                           else rules_lib.KFAC_SHARD_AXES)
        self.factor_bucket_bytes = factor_bucket_bytes
        self.factor_sync_freq = int(factor_sync_freq)
        self._batch_axes = tuple(rules_lib.batch_axes(mesh)) \
            if mesh is not None else ()
        self._batch_shards = rules_lib.shard_count(mesh, self._batch_axes) \
            if mesh is not None else 1
        self.bucketed = bool(factor_bucket_bytes) and self._batch_shards > 1
        self.bucket_assignment: Optional[list] = None
        self._site_norms: dict = {}
        self._warned_fallback = False

    def _stats_dtype(self):
        return (self.config.stats_dtype
                if self.config.stats_dtype is not None
                else self.config.factor_dtype)

    def _shard_count(self) -> int:
        from bert_pytorch_tpu.parallel import rules as rules_lib

        # missing axes count as size 1 so custom meshes lacking data/fsdp
        # degrade to the replicated layout instead of raising KeyError
        # (rules.shard_count implements exactly that)
        return rules_lib.shard_count(self.mesh, self.shard_axes)

    def _stacked_sharding(self, n_layers: int):
        """NamedSharding splitting a leading stacked-layer axis of size
        n_layers, or None when there is no mesh / the axis does not divide
        evenly over the shards — parallel/rules.stacked_spec, the same
        derivation the graph gate and scripts/kfac_shard_audit.py verify
        the live state against."""
        from bert_pytorch_tpu.parallel import rules as rules_lib

        return rules_lib.stacked_spec(self.mesh, n_layers, self.shard_axes)

    def _constrain_stacked(self, tree: Any) -> Any:
        """Apply the L-axis sharding constraint to every stacked array
        leaf of a factor/inverse tree (state_shardings decides which —
        the shared placement derivation); 2D (pooler/NSP) leaves stay
        replicated — their inverses are tiny."""
        if self.mesh is None:
            return tree
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        placements = state_shardings(tree, self.mesh, self.shard_axes)
        return jax.tree_util.tree_unflatten(treedef, [
            x if s is None else jax.lax.with_sharding_constraint(x, s)
            for x, s in zip(leaves, placements)])

    # -- tap plumbing -------------------------------------------------------

    @staticmethod
    def _path_is_stacked(path, ndim: int) -> bool:
        """Does this tap ride a leading (L, ...) scan axis? The tree path
        decides where it can: 'layers' (the scan module) => stacked,
        'layer_{i}' (an unstacked per-layer module) => NOT stacked even at
        high rank — an unstacked qkv tap is (B, S, 3, H, Dh), the same ndim
        range a stacked dense tap occupies, so rank alone would misread it.
        Bare trees without either marker (unit tests, ad-hoc callers) keep
        the legacy rank>=4 heuristic."""
        keys = [getattr(k, "key", str(k)) for k in path]
        if "layers" in keys:
            return True
        if any(_LAYER_I_RE.match(k) for k in keys):
            return False
        return ndim >= 4

    @staticmethod
    def _flatten_acts(a: jax.Array, stacked: bool) -> jax.Array:
        """stacked: (L, B, S, F...) -> (L, rows, F_flat); else
        (B, S, F...) -> (rows, F_flat); (B, F) passes through (pooler/NSP
        taps have no sequence axis)."""
        if stacked:
            L = a.shape[0]
            feat = int(np.prod(a.shape[3:])) if a.ndim > 3 else a.shape[-1]
            return a.reshape(L, a.shape[1] * a.shape[2], feat)
        if a.ndim == 2:
            return a
        feat = int(np.prod(a.shape[2:]))
        return a.reshape(a.shape[0] * a.shape[1], feat)

    @staticmethod
    def _site_map(acts: Any, perts: Any):
        """Align the two tap trees: returns pytree of (a, g) leaf pairs with
        the same structure as perts. Sown values arrive as 1-tuples."""
        def unwrap(x):
            return x[0] if isinstance(x, tuple) else x

        acts = jax.tree.map(unwrap, acts, is_leaf=lambda x: isinstance(x, tuple))
        return acts, perts

    # -- statistics ---------------------------------------------------------

    def compute_stats(self, acts: Any, pert_grads: Any) -> Any:
        """One microbatch's factor statistics: A = aug(a)^T aug(a) / rows,
        G = rows * g^T g  (undoes the mean-loss 1/N in g, kfac convention).

        Bucketed mode (factor_bucket_bytes set, batch sharded): returns
        PARTIAL statistics instead — each leaf grows a leading
        batch-shard axis holding the per-device local contraction,
        computed under shard_map with ZERO collectives; `step` reduces
        them bucketed (see _reduce_stats). Falls back to the reduced
        path, loudly, when the batch rows don't divide the shard
        count."""
        acts, perts = self._site_map(acts, pert_grads)
        if self.bucketed:
            sites = self._collect_sites(acts, perts)
            bad = [self._pathkey(p) for p, a, g, stacked in sites
                   if a.shape[1 if stacked else 0] % self._batch_shards]
            if not bad:
                return self._partial_stats(acts, perts, sites)
            if not self._warned_fallback:
                import sys

                print("WARNING: kfac: bucketed factor reductions DISABLED"
                      f" — batch dim of site(s) {', '.join(bad[:4])} not "
                      f"divisible by the {self._batch_shards}-way batch "
                      "sharding; falling back to one all-reduce per "
                      "factor", file=sys.stderr)
                self._warned_fallback = True
            self.bucketed = False
        sdt = self._stats_dtype()

        def stat(path, a, g):
            stacked = self._path_is_stacked(path, a.ndim)
            a = self._flatten_acts(a, stacked).astype(jnp.float32)
            g = self._flatten_acts(g, stacked).astype(jnp.float32)

            def one(a2, g2):
                rows = a2.shape[0]
                ones = jnp.ones((rows, 1), jnp.float32)
                a_aug = jnp.concatenate([a2, ones], axis=1)
                A = (a_aug.T @ a_aug) / rows
                G = (g2.T @ g2) * rows
                return {"A": A.astype(sdt),
                        "G": G.astype(sdt)}

            if stacked:
                return jax.vmap(one)(a, g)
            return one(a, g)

        return jax.tree_util.tree_map_with_path(
            stat, acts, perts, is_leaf=lambda x: isinstance(x, jax.Array))

    # -- bucketed factor reductions (round 15) ------------------------------

    @staticmethod
    def _pathkey(path) -> str:
        return jax.tree_util.keystr(path)

    def _collect_sites(self, acts: Any, perts: Any) -> list:
        """Flat [(path, a, g, stacked)] site list in deterministic tree
        order — the order every bucket assignment derives from."""
        out = []

        def collect(path, a, g):
            out.append((path, a, g, self._path_is_stacked(path, a.ndim)))
            return a

        jax.tree_util.tree_map_with_path(
            collect, acts, perts, is_leaf=lambda x: isinstance(x, jax.Array))
        return out

    def _partial_stats(self, acts: Any, perts: Any, sites: list) -> Any:
        """Per-device PARTIAL factor contractions under shard_map: each
        site's local rows contracted exactly as GSPMD's partial-dot
        lowering would (same local matmul, bit for bit), returned with a
        leading batch-shard axis and NO collective. Normalization (A /
        rows, G * rows) is deferred to _reduce_stats so it lands AFTER
        the cross-device sum, matching the unbucketed program's
        divide-after-all-reduce order."""
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        in_specs, args = [], []
        for path, a, g, stacked in sites:
            bdim = 1 if stacked else 0
            for x in (a, g):
                spec = [None] * x.ndim
                spec[bdim] = self._batch_axes
                in_specs.append(P(*spec))
                args.append(x)
            # rows of the GLOBAL flattened contraction (B*S, or B for the
            # 2D pooler/NSP taps) — the /rows, *rows normalization
            # constants _reduce_stats applies post-psum
            self._site_norms[self._pathkey(path)] = (
                a.shape[1] * a.shape[2] if stacked
                else (a.shape[0] if a.ndim == 2
                      else a.shape[0] * a.shape[1]))

        sdt = self._stats_dtype()

        def local_contract(*blocks):
            outs = []
            for i, (path, _a, _g, stacked) in enumerate(sites):
                a2 = self._flatten_acts(blocks[2 * i],
                                        stacked).astype(jnp.float32)
                g2 = self._flatten_acts(blocks[2 * i + 1],
                                        stacked).astype(jnp.float32)

                def one(a3, g3):
                    ones = jnp.ones((a3.shape[0], 1), jnp.float32)
                    a_aug = jnp.concatenate([a3, ones], axis=1)
                    return a_aug.T @ a_aug, g3.T @ g3

                A, G = (jax.vmap(one)(a2, g2) if stacked else one(a2, g2))
                # the stats_dtype cast lands BEFORE the bucketed psums in
                # _reduce_stats — bf16 stats halve the factor bytes the
                # coalesced reductions actually move (f32 default: no-op)
                outs += [A[None].astype(sdt), G[None].astype(sdt)]
            return tuple(outs)

        out_specs = []
        for path, a, g, stacked in sites:
            for _ in range(2):
                nd = (4 if stacked else 3)  # (1, [L,] d, d) local blocks
                out_specs.append(P(self._batch_axes,
                                   *([None] * (nd - 1))))
        outs = shard_map(local_contract, mesh=self.mesh,
                         in_specs=tuple(in_specs),
                         out_specs=tuple(out_specs),
                         check_vma=False)(*args)

        results = {self._pathkey(p): {"A": outs[2 * i], "G": outs[2 * i + 1]}
                   for i, (p, _a, _g, _s) in enumerate(sites)}
        return jax.tree_util.tree_map_with_path(
            lambda path, a, g: results[self._pathkey(path)],
            acts, perts, is_leaf=lambda x: isinstance(x, jax.Array))

    def local_partial_stats(self, acts: Any, pert_grads: Any,
                            record_norms: bool = True) -> Any:
        """_partial_stats' per-site local contraction for callers that are
        ALREADY inside a shard_map region (the ZeRO-1 reduce-scatter step
        wraps the whole microbatch fwd/bwd in one): same matmuls, same
        (1, [L,] d, d) leading-partial-axis layout, NO shard_map wrapper —
        the caller's out_specs put the leading axis back on the batch
        axes, so `step`'s bucketed _reduce_stats consumes the result
        unchanged. Tap arrays here are LOCAL shards, so the recorded
        /rows, *rows normalization constants are scaled to the GLOBAL row
        counts _reduce_stats divides by (local rows x batch shards —
        exact, because the region's batch in_specs split the rows evenly
        by construction). record_norms=False skips that bookkeeping for
        shape-only probes (the eval_shape pass that derives the region's
        stats out_specs traces this OUTSIDE shard_map, where shapes are
        global and the constants would be 8x wrong)."""
        acts, perts = self._site_map(acts, pert_grads)
        sites = self._collect_sites(acts, perts)
        sdt = self._stats_dtype()
        results = {}
        for path, a, g, stacked in sites:
            if record_norms:
                local_rows = (a.shape[1] * a.shape[2] if stacked
                              else (a.shape[0] if a.ndim == 2
                                    else a.shape[0] * a.shape[1]))
                self._site_norms[self._pathkey(path)] = (
                    local_rows * self._batch_shards)
            a2 = self._flatten_acts(a, stacked).astype(jnp.float32)
            g2 = self._flatten_acts(g, stacked).astype(jnp.float32)

            def one(a3, g3):
                ones = jnp.ones((a3.shape[0], 1), jnp.float32)
                a_aug = jnp.concatenate([a3, ones], axis=1)
                return a_aug.T @ a_aug, g3.T @ g3

            A, G = (jax.vmap(one)(a2, g2) if stacked else one(a2, g2))
            results[self._pathkey(path)] = {"A": A[None].astype(sdt),
                                            "G": G[None].astype(sdt)}
        return jax.tree_util.tree_map_with_path(
            lambda path, a, g: results[self._pathkey(path)],
            acts, perts, is_leaf=lambda x: isinstance(x, jax.Array))

    def _reduce_stats(self, stats: Any) -> Any:
        """Partial stats -> reduced stats through deterministic
        size-capped buckets: ONE psum per bucket over the batch axes
        (the whole point — a handful of all-reduces instead of one per
        factor), then per-site normalization and the factor-dtype cast,
        both AFTER the reduction exactly where the unbucketed program
        puts them. Records self.bucket_assignment (run-header
        material). No-op passthrough for already-reduced trees."""
        from jax.sharding import PartitionSpec as P

        from bert_pytorch_tpu.parallel.coalesce import _bucketize
        from jax import shard_map

        cfg = self.config
        flat = jax.tree_util.tree_flatten_with_path(stats)
        leaves, treedef = flat[0], flat[1]
        sizes = [int(np.prod(x.shape[1:])) for _p, x in leaves]
        buckets = _bucketize(sizes, int(self.factor_bucket_bytes))
        self.bucket_assignment = [
            {"factors": [self._pathkey(leaves[j][0]) for j in b],
             "elems": sum(sizes[j] for j in b)}
            for b in buckets]

        in_specs = tuple(P(self._batch_axes, *([None] * (x.ndim - 1)))
                         for _p, x in leaves)

        def reduce_buckets(*blocks):
            flats = [b.reshape(-1) for b in blocks]
            out = [None] * len(flats)
            for b in buckets:
                vec = (jnp.concatenate([flats[j] for j in b])
                       if len(b) > 1 else flats[b[0]])
                red = jax.lax.psum(vec, self._batch_axes)
                off = 0
                for j in b:
                    out[j] = red[off:off + sizes[j]]
                    off += sizes[j]
            return tuple(out)

        outs = shard_map(reduce_buckets, mesh=self.mesh,
                         in_specs=in_specs,
                         out_specs=tuple(P() for _ in leaves),
                         check_vma=False)(*[x for _p, x in leaves])

        reduced = []
        for (path, x), vec in zip(leaves, outs):
            site_key = self._pathkey(path[:-1])
            kind = getattr(path[-1], "key", str(path[-1]))
            rows = self._site_norms[site_key]
            if vec.dtype != jnp.float32:
                # bf16 stats: normalize (and EMA-accumulate downstream) in
                # f32 — the trace-time guard keeps the f32-default program
                # free of any convert node, i.e. byte-identical to round 15
                vec = vec.astype(jnp.float32)
            full = vec.reshape(x.shape[1:])
            full = full / rows if kind == "A" else full * rows
            reduced.append(full.astype(cfg.factor_dtype))
        return jax.tree_util.tree_unflatten(treedef, reduced)

    def init(self, acts: Any, pert_grads: Any) -> KFACState:
        """Zero factors/identity inverses shaped from one tap evaluation.
        With a mesh, stacked leaves are placed sharded on their layer axis —
        the distributed-ownership layout every later step preserves."""
        stats = self.compute_stats(acts, pert_grads)
        if self.bucketed:
            stats = self._reduce_stats(stats)
        # factors always rest in factor_dtype — stats_dtype only thins the
        # per-step statistics on the wire, never the EMA accumulator.
        # zeros_like (not zeros): it inherits each stat's placement, which
        # is what keeps the compiled step's factor-input layouts — and
        # therefore its donation aliasing — identical to round 15
        factors = jax.tree.map(
            lambda s: jnp.zeros_like(s, dtype=self.config.factor_dtype),
            stats)

        def eye_like(f):
            n = f.shape[-1]
            e = jnp.broadcast_to(jnp.eye(n, dtype=self.config.inverse_dtype),
                                 f.shape)
            return e

        inverses = jax.tree.map(eye_like, factors)
        if self.mesh is not None:
            def place(tree):
                leaves, treedef = jax.tree_util.tree_flatten(tree)
                placements = state_shardings(tree, self.mesh,
                                             self.shard_axes)
                return jax.tree_util.tree_unflatten(treedef, [
                    x if s is None else jax.device_put(x, s)
                    for x, s in zip(leaves, placements)])

            factors = place(factors)
            inverses = place(inverses)
        return KFACState(factors=factors, inverses=inverses,
                         count=jnp.zeros([], jnp.int32))

    # -- factor EMA + inversion --------------------------------------------

    def _update_factors(self, factors: Any, stats: Any) -> Any:
        d = self.config.stat_decay
        new = jax.tree.map(lambda f, s: d * f + (1.0 - d) * s.astype(f.dtype),
                           factors, stats)
        # stats arrive replicated (the batch-axis psum yields the full
        # contraction on every device); constraining the EMA output keeps
        # the stored factors shard-owned — each device updates only its
        # L-slice, the replicated stats are sliced for free
        return self._constrain_stacked(new)

    def _invert(self, factors: Any) -> Any:
        lam = self.config.damping
        out_dtype = self.config.inverse_dtype

        def inv_site(site):
            A, G = site["A"].astype(jnp.float32), site["G"].astype(jnp.float32)

            def one(A2, G2):
                # factored Tikhonov: pi = sqrt((tr(A)/dA) / (tr(G)/dG))
                tr_a = jnp.trace(A2) / A2.shape[-1]
                tr_g = jnp.trace(G2) / G2.shape[-1]
                pi = jnp.sqrt(jnp.maximum(tr_a, 1e-12)
                              / jnp.maximum(tr_g, 1e-12))
                sqrt_lam = jnp.sqrt(lam)
                eye_a = jnp.eye(A2.shape[-1], dtype=jnp.float32)
                eye_g = jnp.eye(G2.shape[-1], dtype=jnp.float32)
                A_inv = _chol_inverse(A2 + sqrt_lam * pi * eye_a)
                G_inv = _chol_inverse(G2 + sqrt_lam / pi * eye_g)
                return A_inv, G_inv

            if A.ndim == 3:
                A_inv, G_inv = jax.vmap(one)(A, G)
            else:
                A_inv, G_inv = one(A, G)
            return {"A": A_inv.astype(out_dtype), "G": G_inv.astype(out_dtype)}

        # the factors are stored L-sharded (distributed ownership): the
        # constraints pin both the input slices and the output layout, so
        # the vmapped Cholesky of a 24-layer stack runs 1/shards of the
        # work per device instead of replicating the whole inversion —
        # the reference's HYBRID_OPT work partitioning, compiled
        inverted = jax.tree.map(inv_site, self._constrain_stacked(factors),
                                is_leaf=lambda x: isinstance(x, dict)
                                and "A" in x)
        return self._constrain_stacked(inverted)

    # -- preconditioning ----------------------------------------------------

    def _precondition_site(self, inv_site, kernel_grad, bias_grad):
        """Jointly precondition (kernel, bias) via the augmented-A inverse.
        kernel (in, F...) in flax layout; bias (F...,)."""
        A_inv = inv_site["A"].astype(jnp.float32)
        G_inv = inv_site["G"].astype(jnp.float32)

        kshape, bshape = kernel_grad.shape, bias_grad.shape

        def one(A_inv2, G_inv2, kg, bg):
            din = A_inv2.shape[-1] - 1
            dout = G_inv2.shape[-1]
            kg2 = kg.reshape(din, dout).astype(jnp.float32)
            bg2 = bg.reshape(dout).astype(jnp.float32)
            aug = jnp.concatenate([kg2, bg2[None, :]], axis=0)  # (in+1, out)
            pre = A_inv2 @ aug @ G_inv2
            return pre[:-1], pre[-1]

        if A_inv.ndim == 3:  # stacked layers: kernel (L, in, F...)
            L = kshape[0]
            pk, pb = jax.vmap(one)(A_inv, G_inv,
                                   kernel_grad.reshape(L, kshape[1], -1),
                                   bias_grad.reshape(L, -1))
        else:
            pk, pb = one(A_inv, G_inv, kernel_grad, bias_grad)
        return pk.reshape(kshape).astype(kernel_grad.dtype), \
            pb.reshape(bshape).astype(bias_grad.dtype)

    def precondition(self, state: KFACState, grads: Any, lr) -> Any:
        """Replace tapped-site grads with F^{-1} g, then kl_clip-rescale the
        preconditioned sites (reference lib's grad scaling).

        Tap variables are named '<dense>_tap' (flax forbids a perturb variable
        sharing its Dense submodule's name); the trailing suffix is stripped
        to address the corresponding {kernel, bias} grads. Sites whose path
        contains any skip_layers token keep their first-order grads
        (reference skip-list semantics, run_pretraining.py:141-144)."""
        skip = self.config.skip_layers
        flat_inv = [(tuple(p[:-1]) + (_strip_tap(p[-1]),), site)
                    for p, site in _flatten_with_path(state.inverses)
                    if not any(tok in "/".join(p) for tok in skip)]
        sq_sum = jnp.zeros([], jnp.float32)
        pre_by_path = {}
        for path, inv_site in flat_inv:
            sub = _tree_get(grads, path)
            sharding = (self._stacked_sharding(inv_site["A"].shape[0])
                        if inv_site["A"].ndim == 3 else None)
            if sharding is not None:
                # move the stacked grads onto the inverse owners' layout so
                # A^-1 @ g @ G^-1 is shard-local; XLA re-shards the
                # preconditioned result back to the params' layout for the
                # optimizer update (one compiled all-to-all each way)
                sub = {
                    "kernel": jax.lax.with_sharding_constraint(
                        sub["kernel"], sharding),
                    "bias": jax.lax.with_sharding_constraint(
                        sub["bias"], sharding),
                }
            pk, pb = self._precondition_site(inv_site, sub["kernel"],
                                             sub["bias"])
            pre_by_path[path] = {"kernel": pk, "bias": pb}
            sq_sum = sq_sum + jnp.sum(pk.astype(jnp.float32)
                                      * sub["kernel"].astype(jnp.float32))
            sq_sum = sq_sum + jnp.sum(pb.astype(jnp.float32)
                                      * sub["bias"].astype(jnp.float32))

        lr_val = jnp.asarray(lr, jnp.float32)
        nu = jnp.minimum(
            1.0,
            jnp.sqrt(self.config.kl_clip
                     / jnp.maximum(lr_val ** 2 * jnp.abs(sq_sum), 1e-30)))
        for path, pre in pre_by_path.items():
            pre = jax.tree.map(lambda x: (x * nu).astype(x.dtype), pre)
            grads = _tree_set(grads, path, pre)
        return grads

    # -- one optimization step ---------------------------------------------

    def step(self, state: KFACState, stats: Any, grads: Any, lr) -> Tuple[
            KFACState, Any]:
        cfg = self.config
        count = state.count + 1

        do_factor = (state.count % cfg.factor_interval) == 0
        if self.factor_sync_freq > 1:
            # --kfac_factor_sync_freq: sync (reduce + EMA) the factor
            # statistics only every N steps — they are EMA-smoothed, so
            # off-steps skip the factor collectives entirely (with
            # bucketed stats the psums live INSIDE this cond's true
            # branch and genuinely don't execute). freq=1 compiles the
            # exact freq-free predicate (parity-pinned in tests).
            do_factor = jnp.logical_and(
                do_factor, (state.count % self.factor_sync_freq) == 0)
        reduce = self._reduce_stats if self.bucketed else (lambda s: s)
        factors = jax.lax.cond(
            do_factor,
            lambda f: self._update_factors(f, reduce(stats)),
            lambda f: f,
            state.factors)

        do_inv = (state.count % cfg.inv_interval) == 0
        inverses = jax.lax.cond(
            do_inv,
            lambda _: self._invert(factors),
            lambda inv: inv,
            state.inverses)

        grads = self.precondition(
            KFACState(factors=factors, inverses=inverses, count=count),
            grads, lr)
        # re-pin the carried state AFTER the lax.conds: the cond output's
        # sharding is whatever GSPMD merges from the two branches, and on
        # some mesh shapes (observed at data=4, fsdp=1) it resolves a
        # subset of sites to replicated — silently undoing the distributed
        # ownership the train step's output then stores. The constraint is
        # free when the merge already chose the owned layout.
        return KFACState(factors=self._constrain_stacked(factors),
                         inverses=self._constrain_stacked(inverses),
                         count=count), grads


def state_shardings(tree: Any, mesh, shard_axes=None) -> list:
    """Flat per-leaf placement list (jax.tree.leaves order) for a K-FAC
    factor/inverse tree: a NamedSharding splitting the leading
    stacked-layer axis where the rules table distributes ownership
    (parallel/rules.stacked_spec — leaves with a leading (L, d, d) stack
    whose L divides the shard count), None where the leaf stays
    replicated by design (2D pooler/NSP factors, scalars, non-divisible
    stacks). The ONE placement derivation shared by KFAC.init,
    KFAC._constrain_stacked, scripts/kfac_shard_audit.py's expectations,
    and tools/graphcheck.py's sharding_rules pass — the audit's former
    private rank>=3 heuristic retired into it."""
    from bert_pytorch_tpu.parallel import rules as rules_lib

    if shard_axes is None:
        shard_axes = rules_lib.KFAC_SHARD_AXES

    def one(x):
        if getattr(x, "ndim", 0) < 3:
            return None
        return rules_lib.stacked_spec(mesh, x.shape[0], shard_axes)

    return [one(x) for x in jax.tree.leaves(tree)]


TAP_SUFFIX = "_tap"
_LAYER_I_RE = re.compile(r"^layer_\d+$")


def _strip_tap(name: str) -> str:
    return name[:-len(TAP_SUFFIX)] if name.endswith(TAP_SUFFIX) else name


def _chol_inverse(mat: jax.Array) -> jax.Array:
    """Inverse of an SPD matrix via Cholesky (XLA-native; the reference
    needed MAGMA on GPU for this — README.md:181-187)."""
    chol = jnp.linalg.cholesky(mat)
    eye = jnp.eye(mat.shape[-1], dtype=mat.dtype)
    inv_l = jax.scipy.linalg.solve_triangular(chol, eye, lower=True)
    return inv_l.T @ inv_l


def _flatten_with_path(tree: Any):
    """[(path_tuple, site_dict)] for every {'A','G'} site."""
    out = []

    def walk(node, path):
        if isinstance(node, dict) and "A" in node and "G" in node:
            out.append((path, node))
            return
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, path + (k,))

    walk(tree, ())
    return out


def _tree_get(tree: Any, path: Tuple[str, ...]) -> Any:
    node = tree
    for k in path:
        node = node[k]
    return node


def _tree_set(tree: Any, path: Tuple[str, ...], value: Any) -> Any:
    """Non-mutating nested-dict set."""
    if not path:
        return value
    head, rest = path[0], path[1:]
    new = dict(tree)
    new[head] = _tree_set(tree[head], rest, value)
    return new
