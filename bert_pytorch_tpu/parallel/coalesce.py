"""Coalesced cross-device reductions: the scalar-all-reduce storm, bucketed.

Multichip wall time goes to collectives (their share is not measured on
this runtime; the graph lint counts them). The largest *count* contributor in the
compiled train steps is not the gradient traffic (a handful of large,
bandwidth-bound ops) but the reduction STORM of tiny scalars — LAMB's
per-tensor trust-ratio norms alone compile to two `f32[]`/`f32[L]`
all-reduces per parameter leaf (88 of `kfac_zero1_dp8`'s 161 all-reduces
per graph_report), each paying full collective latency to move four
bytes. Latency, not bandwidth, is the bill; batching is the fix — the
same amortization PAPERS.md "Multi-node BERT-pretraining" (2008.00177)
applies to gradient communication.

`NormReducer` coalesces them: per-leaf LOCAL partial sums computed under
`shard_map` (the identical local reduce GSPMD's partial-sum lowering
performs), flattened into deterministic size-capped buckets, ONE `psum`
per bucket, then split back per leaf. Summation grouping is preserved —
local block reduce, then one cross-device sum per element, exactly the
two-level grouping of the per-tensor all-reduces — so the coalesced
update is BIT-IDENTICAL to the per-tensor one (pinned in
tests/test_kfac.py::test_kfac_bucketed_reduction_parity). Leaves whose
layout the reducer cannot bucket fall back to the per-tensor path,
loudly and countably:

- leaves replicated on the mesh need no cross-device reduction at all
  ('local'),
- leaves whose KEPT (per-layer trust ratio) axes are themselves sharded
  would need a sharded output layout ('kept-axis-sharded' — left to
  GSPMD, counted in `summary()`).

The bucket assignment is a pure function of the parameter tree and the
rules-table layout (parallel/rules.py) — deterministic, recorded in the
run header via `summary()` so a bundle/replay can see exactly which
leaves shared a reduction. optim/lamb.py consumes this for the trust
norms (`lamb(norm_reducer=...)`); optim/kfac.py applies the same idea to
the factor-statistic reductions (its own buckets — factor tensors, not
scalars). Both are opt-in: without a reducer the compiled programs are
byte-identical to round 15's.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from bert_pytorch_tpu.parallel.rules import _entry_axes

DEFAULT_BUCKET_BYTES = 4 << 20


def spec_sharded_dims(spec, mesh_sizes: Dict[str, int]) -> Dict[int, tuple]:
    """dim index -> non-trivial mesh axes sharding it, for one
    PartitionSpec (axes of size 1 shard nothing and are ignored)."""
    out: Dict[int, tuple] = {}
    for d, entry in enumerate(tuple(spec) if spec is not None else ()):
        axes = tuple(a for a in _entry_axes(entry)
                     if mesh_sizes.get(a, 1) > 1)
        if axes:
            out[d] = axes
    return out


def _bucketize(sizes: Sequence[int], cap_bytes: int,
               itemsize: int = 4) -> List[List[int]]:
    """Deterministic greedy bucket assignment: walk entries in order,
    start a new bucket when the running payload would exceed the cap.
    Returns index lists; every entry lands in exactly one bucket."""
    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    for i, n in enumerate(sizes):
        b = int(n) * itemsize
        if cur and cur_bytes + b > cap_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += b
    if cur:
        buckets.append(cur)
    return buckets


class NormReducer:
    """Bucketed trust-ratio/global-norm reductions for one parameter
    layout.

    `param_shardings` is the param-shaped tree of NamedShardings (or bare
    PartitionSpecs) the norm inputs will be constrained to when the norms
    are computed — for a ZeRO-1 step that is the plan's grad/shard layout
    (parallel/zero.Zero1Plan.grad_shardings), the layout `_zero1_update`
    pins `norm_params` and the updates to. Deriving the reducer from the
    same tree the plan derived keeps one source of truth: a layout change
    re-derives the buckets.
    """

    def __init__(self, param_shardings: Any, mesh,
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES):
        import jax

        self.mesh = mesh
        self.bucket_bytes = int(bucket_bytes)
        self._specs = [getattr(s, "spec", s)
                       for s in jax.tree.leaves(param_shardings)]
        self._sizes = dict(mesh.shape)
        self._summary: Optional[Dict[str, Any]] = None

    # -- classification -----------------------------------------------------

    def _classify(self, flat_shapes: Sequence[tuple],
                  flat_nbatch: Sequence[int]):
        """(groups, plain): groups maps a sorted tuple of reduction axes
        to the leaf indices bucketed under it; plain lists (index, why)
        for leaves computed per-tensor."""
        groups: Dict[tuple, List[int]] = {}
        plain: List[Tuple[int, str]] = []
        for i, (shape, nb) in enumerate(zip(flat_shapes, flat_nbatch)):
            spec = self._specs[i] if i < len(self._specs) else None
            sd = spec_sharded_dims(spec, self._sizes)
            if not sd:
                plain.append((i, "local"))
            elif any(d < nb for d in sd):
                plain.append((i, "kept-axis-sharded"))
            else:
                key = tuple(sorted({a for axes in sd.values()
                                    for a in axes}))
                groups.setdefault(key, []).append(i)
        return groups, plain

    # -- the coalesced trust norms ------------------------------------------

    def trust_norms(self, pf_tree: Any, u_tree: Any, nbatch_tree: Any,
                    paths: Optional[Sequence[str]] = None
                    ) -> Tuple[Any, Any]:
        """(pn_tree, un_tree): per-leaf L2 norms of `pf_tree` / `u_tree`
        reduced over all but the first nbatch axes (keepdims, like
        optim/lamb.per_tensor computes them), with every cross-device
        reduction bucketed. Bit-identical values to the per-tensor path:
        same local reduce, same per-element cross-device sum, sqrt after
        the reduction in both."""
        import jax
        import jax.numpy as jnp

        from jax import shard_map

        flat_pf, treedef = jax.tree_util.tree_flatten(pf_tree)
        flat_u = jax.tree.leaves(u_tree)
        flat_nb = [int(n) for n in jax.tree.leaves(nbatch_tree)]
        shapes = [tuple(x.shape) for x in flat_pf]
        groups, plain = self._classify(shapes, flat_nb)

        def kept_keepdims(shape, nb):
            return tuple(shape[:nb]) + (1,) * (len(shape) - nb)

        def local_sq(x, nb):
            return jnp.sum(jnp.square(x),
                           axis=tuple(range(nb, x.ndim)))

        pn_out: List[Any] = [None] * len(flat_pf)
        un_out: List[Any] = [None] * len(flat_pf)

        for i, _why in plain:
            nb = flat_nb[i]
            axes = tuple(range(nb, flat_pf[i].ndim))
            pn_out[i] = jnp.sqrt(jnp.sum(jnp.square(flat_pf[i]), axis=axes,
                                         keepdims=True))
            un_out[i] = jnp.sqrt(jnp.sum(jnp.square(flat_u[i]), axis=axes,
                                         keepdims=True))

        summary: Dict[str, Any] = {
            "bucket_bytes": self.bucket_bytes,
            "n_local": len([p for p in plain if p[1] == "local"]),
            "fallback": [
                (paths[i] if paths is not None and i < len(paths)
                 else f"leaf_{i}")
                for i, why in plain if why == "kept-axis-sharded"],
            "groups": [],
        }

        for key in sorted(groups):
            idxs = groups[key]
            # per-leaf partial widths: pn and un contribute kept-size each
            kept_sizes = [int(np.prod(shapes[i][:flat_nb[i]] or (1,)))
                          for i in idxs]
            buckets = _bucketize([2 * k for k in kept_sizes],
                                 self.bucket_bytes)
            summary["groups"].append({
                "axes": list(key),
                "n_leaves": len(idxs),
                "buckets": [
                    {"n_leaves": len(b),
                     "elems": sum(2 * kept_sizes[j] for j in b)}
                    for b in buckets],
            })
            in_specs = tuple(self._specs[i] for i in idxs) * 2
            from jax.sharding import PartitionSpec

            def reduce_group(*blocks, _idxs=idxs, _buckets=buckets,
                             _key=key):
                n = len(_idxs)
                pf_blocks, u_blocks = blocks[:n], blocks[n:]
                partials = []
                for j, i in enumerate(_idxs):
                    nb = flat_nb[i]
                    partials.append(jnp.concatenate([
                        local_sq(pf_blocks[j], nb).reshape(-1),
                        local_sq(u_blocks[j], nb).reshape(-1)]))
                reduced = []
                for b in _buckets:
                    vec = (jnp.concatenate([partials[j] for j in b])
                           if len(b) > 1 else partials[b[0]])
                    red = jax.lax.psum(vec, _key)
                    off = 0
                    for j in b:
                        w = partials[j].shape[0]
                        reduced.append((j, red[off:off + w]))
                        off += w
                reduced.sort(key=lambda t: t[0])
                return tuple(r for _, r in reduced)

            outs = shard_map(
                reduce_group, mesh=self.mesh,
                in_specs=in_specs,
                out_specs=tuple(PartitionSpec() for _ in idxs),
                check_vma=False,
            )(*[flat_pf[i] for i in idxs], *[flat_u[i] for i in idxs])
            for j, i in enumerate(idxs):
                nb = flat_nb[i]
                k = int(np.prod(shapes[i][:nb] or (1,)))
                kd = kept_keepdims(shapes[i], nb)
                pn_out[i] = jnp.sqrt(outs[j][:k].reshape(kd))
                un_out[i] = jnp.sqrt(outs[j][k:].reshape(kd))

        self._summary = summary
        return (jax.tree_util.tree_unflatten(treedef, pn_out),
                jax.tree_util.tree_unflatten(treedef, un_out))

    # -- the coalesced global norm ------------------------------------------

    def global_norm_f32(self, tree: Any) -> Any:
        """fp32-upcast global L2 norm with the cross-device reductions
        bucketed — the drop-in for telemetry/health.global_norm_f32 and
        LAMB's optax.global_norm pre-normalization (both compile one
        scalar all-reduce PER LEAF; this compiles one vector all-reduce
        per reduction-axis group). Bit-identical: same per-leaf local
        reduce, same per-element cross-device sum, and the per-leaf
        totals fold in the same tree-leaves order before the sqrt."""
        import jax
        import jax.numpy as jnp

        from jax.sharding import PartitionSpec

        from jax import shard_map

        flat = [jnp.asarray(x).astype(jnp.float32)
                for x in jax.tree.leaves(tree)]
        shapes = [tuple(x.shape) for x in flat]
        groups, plain = self._classify(shapes, [0] * len(flat))
        totals: List[Any] = [None] * len(flat)
        for i, _why in plain:
            totals[i] = jnp.sum(jnp.square(flat[i]))
        for key in sorted(groups):
            idxs = groups[key]

            def group_sums(*blocks, _key=key):
                vec = jnp.stack([jnp.sum(jnp.square(b)) for b in blocks])
                return jax.lax.psum(vec, _key)

            vec = shard_map(
                group_sums, mesh=self.mesh,
                in_specs=tuple(self._specs[i] for i in idxs),
                out_specs=PartitionSpec(),
                check_vma=False)(*[flat[i] for i in idxs])
            for j, i in enumerate(idxs):
                totals[i] = vec[j]
        return jnp.sqrt(sum(totals))

    def summary(self) -> Optional[Dict[str, Any]]:
        """Deterministic bucket-assignment record (run-header material):
        per reduction-axis group, the bucket layout; plus the fallback
        leaves the reducer left to GSPMD. None until the first traced
        use."""
        return self._summary
