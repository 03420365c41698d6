"""LAMB optimizer (large-batch Adam with layerwise trust ratio).

The reference's large-batch path is apex `FusedLAMB` (run_pretraining.py:285),
a fused CUDA multi-tensor implementation of NVLAMB. Semantics reproduced here
as a pure optax GradientTransformation, jitted into the train step so XLA
fuses the whole update, a fusion a leaf. NVLAMB specifics honored:

1. optional pre-normalization of the *global* gradient by
   max(1, ||g||_global / max_grad_norm)  (apex FusedLAMB max_grad_norm=1.0),
2. Adam moments with bias correction,
3. per-tensor update u = m_hat/(sqrt(v_hat)+eps) + wd*p,
4. trust ratio ||p|| / ||u||, taken as 1 when either norm is zero,
5. p <- p - lr * ratio * u.

Weight-decay masking (bias / LayerNorm params excluded) follows the
reference's two param groups (run_pretraining.py:268-276); the mask fn lives
with the trainer so this transform stays group-agnostic.

Layer-stacked parameters (the nn.scan encoder stores each weight as one
[L, ...] tensor) get PER-LAYER trust ratios via `trust_batch_axes`: apex
FusedLAMB saw 24 separate tensors and computed 24 ratios, so norms here
reduce over all but the leading stack axis and the ratio broadcasts back.
Collapsing the stack into one ratio would silently change the optimizer.
Gradients may arrive in bf16 (the train step accumulates microbatch grads in
the compute dtype — the reference's apex O2 kept fp16 grads); moments are
computed and stored fp32 regardless.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import optax


class LambState(NamedTuple):
    count: jax.Array
    mu: Any
    nu: Any


def lamb(
    learning_rate: Union[float, optax.Schedule],
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-6,
    weight_decay: float = 0.01,
    weight_decay_mask: Optional[Callable[[Any], Any]] = None,
    max_grad_norm: Optional[float] = 1.0,
    bias_correction: bool = True,
    trust_batch_axes: Optional[Callable[[Any], Any]] = None,
    norm_reducer: Optional[Any] = None,
) -> optax.GradientTransformation:
    """apex-FusedLAMB-semantics LAMB. `weight_decay_mask(params)` returns a
    pytree of bools — True where decay applies. `trust_batch_axes(params)`
    returns a pytree of ints: the number of leading "stack" axes a leaf
    carries (1 for the nn.scan [L, ...] encoder weights, 0 otherwise); trust
    norms reduce over the remaining axes so each stacked layer gets its own
    ratio, exactly as apex saw L separate tensors.

    `norm_reducer` (parallel/coalesce.NormReducer, built from the same
    sharding layout the train step constrains params/updates to): compute
    the per-tensor trust norms through BUCKETED cross-device reductions —
    a handful of vector all-reduces instead of two scalar all-reduces per
    parameter leaf (the dominant all-reduce COUNT in the sharded steps,
    see graph_report kfac_zero1_dp8). Values are bit-identical to the
    per-tensor path (same local reduce, same per-element cross-device
    sum — pinned in tests); None keeps the original per-tensor code
    byte-for-byte."""

    def init(params):
        zeros = lambda: jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return LambState(count=jnp.zeros([], jnp.int32), mu=zeros(), nu=zeros())

    def update(grads, state, params):
        if params is None:
            raise ValueError("lamb requires params")
        count = state.count + 1
        cf = count.astype(jnp.float32)

        if max_grad_norm is not None:
            # upcast leaves BEFORE the reduce: grads may arrive bf16 and a
            # sum of ~3e8 squares in 8 mantissa bits is garbage; the cast
            # fuses into the reduction (no extra HBM pass). With a
            # norm_reducer the per-leaf scalar all-reduces coalesce into
            # one bucketed reduction — same upcast, same fold order,
            # bit-identical norm
            if norm_reducer is not None:
                gnorm = norm_reducer.global_norm_f32(grads)
            else:
                gnorm = optax.global_norm(
                    jax.tree.map(lambda g: g.astype(jnp.float32), grads))
            denom = jnp.maximum(1.0, gnorm / max_grad_norm)
        else:
            denom = None

        def norm_g(g):
            g = g.astype(jnp.float32)
            return g / denom if denom is not None else g

        # two traversals, one HLO: XLA CSEs the shared g/denom subexpression
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * norm_g(g),
                          state.mu, grads)
        nu = jax.tree.map(
            lambda v, g: b2 * v + (1 - b2) * jnp.square(norm_g(g)),
            state.nu, grads)

        if bias_correction:
            c1 = 1.0 - b1 ** cf
            c2 = 1.0 - b2 ** cf
        else:
            c1 = c2 = 1.0

        if weight_decay_mask is not None:
            wd_tree = jax.tree.map(
                lambda use: weight_decay if use else 0.0,
                weight_decay_mask(params))
        else:
            wd_tree = jax.tree.map(lambda _: weight_decay, params)
        if trust_batch_axes is not None:
            ba_tree = trust_batch_axes(params)
        else:
            ba_tree = jax.tree.map(lambda _: 0, params)

        lr = learning_rate(count - 1) if callable(learning_rate) else learning_rate

        def per_tensor(p, m, v, wd, nbatch):
            pf = p.astype(jnp.float32)
            u = (m / c1) / (jnp.sqrt(v / c2) + eps) + wd * pf
            axes = tuple(range(nbatch, u.ndim))
            pn = jnp.sqrt(jnp.sum(jnp.square(pf), axis=axes, keepdims=True))
            un = jnp.sqrt(jnp.sum(jnp.square(u), axis=axes, keepdims=True))
            ratio = jnp.where((pn > 0) & (un > 0), pn / jnp.maximum(un, 1e-30),
                              1.0)
            return (-lr * ratio * u).astype(p.dtype)

        if norm_reducer is None:
            updates = jax.tree.map(per_tensor, params, mu, nu, wd_tree,
                                   ba_tree)
        else:
            # same u, same ratio formula — only the pn/un REDUCTIONS are
            # routed through the bucketed reducer (one vector all-reduce
            # per bucket instead of two scalars per leaf)
            pf_tree = jax.tree.map(lambda p: p.astype(jnp.float32), params)
            u_tree = jax.tree.map(
                lambda pf, m, v, wd: (m / c1) / (jnp.sqrt(v / c2) + eps)
                + wd * pf, pf_tree, mu, nu, wd_tree)
            pn_tree, un_tree = norm_reducer.trust_norms(pf_tree, u_tree,
                                                        ba_tree)

            def apply_ratio(p, u, pn, un):
                ratio = jnp.where((pn > 0) & (un > 0),
                                  pn / jnp.maximum(un, 1e-30), 1.0)
                return (-lr * ratio * u).astype(p.dtype)

            updates = jax.tree.map(apply_ratio, params, u_tree, pn_tree,
                                   un_tree)
        return updates, LambState(count=count, mu=mu, nu=nu)

    return optax.GradientTransformation(init, update)


def default_trust_batch_axes(params: Any) -> Any:
    """1 for encoder weights stacked by nn.scan along a leading [L, ...]
    layer axis (path contains the scan collection name 'layers'), else 0.
    Gives layer-stacked tensors per-layer trust ratios (apex parity — it saw
    L separate tensors, run_pretraining.py:268-286). Under the unstacked
    layout (config.stacked_params=False) encoder paths are 'layer_{i}', not
    'layers', so every leaf gets 0 batch axes — one ratio per tensor, which
    IS a per-layer ratio there: both layouts optimize identically."""

    def n_batch(path: tuple) -> int:
        keys = [str(getattr(k, "key", k)) for k in path]
        # a routed layer's (E, ...) expert stacks: one ratio per expert
        # matrix (models/decoder.RoutedExperts), as for a scan's layers
        return 1 if ("layers" in keys
                     or keys[-1].startswith("experts_")) else 0

    return jax.tree_util.tree_map_with_path(lambda p, _: n_batch(p), params)


def default_weight_decay_mask(params: Any) -> Any:
    """True for params that get weight decay: everything except biases and
    LayerNorm scale/bias (reference no_decay list ['bias','gamma','beta',
    'LayerNorm'], run_pretraining.py:268-276)."""

    def is_decay(path: tuple) -> bool:
        keys = [getattr(k, "key", str(k)) for k in path]
        joined = "/".join(str(k) for k in keys).lower()
        if joined.endswith("/bias") or joined == "bias":
            return False
        if "layer_norm" in joined or "layernorm" in joined:
            return False
        # the decoder families' RMSNorm gains and the router's selection
        # bias (a buffer: zero gradient, and with no decay no update)
        if joined.endswith("_norm/scale") or joined.endswith("expert_bias"):
            return False
        # kimi_linear's KDA mixer: the heads' norm gain, the decay's A_log
        # and dt_bias, the output gate's bias
        if str(keys[-1]) in ("o_norm", "A_log", "dt_bias", "g_bias"):
            return False
        return True

    return jax.tree_util.tree_map_with_path(lambda p, _: is_decay(p), params)
