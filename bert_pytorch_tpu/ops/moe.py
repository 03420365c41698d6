"""Routed experts, as one expert-parallel rank computes them.

The layer is told which experts it holds (a half-open range of the router's
width). It routes every token over ALL experts, and computes, for the
(token, expert) pairs whose expert it holds, that expert's SwiGLU MLP times
the pair's gate; what the absent experts would add is left out (their rank
adds it in a deployment; on one chip the partial sum goes on). Nothing here
stands in for the other ranks or their exchange.

Dropless: every selected pair of a held expert is computed, at any
imbalance; there is no capacity factor. The device's work follows the pairs:
they are sorted by expert and the three matrix products are grouped products
over the held experts (`jax.lax.ragged_dot`, which XLA:TPU lowers to its own
grouped-matmul kernel whose tiles cover the groups' rows and no others).
Shapes are static, so the sorted pairs are worked in windows of
`window_rows` rows (up to all T x k pairs if every selected expert of every
token were held), and the loop over the windows runs the LIVE ones only: its
trip count is `live_windows(n_pairs, window_rows)`, a value of the input,
so at an even load one window runs and no other is walked. Reverse mode
cannot differentiate a loop whose bound is data, and a `lax.scan` over all
windows with a `lax.cond` around each, which it can, costs more than the
live window's work: every skipped window hands back zeros of the held
weights' and the tokens' size, and the scan sums them (PERF.md section 6,
PR 39). So the loop has a rule of its own (`_live_windows`, a
`jax.custom_vjp`). Forward: the live windows
in order, each adding into the sum. Backward: every window adds into the
sum, so each takes the same cotangent; a window's own cotangents are
`jax.vjp` of the same `_window`, recomputed there, so one window's
temporaries are alive whatever the imbalance and nothing of a window is
kept (running the first window in line and keeping its gathered tokens and
up-projections for the backward pass was tried: the step then needs 18.5 of
the chip's 15.75 GiB). The last live window's cotangents ARE the sums of x,
w1, w3 and w2; an earlier live window adds its own into them, in the order
reverse mode did, so the gradients are the scan's bit for bit. The rule's
residuals are its inputs.

Scopes (training/pretrain.LM_STEP_SCOPES): `moe/router`, `moe/dispatch`,
`moe/experts`, `moe/combine`, and `moe/accumulate` for what the backward
loop adds across windows, each opened here under its whole name: inside the
loop and the rule an operation's `op_name` keeps the scopes opened in the
body, not the caller's.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class Routing(NamedTuple):
    experts: jax.Array      # (T, k) int32: the selected experts, of all
    gates: jax.Array        # (T, k) float32: their weights


@jax.named_scope("moe/router")
def route(x: jax.Array, kernel: jax.Array, expert_bias, k: int,
          norm_topk: bool, scaling: float,
          scores: str = "sigmoid") -> Routing:
    """Scores of x (T, H) over the router's E outputs, in float32.
    `scores` = "sigmoid": the k experts with the largest sigmoid score +
    `expert_bias` are selected (the bias is a buffer: it takes no gradient),
    the weights are the selected scores WITHOUT the bias, divided by their
    sum + 1e-6 if `norm_topk`, times `scaling`. "softmax": the k largest
    logits are selected and the weights are the softmax over those k (the
    full softmax renormalised over the selected, so `norm_topk` changes
    nothing), times `scaling`; no selection bias."""
    logits = jnp.dot(x.astype(jnp.float32), kernel.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scores == "softmax":
        if expert_bias is not None:
            raise ValueError("route: softmax scores take no selection bias")
        _, experts = jax.lax.top_k(jax.lax.stop_gradient(logits), k)
        gates = jax.nn.softmax(
            jnp.take_along_axis(logits, experts, axis=-1), axis=-1)
        return Routing(experts.astype(jnp.int32), gates * scaling)
    if scores != "sigmoid":
        raise ValueError(f"route: unknown scores {scores!r}")
    scores = jax.nn.sigmoid(logits)
    select = scores if expert_bias is None else (
        scores + expert_bias.astype(jnp.float32))
    _, experts = jax.lax.top_k(jax.lax.stop_gradient(select), k)
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
    return Routing(experts.astype(jnp.int32), gates * scaling)


def _window_sizes(sizes: jax.Array, start, rows: int) -> jax.Array:
    """Rows of each (expert-sorted) group that fall in [start, start + rows)."""
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    return jnp.clip(jnp.minimum(ends, start + rows)
                    - jnp.maximum(starts, start), 0, None).astype(jnp.int32)


_GATE_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _swiglu_experts(xs, w1, w3, w2, sizes, activation: str = "silu"):
    """Grouped gated MLP: rows of xs grouped by expert (`sizes` rows each,
    in order) through w2_e(act(w1_e x) * w3_e x), act SiLU (SwiGLU) or ReLU
    (ReGLU)."""
    h1 = jax.lax.ragged_dot(xs, w1, sizes, preferred_element_type=xs.dtype)
    h3 = jax.lax.ragged_dot(xs, w3, sizes, preferred_element_type=xs.dtype)
    h = (_GATE_ACTIVATIONS[activation](h1.astype(jnp.float32))
         * h3.astype(jnp.float32)).astype(xs.dtype)
    return jax.lax.ragged_dot(h, w2, sizes,
                              preferred_element_type=jnp.float32)


def _window(out, done, x, w1, w3, w2, tokens, gates, sizes, n_pairs, start,
            activation: str = "silu"):
    """`out` (T, H) float32 plus the contribution of the sorted pairs in
    [start, start + len(tokens)), and `done` plus how many of them were
    computed: gather their tokens, the grouped experts, gate, and add into
    the tokens' rows. Rows past the held pairs belong to no group: they are
    zeroed going in and coming out (a grouped product leaves such rows
    unwritten)."""
    rows = tokens.shape[0]
    with jax.named_scope("moe/dispatch"):
        live = (start + jnp.arange(rows) < n_pairs)[:, None]
        xs = jnp.where(live, x[tokens], jnp.zeros([], x.dtype))
        group_rows = _window_sizes(sizes, start, rows)
    with jax.named_scope("moe/experts"):
        ys = _swiglu_experts(xs, w1, w3, w2, group_rows, activation)
    with jax.named_scope("moe/combine"):
        ys = jnp.where(live, ys * gates[:, None], 0.0)
        return out.at[tokens].add(ys), done + jnp.sum(group_rows)


def live_windows(n_pairs, window_rows: int):
    """Windows of `window_rows` sorted rows that hold a held pair: the trip
    count of the loops below (the first window runs whatever it holds)."""
    return jnp.maximum(-(-n_pairs // window_rows), 1).astype(jnp.int32)


def _window_of(i, window_rows, tokens, gates):
    """Window i of the sorted pairs: (its tokens, its gates, its first
    row)."""
    start = i * window_rows
    return (jax.lax.dynamic_slice(tokens, (start,), (window_rows,)),
            jax.lax.dynamic_slice(gates, (start,), (window_rows,)), start)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _live_windows(window_rows, activation, x, w1, w3, w2, tokens, gates,
                  sizes, n_pairs):
    """(the windows' sum (T, H) float32, pairs computed) over the live
    windows of the sorted pairs, in order. The trip count is data, which
    reverse mode cannot differentiate through: the rule below is the
    loop's."""
    def body(i, carry):
        tk, gt, start = _window_of(i, window_rows, tokens, gates)
        return _window(*carry, x, w1, w3, w2, tk, gt, sizes, n_pairs, start,
                       activation)

    return jax.lax.fori_loop(
        0, live_windows(n_pairs, window_rows), body,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros([], jnp.int32)))


def _live_windows_fwd(window_rows, activation, *args):
    # nothing of a window is kept: the inputs, which are alive anyway
    return _live_windows(window_rows, activation, *args), args


def _live_windows_bwd(window_rows, activation, args, cotangents):
    """The live windows again, last to first: every window adds into `out`,
    so each takes the same cotangent `g` of the sum, and its own cotangents
    are `jax.vjp` of `_window`, recomputed here (one window's temporaries
    alive). The last live window's ARE the sums of x, w1, w3, w2; an
    earlier one's are added in place."""
    x, w1, w3, w2, tokens, gates, sizes, n_pairs = args
    g, _ = cotangents

    def cotangents_of(i):
        tk, gt, start = _window_of(i, window_rows, tokens, gates)
        _, pull = jax.vjp(
            lambda x, w1, w3, w2, gt: _window(
                jnp.zeros(x.shape, jnp.float32), 0, x, w1, w3, w2, tk, gt,
                sizes, n_pairs, start, activation)[0], x, w1, w3, w2, gt)
        return pull(g), start

    last = live_windows(n_pairs, window_rows) - 1
    (dx, dw1, dw3, dw2, dgt), start = cotangents_of(last)
    with jax.named_scope("moe/accumulate"):
        dgates = jax.lax.dynamic_update_slice(
            jnp.zeros(gates.shape, gates.dtype), dgt, (start,))

    def body(j, sums):
        (*own, dgt), start = cotangents_of(last - j)
        with jax.named_scope("moe/accumulate"):
            return (*jax.tree.map(jnp.add, sums[:4], tuple(own)),
                    jax.lax.dynamic_update_slice(sums[4], dgt, (start,)))

    dx, dw1, dw3, dw2, dgates = jax.lax.fori_loop(
        1, last + 1, body, (dx, dw1, dw3, dw2, dgates))
    return dx, dw1, dw3, dw2, None, dgates, None, None


_live_windows.defvjp(_live_windows_fwd, _live_windows_bwd)


def held_experts(x: jax.Array, routing: Routing, w1: jax.Array,
                 w3: jax.Array, w2: jax.Array, held: Tuple[int, int],
                 window_rows: int, activation: str = "silu"
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """sum over the selected AND held experts e of gate_e * w2_e(act(w1_e
    x) * w3_e x), for x (T, H); w1, w3 (E_held, H, F), w2 (E_held, F, H);
    `held` = (lo, hi) of the router's outputs; `activation` "silu" or
    "relu". Returns (the sum (T, H)
    float32, tokens per held expert (E_held,) int32, held pairs NOT computed
    () int32: zero by construction, counted so that it is seen to be)."""
    if activation not in _GATE_ACTIVATIONS:
        raise ValueError(f"held_experts: unknown activation {activation!r}")
    lo, hi = held
    n_held = hi - lo
    t, k = routing.experts.shape
    pairs = t * k
    window_rows = min(int(window_rows), pairs)
    n_windows = -(-pairs // window_rows)
    with jax.named_scope("moe/dispatch"):
        expert = routing.experts.reshape(-1)
        is_held = (expert >= lo) & (expert < hi)
        local = jnp.where(is_held, expert - lo, n_held)     # absent: last
        order = jnp.argsort(local, stable=True)
        sizes = jnp.sum(local[:, None] == jnp.arange(n_held)[None, :],
                        axis=0, dtype=jnp.int32)
        n_pairs = jnp.sum(sizes)
        pad = n_windows * window_rows - pairs       # rows of no pair
        tokens = jnp.pad((order // k).astype(jnp.int32), (0, pad))
        gates = jnp.pad(routing.gates.reshape(-1)[order], (0, pad))

    out, done = _live_windows(window_rows, activation, x, w1, w3, w2, tokens,
                              gates, sizes, n_pairs)
    return out, sizes, n_pairs - done
