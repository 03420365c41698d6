"""Plain LFM2-MoE: the benchmark's reference for `correct` in the cells of
the `lfm2_moe` family.

Forward pass, next-token loss, their gradients (jax.grad of the forward) and
the LAMB update in straightforward jax.numpy, float32, under
`jax.default_matmul_precision("highest")`. Written from the family's public
`config.json` (LiquidAI/LFM2-24B-A2B: `layer_types`, `num_dense_layers`,
`conv_L_cache`, `num_experts_per_tok`, `use_expert_bias`, `norm_topk_prob`,
`routed_scaling_factor`, `rope_parameters`, `norm_eps`) and the family's
published description (pre-norm blocks; a gated short convolution or
grouped-query attention with QK-norm and rotary positions as the operator;
a dense SwiGLU MLP in the leading layers, sigmoid-routed experts after
them). No kernels, no sorting, no imports from the program under test; the
matrix product with its lower-precision control, the learning-rate schedule
and the clipped gradient are the BERT reference's (bert_ref.py).

One row (one packed sequence) at a time, so that the published widths fit:
a micro-batch's loss is the sum of its rows' negative log-likelihoods over
the micro-batch's count of predicted positions, its gradient the sum of the
rows' gradients. For the same reason each layer is rematerialised in the
backward pass (`jax.checkpoint`: one layer's activations alive at a time),
as is each attention head's (S, S) score matrix; that changes no value.

Departures from the published description, each because the source does
not say or because the configuration is one rank's share:

- EXPERT-PARALLEL SHARE: the layer is given `held` = [lo, hi), the experts
  this rank holds. The router scores all `experts_total` experts and
  selects `topk` of them; the output is the sum over selected AND held
  experts (a loop over the held experts with a mask). What the absent
  experts would add is left out, and that partial sum goes on to the next
  layer, as it does in the program. `held` = [0, experts_total) is the
  whole layer (tests/benchmark/test_bench_lfm2_reference.py adds the
  shares up to it).
- VOCABULARY SLICE: the table has `vocab` rows, the rank's slice; ids are
  drawn from it and logits, softmax and loss are over it.
- TIED HEAD: logits are the final norm's output times the table transposed
  (the family's config class ties by default; the catalog row has no key).
- The selection bias `b` (`use_expert_bias`) is a held buffer: it takes no
  gradient and no update (its update rule is not in the config). It is ONE
  draw from N(0, init_range), made from the fixed key `BIAS_KEY` and the
  layer's number: not zero, so that the selection by score + `b` differs
  from the selection by score (a program that ignored `b`, or weighted by
  it, picks other experts than this file), and the same for every seed
  (ISSUE 26 asked for a draw from the run's seed; against selection scores
  whose standard deviation over tokens is 0.19 such a draw spreads the
  experts' loads by a quarter, and a new draw per seed moved the share of
  pairs that reach the 8 held experts from 0.112 to 0.133 and with it the
  cell's tokens/s by 0.6 % run to run: PERF.md, PR 26).
- PACKED ROWS (the source defines no packing): a token attends to
  earlier-or-equal positions of its own document only; rotary positions
  restart at each document; a convolution tap that falls before the
  document's first token is zero; the loss is over positions whose
  successor lies in the same document.
- LAMB (the source names no optimizer): the BERT reference's, decay off for
  norm gains and `b`, one trust ratio per tensor and per expert matrix.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.bert_ref import (_mm, clipped_gradient,
                                          poly_warmup_lr)

HARD_MASK = -1e30
BIAS_KEY = 26       # the selection biases' own key: the same in every run
NORM_GAINS = ("op_norm", "ffn_norm", "q_norm", "k_norm", "final_norm")
EXPERT_STACKS = ("ew1", "ew3", "ew2")


def sizes_from_config(cfg: dict) -> dict:
    """The sizes the reference needs, from a configuration file's keys (the
    source's, plus the cut: `layers_kept`, `experts_total`, `experts_held`)."""
    kept = cfg.get("layers_kept") or list(range(cfg["num_hidden_layers"]))
    kinds = [("conv" if cfg["layer_types"][i] == "conv" else "attention",
              "dense" if j < cfg["num_dense_layers"] else "moe")
             for j, i in enumerate(kept)]
    total = int(cfg.get("experts_total") or cfg["num_experts"])
    held = tuple(cfg.get("experts_held") or (0, cfg["num_experts"]))
    return {
        "vocab": int(cfg["vocab_size"]), "hidden": int(cfg["hidden_size"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["hidden_size"]) // int(cfg["num_attention_heads"]),
        "dense_width": int(cfg["intermediate_size"]),
        "expert_width": int(cfg["moe_intermediate_size"]),
        "experts_total": total, "held": (int(held[0]), int(held[1])),
        "topk": int(cfg["num_experts_per_tok"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "scaling": float(cfg["routed_scaling_factor"]),
        "use_bias": bool(cfg["use_expert_bias"]),
        "taps": int(cfg["conv_L_cache"]), "eps": float(cfg["norm_eps"]),
        "theta": float(cfg["rope_parameters"]["rope_theta"]),
        "init_range": float(cfg.get("initializer_range", 0.02)),
        "kinds": tuple(kinds),
    }


def param_shapes(sz: dict) -> dict:
    e, d = sz["hidden"], sz["head_dim"]
    n_held = sz["held"][1] - sz["held"][0]
    layers = []
    for operator, ffn in sz["kinds"]:
        lp = {"op_norm": (e,), "ffn_norm": (e,)}
        if operator == "conv":
            lp.update(w_in=(e, 3 * e), conv_w=(e, sz["taps"]), w_out=(e, e))
        else:
            lp.update(wq=(e, sz["heads"] * d), wk=(e, sz["kv_heads"] * d),
                      wv=(e, sz["kv_heads"] * d), q_norm=(d,), k_norm=(d,),
                      wo=(sz["heads"] * d, e))
        if ffn == "dense":
            f = sz["dense_width"]
            lp.update(w1=(e, f), w3=(e, f), w2=(f, e))
        else:
            f = sz["expert_width"]
            lp.update(wg=(e, sz["experts_total"]), ew1=(n_held, e, f),
                      ew3=(n_held, e, f), ew2=(n_held, f, e))
            if sz["use_bias"]:
                lp["b"] = (sz["experts_total"],)
        layers.append(lp)
    return {"embed": (sz["vocab"], e), "final_norm": (e,), "layers": layers}


def init_params(seed: int, sz: dict) -> dict:
    """Every weight from `seed` in one jitted call: matrices, the table and
    the convolution's taps N(0, init_range); norm gains 1. The selection
    biases `b` are N(0, init_range) too, but from BIAS_KEY and the layer's
    number, so that every seed trains against the same ones."""
    shapes = param_shapes(sz)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    names = [str(getattr(path[-1], "key", path[-1])) for path, _ in flat]
    # a `b` lies at ["layers"][l]["b"]
    bias_keys = [jax.random.fold_in(jax.random.PRNGKey(BIAS_KEY), path[1].idx)
                 if name == "b" else None
                 for name, (path, _) in zip(names, flat)]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(flat))
        keys = [k if b is None else b for k, b in zip(keys, bias_keys)]
        leaves = [jnp.ones(shape, jnp.float32) if name in NORM_GAINS
                  else sz["init_range"] * jax.random.normal(
                      k, shape, jnp.float32)
                  for k, name, (_, shape) in zip(keys, names, flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    seed = int(seed)      # may exceed 32 signed bits: folded in two halves
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return make(key)


# -- the layers, for one row: x (S, hidden) ------------------------------------


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * w


def document_positions(seg):
    """Position of every token inside its document: seg (S,) holds the
    document's number (1..n, 0 = padding), documents are contiguous.
    Padding is no document: every padding token stands at position 0."""
    idx = jnp.arange(seg.shape[0])
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    pos = idx - jax.lax.cummax(jnp.where(first, idx, 0))
    return jnp.where(seg > 0, pos, 0)


def _rotary(x, pos, theta):
    """Rotate-half rotary over the whole head: x (S, heads, D)."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


def _attention(x, lp, seg, pos, sz, quant):
    s = x.shape[0]
    h, hkv, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    q = _mm(x, lp["wq"], quant).reshape(s, h, d)
    k = _mm(x, lp["wk"], quant).reshape(s, hkv, d)
    v = _mm(x, lp["wv"], quant).reshape(s, hkv, d)
    q = _rotary(_rms_norm(q, lp["q_norm"], sz["eps"]), pos, sz["theta"])
    k = _rotary(_rms_norm(k, lp["k_norm"], sz["eps"]), pos, sz["theta"])
    allowed = ((seg[:, None] == seg[None, :]) & (seg[:, None] > 0)
               & (jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]))

    @jax.checkpoint     # one head's (S, S) scores alive at a time
    def head(qh, kh, vh):
        scores = jnp.matmul(qh, kh.T, precision="highest") / jnp.sqrt(
            jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(allowed, scores, HARD_MASK), -1)
        # padding attends nowhere: its output is zero
        return jnp.matmul(probs, vh, precision="highest") * (
            seg[:, None] > 0)

    group = h // hkv
    ctx = jax.lax.map(
        lambda i: head(q[:, i], k[:, i // group], v[:, i // group]),
        jnp.arange(h))                                      # (h, S, d)
    return _mm(ctx.transpose(1, 0, 2).reshape(s, h * d), lp["wo"], quant)


def _short_conv(x, lp, pos, sz, quant):
    b, c, xg = jnp.split(_mm(x, lp["w_in"], quant), 3, axis=-1)
    u = b * xg
    taps = sz["taps"]
    out = jnp.zeros_like(u)
    for j in range(taps):               # tap j reads the token taps-1-j back
        back = taps - 1 - j
        shifted = jnp.pad(u, ((back, 0), (0, 0)))[:u.shape[0]]
        shifted = jnp.where((pos >= back)[:, None], shifted, 0.0)
        out = out + lp["conv_w"][:, j] * shifted
    return _mm(c * out, lp["w_out"], quant)


def _swiglu(x, w1, w3, w2, quant):
    return _mm(jax.nn.silu(_mm(x, w1, quant)) * _mm(x, w3, quant), w2, quant)


def route(x, lp, sz):
    """(selected experts (S, k), their weights (S, k), gap between the k-th
    and (k+1)-th selection scores (S,)): sigmoid scores, the k largest of
    score + b selected, weights the selected scores WITHOUT b, over their
    sum + 1e-6, times the scaling factor."""
    scores = jax.nn.sigmoid(jnp.matmul(x, lp["wg"], precision="highest"))
    select = scores + lp["b"] if "b" in lp else scores
    top, experts = jax.lax.top_k(jax.lax.stop_gradient(select),
                                 sz["topk"] + 1)
    gap = top[:, sz["topk"] - 1] - top[:, sz["topk"]]
    experts = experts[:, :sz["topk"]]
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    if sz["norm_topk"]:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-6)
    return experts, gates * sz["scaling"], gap


def _experts(x, lp, sz, quant, tie_tol):
    """The held experts' part of the routed FFN, the tokens each held
    expert received (padding is routed like any token: the layer does not
    know it), and how many tokens sit within `tie_tol` of another
    selection."""
    experts, gates, gap = route(x, lp, sz)
    lo, hi = sz["held"]

    def add_expert(out, held):
        e, w1, w3, w2 = held
        # this expert's weight for every token: its gate where selected
        weight = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
        out = out + weight[:, None] * _swiglu(x, w1, w3, w2, quant)
        return out, jnp.sum(jnp.any(experts == e, axis=-1))

    # a loop over the held experts, every token through each, masked
    out, counts = jax.lax.scan(
        jax.checkpoint(add_expert), jnp.zeros_like(x),
        (jnp.arange(lo, hi), lp["ew1"], lp["ew3"], lp["ew2"]))
    return out, counts, jnp.sum(gap < tie_tol)


def row_forward(params, ids, seg, sz, quant=None, tie_tol=0.0):
    """One row: ids, seg (S,) -> (logits (S, vocab), per routed layer the
    held experts' token counts (n_routed, n_held) and near-tie tokens
    (n_routed,))."""
    pos = document_positions(seg)
    n_held = sz["held"][1] - sz["held"][0]

    def layer(x, lp, operator, ffn):
        normed = _rms_norm(x, lp["op_norm"], sz["eps"])
        if operator == "conv":
            x = x + _short_conv(normed, lp, pos, sz, quant)
        else:
            x = x + _attention(normed, lp, seg, pos, sz, quant)
        normed = _rms_norm(x, lp["ffn_norm"], sz["eps"])
        if ffn == "dense":
            return (x + _swiglu(normed, lp["w1"], lp["w3"], lp["w2"], quant),
                    jnp.zeros((n_held,), jnp.int32), jnp.zeros([], jnp.int32))
        out, count, tie = _experts(normed, lp, sz, quant, tie_tol)
        return x + out, count, tie

    x = params["embed"][ids]
    counts, ties = [], []
    for lp, (operator, ffn) in zip(params["layers"], sz["kinds"]):
        x, count, tie = jax.checkpoint(layer, static_argnums=(2, 3))(
            x, lp, operator, ffn)
        if ffn == "moe":
            counts.append(count)
            ties.append(tie)
    x = _rms_norm(x, params["final_norm"], sz["eps"])
    return (_mm(x, params["embed"].T, quant),
            jnp.stack(counts) if counts else jnp.zeros((0, n_held), jnp.int32),
            jnp.stack(ties) if ties else jnp.zeros((0,), jnp.int32))


def next_token_labels(ids, seg):
    """The successor's id where it lies in the same document, else -1."""
    nxt_ids = jnp.concatenate([ids[1:], jnp.zeros((1,), ids.dtype)])
    nxt_seg = jnp.concatenate([seg[1:], jnp.zeros((1,), seg.dtype)])
    return jnp.where((seg > 0) & (nxt_seg == seg), nxt_ids, -1)


def row_nll(params, ids, seg, sz, quant=None, tie_tol=0.0):
    """(sum of the row's negative log-likelihoods, (counts, ties))."""
    logits, counts, ties = row_forward(params, ids, seg, sz, quant, tie_tol)
    labels = next_token_labels(ids, seg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None], 1)[:, 0]
    return jnp.sum(jnp.where(labels >= 0, nll, 0.0)), (counts, ties)


class _Sizes(dict):
    """A sizes dict that can be a static argument of jit."""

    def __hash__(self):
        return hash(tuple(sorted((k, v) for k, v in self.items())))


@functools.partial(jax.jit, static_argnames=("sz", "quant", "tie_tol"))
def _row_grad(params, ids, seg, sz, quant, tie_tol):
    return jax.value_and_grad(row_nll, has_aux=True)(
        params, ids, seg, sz, quant, tie_tol)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc, grads, scale):
    return jax.tree.map(lambda a, g: a + g * scale, acc, grads)


def step_loss_and_grad(params, micro_batches, sz: dict, quant=None,
                       tie_tol: float = 0.0):
    """Loss and gradient of one optimisation step: the mean over its
    micro-batches (dicts of input_ids and segment_ids, (rows, S)) of the
    micro-batch's mean negative log-likelihood, one ROW at a time. Also the
    step's held-expert token counts and near-tie tokens per routed layer."""
    sz = _Sizes(sz)
    n = float(len(micro_batches))
    with jax.default_matmul_precision("highest"):
        loss, acc, counts, ties = 0.0, None, 0, 0
        for micro in micro_batches:
            ids, seg = micro["input_ids"], micro["segment_ids"]
            labelled = sum(
                int(jnp.sum(next_token_labels(ids[r], seg[r]) >= 0))
                for r in range(ids.shape[0]))
            scale = 1.0 / (max(labelled, 1) * n)
            for r in range(ids.shape[0]):
                (nll, (c, t)), grads = _row_grad(
                    params, ids[r], seg[r], sz, quant, float(tie_tol))
                loss = loss + nll * scale
                counts, ties = counts + c, ties + t
                acc = (jax.tree.map(lambda g: g * scale, grads)
                       if acc is None else _add(acc, grads, scale))
        return loss, acc, counts, ties


# -- LAMB ----------------------------------------------------------------------


def lamb_init(params):
    """Moments of zero: made inside the first step, not held before it."""
    return {"count": 0, "mu": None, "nu": None}


@functools.partial(jax.jit, donate_argnums=(2, 3))
def _lamb_apply(params, grads, mu, nu, lr, count):
    b1, b2, eps, wd = 0.9, 0.999, 1e-6, 0.01
    if mu is None:
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
    grads, _ = clipped_gradient(grads)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g),
                      nu, grads)
    c1, c2 = 1.0 - b1 ** count, 1.0 - b2 ** count

    def update(path, p, m, v):
        name = str(getattr(path[-1], "key", path[-1]))
        u = (m / c1) / (jnp.sqrt(v / c2) + eps)
        if name not in NORM_GAINS and name != "b":
            u = u + wd * p
        # one trust ratio per tensor; per expert in a stack of experts
        axes = tuple(range(1 if name in EXPERT_STACKS else 0, p.ndim))
        pn = jnp.sqrt(jnp.sum(jnp.square(p), axis=axes, keepdims=True))
        un = jnp.sqrt(jnp.sum(jnp.square(u), axis=axes, keepdims=True))
        ratio = jnp.where((pn > 0) & (un > 0), pn / jnp.maximum(un, 1e-30),
                          1.0)
        return p - lr * ratio * u

    params = jax.tree_util.tree_map_with_path(update, params, mu, nu)
    return params, mu, nu


def lamb_step(params, grads, opt, base_lr: float, total_steps: int,
              warmup: float):
    """One LAMB step (b1 0.9, b2 0.999, eps 1e-6, weight decay 0.01 except on
    norm gains and `b`, bias correction, global-norm pre-normalisation at
    1.0, trust ratio ||p||/||u|| per tensor and per expert, 1 where either
    norm is 0), at the schedule's rate for the count BEFORE this step. `b`
    has no gradient, so with no decay its update is zero."""
    count = opt["count"] + 1
    lr = poly_warmup_lr(count - 1, base_lr, total_steps, warmup)
    params, mu, nu = _lamb_apply(params, grads, opt["mu"], opt["nu"],
                                 jnp.float32(lr), jnp.float32(count))
    return params, {"count": count, "mu": mu, "nu": nu}
