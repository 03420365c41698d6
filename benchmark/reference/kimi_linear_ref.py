"""Plain Kimi Linear: the benchmark's reference for `correct` in the cells
of the `kimi_linear` family.

Forward pass, next-token loss, their gradients (jax.grad of the forward) and
the LAMB update in straightforward jax.numpy, float32, under
`jax.default_matmul_precision("highest")`. Written from the family's public
`config.json` (moonshotai/Kimi-Linear-48B-A3B-Instruct) and the family's
published description. No kernels, no chunks, no sorting, no imports from
the program under test; the matrix product with its lower-precision
control, the schedule and the clipped gradient are the BERT reference's
(bert_ref.py), and RMSNorm, SwiGLU, the routed experts' loop with its
near-tie count, document positions and next-token labels are the lfm2
reference's (lfm2_moe_ref.py).

The layer equations, for x (S, 2304) of one row (pre-norm:
h = x + Mixer(RMSNorm(x)), y = h + FFN(RMSNorm(h)), eps 1e-5; after the last
layer one RMSNorm, logits = that times an UNTIED lm_head (V, 2304)^T;
source layers count from 1, KDA 1, 2, 3, 5, ..., MLA 4, 8, ...; layer 1 has
a dense SwiGLU MLP, every other layer routed experts):

- KDA (H heads of D = 128): q, k, v = x Wq, x Wk, x Wv (no bias), each
  through a depthwise causal convolution of 4 taps (a tap before the
  document's first token is zero) and SiLU; q, k L2-normalised per head
  (x rsqrt(sum x^2 + 1e-6)), q times D^-1/2. g_t = -exp(A_log[h]) *
  softplus(W_f2 (W_f1 x_t) + dt_bias) per channel; beta_t = sigmoid(W_b
  x_t). Per head, S (D x D) zero before the first token of each document,
  TOKEN BY TOKEN (`_delta_rule`):
      S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t
  Output Wo (RMSNorm_head(o_t; gain (D,)) * sigmoid(W_g2 (W_g1 x_t) + b_g)).
- MLA without positions: q = x Wq -> H x 192; [c, k_r] = x W_kva (512 +
  64); [k_n, v] = RMSNorm(c) W_kvb -> H x (128 + 128); k_h = [k_n,h ; k_r];
  softmax(q k^T / sqrt 192) over earlier-or-equal positions of the same
  document, by full scores, a block of query rows at a time; heads of 128
  concatenated times Wo.
- Routed FFN: sigmoid scores over all experts, the k largest of score +
  selection bias, weights the selected scores over their sum (+ 1e-6)
  times the scaling factor, a loop over the HELD experts; plus the shared
  SwiGLU expert on every token, unweighted.

The only structure beyond that is rematerialisation, which changes no
value: each layer is a `jax.checkpoint`, and inside it the MLPs and the KDA
mixer's parts before and after the recurrence; the KDA recurrence is an
outer scan over spans of `SPAN` tokens, each span checkpointed (16,384 kept
states would be 34 GB); attention keeps one block of rows' scores; the head
and the loss run over `LOSS_ROWS` rows at a time. (A row's gradient pass
has to fit beside the weights, their gradient and the step's accumulated
gradient, 7.2 GB at the cell's size: 14.27 GB without the inner checkpoints,
compiled for a described v5e, did not.)

Departures (the config does not say, or the configuration is one rank's
share), as the configuration file's `assumed` lists them: the
expert-parallel share, the vocabulary slice, the selection bias as one held
draw and LAMB exactly as reference/lfm2_moe_ref.py's docstring has them
(the bias from `BIAS_KEY` here); the decay's and the gate's low-rank width
(D); no convolution bias; A_log = log U[1, 16), dt_bias = softplus^-1 of a
step drawn log-uniformly from [1e-3, 0.1), the gate's bias zero; a padding
slot is a document of its own at position 0 (its state restarts, its taps
are zero, it attends nowhere).

The parameter tree carries the program's names (a checkpoint's names), so
the adapter has nothing to rename.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.bert_ref import (_mm, clipped_gradient,
                                          poly_warmup_lr)
from benchmark.reference.lfm2_moe_ref import (HARD_MASK, _experts,
                                              _rms_norm, _Sizes, _swiglu,
                                              document_positions,
                                              next_token_labels)

BIAS_KEY = 33       # the selection biases' own key: the same in every run
SPAN = 128          # tokens of the recurrence kept between checkpoints
ATTENTION_ROWS = 1024
LOSS_ROWS = 2048
MLP_ROWS = 2048
GAINS = ("scale", "o_norm")
NO_DECAY = GAINS + ("A_log", "dt_bias", "g_bias", "expert_bias")
EXPERT_STACKS = ("experts_w1", "experts_w3", "experts_w2")


def sizes_from_config(cfg: dict) -> dict:
    """The sizes the reference needs, from a configuration file's keys (the
    source's, plus the cut: `layers_kept`, `experts_total`, `experts_held`,
    and the `assumed` sizes `kda_chunk_size`, `kda_gate_rank`)."""
    lin = cfg["linear_attn_config"]
    kept = cfg.get("layers_kept") or list(
        range(1, cfg["num_hidden_layers"] + 1))
    kinds = [("kda" if i in lin["kda_layers"] else "mla",
              "dense" if j < cfg["first_k_dense_replace"] else "moe")
             for j, i in enumerate(kept)]
    total = int(cfg.get("experts_total") or cfg["num_experts"])
    held = tuple(cfg.get("experts_held") or (0, cfg["num_experts"]))
    return {
        "vocab": int(cfg["vocab_size"]), "hidden": int(cfg["hidden_size"]),
        "heads": int(cfg["num_attention_heads"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "v_dim": int(cfg["v_head_dim"]), "latent": int(cfg["kv_lora_rank"]),
        "kda_heads": int(lin["num_heads"]), "kda_dim": int(lin["head_dim"]),
        "taps": int(lin["short_conv_kernel_size"]),
        "gate_rank": int(cfg.get("kda_gate_rank") or lin["head_dim"]),
        "dense_width": int(cfg["intermediate_size"]),
        "expert_width": int(cfg["moe_intermediate_size"]),
        "experts_total": total, "held": (int(held[0]), int(held[1])),
        "topk": int(cfg["num_experts_per_token"]),
        "norm_topk": bool(cfg["moe_renormalize"]),
        "scaling": float(cfg["routed_scaling_factor"]),
        "eps": float(cfg["rms_norm_eps"]),
        "init_range": float(cfg.get("initializer_range", 0.02)),
        "kinds": tuple(kinds),
    }


def param_shapes(sz: dict) -> dict:
    e, hd = sz["hidden"], sz["kda_heads"] * sz["kda_dim"]
    h, rank = sz["heads"], sz["gate_rank"]
    n_held = sz["held"][1] - sz["held"][0]

    def swiglu(f):
        return {"w1": {"kernel": (e, f)}, "w3": {"kernel": (e, f)},
                "w2": {"kernel": (f, e)}}

    tree = {"embed_tokens": (sz["vocab"], e), "lm_head": (sz["vocab"], e),
            "final_norm": {"scale": (e,)}}
    for i, (mixer, ffn) in enumerate(sz["kinds"]):
        lp = {"input_norm": {"scale": (e,)}, "ffn_norm": {"scale": (e,)}}
        if mixer == "kda":
            lp["kda"] = dict(
                {f"{n}_proj": (e, hd) for n in "qkv"},
                **{f"{n}_conv": (hd, sz["taps"]) for n in "qkv"},
                f_a_proj=(e, rank), f_b_proj=(rank, hd),
                A_log=(sz["kda_heads"],), dt_bias=(hd,),
                b_proj=(e, sz["kda_heads"]), g_a_proj=(e, rank),
                g_b_proj=(rank, hd), g_bias=(hd,), o_norm=(sz["kda_dim"],),
                out_proj={"kernel": (hd, e)})
        else:
            lp["attention"] = {
                "q_proj": {"kernel": (e, h * (sz["nope"] + sz["rope"]))},
                "kv_a_proj": {"kernel": (e, sz["latent"] + sz["rope"])},
                "kv_norm": {"scale": (sz["latent"],)},
                "kv_b_proj": {"kernel": (sz["latent"],
                                         h * (sz["nope"] + sz["v_dim"]))},
                "out_proj": {"kernel": (h * sz["v_dim"], e)}}
        if ffn == "dense":
            lp["mlp"] = swiglu(sz["dense_width"])
        else:
            f = sz["expert_width"]
            lp["moe"] = {"router": (e, sz["experts_total"]),
                         "expert_bias": (sz["experts_total"],),
                         "experts_w1": (n_held, e, f),
                         "experts_w3": (n_held, e, f),
                         "experts_w2": (n_held, f, e)}
            lp["shared_expert"] = swiglu(f)
        tree[f"layer_{i}"] = lp
    return tree


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def init_params(seed: int, sz: dict) -> dict:
    """Every weight from `seed` in one jitted call: matrices, tables and
    convolution taps N(0, init_range); gains 1; the gate's bias 0; A_log
    and dt_bias as the module docstring says. The selection biases are
    N(0, init_range) from BIAS_KEY and the layer's number: the same for
    every seed."""
    shapes = param_shapes(sz)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    names = [_leaf_name(path) for path, _ in flat]
    bias_keys = [jax.random.fold_in(
        jax.random.PRNGKey(BIAS_KEY), int(str(path[0].key).split("_")[1]))
        if name == "expert_bias" else None
        for name, (path, _) in zip(names, flat)]

    def draw(key, name, shape):
        if name in GAINS:
            return jnp.ones(shape, jnp.float32)
        if name == "g_bias":
            return jnp.zeros(shape, jnp.float32)
        if name == "A_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        if name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(1e-3), math.log(0.1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        return sz["init_range"] * jax.random.normal(key, shape, jnp.float32)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(flat))
        keys = [k if b is None else b for k, b in zip(keys, bias_keys)]
        return jax.tree_util.tree_unflatten(treedef, [
            draw(k, name, shape)
            for k, name, (_, shape) in zip(keys, names, flat)])

    seed = int(seed)      # may exceed 32 signed bits: folded in two halves
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return make(key)


# -- the layers, for one row: x (S, hidden) ------------------------------------


def _conv(u, taps_w, pos):
    """Depthwise causal convolution: tap j reads the token taps-1-j back, and
    is zero where that lies before the document's first token."""
    taps = taps_w.shape[1]
    out = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.pad(u, ((back, 0), (0, 0)))[:u.shape[0]]
        out = out + taps_w[:, j] * jnp.where((pos >= back)[:, None],
                                             shifted, 0.0)
    return out


def _delta_rule(q, k, v, g, beta, start):
    """The recurrence, one token after another: q, k, v, g (S, H, D), beta
    (S, H), start (S,) true where the state restarts before the token."""
    s, h, d = q.shape
    pad = -s % SPAN

    def token(state, x):
        q, k, v, g, beta, start = x
        state = jnp.where(start, 0.0, state) * jnp.exp(g)[:, :, None]
        seen = jnp.einsum("hk,hkv->hv", k, state)
        state = state + (beta[:, None] * k)[:, :, None] * (v - seen)[:, None]
        return state, jnp.einsum("hkv,hk->hv", state, q)

    @jax.checkpoint
    def span(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = [jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
          .reshape((-1, SPAN) + x.shape[1:])
          for x in (q, k, v, g, beta, start)]
    _, out = jax.lax.scan(span, jnp.zeros((h, d, d), jnp.float32), xs)
    return out.reshape(-1, h, d)[:s]


def _kda(x, lp, pos, sz, quant):
    """The mixer of one row. Its three parts before and after the
    recurrence are each a `jax.checkpoint` (their (S, 4096) float32
    intermediates, some twenty of 268 MB at 16,384 tokens, are made again in
    the backward pass, not kept)."""
    s = x.shape[0]
    h, d = sz["kda_heads"], sz["kda_dim"]

    @jax.checkpoint
    def branch(x, w, taps, scale):
        u = jax.nn.silu(_conv(_mm(x, w, quant), taps, pos)).reshape(s, h, d)
        if scale is None:
            return u
        return scale * u * jax.lax.rsqrt(
            jnp.sum(jnp.square(u), -1, keepdims=True) + 1e-6)

    @jax.checkpoint
    def decay(x, down, up, a_log, dt_bias):
        f = _mm(_mm(x, down, quant), up, quant)
        return -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            f + dt_bias).reshape(s, h, d)

    @jax.checkpoint
    def gated(o, x, down, up, bias, gain, wo):
        o = _rms_norm(o, gain, sz["eps"]).reshape(s, h * d)
        gate = _mm(_mm(x, down, quant), up, quant) + bias
        return _mm(o * jax.nn.sigmoid(gate), wo, quant)

    q = branch(x, lp["q_proj"], lp["q_conv"], d ** -0.5)
    k = branch(x, lp["k_proj"], lp["k_conv"], 1.0)
    v = branch(x, lp["v_proj"], lp["v_conv"], None)
    g = decay(x, lp["f_a_proj"], lp["f_b_proj"], lp["A_log"], lp["dt_bias"])
    beta = jax.nn.sigmoid(_mm(x, lp["b_proj"], quant))
    o = _delta_rule(q, k, v, g, beta, pos == 0)
    return gated(o, x, lp["g_a_proj"], lp["g_b_proj"], lp["g_bias"],
                 lp["o_norm"], lp["out_proj"]["kernel"])


def _mla(x, lp, seg, sz, quant):
    s = x.shape[0]
    h, dn, dr, dv = sz["heads"], sz["nope"], sz["rope"], sz["v_dim"]
    q = _mm(x, lp["q_proj"]["kernel"], quant).reshape(s, h, dn + dr)
    latent, shared = jnp.split(_mm(x, lp["kv_a_proj"]["kernel"], quant),
                               [sz["latent"]], axis=-1)
    kv = _mm(_rms_norm(latent, lp["kv_norm"]["scale"], sz["eps"]),
             lp["kv_b_proj"]["kernel"], quant).reshape(s, h, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        shared[:, None, :], (s, h, dr))], axis=-1)
    v = kv[..., dn:]
    rows = ATTENTION_ROWS if s % ATTENTION_ROWS == 0 else s
    index = jnp.arange(s)

    def head(i):
        @jax.checkpoint     # one block of rows' (rows, S) scores at a time
        def block(args):
            qb, segb, at = args
            scores = jnp.matmul(qb, k[:, i].T, precision="highest") / (
                math.sqrt(dn + dr))
            allowed = ((segb[:, None] == seg[None, :]) & (segb[:, None] > 0)
                       & (at[:, None] >= index[None, :]))
            probs = jax.nn.softmax(jnp.where(allowed, scores, HARD_MASK), -1)
            # padding attends nowhere: its output is zero
            return jnp.matmul(probs, v[:, i], precision="highest") * (
                segb[:, None] > 0)

        return jax.lax.map(block, (q[:, i].reshape(-1, rows, dn + dr),
                                   seg.reshape(-1, rows),
                                   index.reshape(-1, rows))).reshape(s, dv)

    ctx = jax.lax.map(head, jnp.arange(h))                   # (h, S, dv)
    return _mm(ctx.transpose(1, 0, 2).reshape(s, h * dv),
               lp["out_proj"]["kernel"], quant)


def _mlp(x, lp, quant):
    """SwiGLU over `MLP_ROWS` rows at a time, each block a checkpoint: the
    (rows, width) gate and up projections of one block are alive."""
    rows = MLP_ROWS if x.shape[0] % MLP_ROWS == 0 else x.shape[0]
    block = jax.checkpoint(lambda xb: _swiglu(
        xb, lp["w1"]["kernel"], lp["w3"]["kernel"], lp["w2"]["kernel"],
        quant))
    return jax.lax.map(block, x.reshape(-1, rows, x.shape[-1])).reshape(
        x.shape)


def _routed(x, lp, sz, quant, tie_tol):
    """The held experts' part (lfm2_moe_ref._experts under its names) plus
    the shared expert, the held experts' token counts, near-tie tokens."""
    moe = lp["moe"]
    out, counts, ties = _experts(
        x, {"wg": moe["router"], "b": moe["expert_bias"],
            "ew1": moe["experts_w1"], "ew3": moe["experts_w3"],
            "ew2": moe["experts_w2"]}, sz, quant, tie_tol)
    return out + _mlp(x, lp["shared_expert"], quant), counts, ties


def row_hidden(params, ids, seg, sz, quant=None, tie_tol=0.0):
    """One row: ids, seg (S,) -> (the final norm's output (S, hidden), per
    routed layer the held experts' token counts and near-tie tokens)."""
    pos = document_positions(seg)
    n_held = sz["held"][1] - sz["held"][0]

    def layer(x, lp, mixer, ffn):
        normed = _rms_norm(x, lp["input_norm"]["scale"], sz["eps"])
        if mixer == "kda":
            x = x + _kda(normed, lp["kda"], pos, sz, quant)
        else:
            x = x + _mla(normed, lp["attention"], seg, sz, quant)
        normed = _rms_norm(x, lp["ffn_norm"]["scale"], sz["eps"])
        if ffn == "dense":
            return (x + _mlp(normed, lp["mlp"], quant),
                    jnp.zeros((n_held,), jnp.int32), jnp.zeros([], jnp.int32))
        out, count, tie = _routed(normed, lp, sz, quant, tie_tol)
        return x + out, count, tie

    x = params["embed_tokens"][ids]
    counts, ties = [], []
    for i, (mixer, ffn) in enumerate(sz["kinds"]):
        x, count, tie = jax.checkpoint(layer, static_argnums=(2, 3))(
            x, params[f"layer_{i}"], mixer, ffn)
        if ffn == "moe":
            counts.append(count)
            ties.append(tie)
    x = _rms_norm(x, params["final_norm"]["scale"], sz["eps"])
    return (x,
            jnp.stack(counts) if counts else jnp.zeros((0, n_held), jnp.int32),
            jnp.stack(ties) if ties else jnp.zeros((0,), jnp.int32))


def row_forward(params, ids, seg, sz, quant=None, tie_tol=0.0):
    """(logits (S, vocab), counts, ties) of one row."""
    x, counts, ties = row_hidden(params, ids, seg, sz, quant, tie_tol)
    return _mm(x, params["lm_head"].T, quant), counts, ties


def row_nll(params, ids, seg, sz, quant=None, tie_tol=0.0):
    """(sum of the row's negative log-likelihoods, (counts, ties))."""
    x, counts, ties = row_hidden(params, ids, seg, sz, quant, tie_tol)
    labels = next_token_labels(ids, seg)
    rows = LOSS_ROWS if x.shape[0] % LOSS_ROWS == 0 else x.shape[0]

    @jax.checkpoint         # one block of rows' logits at a time
    def block(args):
        xb, lb = args
        logp = jax.nn.log_softmax(_mm(xb, params["lm_head"].T, quant), -1)
        nll = -jnp.take_along_axis(logp, jnp.maximum(lb, 0)[:, None], 1)[:, 0]
        return jnp.sum(jnp.where(lb >= 0, nll, 0.0))

    nll = jax.lax.map(block, (x.reshape(-1, rows, x.shape[-1]),
                              labels.reshape(-1, rows)))
    return jnp.sum(nll), (counts, ties)


@functools.partial(jax.jit, static_argnames=("sz", "quant", "tie_tol"))
def _row_grad(params, ids, seg, sz, quant, tie_tol):
    return jax.value_and_grad(row_nll, has_aux=True)(
        params, ids, seg, sz, quant, tie_tol)


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
                # the sum is kept on the HOST: beside the weights the device
                # holds one row's gradient and its pass's temporaries (2.4 +
                # 2.4 + 9.4 GB at the cell's size) and no third copy
                grads = jax.tree.map(
                    lambda g: np.asarray(g) * np.float32(scale), grads)
                acc = grads if acc is None else jax.tree.map(
                    np.add, acc, grads)
        acc = jax.device_put(acc, jax.tree.leaves(params)[0].sharding)
        return loss, acc, counts, ties


# -- LAMB ----------------------------------------------------------------------


def lamb_init(params):
    """Moments of zero: made inside the first step, not held before it."""
    return {"count": 0, "mu": None, "nu": None}


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
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
        name = _leaf_name(path)
        u = (m / c1) / (jnp.sqrt(v / c2) + eps)
        if name not in NO_DECAY:
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
    NO_DECAY, bias correction, global-norm pre-normalisation at 1.0, trust
    ratio ||p||/||u|| per tensor and per expert, 1 where either norm is 0),
    at the schedule's rate for the count BEFORE this step. The arguments'
    buffers are given up (the caller keeps none of them)."""
    count = opt["count"] + 1
    lr = poly_warmup_lr(count - 1, base_lr, total_steps, warmup)
    params, mu, nu = _lamb_apply(params, grads, opt["mu"], opt["nu"],
                                 jnp.float32(lr), jnp.float32(count))
    return params, {"count": count, "mu": mu, "nu": nu}
