"""Plain SmallThinker: the benchmark's reference for `correct` in the cells
of the `smallthinker` family.

Forward pass, next-token loss, their gradients (jax.grad of the forward) and
the LAMB update in straightforward jax.numpy, float32, under
`jax.default_matmul_precision("highest")`. Written from the family's public
`config.json` (PowerInfer/SmallThinker-21BA3B-Instruct) and the family's
published description. No kernels, no band of tiles, no sorting, no imports
from the program under test; the matrix product with its lower-precision
control, the schedule and the clipped gradient are the BERT reference's
(bert_ref.py), RMSNorm, rotary, document positions and next-token labels the
lfm2 reference's (lfm2_moe_ref.py), and LAMB the kimi reference's
(kimi_linear_ref.py: decay off for the norms' gains, one trust ratio per
tensor and per expert matrix).

The layer, for x (S, 2560) of one row, layer l with w_l =
`sliding_window_layout[l]` and p_l = `rope_layout[l]` (52 layers published,
0, 1, 1, 1 repeated: layer 4n attends to the whole document and has NO
positions, the other three to the last 4,096 tokens with rotary positions):

    r = x W_r                                   (S, 64) router logits, from
                                                the layer's INPUT
    a = RMSNorm(x; input_layernorm)             eps 1e-6
    q, k, v = a Wq (28 x 128), a Wk (4 x 128), a Wv (4 x 128)   no bias
    if p_l: q, k = rotary(q), rotary(k)         theta 1.5e6, all 128 dims
    s_ij = q_i . k_j / sqrt 128  over j of i's document with j <= i, and
                                 i - j < 4096 if w_l
    h = x + concat_heads(softmax(s) v) Wo       query head n reads key/value
                                                head n // 7
    m = RMSNorm(h; post_attention_layernorm)
    E_i = the 6 largest of r_i;  g_ie = exp(r_ie) / sum_{e' in E_i} exp(r_ie')
    y = h + sum_{e in E_i, e held} g_ie W2_e(relu(W1_e m_i) * W3_e m_i)

then one RMSNorm (`final_norm`) and logits = that times an UNTIED lm_head
(V, 2560)^T. Attention is by full scores, the 7 query heads of a key/value
head and a block of query rows at a time (28 x 16,384^2 float32 scores would
be 30 GB); a windowed layer computes the same scores and masks more of them.

The only structure beyond that is rematerialisation, which changes no
value: each layer is a `jax.checkpoint`, inside it each block of attention
rows and each held expert; the head and the loss run over `LOSS_ROWS` rows
at a time.

Departures from the published description, each because the source does not
say or because the configuration is one rank's share (the configuration
file's `assumed` lists them):

- ROUTER AHEAD OF THE ATTENTION and ReLU GATE: the `config` carries neither;
  the catalog's description does ("router placed before attention", "sparse
  ReGLU"): r is taken from x, not from m, and the gate is ReLU. It also
  mentions secondary experts, for which the `config` has no key: none here.
- EXPERT-PARALLEL SHARE, VOCABULARY SLICE: as reference/lfm2_moe_ref.py's
  docstring has them (`held` = [lo, hi) of `experts_total`; the router
  scores all of them; the sum is over selected AND held experts; ids, logits
  and loss over the rank's rows of both tables).
- The softmax over the six selected logits: `moe_primary_router_apply_softmax`
  with `norm_topk_prob` is the full softmax renormalised over the selected,
  which is the same number (tests/test_smallthinker.py says so).
- PACKED ROWS (the source defines no packing): lfm2's rules (attention inside
  the query's own document, rotary positions restart at each document, the
  loss over positions whose successor is in the same document, a padding
  slot attends nowhere), and the band counts tokens of the query's own
  document.
- No attention bias, N(0, 0.02) matrices and head, LAMB as the lfm2 cell's.
- THE TABLE'S ROWS ARE N(0, 1) (`EMBED_STD`), not N(0, 0.02): the router
  reads the un-normed residual stream, and beside rows of root mean square
  0.02 that stream is, from the second layer on, mostly what attention and
  the experts added, which at a fresh model is nearly the SAME vector for
  every token of a 16,384-token row (softmax over thousands of random keys
  is an average). Every token then selects the same experts: on the chip a
  layer sent 0.4 k to 71 k of a step's 196,608 (token, expert) pairs to the
  8 held experts (even: 24,576), by the seed's draw, and tokens/s spread
  3.3 % over six seeds (PERF.md section 6, PR 35). A deployed model routes
  near evenly (it is trained to); with unit rows the stream the router reads
  stays the tokens' own and the held experts see their even share, which is
  what the cell says it measures. The source gives no initialiser.

The parameter tree carries the program's names (a checkpoint's names), so
the adapter has nothing to rename.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.bert_ref import _mm, clipped_gradient  # noqa: F401
from benchmark.reference.kimi_linear_ref import (lamb_init,  # noqa: F401
                                                 lamb_step)
from benchmark.reference.lfm2_moe_ref import (HARD_MASK, _rms_norm, _rotary,
                                              _Sizes, document_positions,
                                              next_token_labels)

ATTENTION_ROWS = 1024
LOSS_ROWS = 2048
EMBED_STD = 1.0     # the table's rows; every other matrix `init_range`


def sizes_from_config(cfg: dict) -> dict:
    """The sizes the reference needs, from a configuration file's keys (the
    source's, plus the cut: `experts_total`, `experts_held`)."""
    total = int(cfg.get("experts_total") or cfg["moe_num_primary_experts"])
    held = tuple(cfg.get("experts_held")
                 or (0, cfg["moe_num_primary_experts"]))
    window = int(cfg["sliding_window_size"])
    return {
        "vocab": int(cfg["vocab_size"]), "hidden": int(cfg["hidden_size"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "expert_width": int(cfg["moe_ffn_hidden_size"]),
        "experts_total": total, "held": (int(held[0]), int(held[1])),
        "topk": int(cfg["moe_num_active_primary_experts"]),
        "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
        "init_range": float(cfg.get("initializer_range", 0.02)),
        # (the band's width or 0, rotary positions or not) of every layer
        "kinds": tuple((window if w else 0, bool(p)) for w, p in zip(
            cfg["sliding_window_layout"], cfg["rope_layout"])),
    }


def param_shapes(sz: dict) -> dict:
    e, d, f = sz["hidden"], sz["head_dim"], sz["expert_width"]
    n_held = sz["held"][1] - sz["held"][0]
    tree = {"embed_tokens": (sz["vocab"], e), "lm_head": (sz["vocab"], e),
            "final_norm": {"scale": (e,)}}
    for i in range(len(sz["kinds"])):
        tree[f"layer_{i}"] = {
            "input_layernorm": {"scale": (e,)},
            "post_attention_layernorm": {"scale": (e,)},
            "attention": {"q_proj": (e, sz["heads"] * d),
                          "k_proj": (e, sz["kv_heads"] * d),
                          "v_proj": (e, sz["kv_heads"] * d),
                          "out_proj": {"kernel": (sz["heads"] * d, e)}},
            "moe": {"router": (e, sz["experts_total"]),
                    "experts_w1": (n_held, e, f),
                    "experts_w3": (n_held, e, f),
                    "experts_w2": (n_held, f, e)}}
    return tree


def init_params(seed: int, sz: dict) -> dict:
    """Every weight from `seed` in one jitted call: matrices and the head
    N(0, init_range); the table's rows N(0, EMBED_STD) (the module
    docstring says why); norm gains 1."""
    shapes = param_shapes(sz)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    names = [str(getattr(path[-1], "key", path[-1])) for path, _ in flat]
    stds = [EMBED_STD if name == "embed_tokens" else sz["init_range"]
            for name in names]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(treedef, [
            jnp.ones(shape, jnp.float32) if name == "scale"
            else std * jax.random.normal(k, shape, jnp.float32)
            for k, name, std, (_, shape) in zip(keys, names, stds, flat)])

    seed = int(seed)      # may exceed 32 signed bits: folded in two halves
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return make(key)


# -- the layer, for one row: x (S, hidden) -------------------------------------


def _attention(x, lp, seg, pos, window, rope, sz, quant):
    s = x.shape[0]
    h, hkv, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    group = h // hkv
    q = _mm(x, lp["q_proj"], quant).reshape(s, h, d)
    k = _mm(x, lp["k_proj"], quant).reshape(s, hkv, d)
    v = _mm(x, lp["v_proj"], quant).reshape(s, hkv, d)
    if rope:
        q, k = _rotary(q, pos, sz["theta"]), _rotary(k, pos, sz["theta"])
    rows = ATTENTION_ROWS if s % ATTENTION_ROWS == 0 else s
    index = jnp.arange(s)

    def kv_head(i):
        """The `group` query heads of key/value head i."""
        qg = jax.lax.dynamic_slice_in_dim(q, i * group, group, axis=1)

        @jax.checkpoint     # one block of rows' (group, rows, S) scores
        def block(args):
            qb, segb, at = args
            scores = jnp.einsum("rgd,sd->grs", qb, k[:, i],
                                precision="highest") / math.sqrt(d)
            back = at[:, None] - index[None, :]
            allowed = ((segb[:, None] == seg[None, :]) & (segb[:, None] > 0)
                       & (back >= 0))
            if window:
                allowed &= back < window
            probs = jax.nn.softmax(jnp.where(allowed, scores, HARD_MASK), -1)
            # padding attends nowhere: its output is zero
            return jnp.einsum("grs,sd->rgd", probs, v[:, i],
                              precision="highest") * (segb > 0)[:, None, None]

        return jax.lax.map(block, (qg.reshape(-1, rows, group, d),
                                   seg.reshape(-1, rows),
                                   index.reshape(-1, rows))).reshape(
                                       s, group, d)

    ctx = jax.lax.map(kv_head, jnp.arange(hkv))          # (hkv, S, group, d)
    return _mm(ctx.transpose(1, 0, 2, 3).reshape(s, h * d),
               lp["out_proj"]["kernel"], quant)


def route(r, sz):
    """(selected experts (S, k), their weights (S, k), gap between the k-th
    and (k+1)-th logits over the standard deviation of the row's logits
    (S,)) from the router's logits r (S, experts): the k largest selected,
    weights the softmax over the selected. The gap is RELATIVE because the
    logits are products with the un-normed residual stream, whose size grows
    with depth (root mean square 0.02 at the first layer, 2.6 at the eighth
    at the cell's size, PERF.md PR 35), and rounding moves a logit by a
    share of its size: one absolute tolerance would fit one layer."""
    top, experts = jax.lax.top_k(jax.lax.stop_gradient(r), sz["topk"] + 1)
    gap = (top[:, sz["topk"] - 1] - top[:, sz["topk"]]) / (jnp.std(r) + 1e-30)
    experts = experts[:, :sz["topk"]]
    gates = jax.nn.softmax(jnp.take_along_axis(r, experts, axis=-1), axis=-1)
    return experts, gates, gap


def _reglu(x, w1, w3, w2, quant):
    return _mm(jax.nn.relu(_mm(x, w1, quant)) * _mm(x, w3, quant), w2, quant)


def _experts(m, r, lp, sz, quant, tie_tol):
    """The held experts' part of the routed FFN over m, routed by the logits
    r; the tokens each held expert received (padding is routed like any
    token); how many tokens sit within `tie_tol` (a share of the logits'
    standard deviation: `route`) of another selection."""
    experts, gates, gap = route(r, sz)
    lo, hi = sz["held"]

    def add_expert(out, held):
        e, w1, w3, w2 = held
        # this expert's weight for every token: its gate where selected
        weight = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
        out = out + weight[:, None] * _reglu(m, w1, w3, w2, quant)
        return out, jnp.sum(jnp.any(experts == e, axis=-1))

    # a loop over the held experts, every token through each, masked
    out, counts = jax.lax.scan(
        jax.checkpoint(add_expert), jnp.zeros_like(m),
        (jnp.arange(lo, hi), lp["experts_w1"], lp["experts_w3"],
         lp["experts_w2"]))
    return out, counts, jnp.sum(gap < tie_tol)


def layer_forward(x, lp, seg, pos, window, rope, sz, quant=None,
                  tie_tol=0.0):
    """One layer over one row: (y, held experts' token counts, near-tie
    tokens)."""
    r = jnp.matmul(x, lp["moe"]["router"], precision="highest")
    a = _rms_norm(x, lp["input_layernorm"]["scale"], sz["eps"])
    h = x + _attention(a, lp["attention"], seg, pos, window, rope, sz, quant)
    m = _rms_norm(h, lp["post_attention_layernorm"]["scale"], sz["eps"])
    out, counts, ties = _experts(m, r, lp["moe"], sz, quant, tie_tol)
    return h + out, counts, ties


def row_hidden(params, ids, seg, sz, quant=None, tie_tol=0.0):
    """One row: ids, seg (S,) -> (the final norm's output (S, hidden), per
    layer the held experts' token counts and near-tie tokens)."""
    pos = document_positions(seg)
    x = params["embed_tokens"][ids]
    counts, ties = [], []
    for i, (window, rope) in enumerate(sz["kinds"]):
        x, count, tie = jax.checkpoint(
            lambda x, lp, window=window, rope=rope: layer_forward(
                x, lp, seg, pos, window, rope, sz, quant, tie_tol))(
                    x, params[f"layer_{i}"])
        counts.append(count)
        ties.append(tie)
    x = _rms_norm(x, params["final_norm"]["scale"], sz["eps"])
    return x, jnp.stack(counts), jnp.stack(ties)


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
    step's held-expert token counts and near-tie tokens per layer."""
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
                # holds one row's gradient and its pass's temporaries and no
                # third copy
                grads = jax.tree.map(
                    lambda g: np.asarray(g) * np.float32(scale), grads)
                acc = grads if acc is None else jax.tree.map(
                    np.add, acc, grads)
        acc = jax.device_put(acc, jax.tree.leaves(params)[0].sharding)
        return loss, acc, counts, ties
