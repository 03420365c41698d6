"""Plain Keye-VL-2.0 language model: the benchmark's reference for `correct`
in the cells of the `keye` family.

Forward pass, both loss terms, their gradients (jax.grad of the forward) and
the LAMB update in straightforward jax.numpy, float32, under
`jax.default_matmul_precision("highest")`. Written from the model's public
`config.json` (Kwai-Keye/Keye-VL-2.0-30B-A3B: the language model's keys and
its `sa_config`) and from the published description of the method its
`sa_config` names ("DeepSeek-V3.2-Exp: Boosting Long-Context Efficiency with
DeepSeek Sparse Attention": index scores, top-k token selection, the
indexer's input detached, a KL term of the index distribution against the
L1-normalised head-summed attention over the selected set). No kernels, no
packed masks, no bisection, no imports from the program under test; the
matrix product with its lower-precision control and the clipped gradient are
the BERT reference's (bert_ref.py), RMSNorm, rotary, document positions and
next-token labels the lfm2 reference's, the schedule the kimi reference's.

The layer, for x (S, 2048) of one row, every layer alike; n_t = t's position
inside its document + 1, K = 2048:

    a = RMSNorm(x; input_layernorm)                          eps 1e-6
    q = RMSNorm_128(a Wq) (32 heads), kk = RMSNorm_128(a Wk) (4 heads),
    v = a Wv (4 heads)                    no bias; one (128,) gain for q, kk
    q, kk = rotary(q), rotary(kk)         theta 1e7, all 128 dims, position
                                          inside the document
    ai = stop_gradient(a)
    qI = rotary_64(ai WIq) (16 heads of 64)
    kI = rotary_64(LayerNorm_64(ai WIk)) (ONE head of 64)
    wI = (ai WIw) / sqrt(16 * 64)
    I_ts = sum_j wI_tj relu(qI_tj . kI_s)                    s <= t, s of t's
                                                             document
    S_t = the min(K, n_t) keys with the largest I_ts (`jax.lax.top_k`: of
          equal scores the lower s first)
    s_tsn = q_tn . kk_s,n//8 / sqrt 128  for s in S_t
    h = x + concat_n(softmax_s(s_tsn) v_s,n//8) Wo
    m = RMSNorm(h; post_attention_layernorm)
    E_t = the 8 largest of m Wr (128 logits); g_te = softmax over those 8
    y = h + sum_{e in E_t, e held} g_te W2_e(silu(W1_e m_t) * W3_e m_t)

then `final_norm` and logits = that times an UNTIED lm_head (V, 2048)^T. The
step's loss is L_LM + L_I:

    L_LM = next-token cross-entropy over the positions whose successor is in
           the same document
    p_ts = stop_gradient((1/32) sum_n softmax_s(s_tsn))      s in S_t
    L_I  = sum over layers of mean over real tokens t of
           sum_{s in S_t} p_ts (log p_ts - log softmax_{s in S_t}(I_ts))

Attention is by full scores over a block of `ATTENTION_ROWS` query rows at a
time: (rows, S) index scores, their top-k, the selection as a dense (rows,
S) mask, then the 8 query heads of a key/value head at a time.

The only structure beyond that is rematerialisation, which changes no
value: each layer is a `jax.checkpoint`, inside it each block of attention
rows and each held expert; the head and the loss run over `LOSS_ROWS` rows.

What the `config` does not state and this reading sets (the configuration
file's `assumed` lists each with its reason): q/k norms; the plain rotary
table on text (the three `mrope_section` components are the same position);
the indexer on the normed input; LayerNorm on kI, rotation of all 64 index
dims, the 1 / sqrt(J d) weight scale; KL weight 1, mean over real tokens;
ties to the lower index; the chunk sizes as a tiling, not a selection by
blocks; SiLU; pre-norm; LAMB; table rows N(0, 1). Expert-parallel share and
vocabulary slice as reference/lfm2_moe_ref.py's docstring has them.

Also counted, for the comparison of what is discrete: per layer the
selected pairs by key block of `key_block(S)` keys (512), and the pairs
whose index score lies within `select_tol` (times the standard deviation of
the row's candidate scores) of the row's K-th score, the K-th itself among
them, over the rows that have more candidates than K: a score that rounding
moves across the K-th swaps one selected key for another, and both lie that
near. (`tie_tol` is the routers': the two are of different scores.)

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
from benchmark.reference.kimi_linear_ref import poly_warmup_lr
from benchmark.reference.lfm2_moe_ref import (HARD_MASK, _rms_norm, _rotary,
                                              _Sizes, document_positions,
                                              next_token_labels)

ATTENTION_ROWS = 512
LOSS_ROWS = 2048
EMBED_STD = 1.0     # the table's rows; every other matrix `init_range`
NO_DECAY = ("scale", "bias")
EXPERT_STACKS = ("experts_w1", "experts_w3", "experts_w2")


def key_block(s: int) -> int:
    """Keys a block of the selection's counters: 512, halved down to 128
    until it divides the row, else the row."""
    block = 512
    while block >= 128:
        if s % block == 0:
            return block
        block //= 2
    return s


def sizes_from_config(cfg: dict) -> dict:
    """The sizes the reference needs, from a configuration file's keys (the
    source's, plus the cut: `experts_total`, `experts_held`)."""
    total = int(cfg.get("experts_total") or cfg["num_experts"])
    held = tuple(cfg.get("experts_held") or (0, cfg["num_experts"]))
    sa = cfg["sa_config"]
    return {
        "vocab": int(cfg["vocab_size"]), "hidden": int(cfg["hidden_size"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "index_heads": int(sa["indexer_num_heads"]),
        "index_dim": int(sa["indexer_head_dim"]),
        "select": int(sa["topk"]),
        "expert_width": int(cfg["moe_intermediate_size"]),
        "experts_total": total, "held": (int(held[0]), int(held[1])),
        "topk": int(cfg["num_experts_per_tok"]),
        "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
        "init_range": float(cfg.get("initializer_range", 0.02)),
        # every layer alike: selected attention, routed experts
        "kinds": tuple(("select", "moe")
                       for _ in range(int(cfg["num_hidden_layers"]))),
    }


def param_shapes(sz: dict) -> dict:
    e, d, f = sz["hidden"], sz["head_dim"], sz["expert_width"]
    j, di = sz["index_heads"], sz["index_dim"]
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
                          "q_norm": {"scale": (d,)},
                          "k_norm": {"scale": (d,)},
                          "out_proj": {"kernel": (sz["heads"] * d, e)},
                          "index_q_proj": (e, j * di),
                          "index_k_proj": (e, di),
                          "index_w_proj": (e, j),
                          "index_k_norm": {"scale": (di,), "bias": (di,)}},
            "moe": {"router": (e, sz["experts_total"]),
                    "experts_w1": (n_held, e, f),
                    "experts_w3": (n_held, e, f),
                    "experts_w2": (n_held, f, e)}}
    return tree


def param_count(sz: dict) -> int:
    return sum(math.prod(shape) for shape in jax.tree.leaves(
        param_shapes(sz), is_leaf=lambda x: isinstance(x, tuple)))


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def init_params(seed: int, sz: dict) -> dict:
    """Every weight from `seed` in one jitted call: matrices and the head
    N(0, init_range); the table's rows N(0, EMBED_STD) (smallthinker_ref.py
    says what N(0, 0.02) rows do to a fresh router); gains 1, the
    LayerNorm's bias 0."""
    shapes = param_shapes(sz)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    names = [_leaf_name(path) for path, _ in flat]
    stds = [EMBED_STD if name == "embed_tokens" else sz["init_range"]
            for name in names]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(treedef, [
            jnp.ones(shape, jnp.float32) if name == "scale"
            else jnp.zeros(shape, jnp.float32) if name == "bias"
            else std * jax.random.normal(k, shape, jnp.float32)
            for k, name, std, (_, shape) in zip(keys, names, stds, flat)])

    seed = int(seed)      # may exceed 32 signed bits: folded in two halves
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return make(key)


# -- the layer, for one row: x (S, hidden) -------------------------------------


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def select_rows(scores, allowed, k: int):
    """(rows, S) bools: each row's min(k, allowed) largest `scores` among
    the `allowed` keys, of equal scores the lower key first; and the k-th
    score of the rows that have more than k allowed keys (else +inf: no
    boundary there)."""
    k = min(k, scores.shape[-1])
    top, keys = jax.lax.top_k(jnp.where(allowed, scores, -jnp.inf), k)
    rows = jnp.arange(scores.shape[0])[:, None]
    chosen = jnp.zeros(scores.shape, bool).at[rows, keys].set(top > -jnp.inf)
    bounded = jnp.sum(allowed, axis=-1) > k
    return chosen, jnp.where(bounded, top[:, -1], jnp.inf)


def _attention(a, lp, seg, pos, sz, quant, select_tol):
    """(context times Wo (S, hidden), the layer's KL sum over real tokens,
    selected pairs by key block, near-tie pairs)."""
    s = a.shape[0]
    h, hkv, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    j, di, group = sz["index_heads"], sz["index_dim"], h // hkv
    q = _rms_norm(_mm(a, lp["q_proj"], quant).reshape(s, h, d),
                  lp["q_norm"]["scale"], sz["eps"])
    k = _rms_norm(_mm(a, lp["k_proj"], quant).reshape(s, hkv, d),
                  lp["k_norm"]["scale"], sz["eps"])
    v = _mm(a, lp["v_proj"], quant).reshape(s, hkv, d)
    q, k = _rotary(q, pos, sz["theta"]), _rotary(k, pos, sz["theta"])
    ai = jax.lax.stop_gradient(a)
    q_idx = _rotary(_mm(ai, lp["index_q_proj"], quant).reshape(s, j, di),
                    pos, sz["theta"])
    k_idx = _rotary(_layer_norm(
        _mm(ai, lp["index_k_proj"], quant), lp["index_k_norm"]["scale"],
        lp["index_k_norm"]["bias"], sz["eps"])[:, None, :], pos,
        sz["theta"])[:, 0]
    w_idx = _mm(ai, lp["index_w_proj"], quant) / math.sqrt(j * di)
    rows = ATTENTION_ROWS if s % ATTENTION_ROWS == 0 else s
    index = jnp.arange(s)
    kb = key_block(s)

    @jax.checkpoint     # one block of query rows at a time
    def block(args):
        qb, qib, wib, segb, at = args
        allowed = ((segb[:, None] == seg[None, :]) & (segb[:, None] > 0)
                   & (at[:, None] >= index[None, :]))
        scores = jnp.sum(jax.nn.relu(jnp.einsum(
            "rjd,sd->rjs", qib, k_idx, precision="highest"))
            * wib[:, :, None], axis=1)
        chosen, kth = select_rows(jax.lax.stop_gradient(scores), allowed,
                                  sz["select"])
        spread = jnp.std(scores, axis=-1, where=allowed) + 1e-30
        near = jnp.sum(allowed & (jnp.abs(scores - kth[:, None])
                                  < select_tol * spread[:, None]))
        ctx, probs = [], jnp.zeros(scores.shape, jnp.float32)
        for i in range(hkv):
            qg = qb[:, i * group:(i + 1) * group]
            sc = jnp.einsum("rgd,sd->grs", qg, k[:, i],
                            precision="highest") / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(chosen, sc, HARD_MASK), -1)
            p = jnp.where(chosen, p, 0.0)   # padding selects nothing
            ctx.append(jnp.einsum("grs,sd->rgd", p, v[:, i],
                                  precision="highest"))
            probs = probs + jnp.sum(p, axis=0)
        target = jax.lax.stop_gradient(probs / h)
        log_pi = jax.nn.log_softmax(jnp.where(chosen, scores, HARD_MASK), -1)
        live = chosen & (target > 0)
        kl = jnp.sum(jnp.where(
            live, target * (jnp.log(jnp.where(live, target, 1.0)) - log_pi),
            0.0))
        pairs = jnp.sum(chosen.reshape(rows, s // kb, kb), axis=(0, 2))
        return jnp.concatenate(ctx, axis=1), kl, pairs, near

    ctx, kl, pairs, near = jax.lax.map(block, (
        q.reshape(-1, rows, h, d), q_idx.reshape(-1, rows, j, di),
        w_idx.reshape(-1, rows, j), seg.reshape(-1, rows),
        index.reshape(-1, rows)))
    out = _mm(ctx.reshape(s, h * d), lp["out_proj"]["kernel"], quant)
    return out, jnp.sum(kl), jnp.sum(pairs, axis=0), jnp.sum(near)


def route(r, sz):
    """(selected experts (S, k), their weights (S, k), gap between the k-th
    and (k+1)-th logits over the standard deviation of the row's logits
    (S,)) from the router's logits r (S, experts): the k largest selected,
    weights the softmax over the selected."""
    top, experts = jax.lax.top_k(jax.lax.stop_gradient(r), sz["topk"] + 1)
    gap = (top[:, sz["topk"] - 1] - top[:, sz["topk"]]) / (jnp.std(r) + 1e-30)
    experts = experts[:, :sz["topk"]]
    gates = jax.nn.softmax(jnp.take_along_axis(r, experts, axis=-1), axis=-1)
    return experts, gates, gap


def _swiglu(x, w1, w3, w2, quant):
    return _mm(jax.nn.silu(_mm(x, w1, quant)) * _mm(x, w3, quant), w2, quant)


def _experts(m, lp, sz, quant, tie_tol):
    """The held experts' part of the routed FFN over m; the tokens each held
    expert received (padding is routed like any token); how many tokens sit
    within `tie_tol` (a share of the logits' standard deviation) of another
    selection."""
    r = jnp.matmul(m, lp["router"], precision="highest")
    experts, gates, gap = route(r, sz)
    lo, hi = sz["held"]

    def add_expert(out, held):
        e, w1, w3, w2 = held
        # this expert's weight for every token: its gate where selected
        weight = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
        out = out + weight[:, None] * _swiglu(m, w1, w3, w2, quant)
        return out, jnp.sum(jnp.any(experts == e, axis=-1))

    # a loop over the held experts, every token through each, masked
    out, counts = jax.lax.scan(
        jax.checkpoint(add_expert), jnp.zeros_like(m),
        (jnp.arange(lo, hi), lp["experts_w1"], lp["experts_w3"],
         lp["experts_w2"]))
    return out, counts, jnp.sum(gap < tie_tol)


def layer_forward(x, lp, seg, pos, sz, quant=None, tie_tol=0.0,
                  select_tol=0.0):
    """One layer over one row: (y, (held experts' token counts, near-tie
    tokens, KL sum, selected pairs by key block, near-tie pairs))."""
    a = _rms_norm(x, lp["input_layernorm"]["scale"], sz["eps"])
    attended, kl, pairs, near = _attention(a, lp["attention"], seg, pos, sz,
                                           quant, select_tol)
    h = x + attended
    m = _rms_norm(h, lp["post_attention_layernorm"]["scale"], sz["eps"])
    out, counts, ties = _experts(m, lp["moe"], sz, quant, tie_tol)
    return h + out, (counts, ties, kl, pairs, near)


def row_hidden(params, ids, seg, sz, quant=None, tie_tol=0.0,
               select_tol=0.0):
    """One row: ids, seg (S,) -> (the final norm's output (S, hidden), per
    layer (stacked): the held experts' token counts, near-tie tokens, KL
    sums, selected pairs by key block, near-tie pairs)."""
    pos = document_positions(seg)
    x = params["embed_tokens"][ids]
    aux = []
    for i in range(len(sz["kinds"])):
        x, layer_aux = jax.checkpoint(
            lambda x, lp: layer_forward(x, lp, seg, pos, sz, quant, tie_tol,
                                        select_tol))(x, params[f"layer_{i}"])
        aux.append(layer_aux)
    x = _rms_norm(x, params["final_norm"]["scale"], sz["eps"])
    return x, tuple(jnp.stack(a) for a in zip(*aux))


def row_forward(params, ids, seg, sz, quant=None, tie_tol=0.0):
    """(logits (S, vocab), counts, ties) of one row (the routed layers'
    checks of benchmark/families/lfm2_moe.py read these three)."""
    x, aux = row_hidden(params, ids, seg, sz, quant, tie_tol)
    return _mm(x, params["lm_head"].T, quant), aux[0], aux[1]


def row_terms(params, ids, seg, sz, quant=None, tie_tol=0.0, select_tol=0.0):
    """(sum of the row's negative log-likelihoods, sum over layers and real
    tokens of the KL term, `row_hidden`'s per-layer values)."""
    x, aux = row_hidden(params, ids, seg, sz, quant, tie_tol, select_tol)
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
    return jnp.sum(nll), jnp.sum(aux[2]), aux


def row_loss(params, ids, seg, lm_weight, kl_weight, sz, quant=None,
             tie_tol=0.0, select_tol=0.0):
    """lm_weight * nll + kl_weight * KL of one row, and (nll, KL, aux)."""
    nll, kl, aux = row_terms(params, ids, seg, sz, quant, tie_tol,
                             select_tol)
    return lm_weight * nll + kl_weight * kl, (nll, kl, aux)


@functools.partial(jax.jit,
                   static_argnames=("sz", "quant", "tie_tol", "select_tol"))
def _row_grad(params, ids, seg, lm_weight, kl_weight, sz, quant, tie_tol,
              select_tol):
    return jax.value_and_grad(row_loss, has_aux=True)(
        params, ids, seg, lm_weight, kl_weight, sz, quant, tie_tol,
        select_tol)


def step_loss_and_grad(params, micro_batches, sz: dict, quant=None,
                       tie_tol: float = 0.0, select_tol: float = 0.0):
    """Loss and gradient of one optimisation step: the mean over its
    micro-batches (dicts of input_ids and segment_ids, (rows, S)) of the
    micro-batch's mean negative log-likelihood plus its KL sums over its
    real tokens, one ROW at a time. -> (loss, gradient, details): `details`
    has the two terms (`lm_loss`, `indexer_kl`) and per layer the held
    experts' token counts, near-tie tokens, selected pairs by key block and
    near-tie pairs, summed over the step."""
    sz = _Sizes(sz)
    n = float(len(micro_batches))
    with jax.default_matmul_precision("highest"):
        lm, kl_loss, acc, sums = 0.0, 0.0, None, None
        for micro in micro_batches:
            ids, seg = micro["input_ids"], micro["segment_ids"]
            labelled = sum(
                int(jnp.sum(next_token_labels(ids[r], seg[r]) >= 0))
                for r in range(ids.shape[0]))
            lm_w = 1.0 / (max(labelled, 1) * n)
            kl_w = 1.0 / (max(int(jnp.sum(seg > 0)), 1) * n)
            for r in range(ids.shape[0]):
                (_, (nll, kl, aux)), grads = _row_grad(
                    params, ids[r], seg[r], jnp.float32(lm_w),
                    jnp.float32(kl_w), sz, quant, float(tie_tol),
                    float(select_tol))
                lm, kl_loss = lm + nll * lm_w, kl_loss + kl * kl_w
                counts = (aux[0], aux[1], aux[3], aux[4])
                sums = counts if sums is None else tuple(
                    a + b for a, b in zip(sums, counts))
                # the sum is kept on the HOST: beside the weights the device
                # holds one row's gradient and its pass's temporaries and no
                # third copy
                grads = jax.tree.map(np.asarray, grads)
                acc = grads if acc is None else jax.tree.map(
                    np.add, acc, grads)
        acc = jax.device_put(acc, jax.tree.leaves(params)[0].sharding)
        details = dict(zip(("expert_counts", "near_ties", "block_pairs",
                            "near_pairs"), sums),
                       lm_loss=lm, indexer_kl=kl_loss)
        return lm + kl_loss, acc, details


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
    """One LAMB step (b1 0.9, b2 0.999, eps 1e-6, weight decay 0.01 except
    on the norms' gains and the LayerNorm's bias, bias correction,
    global-norm pre-normalisation at 1.0, trust ratio ||p||/||u|| per tensor
    and per expert, 1 where either norm is 0), at the schedule's rate for
    the count BEFORE this step. The arguments' buffers are given up."""
    count = opt["count"] + 1
    lr = poly_warmup_lr(count - 1, base_lr, total_steps, warmup)
    params, mu, nu = _lamb_apply(params, grads, opt["mu"], opt["nu"],
                                 jnp.float32(lr), jnp.float32(count))
    return params, {"count": count, "mu": mu, "nu": nu}
