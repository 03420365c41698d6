"""Plain Laguna: the benchmark's reference for `correct` in the cells of the
`laguna` family.

Forward pass, next-token loss, their gradients (jax.grad of the forward) and
the LAMB update in straightforward jax.numpy, float32, under
`jax.default_matmul_precision("highest")`. Written from the family's public
`config.json` (poolside/Laguna-XS.2) and the equations below. No kernels, no
band of tiles, no sorting, no imports from the program under test: its own
rotary tables (`rope_table`, YaRN written out), its own band mask, its own
routing. The matrix product with its lower-precision control and the clipped
gradient are the BERT reference's (bert_ref.py), RMSNorm, document positions
and next-token labels the lfm2 reference's (lfm2_moe_ref.py), and LAMB the
kimi reference's (kimi_linear_ref.py: decay off for the norms' gains and the
selection bias, one trust ratio per tensor and per expert matrix).

The layer, for x (S, 2048) of one row, layer l of kind t_l (`layer_types`:
full | sliding; published (full, sliding, sliding, sliding) x 10), H_l =
`num_attention_heads_per_layer[l]` (48 in a full layer, 64 in a windowed
one), 8 key/value heads, D = 128:

    a = RMSNorm(x; input_layernorm)             eps 1e-6
    q, k, v = a Wq (H_l x 128), a Wk (8 x 128), a Wv (8 x 128)   no bias,
                                                no q/k norm
    q[..., :R_t], k[..., :R_t] = rot(q[..., :R_t]), rot(k[..., :R_t]);
                                                the other D - R_t dims pass
        R_sliding = 128, R_full = 64 (partial_rotary_factor 0.5);
        rot(u) = u * (c cos(p f)) + rotate_half(u) * (c sin(p f)),
        p the position inside the document
        sliding: f_i = 10000^(-2i/R), c = 1
        full (YaRN): e_i = 500000^(-2i/R);
            dim(n) = R ln(4096 / (2 pi n)) / (2 ln 500000);
            lo = max(floor(dim(64)), 0), hi = min(ceil(dim(1)), R - 1);
            r_i = clip((i - lo) / (hi - lo), 0, 1);
            f_i = (e_i / 64) r_i + e_i (1 - r_i); c = 1.4158883083359672
    s_ij = q_i . k_j / sqrt 128  over j of i's document, j <= i, and
                                 i - j < 512 if sliding
    o_n = softmax(s) v for query head n, reading key/value head
          n // (H_l / 8)
    g = sigmoid(a Wg), Wg (2048, H_l);  h = x + concat_n(g_n o_n) Wo
    m = RMSNorm(h; post_attention_layernorm)
    layer 0:      y = h + W2(silu(W1 m) * W3 m), 8192 wide
    layers 1-39:  sc = sigmoid(m W_r) (256);
                  E_i = the 8 largest of sc_i + b (b a held buffer, no
                  gradient);
                  w_ie = 2.5 sc_ie / (sum_{e' in E_i} sc_ie' + 1e-6)
                  y = h + shared(m)
                        + sum_{e in E_i, e held} w_ie W2_e(silu(W1_e m_i) * W3_e m_i)
                  experts and the shared expert 512 wide

then one RMSNorm (`final_norm`) and logits = that times an UNTIED lm_head
(V, 2048)^T. Attention is by full scores, the query heads of a key/value
head and a block of query rows at a time (64 x 16,384^2 float32 scores would
be 69 GB); a windowed layer computes the same scores and masks more of them.

The only structure beyond that is rematerialisation, which changes no
value: each layer is a `jax.checkpoint`, inside it each block of attention
rows, each held expert and each block of an MLP's rows; the head and the
loss run over `LOSS_ROWS` rows at a time.

Readings of what the `config` does not say, and departures because the
configuration is one rank's share (the configuration file's `assumed` lists
each with its reason):

- THE ROUTER: `config` gives 256 experts, 8 a token, a scaling factor of 2.5
  and one shared expert, and neither the score function nor how the
  selected are weighted. Read as the convention of the models with exactly
  that set of keys: sigmoid scores, a selection bias that only selects, the
  selected scores over their sum (+ 1e-6) times the factor.
- `gating: true`: ONE sigmoid gate per query head on the attention's
  output, from the normed input a (Wg has hidden x H_l entries: 131,072 in
  a windowed layer). The larger sibling of the same family says
  `"gating": "per-head"` in its config. The per-element reading (Wg hidden x
  H_l D: 16.8 M a windowed layer) is not taken.
- SiLU in every MLP, pre-norm placement, the rotated dims the head's FIRST
  R: the config names none; the family's transformers conventions.
- EXPERT-PARALLEL SHARE, VOCABULARY SLICE: as reference/lfm2_moe_ref.py's
  docstring has them (`held` = [lo, hi) of `experts_total`; the router
  scores all of them; the sum is over selected AND held experts; the shared
  expert is whole on every rank; ids, logits and loss over the rank's rows
  of both tables). The selection bias is one held draw, from `BIAS_KEY`.
- PACKED ROWS (the source defines no packing): lfm2's rules (attention
  inside the query's own document, positions restart at each document, the
  loss over positions whose successor is in the same document, a padding
  slot attends nowhere), and the band counts tokens of the query's own
  document.
- N(0, 0.02) matrices and head, the table's rows N(0, 1) (`EMBED_STD`) as
  the smallthinker reference's (the source gives no initialiser; with unit
  rows the residual stream stays the tokens' own through the fresh layers,
  and the held experts see their even share: PERF.md section 6, PR 35), LAMB
  as the lfm2 cell's.

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
from benchmark.reference.lfm2_moe_ref import (HARD_MASK, _rms_norm, _Sizes,
                                              document_positions,
                                              next_token_labels)

BIAS_KEY = 41       # the selection biases' own key: the same in every run
ATTENTION_ROWS = 1024
LOSS_ROWS = 2048
MLP_ROWS = 2048
EMBED_STD = 1.0     # the table's rows; every other matrix `init_range`


def rope_table(head_dim: int, group: dict) -> tuple:
    """(R, c, (f_0 .. f_{R/2-1})) of one kind of layer from its sub-group of
    the config's `rope_parameters`, as the module docstring writes it."""
    r = int(head_dim * float(group.get("partial_rotary_factor", 1.0)))
    theta = float(group["rope_theta"])
    e = [theta ** (-2.0 * i / r) for i in range(r // 2)]
    if group.get("rope_type", "default") == "default":
        return r, 1.0, tuple(e)
    if group["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {group['rope_type']!r}")
    s = float(group["factor"])
    length = float(group["original_max_position_embeddings"])

    def dim(n):
        return r * math.log(length / (2.0 * math.pi * n)) / (
            2.0 * math.log(theta))

    lo = max(math.floor(dim(float(group["beta_fast"]))), 0)
    hi = min(math.ceil(dim(float(group["beta_slow"]))), r - 1)
    ramp = [min(max((i - lo) / (hi - lo), 0.0), 1.0) for i in range(r // 2)]
    return r, float(group["attention_factor"]), tuple(
        (ei / s) * ri + ei * (1.0 - ri) for ei, ri in zip(e, ramp))


def sizes_from_config(cfg: dict) -> dict:
    """The sizes the reference needs, from a configuration file's keys (the
    source's, plus the cut: `experts_total`, `experts_held`)."""
    total = int(cfg.get("experts_total") or cfg["num_experts"])
    held = tuple(cfg.get("experts_held") or (0, cfg["num_experts"]))
    kind = {"sliding_attention": "sliding", "full_attention": "full"}
    ffn = {"dense": "dense", "sparse": "moe"}
    ropes = cfg["rope_parameters"]
    return {
        "vocab": int(cfg["vocab_size"]), "hidden": int(cfg["hidden_size"]),
        "layer_heads": tuple(int(h) for h in
                             cfg["num_attention_heads_per_layer"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "window": int(cfg["sliding_window"]),
        "dense_width": int(cfg["intermediate_size"]),
        "expert_width": int(cfg["moe_intermediate_size"]),
        "shared_width": int(cfg["shared_expert_intermediate_size"]),
        "experts_total": total, "held": (int(held[0]), int(held[1])),
        "topk": int(cfg["num_experts_per_tok"]),
        "scaling": float(cfg["moe_routed_scaling_factor"]),
        "eps": float(cfg["rms_norm_eps"]),
        "init_range": float(cfg.get("initializer_range", 0.02)),
        "rope": tuple((k, rope_table(
            int(cfg["head_dim"]), dict(
                {"partial_rotary_factor": cfg.get("partial_rotary_factor", 0.5)},
                **ropes[name])))
            for name, k in sorted(kind.items()) if name in ropes),
        # (attention, ffn) of every layer: "sliding" | "full", "dense" | "moe"
        "kinds": tuple((kind[a], ffn[m]) for a, m in zip(
            cfg["layer_types"], cfg["mlp_layer_types"])),
    }


def param_shapes(sz: dict) -> dict:
    e, d = sz["hidden"], sz["head_dim"]
    n_held = sz["held"][1] - sz["held"][0]

    def swiglu(f):
        return {"w1": {"kernel": (e, f)}, "w3": {"kernel": (e, f)},
                "w2": {"kernel": (f, e)}}

    tree = {"embed_tokens": (sz["vocab"], e), "lm_head": (sz["vocab"], e),
            "final_norm": {"scale": (e,)}}
    for i, ((_, ffn), h) in enumerate(zip(sz["kinds"], sz["layer_heads"])):
        lp = {"input_layernorm": {"scale": (e,)},
              "post_attention_layernorm": {"scale": (e,)},
              "attention": {"q_proj": (e, h * d),
                            "k_proj": (e, sz["kv_heads"] * d),
                            "v_proj": (e, sz["kv_heads"] * d),
                            "gate_proj": (e, h),
                            "out_proj": {"kernel": (h * d, e)}}}
        if ffn == "dense":
            lp["mlp"] = swiglu(sz["dense_width"])
        else:
            f = sz["expert_width"]
            lp["moe"] = {"router": (e, sz["experts_total"]),
                         "expert_bias": (sz["experts_total"],),
                         "experts_w1": (n_held, e, f),
                         "experts_w3": (n_held, e, f),
                         "experts_w2": (n_held, f, e)}
            lp["shared_expert"] = swiglu(sz["shared_width"])
        tree[f"layer_{i}"] = lp
    return tree


def init_params(seed: int, sz: dict) -> dict:
    """Every weight from `seed` in one jitted call: matrices and the head
    N(0, init_range); the table's rows N(0, EMBED_STD); norm gains 1. The
    selection biases are N(0, init_range) from BIAS_KEY and the layer's
    number: the same for every seed."""
    shapes = param_shapes(sz)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    names = [str(getattr(path[-1], "key", path[-1])) for path, _ in flat]
    stds = [EMBED_STD if name == "embed_tokens" else sz["init_range"]
            for name in names]
    bias_keys = [jax.random.fold_in(
        jax.random.PRNGKey(BIAS_KEY), int(str(path[0].key).split("_")[1]))
        if name == "expert_bias" else None
        for name, (path, _) in zip(names, flat)]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(flat))
        keys = [k if b is None else b for k, b in zip(keys, bias_keys)]
        return jax.tree_util.tree_unflatten(treedef, [
            jnp.ones(shape, jnp.float32) if name == "scale"
            else std * jax.random.normal(k, shape, jnp.float32)
            for k, name, std, (_, shape) in zip(keys, names, stds, flat)])

    seed = int(seed)      # may exceed 32 signed bits: folded in two halves
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return make(key)


# -- the layer, for one row: x (S, hidden) -------------------------------------


def _rope(x, pos, table):
    """x (S, heads, D): the first R dims of every head turned (rotate-half:
    dim i pairs with dim i + R/2) by pos * f_i, cos and sin times c; the
    dims from R on pass."""
    r, c, freqs = table
    angle = pos.astype(jnp.float32)[:, None] * jnp.asarray(freqs, jnp.float32)
    cos = c * jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None]
    sin = c * jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None]
    u, rest = x[..., :r], x[..., r:]
    turned = jnp.concatenate([-u[..., r // 2:], u[..., :r // 2]], -1)
    return jnp.concatenate([u * cos + turned * sin, rest], -1)


def _attention(x, lp, seg, pos, kind, h, sz, quant):
    s = x.shape[0]
    hkv, d = sz["kv_heads"], sz["head_dim"]
    group = h // hkv
    table = dict(sz["rope"])[kind]
    window = sz["window"] if kind == "sliding" else 0
    q = _rope(_mm(x, lp["q_proj"], quant).reshape(s, h, d), pos, table)
    k = _rope(_mm(x, lp["k_proj"], quant).reshape(s, hkv, d), pos, table)
    v = _mm(x, lp["v_proj"], quant).reshape(s, hkv, d)
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
    ctx = ctx.transpose(1, 0, 2, 3).reshape(s, h, d)
    gate = jax.nn.sigmoid(_mm(x, lp["gate_proj"], quant))          # (S, h)
    return _mm((ctx * gate[:, :, None]).reshape(s, h * d),
               lp["out_proj"]["kernel"], quant)


def _swiglu(x, w1, w3, w2, quant):
    return _mm(jax.nn.silu(_mm(x, w1, quant)) * _mm(x, w3, quant), w2, quant)


def _mlp(x, lp, quant):
    """SwiGLU over `MLP_ROWS` rows at a time, each block a checkpoint."""
    rows = MLP_ROWS if x.shape[0] % MLP_ROWS == 0 else x.shape[0]
    block = jax.checkpoint(lambda xb: _swiglu(
        xb, lp["w1"]["kernel"], lp["w3"]["kernel"], lp["w2"]["kernel"],
        quant))
    return jax.lax.map(block, x.reshape(-1, rows, x.shape[-1])).reshape(
        x.shape)


def route(m, moe, sz):
    """(selected experts (S, k), their weights (S, k), gap between the k-th
    and (k+1)-th selection scores (S,)): sigmoid scores over all experts,
    the k largest of score + b selected, weights the selected scores WITHOUT
    b over their sum + 1e-6, times the scaling factor."""
    scores = jax.nn.sigmoid(jnp.matmul(m, moe["router"],
                                       precision="highest"))
    select = jax.lax.stop_gradient(scores + moe["expert_bias"])
    top, experts = jax.lax.top_k(select, sz["topk"] + 1)
    gap = top[:, sz["topk"] - 1] - top[:, sz["topk"]]
    experts = experts[:, :sz["topk"]]
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    weights = sz["scaling"] * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    return experts, weights, gap


def _routed(m, lp, sz, quant, tie_tol):
    """The held experts' part of the routed FFN over m plus the shared
    expert; the tokens each held expert received (padding is routed like
    any token); how many tokens sit within `tie_tol` of another
    selection."""
    moe = lp["moe"]
    experts, weights, gap = route(m, moe, sz)
    lo, hi = sz["held"]

    @jax.checkpoint
    def term(held):
        e, w1, w3, w2 = held
        # this expert's weight for every token: its weight where selected
        weight = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
        return weight[:, None] * _swiglu(m, w1, w3, w2, quant)

    def add_expert(out, held):
        # the running sum is no input of the checkpoint: the backward pass
        # keeps none of its 32 values (128 MB each at the cell's size)
        return out + term(held), jnp.sum(jnp.any(experts == held[0], axis=-1))

    # a loop over the held experts, every token through each, masked
    out, counts = jax.lax.scan(
        add_expert, jnp.zeros_like(m),
        (jnp.arange(lo, hi), moe["experts_w1"], moe["experts_w3"],
         moe["experts_w2"]))
    return (out + _mlp(m, lp["shared_expert"], quant), counts,
            jnp.sum(gap < tie_tol))


def layer_forward(x, lp, seg, pos, kind, ffn, h, sz, quant=None,
                  tie_tol=0.0):
    """One layer over one row: (y, held experts' token counts, near-tie
    tokens); a dense layer counts none."""
    a = _rms_norm(x, lp["input_layernorm"]["scale"], sz["eps"])
    x = x + _attention(a, lp["attention"], seg, pos, kind, h, sz, quant)
    m = _rms_norm(x, lp["post_attention_layernorm"]["scale"], sz["eps"])
    if ffn == "dense":
        n_held = sz["held"][1] - sz["held"][0]
        return (x + _mlp(m, lp["mlp"], quant),
                jnp.zeros((n_held,), jnp.int32), jnp.zeros([], jnp.int32))
    out, counts, ties = _routed(m, lp, sz, quant, tie_tol)
    return x + out, counts, ties


def row_hidden(params, ids, seg, sz, quant=None, tie_tol=0.0):
    """One row: ids, seg (S,) -> (the final norm's output (S, hidden), per
    routed layer the held experts' token counts and near-tie tokens)."""
    pos = document_positions(seg)
    n_held = sz["held"][1] - sz["held"][0]
    x = params["embed_tokens"][ids]
    counts, ties = [], []
    for i, ((kind, ffn), h) in enumerate(zip(sz["kinds"],
                                             sz["layer_heads"])):
        x, count, tie = jax.checkpoint(
            lambda x, lp, kind=kind, ffn=ffn, h=h: layer_forward(
                x, lp, seg, pos, kind, ffn, h, sz, quant, tie_tol))(
                    x, params[f"layer_{i}"])
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
                # holds one row's gradient and its pass's temporaries and no
                # third copy
                grads = jax.tree.map(
                    lambda g: np.asarray(g) * np.float32(scale), grads)
                acc = grads if acc is None else jax.tree.map(
                    np.add, acc, grads)
        acc = jax.device_put(acc, jax.tree.leaves(params)[0].sharding)
        return loss, acc, counts, ties
