"""Plain BERT: the benchmark's reference for `correct`.

Forward pass, MLM + NSP loss, their gradients (jax.grad of the forward) and
the LAMB update, in straightforward jax.numpy, float32, with
`jax.default_matmul_precision("highest")` set by the callers in this file.
Written from the BERT paper (Devlin et al. 2018: post-LN transformer encoder,
learned positions, tied MLM decoder, pooled NSP head) and the LAMB paper
(You et al. 2019) with NVLAMB's global-norm pre-normalisation. No kernels, no
packing tricks, no imports from the program under test.

Where the program's stated design fixes a free choice, the reference follows
the statement, not the code:

- the vocabulary table has `vocab_rows` rows (the config's vocab_size padded
  up to a multiple the run states); every row takes part in the softmax;
- Q, K and V are ONE (E, 3E) tensor per layer, so LAMB's trust ratio is taken
  per layer over the fused tensor (the program defines its tensors so; the
  paper's three matrices would give three ratios);
- GELU is the exact erf form, LayerNorm eps 1e-12, padding keys get -1e4
  added to their scores (the original implementation's constants);
- packed rows (several documents in one row): a token attends only inside
  its own document, positions restart per document, every document's first
  token feeds the pooler and the NSP loss;
- the loss of a step is the mean over its micro-batches of (mean MLM
  cross-entropy over that micro-batch's masked tokens + mean NSP
  cross-entropy over its documents); gradients are averaged the same way.

Dropout. A training step at the configuration's dropout probability can be
followed only with the program's own masks, so the reference computes them
from the program's STATED rule, in its own code (section "dropout" below):
every site's keep mask is a counter hash of (seed, row, column) whose
constants and threshold `ops/layernorm.row_col_keep` and
`ops/pallas/flash_attention._keep_mask` document, and every site's seed is
drawn from the step's key (an input of the step, observed like the batch) the
way flax.linen derives a module's rng: split per micro-batch, split per
layer, SHA-1 of the module path folded in. Nothing the program computed goes
in: a program whose masks departed from the rule would disagree with these.

`quant` (None or "fp8") is the control of the comparison, not a feature: what
an fp8 training path, one step below the configuration's bfloat16, would
compute (Micikevicius et al. 2022): both operands of every matrix product
rounded to float8_e4m3 forward, the incoming gradient of every weight-matrix
product rounded to float8_e5m2 backward, each with a per-tensor scale.
"""

from __future__ import annotations

import functools
import hashlib
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-12
PAD_BIAS = -1e4
HARD_MASK = -1e30

LAYER_KEYS = ("wqkv", "bqkv", "wo", "bo", "ln1_g", "ln1_b",
              "w1", "b1", "w2", "b2", "ln2_g", "ln2_b")


# -- sizes --------------------------------------------------------------------


def sizes_from_config(cfg: dict, vocab_pad_multiple: int = 1) -> dict:
    """The handful of sizes the reference needs, from a BERT config dict."""
    v = int(cfg["vocab_size"])
    m = int(vocab_pad_multiple)
    return {
        "vocab_rows": (v + m - 1) // m * m,
        "hidden": int(cfg["hidden_size"]),
        "layers": int(cfg["num_hidden_layers"]),
        "heads": int(cfg["num_attention_heads"]),
        "ffn": int(cfg["intermediate_size"]),
        "positions": int(cfg["max_position_embeddings"]),
        "types": int(cfg.get("type_vocab_size", 2)),
        "init_range": float(cfg.get("initializer_range", 0.02)),
    }


# -- weights from a seed ------------------------------------------------------


def param_shapes(sz: dict, head: str = "pretrain") -> dict:
    e, f, n, v = sz["hidden"], sz["ffn"], sz["layers"], sz["vocab_rows"]
    shapes = {
        "word": (v, e), "pos": (sz["positions"], e), "type": (sz["types"], e),
        "emb_ln_g": (e,), "emb_ln_b": (e,),
        "layers": {
            "wqkv": (n, e, 3 * e), "bqkv": (n, 3 * e),
            "wo": (n, e, e), "bo": (n, e),
            "ln1_g": (n, e), "ln1_b": (n, e),
            "w1": (n, e, f), "b1": (n, f), "w2": (n, f, e), "b2": (n, e),
            "ln2_g": (n, e), "ln2_b": (n, e),
        },
    }
    if head == "pretrain":
        shapes.update({
            "pool_w": (e, e), "pool_b": (e,),
            "mlm_w": (e, e), "mlm_b": (e,), "mlm_ln_g": (e,),
            "mlm_ln_b": (e,), "mlm_bias": (v,),
            "nsp_w": (e, 2), "nsp_b": (2,),
        })
    elif head != "encoder":
        raise ValueError(f"unknown head {head!r}")
    return shapes


def _is_weight(name: str) -> bool:
    """Matrices and embedding tables: drawn N(0, init_range), weight-decayed.
    Everything else is a bias (zeros) or a LayerNorm gain (ones)."""
    return name in ("word", "pos", "type", "wqkv", "wo", "w1", "w2",
                    "pool_w", "mlm_w", "nsp_w")


def init_params(seed: int, sz: dict, head: str = "pretrain") -> dict:
    """Every weight from `seed`, in one jitted call, on the default device:
    matrices and tables N(0, init_range), biases 0, LayerNorm gains 1 (the
    paper's initialisation)."""
    shapes = param_shapes(sz, head)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    names = [str(getattr(path[-1], "key", path[-1])) for path, _ in flat]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(flat))
        leaves = []
        for k, name, (_, shape) in zip(keys, names, flat):
            if _is_weight(name):
                leaves.append(sz["init_range"] * jax.random.normal(
                    k, shape, jnp.float32))
            elif name.endswith("_g"):
                leaves.append(jnp.ones(shape, jnp.float32))
            else:
                leaves.append(jnp.zeros(shape, jnp.float32))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # a seed may exceed 32 signed bits: fold it in two halves
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return make(key)


# -- the matrix product, and its lower-precision control -----------------------


def _round_to(x, dtype, largest):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = largest / amax
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _fp8(x):
    """Round to float8_e4m3 with a per-tensor scale; straight-through in the
    backward pass (the cotangent is not rounded)."""
    q = _round_to(x, jnp.float8_e4m3fn, 448.0)
    return x + jax.lax.stop_gradient(q - x)


@jax.custom_vjp
def _mm_fp8(x, w):
    """x @ w as an fp8 training path computes it, forward and backward."""
    return jnp.matmul(_round_to(x, jnp.float8_e4m3fn, 448.0),
                      _round_to(w, jnp.float8_e4m3fn, 448.0),
                      precision=jax.lax.Precision.HIGHEST)


def _mm_fp8_fwd(x, w):
    qx = _round_to(x, jnp.float8_e4m3fn, 448.0)
    qw = _round_to(w, jnp.float8_e4m3fn, 448.0)
    return jnp.matmul(qx, qw, precision=jax.lax.Precision.HIGHEST), (qx, qw)


def _mm_fp8_bwd(saved, g):
    qx, qw = saved
    g = _round_to(g, jnp.float8_e5m2, 57344.0)
    dx = jnp.matmul(g, qw.T, precision=jax.lax.Precision.HIGHEST)
    dw = jnp.matmul(qx.reshape(-1, qx.shape[-1]).T,
                    g.reshape(-1, g.shape[-1]),
                    precision=jax.lax.Precision.HIGHEST)
    return dx, dw


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(x, w, quant):
    if quant == "fp8":
        return _mm_fp8(x, w)
    if quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


# -- dropout: the program's masks, from its stated rule ----------------------------

# Where the program's modules draw a dropout key: the flax scope path of the
# module that calls make_rng("dropout"), and the call's counter in that scope.
# The encoder's layers are one scanned module ("layers"), which flax traces
# twice, so the counter of the draws that run is 2 there. A program that
# renames or restructures these modules draws other masks: the table then
# needs a `benchmark` PR.
SITE_EMBEDDINGS = ("bert", "embeddings", 1)
_LAYER = ("bert", "encoder", "layers", "layer")
SITE_ATTENTION_PROBS = _LAYER + ("attention", 2)
SITE_ATTENTION_OUT = _LAYER + ("attention_layer_norm", 2)
SITE_MLP_OUT = _LAYER + ("output_layer_norm", 2)


def _site_key(key, path):
    """flax.linen's per-site key: the first four bytes of the SHA-1 of the
    path's names (and the counter's big-endian bytes) folded into `key`."""
    m = hashlib.sha1()
    for x in path:
        m.update(x.encode("utf-8") if isinstance(x, str)
                 else x.to_bytes((x.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(
        key, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def _seed_bits(key):
    return jax.random.bits(key, (), jnp.uint32)


def _seed_flash(key):
    # the flash path folds its key into a non-negative 31-bit seed
    return jax.random.randint(key, (), 0, 2 ** 31 - 1,
                              dtype=jnp.int32).astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("accum", "layers", "flash"))
def dropout_seeds(step_key, accum: int, layers: int, flash: bool):
    """The uint32 seed of every dropout site of one optimisation step, from
    the step's key (uint32[2], threefry): {"emb": (accum,), "probs" / "attn"
    / "mlp": (accum, layers)}. `flash`: the attention probabilities are
    dropped inside the flash kernel (sequences over 256), whose seed is
    drawn as a 31-bit integer."""
    probs_seed = _seed_flash if flash else _seed_bits

    def micro(key):
        per_layer = jax.random.split(key, layers)
        site = lambda path, draw: jax.vmap(            # noqa: E731
            lambda k: draw(_site_key(k, path)))(per_layer)
        return {"emb": _seed_bits(_site_key(key, SITE_EMBEDDINGS)),
                "probs": site(SITE_ATTENTION_PROBS, probs_seed),
                "attn": site(SITE_ATTENTION_OUT, _seed_bits),
                "mlp": site(SITE_MLP_OUT, _seed_bits)}

    return jax.vmap(micro)(jax.random.split(step_key, accum))


def _mix(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    return x * jnp.uint32(0x846CA68B)


def _keep_row_col(seed, shape, rate: float):
    """The program's positional mask: over the (rows, last axis) view of
    `shape`, keep where the hash of (row, column, seed) exceeds rate * 2^32."""
    rows = math.prod(shape[:-1])
    r = jax.lax.broadcasted_iota(jnp.uint32, (rows, shape[-1]), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (rows, shape[-1]), 1)
    x = (r * jnp.uint32(0x9E3779B1)) ^ (c * jnp.uint32(0x85EBCA77))
    x = x ^ (seed.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D))
    return (_mix(x) > jnp.uint32(int(rate * float(2 ** 32)))).reshape(shape)


def _keep_flash(seed, shape, rate: float):
    """The flash kernel's mask over (batch, heads, query, key): the counter
    is (query, key), the seed is offset by batch * heads + head, and the top
    23 bits are compared with rate * 2^23."""
    b, h, s, _ = shape
    bh = jax.lax.broadcasted_iota(jnp.uint32, (b * h, s, s), 0)
    q = jax.lax.broadcasted_iota(jnp.uint32, (b * h, s, s), 1)
    k = jax.lax.broadcasted_iota(jnp.uint32, (b * h, s, s), 2)
    x = (q * jnp.uint32(0x9E3779B1)) ^ (k * jnp.uint32(0x85EBCA77))
    x = x ^ (seed.astype(jnp.uint32) + bh * jnp.uint32(0xC2B2AE3D))
    keep = (_mix(x) >> 9) >= jnp.uint32(int(rate * (1 << 23)))
    return keep.reshape(shape)


def _dropout(x, keep, rate: float):
    return jnp.where(keep, x / (1.0 - rate), 0.0)


# -- forward ------------------------------------------------------------------


def _layer_norm(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _layer(h, lp, score_bias, heads, quant, drop=None, seeds=None):
    """One encoder layer. `drop` is None or the static (hidden rate,
    attention rate, flash); `seeds` then holds this layer's three seeds."""
    b, s, e = h.shape
    d = e // heads
    qkv = _mm(h, lp["wqkv"], quant) + lp["bqkv"]
    q, k, v = [t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1)]
    if quant == "fp8":
        q, k = _fp8(q), _fp8(k)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(d)
    probs = jax.nn.softmax(scores + score_bias, axis=-1)
    if drop is not None and drop[1] > 0.0:
        keep = (_keep_flash if drop[2] else _keep_row_col)(
            seeds["probs"], probs.shape, drop[1])
        probs = _dropout(probs, keep, drop[1])
    if quant == "fp8":
        probs, v = _fp8(probs), _fp8(v)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v,
                     precision=jax.lax.Precision.HIGHEST)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, e)
    attn = _mm(ctx, lp["wo"], quant) + lp["bo"]
    if drop is not None and drop[0] > 0.0:
        attn = _dropout(attn, _keep_row_col(seeds["attn"], attn.shape,
                                            drop[0]), drop[0])
    h = _layer_norm(h + attn, lp["ln1_g"], lp["ln1_b"])
    inter = _gelu(_mm(h, lp["w1"], quant) + lp["b1"])
    out = _mm(inter, lp["w2"], quant) + lp["b2"]
    if drop is not None and drop[0] > 0.0:
        out = _dropout(out, _keep_row_col(seeds["mlp"], out.shape, drop[0]),
                       drop[0])
    return _layer_norm(h + out, lp["ln2_g"], lp["ln2_b"])


def encode(params, batch, heads: int, quant=None, drop=None, seeds=None):
    """(B, S, E) final hidden states. `batch` holds input_ids and either
    attention_mask (one document per row) or segment_ids (+ position_ids) for
    packed rows; token_type_ids are optional. With `drop` (static: hidden
    rate, attention rate, flash) and one micro-batch's `seeds` (one entry of
    dropout_seeds), dropout as a training step applies it: after the
    embeddings' LayerNorm, on the attention probabilities, and on both
    residual branches of every layer."""
    ids = batch["input_ids"]
    b, s = ids.shape
    seg = batch.get("segment_ids")
    if seg is not None:
        allowed = (seg[:, :, None] == seg[:, None, :]) & (seg[:, :, None] > 0)
        score_bias = jnp.where(allowed, 0.0, HARD_MASK)[:, None]
    else:
        mask = batch["attention_mask"].astype(jnp.float32)
        score_bias = ((1.0 - mask) * PAD_BIAS)[:, None, None, :]
    pos = batch.get("position_ids")
    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    types = batch.get("token_type_ids")
    if types is None:
        types = jnp.zeros_like(ids)
    h = params["word"][ids] + params["pos"][pos] + params["type"][types]
    h = _layer_norm(h, params["emb_ln_g"], params["emb_ln_b"])
    per_layer = None
    if drop is not None:
        if drop[0] > 0.0:
            h = _dropout(h, _keep_row_col(seeds["emb"], h.shape, drop[0]),
                         drop[0])
        per_layer = {k: seeds[k] for k in ("probs", "attn", "mlp")}

    @jax.checkpoint
    def body(h, xs):
        lp, layer_seeds = xs
        return _layer(h, lp, score_bias, heads, quant, drop,
                      layer_seeds), None

    h, _ = jax.lax.scan(body, h, (params["layers"], per_layer))
    return h


def _cross_entropy(logits, labels):
    """Mean over labels != -1 of -log softmax(logits)[label]; 0 if none."""
    valid = labels != -1
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(
        jnp.sum(valid), 1)


def pretrain_logits(params, batch, heads: int, max_predictions: int,
                    quant=None, drop=None, seeds=None):
    """MLM logits at (up to `max_predictions`) labelled positions per row,
    their labels, and the NSP logits."""
    h = encode(params, batch, heads, quant, drop, seeds)
    labels = batch["masked_lm_labels"]
    order = jnp.argsort(labels == -1, axis=-1, stable=True)[
        :, :max_predictions]
    mlm_labels = jnp.take_along_axis(labels, order, axis=-1)
    hm = jnp.take_along_axis(h, order[..., None], axis=1)
    t = _gelu(_mm(hm, params["mlm_w"], quant) + params["mlm_b"])
    t = _layer_norm(t, params["mlm_ln_g"], params["mlm_ln_b"])
    mlm_logits = _mm(t, params["word"].T, quant) + params["mlm_bias"]
    cls_pos = batch.get("nsp_positions")
    if cls_pos is None:
        cls = h[:, 0]
    else:
        cls = jnp.take_along_axis(h, cls_pos[..., None], axis=1)
    pooled = jnp.tanh(_mm(cls, params["pool_w"], quant) + params["pool_b"])
    nsp_logits = _mm(pooled, params["nsp_w"], quant) + params["nsp_b"]
    return mlm_logits, mlm_labels, nsp_logits


def pretrain_loss(params, batch, heads: int, max_predictions: int,
                  quant=None, drop=None, seeds=None):
    mlm_logits, mlm_labels, nsp_logits = pretrain_logits(
        params, batch, heads, max_predictions, quant, drop, seeds)
    return (_cross_entropy(mlm_logits, mlm_labels)
            + _cross_entropy(nsp_logits, batch["next_sentence_labels"]))


# -- one optimisation step, over micro-batches ---------------------------------


@functools.partial(jax.jit, static_argnames=("heads", "max_predictions",
                                             "quant", "drop"))
def _micro_grad(params, micro, seeds, heads, max_predictions, quant, drop):
    return jax.value_and_grad(pretrain_loss)(params, micro, heads,
                                             max_predictions, quant, drop,
                                             seeds)


@jax.jit
def _accumulate(acc, grads):
    return jax.tree.map(jnp.add, acc, grads)


def step_loss_and_grad(params, micro_batches, heads: int,
                       max_predictions: int, quant=None, dropout=None):
    """Loss and gradient of one optimisation step: the mean over its
    micro-batches (a list of batch dicts), one micro-batch at a time so that
    the activations of only one are alive. `dropout`: None, or (hidden rate,
    attention rate, flash, seeds) with `seeds` the step's dropout_seeds."""
    drop = None if dropout is None else tuple(dropout[:3])
    with jax.default_matmul_precision("highest"):
        total, acc = 0.0, None
        for i, micro in enumerate(micro_batches):
            seeds = (None if drop is None else
                     jax.tree.map(lambda x: x[i], dropout[3]))
            loss, grads = _micro_grad(params, micro, seeds, heads,
                                      max_predictions, quant, drop)
            total = total + loss
            acc = grads if acc is None else _accumulate(acc, grads)
        n = float(len(micro_batches))
        return total / n, jax.tree.map(lambda g: g / n, acc)


# -- LAMB ----------------------------------------------------------------------


def poly_warmup_lr(step: int, base_lr: float, total_steps: int,
                   warmup: float) -> float:
    """Linear warm-up over warmup*total_steps, then (1 - progress)**0.5."""
    progress = step / float(max(total_steps, 1))
    if progress < warmup:
        return base_lr * step / (warmup * total_steps)
    return base_lr * (1.0 - min(max(progress, 0.0), 1.0)) ** 0.5


def lamb_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"count": 0, "mu": zeros, "nu": jax.tree.map(jnp.zeros_like,
                                                        params)}


def clipped_gradient(grads, max_grad_norm: float = 1.0):
    """The gradient as LAMB's moments receive it: divided by
    max(1, global norm / max_grad_norm)."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    denom = jnp.maximum(1.0, gnorm / max_grad_norm)
    return jax.tree.map(lambda g: g / denom, grads), gnorm


@jax.jit
def _lamb_apply(params, grads, mu, nu, lr, count):
    b1, b2, eps, wd = 0.9, 0.999, 1e-6, 0.01
    grads, _ = clipped_gradient(grads)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g),
                      nu, grads)
    c1, c2 = 1.0 - b1 ** count, 1.0 - b2 ** count

    def update(path, p, m, v):
        name = str(getattr(path[-1], "key", path[-1]))
        stacked = any(str(getattr(k, "key", k)) == "layers" for k in path)
        u = (m / c1) / (jnp.sqrt(v / c2) + eps)
        if _is_weight(name):
            u = u + wd * p
        axes = tuple(range(1 if stacked else 0, p.ndim))
        pn = jnp.sqrt(jnp.sum(jnp.square(p), axis=axes, keepdims=True))
        un = jnp.sqrt(jnp.sum(jnp.square(u), axis=axes, keepdims=True))
        ratio = jnp.where((pn > 0) & (un > 0), pn / jnp.maximum(un, 1e-30),
                          1.0)
        return p - lr * ratio * u

    params = jax.tree_util.tree_map_with_path(update, params, mu, nu)
    return params, mu, nu


def lamb_step(params, grads, opt, base_lr: float, total_steps: int,
              warmup: float):
    """One LAMB step (b1 0.9, b2 0.999, eps 1e-6, weight decay 0.01 on
    matrices and tables only, bias correction, global-norm pre-normalisation
    at 1.0, trust ratio ||p||/||u|| per tensor and per layer, 1 where either
    norm is 0). The learning rate is the schedule's at the count BEFORE this
    step, so the very first step of a warm-up moves nothing."""
    count = opt["count"] + 1
    lr = poly_warmup_lr(count - 1, base_lr, total_steps, warmup)
    params, mu, nu = _lamb_apply(params, grads, opt["mu"], opt["nu"],
                                 jnp.float32(lr), jnp.float32(count))
    return params, {"count": count, "mu": mu, "nu": nu}
