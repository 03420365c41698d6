"""Pallas kernel tests in interpret mode: fused LayerNorm fwd/bwd vs XLA
reference, flash attention fwd/bwd vs plain softmax attention, dropout mask
consistency, multi-tensor l2norm/scale/clip."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

from bert_pytorch_tpu.ops.layernorm import _layer_norm_xla
from bert_pytorch_tpu.ops.pallas.flash_attention import flash_attention
from bert_pytorch_tpu.ops.pallas.layernorm import layer_norm_pallas


# -- layernorm --------------------------------------------------------------

def test_layernorm_pallas_forward_matches_xla():
    rng = np.random.RandomState(0)
    x = rng.randn(4, 300, 256).astype(np.float32)  # rows not divisible: pad path
    scale = rng.randn(256).astype(np.float32)
    bias = rng.randn(256).astype(np.float32)
    got = layer_norm_pallas(jnp.array(x), jnp.array(scale), jnp.array(bias),
                            1e-12, True)
    want = _layer_norm_xla(jnp.array(x), jnp.array(scale), jnp.array(bias),
                           1e-12)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_layernorm_pallas_grads_match_xla():
    rng = np.random.RandomState(1)
    x = rng.randn(2, 128, 256).astype(np.float32)
    scale = rng.randn(256).astype(np.float32)
    bias = rng.randn(256).astype(np.float32)

    def loss_pallas(x, s, b):
        return jnp.sum(jnp.sin(layer_norm_pallas(x, s, b, 1e-12, True)))

    def loss_xla(x, s, b):
        return jnp.sum(jnp.sin(_layer_norm_xla(x, s, b, 1e-12)))

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(
        jnp.array(x), jnp.array(scale), jnp.array(bias))
    gx = jax.grad(loss_xla, argnums=(0, 1, 2))(
        jnp.array(x), jnp.array(scale), jnp.array(bias))
    for a, b_ in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_layernorm_pallas_bf16_dtype_preserved():
    x = jnp.ones((8, 256), jnp.bfloat16)
    s = jnp.ones((256,), jnp.float32)
    b = jnp.zeros((256,), jnp.float32)
    y = layer_norm_pallas(x, s, b, 1e-12, True)
    assert y.dtype == jnp.bfloat16


# -- fused residual + dropout + LayerNorm -----------------------------------

from bert_pytorch_tpu.ops.layernorm import (_add_dropout_layer_norm_xla,
                                            _hash_keep_mask)
from bert_pytorch_tpu.ops.pallas.layernorm import (
    add_dropout_layer_norm_pallas)


def test_adln_rate0_equals_plain_layernorm():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 200, 256).astype(np.float32)  # pad path
    res = rng.randn(2, 200, 256).astype(np.float32)
    s = rng.randn(256).astype(np.float32)
    b = rng.randn(256).astype(np.float32)
    got = add_dropout_layer_norm_pallas(
        jnp.array(x), jnp.array(res), jnp.array(s), jnp.array(b),
        jnp.int32(7), 0.0, 1e-12, True)
    want = _layer_norm_xla(jnp.array(res + x), jnp.array(s), jnp.array(b),
                           1e-12)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_adln_kernel_matches_xla_mirror_bitmask():
    """The Pallas kernel and the XLA fallback must drop the SAME units
    (identical counter-hash mask) and produce matching outputs."""
    rng = np.random.RandomState(1)
    x = rng.randn(4, 64, 256).astype(np.float32)
    res = rng.randn(4, 64, 256).astype(np.float32)
    s = rng.randn(256).astype(np.float32)
    b = rng.randn(256).astype(np.float32)
    for seed in (0, 123, -5):
        got = add_dropout_layer_norm_pallas(
            jnp.array(x), jnp.array(res), jnp.array(s), jnp.array(b),
            jnp.int32(seed), 0.1, 1e-12, True)
        want = _add_dropout_layer_norm_xla(
            jnp.array(x), jnp.array(res), jnp.array(s), jnp.array(b),
            jnp.int32(seed), 0.1, 1e-12)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_adln_grads_match_xla_mirror():
    """custom_vjp backward (mask regenerated in-kernel) vs autodiff of the
    XLA mirror that materializes the same mask."""
    rng = np.random.RandomState(2)
    x = rng.randn(2, 128, 256).astype(np.float32)
    res = rng.randn(2, 128, 256).astype(np.float32)
    s = rng.randn(256).astype(np.float32)
    b = rng.randn(256).astype(np.float32)
    seed = jnp.int32(99)

    def loss_pallas(x, res, s, b):
        return jnp.sum(jnp.sin(add_dropout_layer_norm_pallas(
            x, res, s, b, seed, 0.1, 1e-12, True)))

    def loss_xla(x, res, s, b):
        return jnp.sum(jnp.sin(_add_dropout_layer_norm_xla(
            x, res, s, b, seed, 0.1, 1e-12)))

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2, 3))(
        jnp.array(x), jnp.array(res), jnp.array(s), jnp.array(b))
    gx = jax.grad(loss_xla, argnums=(0, 1, 2, 3))(
        jnp.array(x), jnp.array(res), jnp.array(s), jnp.array(b))
    for a, b_ in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_adln_mask_statistics():
    """Keep rate ~= 1-p; different seeds draw different masks."""
    m1 = np.asarray(_hash_keep_mask(jnp.int32(1), (512, 256), 0.1))
    m2 = np.asarray(_hash_keep_mask(jnp.int32(2), (512, 256), 0.1))
    assert abs(m1.mean() - 0.9) < 5e-3
    assert abs(m2.mean() - 0.9) < 5e-3
    assert 0.17 < (m1 != m2).mean() < 0.19  # 2*p*(1-p) = 0.18 if independent
    # dropped units are scaled by exactly 1/(1-p)
    x = np.ones((512, 256), np.float32)
    seed = jnp.int32(1)
    # bypass LN: recover dropout output via h = residual + dropout(x) with
    # scale chosen to make LN identity is fiddly; instead check the mask
    # applied inside the XLA mirror directly
    keep = np.asarray(_hash_keep_mask(seed, x.shape, 0.1))
    dropped = np.where(keep, x / 0.9, 0.0)
    assert np.allclose(np.unique(dropped), [0.0, 1.0 / 0.9])


def test_adln_bf16_dtype_preserved():
    x = jnp.ones((8, 256), jnp.bfloat16)
    res = jnp.ones((8, 256), jnp.bfloat16)
    s = jnp.ones((256,), jnp.float32)
    b = jnp.zeros((256,), jnp.float32)
    y = add_dropout_layer_norm_pallas(x, res, s, b, jnp.int32(3), 0.1,
                                      1e-12, True)
    assert y.dtype == jnp.bfloat16


def test_hash_dropout_grads_match_materialized_mask():
    """hash_dropout's custom backward (mask regenerated from the seed) must
    equal autodiff of the same mask applied via where()."""
    from bert_pytorch_tpu.ops.attention import hash_dropout
    from bert_pytorch_tpu.ops.layernorm import row_col_keep

    rng = np.random.RandomState(3)
    x = jnp.array(rng.randn(4, 8, 16, 128).astype(np.float32))
    seed = jnp.int32(42)
    rate = 0.1

    y = hash_dropout(x, seed, rate)
    keep = row_col_keep(seed, 0, 4 * 8 * 16, 128, rate).reshape(x.shape)
    want = jnp.where(keep, x / (1 - rate), 0.0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-6)
    # keep statistics
    assert abs(np.asarray(keep).mean() - 0.9) < 2e-2

    g1 = jax.grad(lambda a: jnp.sum(jnp.sin(hash_dropout(a, seed, rate))))(x)
    g2 = jax.grad(lambda a: jnp.sum(jnp.sin(
        jnp.where(keep, a / (1 - rate), 0.0))))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-5,
                               atol=1e-6)


# -- flash attention --------------------------------------------------------

def _ref_attention(q, k, v, bias=None):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(d)
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


def _qkv(b=2, s=256, h=4, d=64, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.array(rng.randn(b, s, h, d).astype(np.float32)) * 0.5
    q, k, v = mk(), mk(), mk()
    mask = np.ones((b, s), np.float32)
    mask[:, s - 17:] = 0  # padded tail
    bias = jnp.array((1.0 - mask) * -10000.0)[:, None, None, :]
    return q, k, v, bias


def test_flash_forward_matches_reference():
    q, k, v, bias = _qkv()
    got = flash_attention(q, k, v, bias=bias, interpret=True)
    want = _ref_attention(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_forward_no_bias():
    q, k, v, _ = _qkv(s=128)
    got = flash_attention(q, k, v, interpret=True)
    want = _ref_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bwd", ["fused", "split"])
def test_flash_grads_match_reference(bwd, force_flash_path):
    # both backward paths: the fused dq/dk/dv kernel (default, S <= 2048)
    # and the split two-kernel path that serves longer sequences
    force_flash_path("native" if bwd == "fused" else "bh", bwd)
    q, k, v, bias = _qkv(s=128)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, bias=bias,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref_attention(q, k, v, bias) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


@pytest.mark.slow  # re-tiered out of tier-1's 870s wall-clock budget
def test_flash_dropout_deterministic_and_unbiased():
    q, k, v, bias = _qkv(s=128)
    seed = jnp.array(7, jnp.int32)
    o1 = flash_attention(q, k, v, bias=bias, dropout_seed=seed,
                         dropout_rate=0.3, interpret=True)
    o2 = flash_attention(q, k, v, bias=bias, dropout_seed=seed,
                         dropout_rate=0.3, interpret=True)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))

    o3 = flash_attention(q, k, v, bias=bias,
                         dropout_seed=jnp.array(8, jnp.int32),
                         dropout_rate=0.3, interpret=True)
    assert not np.allclose(np.asarray(o1), np.asarray(o3))

    # expectation over seeds approximates the undropped output
    outs = [np.asarray(flash_attention(
        q, k, v, bias=bias, dropout_seed=jnp.array(s_, jnp.int32),
        dropout_rate=0.3, interpret=True)) for s_ in range(24)]
    mean = np.mean(outs, axis=0)
    want = np.asarray(_ref_attention(q, k, v, bias))
    err = np.abs(mean - want).mean() / (np.abs(want).mean() + 1e-9)
    assert err < 0.15, err


@pytest.mark.parametrize("bwd", ["fused", "split"])
@pytest.mark.parametrize("d", [64, 128])
def test_flash_dropout_grads_flow(d, bwd, force_flash_path):
    """The dropout forward and backward (masks regenerated in-kernel) must
    equal a pure-jnp mirror applying the IDENTICAL keep mask, and its
    autodiff. This replaces the original single-coordinate
    finite-difference check, which was fp32-noise-limited: the loss is a
    sum over B*S*H*D squared terms, so an eps=1e-3 secant carries ~1e-2 of
    rounding noise — 20x the true gradient at the probed coordinate (the
    analytic value is verified here to 1e-8 against the exact-mask mirror).
    D = 64: the softmax scale is a power of two and rides on the (blk, D)
    dot operand; D = 128: it is not, and stays a multiply of the score
    tile. Both put the dropout rescale on the (blk, D) results."""
    from bert_pytorch_tpu.ops.pallas.flash_attention import _keep_mask

    force_flash_path("native" if bwd == "fused" else "bh", bwd)
    b, s, h = 2, 128, 4
    q, k, v, bias = _qkv(s=s, d=d)
    seed = jnp.array(3, jnp.int32)
    rate = 0.2

    def mirror(q, k, v):
        sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(d)
        sc = sc + bias.astype(jnp.float32)
        p = jax.nn.softmax(sc, axis=-1)
        keep = jnp.stack([jnp.stack([
            _keep_mask(seed, bi * h + hh, 0, 0, s, s, rate)
            for hh in range(h)]) for bi in range(b)])
        p = jnp.where(keep, p / (1 - rate), 0.0)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, bias=bias, dropout_seed=seed,
                                       dropout_rate=rate,
                                       interpret=True) ** 2)

    out = flash_attention(q, k, v, bias=bias, dropout_seed=seed,
                          dropout_rate=rate, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(mirror(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(mirror(q, k, v) ** 2),
                     argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(g, g_ref):
        arr = np.asarray(a)
        assert np.isfinite(arr).all() and np.abs(arr).sum() > 0
        np.testing.assert_allclose(arr, np.asarray(r), rtol=5e-4, atol=5e-5)


# -- nothing of the row or the column alone on the (q, k) tile (PR 27) ------

_HASH = dict(row=0x9E3779B1, col=0x85EBCA77, head=0xC2B2AE3D,
             m1=0x7FEB352D, m2=0x846CA68B)


def _documented_keep(seed, bh, q0, k0, bq, bk, rate):
    """`_keep_mask`'s docstring in numpy uint32, one full tile at a time."""
    u = np.uint32
    with np.errstate(over="ignore"):
        rows = (np.arange(bq, dtype=u) + u(q0))[:, None]
        cols = (np.arange(bk, dtype=u) + u(k0))[None, :]
        x = (rows * u(_HASH["row"])) ^ (cols * u(_HASH["col"]))
        x = x ^ (u(seed) + u(bh) * u(_HASH["head"]))
        x = x ^ (x >> u(16))
        x = x * u(_HASH["m1"])
        x = x ^ (x >> u(15))
        x = x * u(_HASH["m2"])
    return (x >> u(9)) >= u(int(rate * (1 << 23)))


@pytest.mark.parametrize("rate", [0.1, 0.3])
@pytest.mark.parametrize("seed", [7, 2147483000])
@pytest.mark.parametrize("bq,bk,q0,k0", [(128, 128, 384, 256),
                                         (256, 512, 768, 1536),
                                         (512, 512, 7680, 3584)])
def test_flash_split_hash_is_the_documented_one(bq, bk, q0, k0, seed, rate):
    """The kernels build the keep hash from a per-row and a per-column
    vector and compare against a shifted threshold; the bits are those of
    the documented full-tile formula, whatever the tile and its offset."""
    fa = importlib.import_module(
        "bert_pytorch_tpu.ops.pallas.flash_attention")
    bh = 37
    want = _documented_keep(seed, bh, q0, k0, bq, bk, rate)
    got = fa._keep_tile(fa._keep_rows(q0, bq),
                        fa._keep_cols(jnp.int32(seed), bh, k0, bk), rate)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        np.asarray(fa._keep_mask(jnp.int32(seed), bh, q0, k0, bq, bk, rate)),
        want)


def _packed_fixture(b=3, s=256, h=2, d=64, seed=0):
    """Packed rows at the default blocks (one tile a head): a row with a
    pad tail, a full row, and a row of nothing but pad (an empty slot of
    the server's batch)."""
    rng = np.random.RandomState(seed)
    q, k, v = (jnp.asarray(rng.randn(b, s, h, d).astype(np.float32)) * 0.5
               for _ in range(3))
    seg = np.zeros((b, s), np.int32)
    seg[0, :100], seg[0, 100:230] = 1, 2            # 26 pad positions
    seg[1, :60], seg[1, 60:] = 1, 2                 # no pad
    seg = jnp.asarray(seg)                          # row 2: all pad
    bias = jnp.where(seg > 0, 0.0, -10000.0)[:, None, None, :] \
        .astype(jnp.float32)
    return q, k, v, bias, seg


def test_flash_forward_bits_are_the_parents():
    """Forward output at D = 64 with pad bias, segments and dropout equals,
    bit for bit, the formula the kernel had before PR 27 took the
    per-row / per-column work off the tile (kept here as the mirror, one
    jitted head at a time): scale multiplied onto the float32 score tile,
    `(qs == ks) & (qs > 0)`, the keep hash from two full-tile iotas with
    `(x >> 9) >= t` (`_documented_keep`). Every moved piece is exact
    (uint32 arithmetic; a power-of-two scale on the dot operand; pad keys
    coded -1), so interpret mode shows no difference. (The backward
    kernels put the dropout rescale on their (blk, D) results, which moves
    float32 rounding: they are held by test_flash_dropout_grads_flow's
    tolerances, not bit for bit.)"""
    from bert_pytorch_tpu.ops.pallas.flash_attention import NEG_INF

    q, k, v, bias, seg = _packed_fixture()
    b, s, h, d = q.shape
    seed, rate = 5, 0.1

    @jax.jit
    def head(qh, kh, vh, bias_row, seg_row, keep):
        sc = jax.lax.dot_general(
            qh, kh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (1.0 / d ** 0.5)
        sc = sc + bias_row[None, :]
        qs = seg_row[:, None]
        sc = jnp.where((qs == seg_row[None, :]) & (qs > 0), sc, NEG_INF)
        m = jnp.max(sc, axis=-1, keepdims=True)
        p = jnp.exp(sc - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = jnp.dot(jnp.where(keep, p, 0.0).astype(vh.dtype), vh,
                      preferred_element_type=jnp.float32)
        out = acc / jnp.maximum(l, 1e-30) / (1.0 - rate)
        return jnp.where(qs > 0, out, 0.0)

    got = np.asarray(flash_attention(q, k, v, bias, seg, jnp.int32(seed),
                                     rate, True))
    for bi in range(b):
        for hh in range(h):
            keep = _documented_keep(seed, bi * h + hh, 0, 0, s, s, rate)
            want = head(q[bi, :, hh], k[bi, :, hh], v[bi, :, hh],
                        bias[bi, 0, 0], seg[bi], keep)
            np.testing.assert_array_equal(got[bi, :, hh], np.asarray(want))
    # the pad bias adds 0 to every allowed pair: without it, the same bits
    no_bias = flash_attention(q, k, v, None, seg, jnp.int32(seed), rate, True)
    np.testing.assert_array_equal(np.asarray(no_bias), got)


def _sub_jaxprs(eqn):
    """The jaxprs an equation carries in its parameters (cond branches, a
    custom_vjp's body, a kernel), one level down."""
    for value in eqn.params.values():
        for item in value if isinstance(value, (tuple, list)) else (value,):
            if isinstance(item, jex_core.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, jex_core.Jaxpr):
                yield item


def _kernel_equations(fn, *args, count):
    """{kernel name: equations `count(eqn)` holds for} over every
    pallas_call under fn, from the traced kernels — cond branches included,
    nothing run."""
    counts = {}

    def walk(jaxpr, name):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                name_in = e.params["name"]
                counts[name_in] = 0
                walk(e.params["jaxpr"], name_in)
                continue
            for sub in _sub_jaxprs(e):
                walk(sub, name)
            if name and count(e):
                counts[name] += 1

    walk(jax.make_jaxpr(fn)(*args).jaxpr, None)
    return counts


def _tile_equations(fn, *args, tile):
    """Vector equations whose result is `tile`-shaped, per kernel (dots
    apart: the MXU's; an equation that only carries sub-jaxprs is counted
    by what is inside)."""
    return _kernel_equations(fn, *args, count=lambda e: (
        e.primitive.name != "dot_general" and not any(_sub_jaxprs(e))
        and any(getattr(v.aval, "shape", None) == tile for v in e.outvars)))


# per kernel: vector equations on ONE (blk_q, blk_k) tile, as the cells
# trace them. Before PR 27: 27 / 34 (packed-512), 14 / 16 / 17 (causal +
# segments), 4 / 7 (plain). `conds`: the `lax.cond`s a program traces
@pytest.mark.parametrize("case,shape,kw,ceilings,conds", [
    # large-pretrain-512-packed: native layout, fused backward, pad bias +
    # segments + dropout, one tile a head and two heads a program; the two
    # conds are `_program`'s test of a row of nothing but pad
    ("packed-512", (1, 512, 2, 2, 64),
     dict(bias=True, segments=True, rate=0.1),
     {"flash_fwd": 17, "flash_bwd_dqkv": 22}, 2),
    # lfm2's kernels at a short S: bh layout, grouped heads, causal +
    # segments, split backward; a program owns the two query heads of a
    # key/value head and walks two k (or q) blocks: TWO tile bodies, each
    # run for both heads under ONE test of its (q block, k block) pair
    ("causal-segments", (1, 1024, 4, 2, 64),
     dict(segments=True, causal=True, split=True),
     {"flash_fwd": 8, "flash_bwd_dq": 10, "flash_bwd_dkv": 11}, 2),
    ("plain", (1, 512, 2, 2, 64), dict(),
     {"flash_fwd": 3, "flash_bwd_dqkv": 6}, 0),
])
def test_flash_tile_equation_ceilings(case, shape, kw, ceilings, conds,
                                      force_flash_path):
    """Nothing that is a function of the row or the column alone is
    computed on the (q, k) tile: per kernel, the count of tile-shaped
    vector equations stays at what PR 27 reached (scale on the (blk, D) dot
    operand at D = 64, hash prefix and pad test on vectors, dropout rescale
    on the (blk, D) results, one compare per mask condition). An edit that
    puts per-row or per-column work back on the tile fails here by the
    kernel's name. And in the bh layout the tile and the skip test of a
    (q block, k block) pair are traced once for every head of a program
    (PR 34): S / blk tile bodies and S / blk conds a program, not
    heads x S / blk."""
    fa = importlib.import_module(
        "bert_pytorch_tpu.ops.pallas.flash_attention")
    b, s, h, hkv, d = shape
    if kw.get("split"):
        force_flash_path("bh", "split")
    blk_q = fa._pick_block(s, fa.DEFAULT_BLK_Q)
    blk_k = fa._pick_block(s, fa.DEFAULT_BLK_K)
    # tile bodies a program traces. Native: its heads x the k blocks it
    # walks (the fused backward walks q blocks too). bh: its heads are a
    # rolled loop inside each (q block, k block) pair, ONE body a pair
    lay = fa._layout(b, s, h, d, h // hkv)
    assert lay.heads_per_prog == 2
    heads = 2 if lay.native else 1
    tiles = {"flash_fwd": heads * (s // blk_k),
             "flash_bwd_dq": s // blk_k, "flash_bwd_dkv": s // blk_q,
             "flash_bwd_dqkv": heads * (s // blk_q) * (s // blk_k)}
    q = jnp.zeros((b, s, h, d), jnp.bfloat16)
    k = jnp.zeros((b, s, hkv, d), jnp.bfloat16)
    bias = jnp.zeros((b, 1, 1, s), jnp.float32) if kw.get("bias") else None
    seg = jnp.ones((b, s), jnp.int32) if kw.get("segments") else None
    rate = kw.get("rate", 0.0)
    seed = jnp.int32(1) if rate else None

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: flash_attention(
            q, k, v, bias, seg, seed, rate, True, kw.get("causal", False))
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    counts = _tile_equations(grads, q, k, k, tile=(blk_q, blk_k))
    assert set(counts) == set(ceilings), counts
    per_tile = {name: n / tiles[name] for name, n in counts.items()}
    over = {name: (n, ceilings[name]) for name, n in per_tile.items()
            if n > ceilings[name]}
    assert not over, f"{case}: (tile equations, ceiling) {over}"
    found = _kernel_equations(grads, q, k, k,
                              count=lambda e: e.primitive.name == "cond")
    assert found == dict.fromkeys(ceilings, conds), f"{case}: conds {found}"


def _documents(b, s, cuts):
    """(B, S) segment ids: row r's documents end at cuts[r]; the rest of
    the row is pad."""
    seg = np.zeros((b, s), np.int32)
    for r, ends in enumerate(cuts):
        for n, (lo, hi) in enumerate(zip((0,) + ends[:-1], ends)):
            seg[r, lo:hi] = n + 1
    return jnp.asarray(seg)


@pytest.mark.parametrize("hp", [1, 2, 4])
@pytest.mark.parametrize("form", ["causal-grouped", "keys192-values128",
                                  "dropout"])
def test_flash_bh_heads_of_a_program(form, hp, monkeypatch,
                                     force_flash_path):
    """The bh-layout kernels (forward, split dq and dkv) at 1, 2 and 4
    heads a program, with 128-wide blocks so that every program walks
    several (q block, k block) pairs under their one skip test: lfm2's form
    (causal, packed, the `hp` query heads of a key/value head), kimi's
    (keys of 192, values of 128) and BERT's long rows (bidirectional,
    packed, dropout). Outputs, dq, dk and dv against the XLA path; the outputs of
    skipped against masked tiles bit for bit where a position is no pad; with
    dropout against the native layout and its fused backward, whose keep
    masks the bh layout has to draw bit for bit (V picks the probabilities
    of one key block out, so a dropped pair is an exact zero)."""
    from bert_pytorch_tpu.ops.attention import dot_product_attention

    fa = importlib.import_module(
        "bert_pytorch_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "DEFAULT_BLK_Q", 128)
    monkeypatch.setattr(fa, "DEFAULT_BLK_K", 128)
    monkeypatch.setattr(fa, "_MAX_HEADS_PER_PROG", hp)
    b, s, d, dv, hkv = {"causal-grouped": (2, 512, 64, 64, 2),
                        "keys192-values128": (1, 256, 192, 128, 4),
                        "dropout": (2, 256, 128, 128, 4)}[form]
    h = hkv * hp if form == "causal-grouped" else hkv
    causal = form != "dropout"
    rate = 0.0 if causal else 0.25
    seed = None if causal else jnp.int32(11)
    seg = _documents(b, s, [(s // 3, s - s // 5 - 9, s - 17),
                            (s // 2 + 5, s)][:b])
    real = np.asarray(seg) > 0
    keys = jax.random.split(jax.random.PRNGKey(hp), 4)
    q = jax.random.normal(keys[0], (b, s, h, d)) * 0.5
    k = jax.random.normal(keys[1], (b, s, hkv, d)) * 0.5
    v = jax.random.normal(keys[2], (b, s, hkv, dv)) * 0.5
    w = jax.random.normal(keys[3], (b, s, h, dv)) * real[:, :, None, None]

    def run(v=v, layout="bh", skip="1"):
        force_flash_path(layout, "split" if layout == "bh" else "fused")
        monkeypatch.setenv("FLASH_SEG_SKIP", skip)
        assert fa._layout(b, s, h, d, h // hkv, dv).heads_per_prog == (
            hp if layout == "bh" else 1)

        def f(q, k, v):
            out = flash_attention(q, k, v, None, seg, seed, rate, True,
                                  causal)
            return jnp.sum(out * w), out
        (_, out), grads = jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return [np.asarray(x) for x in (out,) + grads]

    got, masked = run(), run(skip="0")
    np.testing.assert_array_equal(got[0][real], masked[0][real])
    for a, m in zip(got[1:], masked[1:]):   # float32 sums in another order
        np.testing.assert_allclose(a[real], m[real], atol=1e-6)
    if causal:
        def xla(q, k, v):
            out = dot_product_attention(q, k, v, segment_ids=seg,
                                        impl="xla", causal=True)
            return jnp.sum(out * w), out
        (_, out), grads = jax.value_and_grad(
            xla, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        wants = (out,) + grads
    else:
        wants = run(layout="native")
        # the keep masks: probabilities of the pairs with one key block
        for block in range(s // dv):
            pick = jnp.zeros((s, dv)).at[block * dv:(block + 1) * dv].set(
                jnp.eye(dv))
            pick = jnp.broadcast_to(pick[None, :, None], v.shape)
            probs, want = run(pick)[0], run(pick, "native")[0]
            assert 0.15 < (probs[real] == 0).mean() < 0.95
            np.testing.assert_array_equal(probs == 0, want == 0)
    np.testing.assert_allclose(got[0][real], np.asarray(wants[0])[real],
                               atol=2e-5)
    for a, want in zip(got[1:], wants[1:]):
        np.testing.assert_allclose(a, np.asarray(want), atol=5e-5)


@pytest.mark.parametrize("layout,bwd,skip", [
    ("native", "fused", "1"), ("native", "fused", "0"),
    ("bh", "split", "1"), ("bh", "split", "0"), ("bh", "fused", "1"),
])
def test_flash_one_tile_all_pad_row(layout, bwd, skip, monkeypatch,
                                    force_flash_path):
    """Segments where the one tile is the whole row (the default blocks at
    the cells' and the server's S) and a row of the batch is nothing but
    pad: the row's program is skipped by ONE test around it
    (`_skip_pad_rows`; none under FLASH_SEG_SKIP=0, and none around a
    head's tile either way), its outputs and gradients are zero, and every
    other row's output and finite gradients are the dense block-diagonal
    reference's."""
    force_flash_path(layout, bwd)
    monkeypatch.setenv("FLASH_SEG_SKIP", skip)
    q, k, v, _, seg = _packed_fixture()
    real = np.asarray(seg) > 0
    assert real[:2].any(axis=1).all() and not real[2].any()
    allowed = (seg[:, :, None] == seg[:, None, :]) & (seg[:, :, None] > 0)
    dense_bias = jnp.where(allowed, 0.0, -1e30)[:, None]     # (B, 1, S, S)
    weight = jnp.asarray(real, jnp.float32)[:, :, None, None]

    def loss(attend):       # no loss term reads a pad position
        return lambda q, k, v: jnp.sum((attend(q, k, v) * weight) ** 2)

    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, None, seg, None, 0.0, True)
    dense = lambda q, k, v: _ref_attention(q, k, v, dense_bias)  # noqa: E731

    conds = _kernel_equations(
        jax.grad(loss(flash), argnums=(0, 1, 2)), q, k, v,
        count=lambda e: e.primitive.name == "cond")
    assert set(conds.values()) == ({2} if skip == "1" else {0}), conds

    got = np.asarray(flash(q, k, v))
    assert (got[~real] == 0).all()
    np.testing.assert_allclose(got[real], np.asarray(dense(q, k, v))[real],
                               rtol=2e-5, atol=2e-5)
    grads = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for g, want in zip(grads, wants):
        g = np.asarray(g)
        assert np.isfinite(g).all() and (g[2] == 0).all()
        assert np.abs(g[:2]).sum() > 0
        np.testing.assert_allclose(g[real], np.asarray(want)[real],
                                   rtol=5e-4, atol=5e-5)


def test_flash_native_layout_matches_bh_layout(force_flash_path):
    """The native (B, S, H*D) addressing (default where heads tile into
    128-lane blocks) and the transposing (BH, S, D) grid are the SAME
    computation: outputs match to float tolerance and the dropout
    keep-masks are bit-identical (both fold batch*H + head into the hash
    counter)."""
    from bert_pytorch_tpu.ops.pallas.flash_attention import _use_native

    q, k, v, bias = _qkv(s=256)
    seed = jnp.array(11, jnp.int32)
    assert _use_native(256, 4, 64)

    def run(layout):
        force_flash_path(layout)
        out = flash_attention(q, k, v, bias=bias, interpret=True)
        drop = flash_attention(q, k, v, bias=bias, dropout_seed=seed,
                               dropout_rate=0.3, interpret=True)
        g = jax.grad(lambda a, b, c: jnp.sum(flash_attention(
            a, b, c, bias=bias, dropout_seed=seed, dropout_rate=0.3,
            interpret=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        return out, drop, g

    out_n, drop_n, g_n = run("native")
    out_b, drop_b, g_b = run("bh")
    np.testing.assert_allclose(np.asarray(out_n), np.asarray(out_b),
                               rtol=1e-6, atol=1e-6)
    # identical masks -> identical zero patterns, values to float tolerance
    np.testing.assert_array_equal(np.asarray(drop_n) == 0,
                                  np.asarray(drop_b) == 0)
    np.testing.assert_allclose(np.asarray(drop_n), np.asarray(drop_b),
                               rtol=1e-6, atol=1e-6)
    for a, b in zip(g_n, g_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)


def test_flash_layout_chosen_by_shape_alone(monkeypatch):
    """One layout per shape, whatever the environment says: native where
    heads tile into 128-lane blocks and the fused backward fits them, the
    transposing grid elsewhere."""
    from bert_pytorch_tpu.ops.pallas.flash_attention import _use_native

    monkeypatch.setenv("FLASH_LAYOUT", "bh")   # the removed switches
    monkeypatch.setenv("FLASH_BWD", "split")
    assert _use_native(512, 16, 64)        # BERT-Large phase 2
    assert _use_native(128, 12, 64)        # BERT-Base phase 1
    assert _use_native(1024, 8, 128)
    assert not _use_native(2048, 16, 64)   # fused bwd: one head / program
    assert not _use_native(4096, 16, 64)   # long context: split kernels
    assert not _use_native(512, 3, 64)     # odd head count at D=64
    assert not _use_native(512, 4, 48)     # D does not tile 128 lanes


@pytest.mark.parametrize("shape,heads,rows", [
    # lfm2-ep8-clm-8k-packed: the four query heads of a key/value head
    (dict(b=4, s=8192, h=32, d=64, group=4), 4, 32),
    # kimi-linear-ep32-clm-16k-packed: keys 192, values 128; four heads'
    # K and V panels are 48 of the kernels' 64 MiB of VMEM
    (dict(b=1, s=16384, h=32, d=192, dv=128), 4, 8),
    # BERT-Large beyond the native layout: the fused backward's bound
    # (the forward alone takes the heads), and the split kernels
    (dict(b=4, s=2048, h=16, d=64), 4, 16),
    (dict(b=2, s=4096, h=16, d=64), 4, 8),
    (dict(b=1, s=32768, h=16, d=64), 2, 8),   # 16 MiB of panels a head
    (dict(b=8, s=512, h=3, d=64), 3, 8),      # heads that tile no lanes
])
def test_flash_bh_heads_a_program_by_shape(shape, heads, rows):
    """Heads of a bh-layout program are a function of shapes alone
    (`_bh_heads_per_prog`): pinned for the two decoder cells and for
    BERT's long rows."""
    from bert_pytorch_tpu.ops.pallas.flash_attention import _layout

    lay = _layout(**shape)
    assert not lay.native
    assert (lay.heads_per_prog, lay.rows) == (heads, rows)


@pytest.mark.parametrize("seq,interpret,match", [
    (128, "0", "needs a TPU backend"),        # off-TPU, no interpret mode
    (96, "1", "multiple of 128"),             # kernel cannot tile the shape
])
def test_explicit_pallas_raises_instead_of_silent_xla(seq, interpret, match,
                                                      monkeypatch):
    """impl="pallas" asked for by name gets the kernel or an error — never
    the XLA result under the kernel's name. "auto" may still choose XLA."""
    from bert_pytorch_tpu.ops import attention

    monkeypatch.setenv("BPT_PALLAS_INTERPRET", interpret)
    q, k, v, _ = _qkv(s=seq)
    with pytest.raises(ValueError, match=match):
        attention.dot_product_attention(q, k, v, impl="pallas")
    auto = attention.dot_product_attention(q, k, v, impl="auto")
    xla = attention.dot_product_attention(q, k, v, impl="xla")
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(xla))


def test_explicit_pallas_runs_the_kernel_in_interpret_mode(monkeypatch):
    from bert_pytorch_tpu.ops import attention

    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    q, k, v, bias = _qkv(s=128)
    got = attention.dot_product_attention(q, k, v, bias=bias, impl="pallas")
    want = flash_attention(q, k, v, bias=bias, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


