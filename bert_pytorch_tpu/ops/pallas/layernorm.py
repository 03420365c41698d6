"""Fused LayerNorm Pallas kernel with hand-written VJP.

Replaces apex's FusedLayerNormAffineFunction CUDA kernel (reference
src/modeling.py:303,320-323; eps 1e-12). One pass over rows computes
mean/rstd/normalized output; the backward kernel fuses dx with the dscale /
dbias cross-row reductions, accumulating partials across sequential grid
steps (TPU grid iteration is sequential, so '+=' into a fixed output block
is a legal reduction).

Layout: input flattened to (R, E) rows; blocks of ROWS rows; E (the hidden
size) must be a multiple of 128 (lane width) — ops/layernorm.py gates the
dispatch and falls back to the XLA path otherwise. All refs are 2D: scale /
bias ride as (1, E), row statistics as (ROWS, 1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 256  # rows per grid step


def _fwd_kernel(x_ref, scale_ref, bias_ref, y_ref, mean_ref, rstd_ref, *,
                eps: float):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    centered = x - mean
    var = jnp.mean(centered * centered, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = centered * rstd
    y_ref[:] = (y * scale_ref[:].astype(jnp.float32)
                + bias_ref[:].astype(jnp.float32)).astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _bwd_kernel(x_ref, scale_ref, mean_ref, rstd_ref, g_ref,
                dx_ref, dscale_ref, dbias_ref):
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    scale = scale_ref[:].astype(jnp.float32)
    mean = mean_ref[:]
    rstd = rstd_ref[:]

    xhat = (x - mean) * rstd
    gs = g * scale
    # dx = rstd * (gs - mean(gs) - xhat * mean(gs * xhat))
    E = x.shape[-1]
    m1 = jnp.sum(gs, axis=-1, keepdims=True) / E
    m2 = jnp.sum(gs * xhat, axis=-1, keepdims=True) / E
    dx_ref[:] = (rstd * (gs - m1 - xhat * m2)).astype(dx_ref.dtype)

    part_dscale = jnp.sum(g * xhat, axis=0, keepdims=True)
    part_dbias = jnp.sum(g, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _():
        dscale_ref[:] = part_dscale
        dbias_ref[:] = part_dbias

    @pl.when(i > 0)
    def _():
        dscale_ref[:] = dscale_ref[:] + part_dscale
        dbias_ref[:] = dbias_ref[:] + part_dbias


def _pad_rows(x2, rows):
    R = x2.shape[0]
    pad = (-R) % rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, R


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def layer_norm_pallas(x, scale, bias, eps: float = 1e-12,
                      interpret: bool = False):
    y, _, _ = _forward(x, scale, bias, eps, interpret)
    return y


def _forward(x, scale, bias, eps, interpret):
    orig_shape = x.shape
    E = orig_shape[-1]
    x2, R = _pad_rows(x.reshape(-1, E), ROWS)
    Rp = x2.shape[0]
    grid = (Rp // ROWS,)
    y, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS, E), lambda i: (i, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ROWS, E), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, E), x.dtype),
            jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
        ],
        name="layernorm_fwd",
        interpret=interpret,
    )(x2, scale.reshape(1, E), bias.reshape(1, E))
    return y[:R].reshape(orig_shape), mean, rstd


def _fwd_rule(x, scale, bias, eps, interpret):
    y, mean, rstd = _forward(x, scale, bias, eps, interpret)
    return y, (x, scale, mean, rstd)


def _bwd_rule(eps, interpret, res, g):
    x, scale, mean, rstd = res
    orig_shape = x.shape
    E = orig_shape[-1]
    x2, R = _pad_rows(x.reshape(-1, E), ROWS)
    g2, _ = _pad_rows(g.reshape(-1, E), ROWS)
    Rp = x2.shape[0]
    grid = (Rp // ROWS,)
    dx, dscale, dbias = pl.pallas_call(
        _bwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS, E), lambda i: (i, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, E), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ROWS, E), lambda i: (i, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),  # fixed block: reduction
            pl.BlockSpec((1, E), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, E), x.dtype),
            jax.ShapeDtypeStruct((1, E), jnp.float32),
            jax.ShapeDtypeStruct((1, E), jnp.float32),
        ],
        name="layernorm_bwd",
        interpret=interpret,
    )(x2, scale.reshape(1, E), mean, rstd, g2)
    return (dx[:R].reshape(orig_shape),
            dscale.reshape(E).astype(scale.dtype),
            dbias.reshape(E).astype(scale.dtype))


layer_norm_pallas.defvjp(_fwd_rule, _bwd_rule)


# ---------------------------------------------------------------------------
# fused residual + dropout + LayerNorm
# ---------------------------------------------------------------------------
#
# y = LN(residual + dropout(x)) is the tail of BOTH residual sites in every
# BertLayer (dense -> dropout -> LN(residual + .), reference
# src/modeling.py:439-487). Keeping dropout in the XLA graph next to a
# Pallas LN custom call forces the mask bits and the dropped tensor through
# HBM (XLA cannot fuse elementwise producers into a custom call), and even
# with the XLA LN the saved-for-backward mask traffic bloats every
# surrounding matmul fusion (the cost is not measured on this runtime).
#
# This kernel evaluates the keep-mask from a counter-based hash of the
# (global row, column, seed) — the same construction flash_attention.py uses
# for attention dropout — so the mask NEVER exists in HBM: the forward
# applies it inline, the backward regenerates it from the same counters.
# Residuals saved for backward are (x, residual, mean, rstd): no dropped
# tensor, no LN input h, no mask.


# The keep-mask hash is shared with the XLA fallback — ONE implementation
# (ops/layernorm.row_col_keep) so the two paths cannot drift. Pure jnp, so
# it traces inside the Pallas kernel unchanged.
from bert_pytorch_tpu.ops.layernorm import row_col_keep as _row_col_keep


def _adln_fwd_kernel(seed_ref, x_ref, res_ref, scale_ref, bias_ref,
                     y_ref, mean_ref, rstd_ref, *, eps: float, rate: float):
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    if rate > 0.0:
        keep = _row_col_keep(seed_ref[0], i * x.shape[0], x.shape[0],
                             x.shape[1], rate)
        x = jnp.where(keep, x / (1.0 - rate), 0.0)
    h = res_ref[:].astype(jnp.float32) + x
    mean = jnp.mean(h, axis=-1, keepdims=True)
    centered = h - mean
    var = jnp.mean(centered * centered, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = centered * rstd
    y_ref[:] = (y * scale_ref[:].astype(jnp.float32)
                + bias_ref[:].astype(jnp.float32)).astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _adln_bwd_kernel(seed_ref, x_ref, res_ref, scale_ref, mean_ref, rstd_ref,
                     g_ref, dx_ref, dres_ref, dscale_ref, dbias_ref, *,
                     rate: float):
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    scale = scale_ref[:].astype(jnp.float32)
    mean = mean_ref[:]
    rstd = rstd_ref[:]

    if rate > 0.0:
        keep = _row_col_keep(seed_ref[0], i * x.shape[0], x.shape[0],
                             x.shape[1], rate)
        xd = jnp.where(keep, x / (1.0 - rate), 0.0)
    else:
        xd = x
    h = res_ref[:].astype(jnp.float32) + xd
    xhat = (h - mean) * rstd
    gs = g * scale
    E = x.shape[-1]
    m1 = jnp.sum(gs, axis=-1, keepdims=True) / E
    m2 = jnp.sum(gs * xhat, axis=-1, keepdims=True) / E
    dh = rstd * (gs - m1 - xhat * m2)
    dres_ref[:] = dh.astype(dres_ref.dtype)
    if rate > 0.0:
        dx_ref[:] = jnp.where(keep, dh / (1.0 - rate), 0.0).astype(
            dx_ref.dtype)
    else:
        dx_ref[:] = dh.astype(dx_ref.dtype)

    part_dscale = jnp.sum(g * xhat, axis=0, keepdims=True)
    part_dbias = jnp.sum(g, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _():
        dscale_ref[:] = part_dscale
        dbias_ref[:] = part_dbias

    @pl.when(i > 0)
    def _():
        dscale_ref[:] = dscale_ref[:] + part_dscale
        dbias_ref[:] = dbias_ref[:] + part_dbias


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def add_dropout_layer_norm_pallas(x, residual, scale, bias, seed,
                                  rate: float, eps: float = 1e-12,
                                  interpret: bool = False):
    """y = LayerNorm(residual + dropout(x, rate)); mask from the in-kernel
    counter hash keyed on (flat row, column, seed). seed: traced int32
    scalar (fresh per step); non-differentiable."""
    y, _, _ = _adln_forward(x, residual, scale, bias, seed, rate, eps,
                            interpret)
    return y


def _adln_forward(x, residual, scale, bias, seed, rate, eps, interpret):
    orig_shape = x.shape
    E = orig_shape[-1]
    x2, R = _pad_rows(x.reshape(-1, E), ROWS)
    r2, _ = _pad_rows(residual.reshape(-1, E), ROWS)
    Rp = x2.shape[0]
    grid = (Rp // ROWS,)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1)
    y, mean, rstd = pl.pallas_call(
        functools.partial(_adln_fwd_kernel, eps=eps, rate=rate),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),          # seed
            pl.BlockSpec((ROWS, E), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, E), lambda i: (i, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ROWS, E), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, E), x.dtype),
            jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
        ],
        name="add_dropout_layernorm_fwd",
        interpret=interpret,
    )(seed_arr, x2, r2, scale.reshape(1, E), bias.reshape(1, E))
    return y[:R].reshape(orig_shape), mean, rstd


def _adln_fwd_rule(x, residual, scale, bias, seed, rate, eps, interpret):
    y, mean, rstd = _adln_forward(x, residual, scale, bias, seed, rate, eps,
                                  interpret)
    return y, (x, residual, scale, mean, rstd, seed)


def _adln_bwd_rule(rate, eps, interpret, res, g):
    x, residual, scale, mean, rstd, seed = res
    orig_shape = x.shape
    E = orig_shape[-1]
    x2, R = _pad_rows(x.reshape(-1, E), ROWS)
    r2, _ = _pad_rows(residual.reshape(-1, E), ROWS)
    g2, _ = _pad_rows(g.reshape(-1, E), ROWS)
    Rp = x2.shape[0]
    grid = (Rp // ROWS,)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1)
    dx, dres, dscale, dbias = pl.pallas_call(
        functools.partial(_adln_bwd_kernel, rate=rate),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),          # seed
            pl.BlockSpec((ROWS, E), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, E), lambda i: (i, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, E), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ROWS, E), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, E), lambda i: (i, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),  # fixed block: reduction
            pl.BlockSpec((1, E), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, E), x.dtype),
            jax.ShapeDtypeStruct((Rp, E), x.dtype),
            jax.ShapeDtypeStruct((1, E), jnp.float32),
            jax.ShapeDtypeStruct((1, E), jnp.float32),
        ],
        name="add_dropout_layernorm_bwd",
        interpret=interpret,
    )(seed_arr, x2, r2, scale.reshape(1, E), mean, rstd, g2)
    return (dx[:R].reshape(orig_shape), dres[:R].reshape(orig_shape),
            dscale.reshape(E).astype(scale.dtype),
            dbias.reshape(E).astype(scale.dtype),
            # integer seed primal -> float0 cotangent (JAX convention; an
            # int32 zeros trips stricter custom_vjp aval checking)
            jax.custom_derivatives.zero_from_primal(
                jnp.asarray(seed, jnp.int32)))


add_dropout_layer_norm_pallas.defvjp(_adln_fwd_rule, _adln_bwd_rule)
