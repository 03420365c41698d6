"""Elementwise pieces of the decoder families (models/lfm2_moe.py): RMSNorm,
rotary positions (over the whole head or a part of it, at a given table of
frequencies), and the depthwise causal short convolution. Plain
jax.numpy in float32 (XLA fuses each into its neighbours); every one is
per-token or looks back a fixed number of tokens, and none looks across a
document boundary of a packed row.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


@jax.named_scope("rmsnorm")
def rms_norm(x: jax.Array, scale: jax.Array, eps: float,
             dtype=None) -> jax.Array:
    """x * rsqrt(mean(x^2) + eps) * scale over the last axis, in float32;
    returned in `dtype` (default: x's)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                            + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype or x.dtype)


def rotary(x: jax.Array, position_ids: jax.Array, theta: float = None,
           inv_freq=None, rotated: int = None,
           factor: float = 1.0) -> jax.Array:
    """Rotary position embedding in the rotate-half convention: x
    (B, S, H, D), position_ids (B, S) (restarting at each document of a
    packed row). The head's first `rotated` dims (default: all of D) turn,
    pair (i, i + rotated/2) by position * inv_freq[i]; the dims after them
    pass. `inv_freq` (rotated/2,) is the given table (`rotary_table`), or
    theta^(-2i/rotated) where none is given; `factor` multiplies cos and
    sin (YaRN's attention factor). float32 in, float32 out."""
    d = x.shape[-1]
    r = d if rotated is None else int(rotated)
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32)
                                    / r))
    else:
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
    angles = position_ids.astype(jnp.float32)[:, :, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x = x.astype(jnp.float32)
    if r == d:      # the whole head: traced as it always was, no slice
        half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
        return x * cos + half * sin
    u = x[..., :r]
    half = jnp.concatenate([-u[..., r // 2:], u[..., :r // 2]], axis=-1)
    return jnp.concatenate([u * cos + half * sin, x[..., r:]], axis=-1)


def rotary_table(head_dim: int, params) -> tuple:
    """(inv_freq (R/2,) float32 numpy, R, factor) of one kind of layer from
    its rotary parameters (a config's `rope_parameters` sub-group): R =
    head_dim * partial_rotary_factor dims turn, at e_i = theta^(-2i/R) for
    `rope_type` "default" (factor 1), and under "yarn" at

        f_i = (e_i / s) r_i + e_i (1 - r_i),  r_i = clip((i - lo) / (hi - lo), 0, 1)
        lo = max(floor(dim(beta_fast)), 0),  hi = min(ceil(dim(beta_slow)), R - 1)
        dim(n) = R ln(L / (2 pi n)) / (2 ln theta)

    with s = `factor` and L = `original_max_position_embeddings`: pairs that
    turn more than beta_fast times in L positions keep their frequency,
    pairs that turn less than beta_slow times are slowed s-fold, a ramp
    between; cos and sin times `attention_factor` (0.1 ln s + 1 where the
    group has none)."""
    r = int(head_dim * float(params.get("partial_rotary_factor", 1.0)))
    theta = float(params["rope_theta"])
    freq = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    kind = params.get("rope_type", "default")
    if kind == "default":
        return freq.astype(np.float32), r, 1.0
    if kind != "yarn":
        raise ValueError(f"rotary_table: unknown rope_type {kind!r}")
    s, length = float(params["factor"]), float(
        params["original_max_position_embeddings"])

    def dim(turns):
        return r * math.log(length / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    lo = max(math.floor(dim(float(params.get("beta_fast", 32)))), 0)
    hi = min(math.ceil(dim(float(params.get("beta_slow", 1)))), r - 1)
    ramp = np.clip((np.arange(r // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    factor = params.get("attention_factor")
    if factor is None:
        factor = 0.1 * math.log(s) + 1.0
    return ((freq / s * ramp + freq * (1.0 - ramp)).astype(np.float32), r,
            float(factor))


def short_conv(u: jax.Array, weight: jax.Array,
               position_ids: jax.Array) -> jax.Array:
    """Depthwise causal convolution over the sequence: u (B, S, C), weight
    (C, L); c_t = sum_j weight[:, j] * u_{t-(L-1)+j}, the last tap on the
    token itself. A tap that would fall before the first token of the
    token's document (position_ids counts from 0 inside each document of a
    packed row) is zero, as is one before the row. float32."""
    u = u.astype(jnp.float32)
    w = weight.astype(jnp.float32)
    taps = w.shape[1]
    out = u * w[:, taps - 1]
    for back in range(1, taps):
        shifted = jnp.pad(u[:, :-back], ((0, 0), (back, 0), (0, 0)))
        inside = (position_ids >= back)[:, :, None]
        out = out + jnp.where(inside, shifted, 0.0) * w[:, taps - 1 - back]
    return out
