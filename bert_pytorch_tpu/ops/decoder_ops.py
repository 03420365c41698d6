"""Elementwise pieces of the decoder families (models/lfm2_moe.py): RMSNorm,
rotary positions, and the depthwise causal short convolution. Plain
jax.numpy in float32 (XLA fuses each into its neighbours); every one is
per-token or looks back a fixed number of tokens, and none looks across a
document boundary of a packed row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("rmsnorm")
def rms_norm(x: jax.Array, scale: jax.Array, eps: float,
             dtype=None) -> jax.Array:
    """x * rsqrt(mean(x^2) + eps) * scale over the last axis, in float32;
    returned in `dtype` (default: x's)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                            + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype or x.dtype)


def rotary(x: jax.Array, position_ids: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding over the whole head dimension in the
    rotate-half convention: x (B, S, H, D), position_ids (B, S) (restarting
    at each document of a packed row). Pair (i, i + D/2) turns by
    position * theta^(-2i/D). float32 in, float32 out."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = position_ids.astype(jnp.float32)[:, :, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, :, None, :]
    x = x.astype(jnp.float32)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + half * sin


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
