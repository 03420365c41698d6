"""Elementwise pieces of the decoder families (models/decoder.py and the
family modules beside it): RMSNorm, rotary positions (over the whole head
or a part of it, at a given table of frequencies), and the depthwise
causal short convolution. Plain
jax.numpy in float32 (XLA fuses each into its neighbours), but for the
rotation of heads of 128 lanes on a TPU, which is one kernel call a
direction (ops/pallas/rotary.py; `rotary` says which calls take it); every
one is per-token or looks back a fixed number of tokens, and none looks
across a document boundary of a packed row.
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


def _rotary_angles(position_ids, r: int, theta, inv_freq):
    """position * inv_freq, (B, S, r/2) float32."""
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32)
                                    / r))
    else:
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
    return position_ids.astype(jnp.float32)[:, :, None] * inv_freq


def _rotary_kernel_mode(s: int, d: int):
    """None where the call takes the plain function; else the `interpret`
    argument of ops/pallas/rotary.py's kernels (the convention of
    ops/kda.kernel_mode), by what the call can see: heads of whole 128-lane
    rows, rows that tile, a TPU backend (or BPT_PALLAS_INTERPRET=1
    elsewhere), and one device: a mesh cannot split a kernel."""
    from bert_pytorch_tpu.ops.attention import _pallas_interpret, active_mesh
    from bert_pytorch_tpu.ops.pallas.rotary import supported

    on_tpu = jax.default_backend() == "tpu"
    if (not supported(s, d) or not (on_tpu or _pallas_interpret())
            or active_mesh() is not None):
        return None
    return not on_tpu


def rotary(x: jax.Array, position_ids: jax.Array, theta: float = None,
           inv_freq=None, rotated: int = None, factor: float = 1.0,
           out_dtype=None, heads: tuple = None) -> jax.Array:
    """Rotary position embedding in the rotate-half convention: x
    (B, S, H, D), position_ids (B, S) (restarting at each document of a
    packed row). The head's first `rotated` dims (default: all of D) turn,
    pair (i, i + rotated/2) by position * inv_freq[i]; the dims after them
    pass. `inv_freq` (rotated/2,) is the given table (`rotary_table`), or
    theta^(-2i/rotated) where none is given; `factor` multiplies cos and
    sin (YaRN's attention factor). float32 arithmetic, the result in
    `out_dtype` (float32 where none is given).

    `heads` (first, H, D): x is a (B, S, W) matrix, a fused projection's
    output, whose columns from first * D on are the H heads to turn; the
    result is theirs, (B, S, H, D).

    Heads of whole 128-lane rows on a TPU take one kernel call a direction
    (ops/pallas/rotary.py: x read once where it lies and as it arrives, the
    result written once in `out_dtype`, the same products and sums in
    VMEM); every other call is the plain function below, which is also what
    the kernels are tested against."""
    first, h, d = heads or (0,) + x.shape[2:]
    r = d if rotated is None else int(rotated)
    interpret = _rotary_kernel_mode(x.shape[1], d)
    if interpret is not None:
        from bert_pytorch_tpu.ops.pallas.rotary import rotate

        angles = _rotary_angles(position_ids, r, theta, inv_freq)
        cos, sin = jnp.cos(angles), jnp.sin(angles)
        if factor != 1.0:
            cos, sin = cos * factor, sin * factor
        # the kernels' tables, (B, S, D): cos on the lanes that turn and 1
        # on those that pass; -sin on the first half of the turning lanes,
        # +sin on the second, 0 elsewhere
        lanes = [(0, 0), (0, 0)]
        c = jnp.pad(jnp.concatenate([cos, cos], -1), lanes + [(0, d - r)],
                    constant_values=1.0)
        sa = jnp.pad(-sin, lanes + [(0, d - r // 2)])
        sb = jnp.pad(sin, lanes + [(r // 2, d - r)])
        return rotate(x.reshape(x.shape[:2] + (-1,)), c, sa, sb, first, h, r,
                      jnp.dtype(out_dtype or jnp.float32), interpret)
    if heads is not None:
        x = x[..., first * d:(first + h) * d].reshape(x.shape[:2] + (h, d))
    out = _rotary_plain(x, position_ids, theta, inv_freq, r, factor)
    return out if out_dtype is None else out.astype(out_dtype)


def _rotary_plain(x, position_ids, theta, inv_freq, r: int, factor: float):
    """`rotary` in plain jax.numpy: float32 in, float32 out."""
    d = x.shape[-1]
    angles = _rotary_angles(position_ids, r, theta, inv_freq)
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
