"""The rotation of q and k (ops/decoder_ops.rotary has the mathematics and
its plain-XLA form, which is what these are tested against) as ONE pass a
direction over heads of 128 lanes.

In plain XLA the rotate-half is `slice -> neg -> concatenate` on a float32
copy of x, and its transpose pads and slices that copy again: several
float32 (B, S, H, D) arrays in HBM between fusions, three times a step
(PERF.md section 6, PR 45, has the times). Here a tile of rows is read
once in the dtype it arrives in, turned in float32 in VMEM and written once
in the dtype the caller wants, by head. The half-turn is two lane rolls against
tables that carry the sign and the mask: with R the rotated width,

    y = x * C + roll(x, D - R/2) * SA + roll(x, R/2) * SB
    C  = factor * cos on lanes [0, R), 1 on lanes [R, D)
    SA = -factor * sin on lanes [0, R/2), 0 elsewhere
    SB = +factor * sin on lanes [R/2, R), 0 elsewhere

(roll as jnp.roll: roll(x, s)[i] = x[(i - s) mod D]), so the whole head, a
part of it and YaRN's factor are the same code at other tables. The
rotation is linear in x and its transpose is the rotation by the opposite
angle: the backward pass is the same body at `transposed` tables
(`dx = dy * C + roll(dy * SA, R/2) + roll(dy * SB, -R/2)`, the products
taken before or after the roll being the same products), keeps nothing of
x, and is named `rotary_bwd` so that a trace and `program_kernels` tell the
directions apart.

The tables are (B, S, D) float32, made by plain XLA from `position_ids`
(ops/decoder_ops.rotary): positions restart at each document of a
packed row and nothing of that is the kernel's business.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
# a block's bytes at most (x in and y out, each double-buffered, stay well
# inside the 16 MiB a v5e program gets unasked)
_BLOCK_BYTES = 1024 * 1024
_ROW_TILES = (512, 256, 128, 64, 32, 16)


def row_tile(s: int) -> int:
    """Rows a block: the largest of `_ROW_TILES` that divides S, or 0. 16 is
    a bfloat16 tile's sublanes."""
    return next((t for t in _ROW_TILES if s % t == 0), 0)


def supported(s: int, d: int) -> bool:
    """Shapes the kernels take: heads of whole 128-lane rows and rows that
    tile."""
    return d % LANES == 0 and row_tile(s) > 0


def _heads_per_block(first: int, heads: int, d: int, tile: int,
                     itemsize: int) -> int:
    """Heads a block: the most whose (tile, heads * D) block stays within
    `_BLOCK_BYTES`, a divisor of `heads` and of `first`, so that the first
    head's columns start a block."""
    most = max(_BLOCK_BYTES // (tile * d * itemsize), 1)
    return max(n for n in range(1, heads + 1)
               if heads % n == 0 and first % n == 0 and n <= most)


def _rotate_kernel(x_ref, c_ref, *refs, heads: int, d: int, shifts: tuple,
                   by_head: bool):
    """`refs`: a sine table for each of `shifts`, then the output. `by_head`:
    the backward pass, x_ref (1, heads, tile, D) and o_ref (1, tile,
    heads * D); the forward pass the other way round."""
    from jax.experimental.pallas import tpu as pltpu

    *sine_refs, o_ref = refs
    c, sines = c_ref[0], [ref[0] for ref in sine_refs]
    for h in range(heads):
        columns = (0, slice(None), slice(h * d, (h + 1) * d))
        x = x_ref[(0, h) if by_head else columns].astype(jnp.float32)
        y = x * c
        for shift, sine in zip(shifts, sines):
            y = y + pltpu.roll(x, shift, 1) * sine
        o_ref[columns if by_head else (0, h)] = y.astype(o_ref.dtype)


def _one_roll(sines: tuple, shifts: tuple):
    """The whole head turns (R = D): both rolls are the half-turn, and a
    lane has one of the two sines and a zero of the other, so their sum at
    ONE roll is the same products and the same sum."""
    if shifts[0] == shifts[1]:
        return (sines[0] + sines[1],), shifts[:1]
    return sines, shifts


def _rotate(name: str, x, tables, shifts: tuple, first: int, heads: int,
            out_dtype, by_head: bool, interpret: bool):
    """One pass: the forward (`by_head` False) reads heads `first` ..
    `first + heads` of the (B, S, W) matrix x, D columns each, and writes
    (B, heads, S, D); the backward reads (B, heads, S, D) and writes the
    heads' (B, S, heads * D) columns. `tables`: C, SA, SB (B, S, D)
    float32; `shifts`: the rolls of SA's and SB's term."""
    from jax.experimental.pallas import tpu as pltpu

    c, *sines = tables
    b, s, d = c.shape
    sines, shifts = _one_roll(tuple(sines), shifts)
    tile = row_tile(s)
    hb = _heads_per_block(first, heads, d, tile, max(
        x.dtype.itemsize, jnp.dtype(out_dtype).itemsize))
    columns = pl.BlockSpec((1, tile, hb * d),
                           lambda i, j, g: (i, j, first // hb + g))
    by_heads = pl.BlockSpec((1, hb, tile, d), lambda i, j, g: (i, g, j, 0))
    table = pl.BlockSpec((1, tile, d), lambda i, j, g: (i, j, 0))
    return pl.pallas_call(
        functools.partial(_rotate_kernel, heads=hb, d=d, shifts=shifts,
                          by_head=by_head),
        # the heads innermost: a tile's tables are fetched once
        grid=(b, s // tile, heads // hb),
        in_specs=([by_heads if by_head else columns]
                  + [table] * (1 + len(sines))),
        out_specs=columns if by_head else by_heads,
        out_shape=jax.ShapeDtypeStruct(
            (b, s, heads * d) if by_head else (b, heads, s, d), out_dtype),
        name=name, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x, c, *sines)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def rotate(x, c, sa, sb, first: int, heads: int, rotated: int, out_dtype,
           interpret: bool):
    """Heads `first` .. `first + heads` of the (B, S, W) matrix x (D columns
    a head: a fused projection's output, read where it lies), turned at the
    tables C, SA, SB (B, S, D) float32 of a rotated width `rotated`:
    (B, S, heads, D) in `out_dtype`, laid out by head, (B, heads, S, D),
    which is how the flash kernels of grouped heads read q and k
    (ops/pallas/flash_attention._to_bh: the transposition there and this
    one cancel). The tables take no gradient."""
    d = c.shape[-1]
    shifts = (d - rotated // 2, rotated // 2)
    return _rotate("rotary_fwd", x, (c, sa, sb), shifts, first, heads,
                   out_dtype, False, interpret).transpose(0, 2, 1, 3)


def _rotate_fwd(x, c, sa, sb, first, heads, rotated, out_dtype, interpret):
    # the cotangent is x's in shape and dtype: a zero-size array carries both
    return (rotate(x, c, sa, sb, first, heads, rotated, out_dtype, interpret),
            (c, sa, sb, jnp.zeros((0,) + x.shape, x.dtype)))


def _rotate_bwd(first, heads, rotated, out_dtype, interpret, res, dy):
    c, sa, sb, like = res
    d = c.shape[-1]
    half = rotated // 2
    # roll(dy * SA, R/2) = roll(dy, R/2) * roll(SA, R/2), and SB's likewise:
    # the forward's body, the sine tables changing places
    tables = (c, jnp.roll(sb, -half, axis=-1), jnp.roll(sa, half, axis=-1))
    dx = _rotate("rotary_bwd", dy.transpose(0, 2, 1, 3), tables,
                 (d - half, half), 0, heads, like.dtype, True, interpret)
    # the other columns of x fed nothing here
    after = like.shape[-1] - (first + heads) * d
    dx = jnp.pad(dx, ((0, 0), (0, 0), (first * d, after)))
    return dx, None, None, None


rotate.defvjp(_rotate_fwd, _rotate_bwd)
