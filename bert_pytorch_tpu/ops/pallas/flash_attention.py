"""Blockwise fused attention (flash attention) for TPU, fwd + bwd.

The TPU answer to the reference's explicit torch matmul attention
(src/modeling.py:403-437), which materializes the (B, H, S, S) score matrix
in memory: here scores live only as (BLK_Q, BLK_K) tiles in VMEM with an
online-softmax running max/sum, so HBM traffic is O(S*D) not O(S^2). Backward
recomputes tiles from the saved logsumexp (standard flash algorithm).

Attention dropout matches the reference semantics (dropout on normalized
probs, run_pretraining hot path) and is generated *positionally*: a
counter-based hash of (seed, head, q_pos, k_pos) yields the keep mask, so
forward and both backward kernels reproduce the identical mask regardless of
tile shapes — and the implementation runs under interpret mode on CPU (TPU
PRNG primitives don't).

Layout contract: q/k/v are (B, S, H, D); bias broadcastable (B, 1, 1, S)
additive mask. S must divide by the q/k block size (ops/attention.py gates).

Two kernel-grid layouts exist behind the same public function, chosen by
shape alone (`_use_native`); the fused backward kernel serves both, the
forward has a kernel for each:

- **native** (wherever heads tile into 128-lane blocks and the fused
  backward fits them — every BERT-Base/Large shape up to S=1024): the kernels
  consume the (B, S, H*D) row-major view of the model's (B, S, H, D) arrays,
  a reshape that moves no bytes. Mosaic's tiling rule wants a block's lane
  dim to be a multiple of 128, so a program owns the 128 // D heads that
  share one lane block (two at D=64) and works on their static D-lane
  slices. No (B,S,H,D)->(BH,S,D) transpose pass on q/k/v/do/outputs.
- **bh** (other head shapes, and S*D beyond the fused backward's VMEM
  bound, where the split backward kernels take over): the (BH, S, D) view
  with a transpose pass either side, unbounded S. A program owns several
  heads (`_bh_heads_per_prog`: the query heads of a key/value head, or as
  many heads as the kernels' VMEM holds panels of), as (heads, blk, D)
  blocks of the same arrays, and walks the k blocks (the q blocks in the dkv
  kernel) OUTSIDE, unrolled, and its heads INSIDE, as a rolled loop under
  ONE skip test (`_each_head`): whether a (q block, k block) pair holds an
  allowed pair is a function of the row and the two blocks alone. A
  long-sequence program is S / blk unrolled tile bodies, each run at most
  once a head, and with one head a program its instructions were fetched
  anew for every program: what the kernels lost their time to on the v5e
  was not the tiles' arithmetic but their instructions (PERF.md section 6,
  PR 34: with the heads unrolled beside each other, four times the code, the
  kernels ran 25-40 % SLOWER; rolled, the same tiles run in half the time).
  A pair's tile is now fetched once and run for every head of the program.
  The fused backward keeps one head a program.

Both layouts draw identical dropout masks (the kernels' counter is
batch * H + head in either addressing), so they are the same training run.

**Packed sequences** (`segment_ids`, the round-9 unpadded-pretraining path):
a (B, S) int32 array assigning each position a packing segment (1..n per
row, 0 = pad) restricts attention to `q_seg == k_seg` blocks — the static-
shape TPU form of un-padding ("Boosting Distributed Training Performance of
the Unpadded BERT Model", PAPERS.md). The mask is applied additively inside
every kernel exactly like the padding bias, and because segments occupy
contiguous position ranges, a (q, k) tile whose segment ranges don't
intersect is *skipped wholesale* (`jax.lax.cond` around the tile body — no
scores, no dropout hash, no dots), which is where the block-diagonal FLOP
saving is realized. Where one tile is the whole sequence (S = 512 at the
default blocks) only a row of nothing but pad can skip it — an empty slot of
the server's fixed batch — and the one test stands around the whole program
instead of around each head's tile (`_program`), where it builds no
wall between the heads. FLASH_SEG_SKIP=0 disables the skip (mask-only, for
A/B isolation); skipped and masked-but-computed tiles contribute exactly zero
either way, so the two settings are bit-identical on every non-pad row.
Rows of all-pad positions (segment 0) have their outputs explicitly zeroed
in the forward epilogue (their degenerate softmax would otherwise emit
tile-layout-dependent garbage), so pad activations are identical across
skip settings, layouts and the XLA fallback — keeping full-(B, S, E)
consumers like the K-FAC factor taps kernel-configuration-independent.
Their gradients are zero because no loss term reads pad positions.

**Causal attention and grouped heads** (`causal=True`; k/v with fewer heads
than q — the decoder families, models/lfm2_moe.py): a query attends to
earlier-or-equal positions only, of its own segment where rows are packed.
The mask is one more condition of the same `jnp.where`, and a (q, k) tile
that lies wholly above the diagonal is skipped like a tile of disjoint
segments. Values may have a width of their own (latent attention,
models/kimi_linear.py: keys of 192, values of 128): the kernels slice v, dO
and the output at that width. With H query heads over H/G key/value heads a
program owns the G query heads of ONE key/value head: it fetches that head's
K and V panels once (no repeated copy of K and V), and the dkv kernel adds
the group's dk and dv in its float32 accumulators and writes one block a
key/value head, in the parameters' dtype. Both take the bh layout and the
split backward whatever the shape.

**A band** (`window=W` with `causal=True`; the sliding-window layers of
models/smallthinker.py and models/laguna.py): a query attends to the last W
positions up to its own, `0 <= q_pos - k_pos < W`, of its own segment where
rows are packed (documents are contiguous, so the distance inside a row is
the distance inside the document). One more condition of the same mask. The
banded calls take the bh layout and the split backward whatever the shape,
and carry kernel names of their own (`flash_win_fwd`, `flash_win_bwd_dq`,
`flash_win_bwd_dkv`), so that a trace tells the two kinds of layer apart.
Their form follows their shapes (`_band_steps`; no flag chooses):

- where the band spans fewer blocks than the row (`band_blocks`: 2 of 32 at
  S = 16,384, W = 512 and 512 x 512 tiles, 9 at W = 4,096) the loop over
  the other side's blocks is OUT of the program's text: the grid's last,
  sequential axis walks the band's blocks only, K and V (Q, dO and the row
  statistics in the dkv kernel) arrive as BLOCKS by an index map clamped
  into the row, and a program is ONE tile body with its heads' running
  results in VMEM scratch from the band's first step to its last
  (`_fwd_band_kernel`, `_dq_band_kernel`, `_dkv_band_kernel`). No (S, D)
  panel is held (16 MiB of VMEM asked at S = 16,384 where panels asked 80),
  and a head's programs visit `band_tiles` pairs (63, 252), all of them
  live, in the order the unrolled loop ran them: the same sums, bit for
  bit. What an S / blk-body program paid for a thin band is in PERF.md
  section 6, PR 42;
- where the band spans every block of the row it is a lower edge of the
  panel-walking programs' skip test: a (q block, k block) pair wholly
  BEHIND the band is skipped like one above the diagonal, under the one
  test a pair for all heads of the program.
"""

from __future__ import annotations

import functools
import math
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

# Block sizes (env-overridable for tuning sweeps): 512 x 512; _pick_block
# halves the target until it divides S, falling back to one whole-sequence
# block only when no power-of-two fraction >= 128 does. The (blk_q, blk_k)
# fp32 score tile plus q/k/v blocks is ~1.5 MB of VMEM at D=64. What the
# attached v5e said (PERF.md section 6, PR 26 and PR 27): at S = 8,192
# causal the three kernels take 117.5 ms a step at 512 x 512, 116.2 at
# 1024 x 1024, 213.3 at 256 x 256. At S = 512 packed (lognormal documents,
# median 180) smaller blocks have next to nothing to skip — 99.9 % of
# 256-wide and 84.9 % of 128-wide tiles hold an allowed pair, the documents
# need 63.9 % of the square — and every tile under a `lax.cond` is a basic
# block the compiler schedules alone: the one cond around the one 512 x 512
# tile of each head cost 32 % of the forward and 34 % of the fused backward
# kernel (device trace, ms a call of 16 rows x 16 heads: 0.785 -> 0.534 and
# 1.010 -> 0.663), so there the test stands once around the program, on a
# flag that comes in SMEM (`_program`, `_live_rows`), and the heads' work
# is one block again: 0.503 and 0.615 with every item, 0.508 and 0.606
# with no test at all.
#
# The rule of the tile bodies: NOTHING THAT IS A FUNCTION OF THE ROW OR THE
# COLUMN ALONE IS COMPUTED ON THE (blk_q, blk_k) TILE. The softmax scale
# rides on a (blk, D) dot operand where it is a power of two; the dropout
# hash starts from a (blk_q, 1) row term and a (1, blk_k) column term; the
# dropout rescale rides on the (blk, D) results; pad is a value of the
# key-side segment vector that equals no query's; causal positions are two
# vectors; a block's segment range is taken once per block, from the
# lane-dense ids. tests/test_pallas.py::test_flash_tile_equation_ceilings
# counts what is left on the tile, per kernel.


# 256 / 512 / 1,024 raced on a v5e (PERF.md, PR 26)
DEFAULT_BLK_Q = DEFAULT_BLK_K = 512
NEG_INF = -1e30
_SEG_BIG = 2 ** 30  # sentinel above any real segment index


def _seg_skip_enabled() -> bool:
    """FLASH_SEG_SKIP=0 disables block-level tile skipping (the masked
    tiles are computed and contribute exact zeros instead). A/B hatch
    for the skipped-vs-masked bit-identity tests."""
    return os.environ.get("FLASH_SEG_SKIP", "1") != "0"


def _rows(dtype, n: int):
    """(n, 1) iota: a per-query-row vector, broadcast along the lanes."""
    return jax.lax.broadcasted_iota(dtype, (n, 1), 0)


def _cols(dtype, n: int):
    """(1, n) iota: a per-key-column vector, broadcast along the sublanes."""
    return jax.lax.broadcasted_iota(dtype, (1, n), 1)


def _scale_operand(x, scale: float):
    """(operand, tile_scale): where the softmax scale is a power of two
    (D = 64: 2^-3) it rides on the (blk, D) dot operand `x` — the product is
    the same number in bf16 and float32 alike and (x * scale) . y
    == scale * (x . y) bit for bit — and the float32 score tile needs no
    multiply (tile_scale None). Elsewhere (D = 128) x as it is, and the
    scale for the tile."""
    if math.frexp(scale)[0] != 0.5:
        return x, scale
    return (x.astype(jnp.float32) * scale).astype(x.dtype), None


def _seg_keys(seg):
    """Key-side segment ids with pad (0) sent to -1, which is no query's
    id: a pad key then fails `_mask`'s one equality test, as a pad query
    (0) does against every key."""
    return jnp.where(seg > 0, seg, -1)


def _seg_range(seg):
    """(min, max) of a block's non-pad segment ids, in any layout. Segments
    occupy contiguous, increasing position ranges within a row, so a
    block's non-pad ids form a contiguous integer range. O(blk) work that
    belongs to the block alone: a kernel takes it once per q block and once
    per k block, not once per tile."""
    big = jnp.int32(_SEG_BIG)
    return jnp.min(jnp.where(seg > 0, seg, big)), jnp.max(seg)


def _seg_overlap(qrange, krange):
    """Scalar bool: does this (q, k) tile contain ANY allowed pair? Two
    blocks share a segment iff their `_seg_range`s intersect."""
    (qmn, qmx), (kmn, kmx) = qrange, krange
    return (qmx > 0) & (kmx > 0) & (qmx >= kmn) & (kmx >= qmn)


def _skip_pred(qrange=None, krange=None, live=None):
    """Scalar bool, or None where the tile always runs: does the (q, k) tile
    hold any allowed pair? By the blocks' segment ranges (None without
    segments, also under FLASH_SEG_SKIP=0) and by `live` (causal attention:
    false where the tile lies wholly above the diagonal; None where
    attention is bidirectional). A tile that runs all masked contributes
    exact zeros, so skipped and masked are bit-identical on non-pad rows."""
    pred = None
    if qrange is not None:
        pred = _seg_overlap(qrange, krange)
    if live is not None:
        pred = live if pred is None else pred & live
    return pred


def _maybe_skip(tile_fn, carry, qrange=None, krange=None, live=None):
    """Run tile_fn(carry) -> carry unless `_skip_pred` proves the tile
    all-masked."""
    pred = _skip_pred(qrange, krange, live)
    if pred is None:
        return tile_fn(carry)
    return jax.lax.cond(pred, tile_fn, lambda c: c, carry)


def _each_head(heads: int, body, qrange=None, krange=None, live=None):
    """Run body(t) for t in 0 .. heads - 1 (its results go to refs) unless
    `_skip_pred` proves the heads' tiles of this (q block, k block) pair
    all-masked: ONE test for every head of the program, and the heads a
    ROLLED loop, so that the pair's tile is compiled once and its
    instructions, once fetched, serve every head."""
    def run():
        if heads == 1:
            body(0)
        else:
            jax.lax.fori_loop(0, heads, lambda t, _: body(t), None)

    pred = _skip_pred(qrange, krange, live)
    if pred is None:
        run()
    else:
        pl.when(pred)(run)


def _tile_skip(has_segments: bool, tiles: int) -> bool:
    """Are a kernel's tiles skipped one by one, by their blocks' segment
    ranges? Not without segments, not under FLASH_SEG_SKIP=0, and not where
    the one tile is the whole (S, S) square (`tiles` == 1): only a row of
    nothing but pad can skip it, and the `lax.cond` around a tile is a wall
    the compiler's scheduler moves nothing across, head to head (a third of
    both kernels at S = 512: header comment). `_program` skips such a
    row there."""
    return has_segments and _seg_skip_enabled() and tiles > 1


def _skip_pad_rows(has_segments: bool, tiles: int) -> bool:
    """Where the one tile is the whole (S, S) square (`tiles` == 1:
    `_tile_skip` makes no test there), is a row of nothing but pad skipped
    as a whole program (`_program`)? With segments, unless FLASH_SEG_SKIP=0."""
    return has_segments and _seg_skip_enabled() and tiles == 1


def _live_rows(seg2, skip_pad_rows: bool) -> tuple:
    """(in_specs, operands) of the leading operand `_program` reads where
    it skips rows of pad: (B,) int32, 1 where the row holds any position
    that is not pad. Taken here, outside the kernel, and handed over in
    SMEM: taken inside, from the segment ids in VMEM, the same test is a
    vector reduction whose way to the scalar unit every program waits for
    (+3.6 % on the forward and +5.2 % on the backward kernel at S = 512;
    this form -1.1 % and +1.5 %). Both empty where no row is skipped."""
    if not skip_pad_rows:
        return [], []
    from jax.experimental.pallas import tpu as pltpu

    live = (jnp.max(seg2, axis=(1, 2)) > 0).astype(jnp.int32)
    return [pl.BlockSpec(memory_space=pltpu.SMEM)], [live]


def _block_ranges(seg2, blk_q: int, blk_k: int, wanted: bool) -> tuple:
    """(in_specs, operands) of the operand a bh-layout kernel reads its
    blocks' `_seg_range`s from where it skips tiles (`_tile_skip`):
    (B, 2, S / blk_q + S / blk_k) int32 in SMEM, the q blocks' (min, max)
    first, then the k blocks'. Taken here, once a call, for the reason
    `_live_rows` gives: inside a kernel each range is a vector reduction
    that travels to the scalar unit, 2 + 2 S / blk of them a program. Both
    empty where not `wanted`."""
    if not wanted:
        return [], []
    from jax.experimental.pallas import tpu as pltpu

    b, _, s = seg2.shape

    def ranges(blk):
        ids = seg2.reshape(b, s // blk, blk)
        return jnp.stack([jnp.min(jnp.where(ids > 0, ids, _SEG_BIG), axis=-1),
                          jnp.max(ids, axis=-1)], axis=1)

    both = jnp.concatenate([ranges(blk_q), ranges(blk_k)], axis=-1)
    return [pl.BlockSpec(memory_space=pltpu.SMEM)], [both]


def _block_range(ranges_ref, batch, block):
    """A block's `_seg_range` from `_block_ranges`' operand; None where the
    kernel has none, because it skips no tile."""
    if ranges_ref is None:
        return None
    return ranges_ref[batch, 0, block], ranges_ref[batch, 1, block]


def _program(kernel, grid_rank: int, n_out: int, batch_of=None,
             ranges: bool = False, n_scratch: int = 0):
    """The body of a pallas_call from `kernel(ids, *refs)`: `ids` is the
    program's grid position, read here, at the top (interpret mode
    resolves `program_id` nowhere else). With `batch_of` (grid row ->
    batch index; `_skip_pad_rows`) the first operand is `_live_rows` and a
    row of nothing but pad — an empty slot of a batch of fixed size — is
    skipped: its program writes zeros to its `n_out` outputs (the refs
    before the last `n_scratch`) and runs nothing else. ONE test around the
    whole program, so the heads' tiles inside stay one block for the
    scheduler. A skipped row's logsumexp reads 0 and no kernel reads it
    back, its backward program being skipped by the same test. With
    `ranges` the first operand is `_block_ranges`', handed to the kernel as
    `ranges_ref` (never both: a kernel skips tiles or whole rows)."""

    def program(*refs):
        ids = tuple(pl.program_id(axis) for axis in range(grid_rank))
        if ranges:
            return kernel(ids, *refs[1:], ranges_ref=refs[0])
        if batch_of is None:
            return kernel(ids, *refs)
        live = refs[0][batch_of(ids[0])] > 0
        refs = refs[1:]
        pl.when(live)(lambda: kernel(ids, *refs))

        @pl.when(jnp.logical_not(live))
        def _():
            for ref in refs[len(refs) - n_scratch - n_out:
                            len(refs) - n_scratch]:
                ref[...] = jnp.zeros(ref.shape, ref.dtype)

    return program


def _causal_live(causal: bool, q0, bq: int, k0, bk: int = 0,
                 window: int = 0):
    """Does the (q, k) tile at rows q0.., columns k0.. hold any pair with
    k <= q (and, under a band of `window` positions, q - k < window: the
    tile's nearest pair is its first row against its last column)? None
    where attention is not causal."""
    if not causal:
        return None
    live = k0 <= q0 + (bq - 1)
    if window:
        live = live & (q0 - (k0 + (bk - 1)) < window)
    return live


def band_blocks(blk: int, window: int) -> int:
    """The k blocks that hold a q block's band of `window` positions (its
    own and those up to `window` - 1 positions back: `_causal_live` of every
    other is false), where both blocks are `blk` long: 2 of a row's 32 at
    S = 16,384, blk 512 and a band of 512, 9 at a band of 4,096. 0 without a
    band; a row may have fewer."""
    return -(-(window - 1) // blk) + 1 if window else 0


def band_tiles(s: int, blk: int, window: int) -> int:
    """The (q block, k block) pairs of a row inside the band, every one of
    them live (`_causal_live`): what a banded program set visits for a
    head, 63 of the row's 32 x 32 and 252 in `band_blocks`' two cases."""
    nb = band_blocks(blk, window)
    return sum(min(qi + 1, nb) for qi in range(s // blk))


def _band_steps(s: int, blk_q: int, blk_k: int, window: int) -> int:
    """The form of a banded call, by its shapes alone: `band_blocks` where
    the band spans fewer blocks than the row (the kernels then walk the
    band's blocks as a grid axis of that many steps: `_fwd_band_kernel`),
    0 where it spans them all, or there is no band (the panel-walking
    kernels)."""
    if blk_q != blk_k:
        return 0
    nb = band_blocks(blk_q, window)
    return nb if nb < s // blk_q else 0


def _causal_pos(causal: bool, q0, k0, bq: int, bk: int):
    """(rows, cols) of `_mask` for the tile at rows q0.., columns k0..: a
    pair is allowed where rows >= cols. A (bq, 1) and a (1, bk) vector,
    both relative to q0, so that the rows are the same for every tile."""
    if not causal:
        return None, None
    return _rows(jnp.int32, bq), _cols(jnp.int32, bk) + (k0 - q0)


def _mask(s, rows, cols, segq, segk, window: int = 0, sel=None):
    """Scores with the disallowed pairs at NEG_INF: other segments and pad
    (packed rows: segq (bq, 1), segk (1, bk) from `_seg_keys`, allowed
    where equal; None otherwise), later positions (causal: `_causal_pos`;
    None otherwise), positions `window` or more back (a band; 0: none), and
    pairs a selection leaves out (`sel`: the tile's (bq, bk) bools from
    `_select_tile`; None: no selection). One compare per condition on the
    tile; everything else is the vectors'."""
    allowed = None if segq is None else segq == segk
    if rows is not None:
        tri = rows >= cols
        if window:
            tri = tri & (rows < cols + window)
        allowed = tri if allowed is None else allowed & tri
    if sel is not None:
        allowed = sel if allowed is None else allowed & sel
    return s if allowed is None else jnp.where(allowed, s, NEG_INF)


# A selection (`flash_select_attention`): which keys each query attends to,
# a (query, key) MATRIX that differs by row, so no pair of vectors carries
# it. It comes bit-packed BY BLOCK: word [q, c] of `by_q` (B, W, S, blk_k)
# int32 holds, at bit j % 32 of word plane j // 32, whether query q selects
# key j * blk_k + c. A q block's (W, blk_q, blk_k) words are then ONE block
# that serves every k block of the row: a program fetches 1 MiB once (S =
# 16,384: 32 k blocks, W = 1) where an int8 matrix would stream 8 MiB, and a
# tile's bools are one AND and one compare on words that already lie as the
# scores do, no relayout (a mask packed along the keys or the queries would
# have to be spread over lanes or sublanes first). 32 MiB a layer at
# S = 16,384 against 256 MiB as int8. The dkv kernel walks q blocks, so it
# reads the transposed packing `by_k` (B, W, blk_q, S): bit i % 32 of plane
# i // 32 of word [r, k] says whether query i * blk_q + r selects key k.
SELECT_WORD = 32


def _select_tile(sel_ref, n):
    """The (blk_q, blk_k) bools of a tile from a program's block of packed
    words: bit n % 32 of plane n // 32 (`n`: the k block in the forward and
    dq kernels, the q block in the dkv kernel; static)."""
    bit = n % SELECT_WORD
    mask = -(1 << 31) if bit == 31 else 1 << bit
    return (sel_ref[0, n // SELECT_WORD] & jnp.int32(mask)) != 0


def _pick_block(s: int, target: int) -> int:
    while target >= 128:
        if s % target == 0:
            return target
        target //= 2
    return s


_HASH_ROW, _HASH_COL, _HASH_HEAD = 0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D


def _keep_rows(q0, bq: int):
    """(bq, 1) uint32, the keep hash's row term: q_pos * _HASH_ROW."""
    return (_rows(jnp.uint32, bq) + jnp.uint32(q0)) * jnp.uint32(_HASH_ROW)


def _keep_cols(seed, bh, k0, bk: int):
    """(1, bk) uint32, the keep hash's column term with the scalar folded
    in: k_pos * _HASH_COL ^ (seed + bh * _HASH_HEAD)."""
    cols = (_cols(jnp.uint32, bk) + jnp.uint32(k0)) * jnp.uint32(_HASH_COL)
    return cols ^ (jnp.uint32(seed) + jnp.uint32(bh) * jnp.uint32(_HASH_HEAD))


def _keep_tile(rows, cols, rate: float):
    """The (bq, bk) keep mask from `_keep_rows` and `_keep_cols`: the part
    of the hash that needs both coordinates (8 tile equations)."""
    x = rows ^ cols
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    # top 23 bits uniform in [0, 2^23); keep iff >= rate * 2^23
    return x >= jnp.uint32(int(rate * (1 << 23)) << 9)


def _keep_mask(seed, bh, q0, k0, bq, bk, rate: float):
    """Counter-based keep mask over global (q_pos, k_pos) — two
    multiply-xorshift rounds on a per-position counter, integer threshold
    compare. uint32 VPU ops only:

        x = (q_pos * 0x9E3779B1) ^ (k_pos * 0x85EBCA77)
              ^ (seed + bh * 0xC2B2AE3D)
        x ^= x >> 16;  x *= 0x7FEB352D;  x ^= x >> 15;  x *= 0x846CA68B
        keep = (x >> 9) >= int(rate * 2^23)

    The kernels build the first line from a per-row and a per-column vector
    (`_keep_rows`, `_keep_cols`: xor is associative, so the bits are these)
    and compare x with the threshold shifted up instead of x shifted down
    (the same test for unsigned x).

    The mask is evaluated over S^2 elements per (batch, head) in forward AND
    backward, so every op here is step-time. Two rounds are the floor that
    keeps dropout statistics clean: one round leaves 0.23 cross-seed mask
    correlation (additive seed injection is worse still — near-duplicate
    masks for some seed pairs); with two rounds keep-rate bias < 5e-4,
    cross-seed / adjacent-position correlations are chance-level (<0.015),
    verified over 24 seeds x 256^2 at rates 0.1/0.3. The final murmur
    xor-shift only feeds bits below the 23 used by the compare, and the
    int compare replaces the bitcast->f32->scale->cmp tail; both are dropped
    (~3 VPU ops/element saved, identical top-23-bit statistics)."""
    return _keep_tile(_keep_rows(q0, bq), _keep_cols(seed, bh, k0, bk), rate)


def _fwd_tile(carry, q, kb, vb, tile_scale, bias, pos, segq, segk, keep,
              rate: float, window: int = 0, sel=None):
    """One (blk_q, blk_k) tile of the online softmax: (m, l, acc) with the
    keys kb and values vb taken in. `bias` (None, or the pad bias's
    (1, blk_k) row), `pos` (`_causal_pos`) and `keep` (the dropout keep
    mask, rate > 0) are thunks, called where the tile needs them.
    Matmul inputs stay in the stored dtype (bf16): the MXU multiplies
    bf16 x bf16 into an fp32 accumulator at full rate, while fp32 inputs
    run at a fraction of it. Softmax statistics and accumulators are fp32 —
    identical numerics to the XLA attention path (probs cast to the compute
    dtype before the PV matmul)."""
    m, l, acc = carry
    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if tile_scale is not None:
        s = s * tile_scale
    if bias is not None:
        s = s + bias()
    s = _mask(s, *pos(), segq, segk, window, sel)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    p_acc = jnp.where(keep(), p, 0.0) if rate > 0.0 else p
    acc = acc * alpha + jnp.dot(p_acc.astype(vb.dtype), vb,
                                preferred_element_type=jnp.float32)
    return m_new, l, acc


def _fwd_out(l, acc, segq, rate: float):
    """A q block's output from its last (l, acc), and l floored: its
    logsumexp is m + log of that."""
    l_safe = jnp.maximum(l, 1e-30)
    out = acc / l_safe
    if rate > 0.0:
        out = out / (1.0 - rate)
    if segq is not None:
        # pad (segment-0) rows attend nowhere; without this their softmax
        # degenerates to skip-/tile-layout-dependent garbage (uniform over
        # whatever tiles ran). Zeroing makes every path — skip on/off, both
        # layouts, XLA fallback — emit identical pad activations, which
        # keeps downstream consumers of full (B, S, E) hiddens (K-FAC
        # factor taps) bit-independent of the kernel configuration.
        out = jnp.where(segq > 0, out, 0.0)
    return out, l_safe


def _fwd_kernel(ids, seed_ref, q_ref, k_ref, v_ref, bias_ref, segq_ref,
                segk_ref, o_ref, lse_ref, *, scale: float, blk_k: int,
                rate: float, has_bias: bool, has_segments: bool,
                heads_per_prog: int, heads_per_row: int,
                causal: bool = False):
    """Native layout: one program per (batch row, lane block, q-block) of
    the (B, S, H * D) view; it loops the `heads_per_prog` heads that share
    its lane block (static lane slices of width D), then the k-blocks. The
    dropout counter is batch * H + head (`heads_per_row` = H), as the bh
    kernel's."""
    row, group, qi = ids
    bq = q_ref.shape[1]
    d = q_ref.shape[2] // heads_per_prog
    s_len = k_ref.shape[1]
    nk = s_len // blk_k
    q0 = qi * bq if causal else 0   # first row of this tile (causal only)
    skip = _tile_skip(has_segments, (s_len // bq) * nk)
    # what belongs to the q block alone, once for every head and k block
    segq = segq_ref[0, 0][:, None] if has_segments else None
    qrange = _seg_range(segq_ref[0, 0][None, :]) if skip else None
    keep_rows = _keep_rows(qi * bq, bq) if rate > 0.0 else None
    # and to a k block alone, once for every head
    segks = [_seg_keys(segk_ref[0, 0, j * blk_k:(j + 1) * blk_k])[None, :]
             if has_segments else None for j in range(nk)]
    kranges = [_seg_range(segks[j]) if skip else None for j in range(nk)]

    for t in range(heads_per_prog):
        lanes = slice(t * d, (t + 1) * d)
        bh = row * heads_per_row + group * heads_per_prog + t
        q, tile_scale = _scale_operand(q_ref[0, :, lanes], scale)
        carry = (jnp.full((bq, 1), NEG_INF, jnp.float32),
                 jnp.zeros((bq, 1), jnp.float32),
                 jnp.zeros((bq, d), jnp.float32))

        for j in range(nk):
            cols = slice(j * blk_k, (j + 1) * blk_k)

            def tile(carry, lanes=lanes, bh=bh, j=j, cols=cols, q=q):
                return _fwd_tile(
                    carry, q, k_ref[0, cols, lanes], v_ref[0, cols, lanes],
                    tile_scale,
                    (lambda: bias_ref[0, 0, cols][None, :]) if has_bias
                    else None,
                    lambda: _causal_pos(causal, q0, j * blk_k, bq, blk_k),
                    segq, segks[j],
                    lambda: _keep_tile(
                        keep_rows,
                        _keep_cols(seed_ref[0], bh, j * blk_k, blk_k), rate),
                    rate)

            carry = _maybe_skip(tile, carry, qrange, kranges[j],
                                _causal_live(causal, q0, bq, j * blk_k))

        m, l, acc = carry
        out, l_safe = _fwd_out(l, acc, segq, rate)
        o_ref[0, :, lanes] = out.astype(o_ref.dtype)
        lse_ref[0, 0, t, :] = (m + jnp.log(l_safe))[:, 0]


# The bh-layout kernels. A program owns `hp` heads: q_ref / do_ref / the
# outputs are (hp, blk, D) blocks of the (B * H, S, D) arrays, k_ref and
# v_ref their heads' panels ((hp, S, D)) or, where the hp query heads are
# the group of ONE key/value head, that head's ((1, S, D)); the per-head
# row statistics (hp, 1, blk) blocks; the dropout counter of head t of grid
# row r is r * hp + t = batch * H + head. Every kernel walks its k blocks
# (q blocks in the dkv kernel) OUTSIDE, unrolled, and its heads INSIDE,
# rolled (`_each_head`), with the heads' running results in VMEM scratch.


def _sel_kw(sel_ref, n) -> dict:
    """A head body's `sel` keyword for block `n` of the side its kernel
    walks: the tile's selected pairs as a thunk; nothing without a
    selection."""
    if sel_ref is None:
        return {}
    return {"sel": lambda: _select_tile(sel_ref, n)}


def _with_select(kernel):
    """`kernel` with the packed selection as its first operand (after what
    `_program` takes off)."""
    def selecting(ids, sel_ref, *refs, **kw):
        return kernel(ids, *refs, sel_ref=sel_ref, **kw)
    return selecting


def _kv_at(kv_ref, t):
    """Where head t's panel is in a block of key/value panels."""
    return t if kv_ref.shape[0] > 1 else 0


def _fwd_heads(seed_ref, q_ref, k_ref, v_ref, bias_ref, m_ref, l_ref, acc_ref,
               *, row, q0, bk: int, segq, keep_rows, scale: float,
               rate: float, has_bias: bool, causal: bool, window: int):
    """head(t, cols, k0, segk) of a forward program: head t's tile against
    the keys and values at `cols` of k_ref / v_ref (a static slice of its
    panel, or the whole of a block), whose first position is `k0`, folded
    into the head's running (m, l, acc)."""
    hp, bq, _ = q_ref.shape

    def head(t, cols, k0, segk, sel=None):
        q, tile_scale = _scale_operand(q_ref[t], scale)
        m_ref[t], l_ref[t], acc_ref[t] = _fwd_tile(
            (m_ref[t], l_ref[t], acc_ref[t]), q,
            k_ref[_kv_at(k_ref, t), cols, :],
            v_ref[_kv_at(v_ref, t), cols, :], tile_scale,
            (lambda: bias_ref[0, 0, cols][None, :]) if has_bias else None,
            lambda: _causal_pos(causal, q0, k0, bq, bk),
            segq, segk,
            lambda: _keep_tile(
                keep_rows, _keep_cols(seed_ref[0], row * hp + t, k0, bk),
                rate),
            rate, window, None if sel is None else sel())

    return head


def _fwd_start(m_ref, l_ref, acc_ref):
    """The heads' running max, sum and accumulator before their first
    tile."""
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)


def _fwd_finish(o_ref, lse_ref, m_ref, l_ref, acc_ref, segq, rate: float):
    """The heads' outputs and logsumexps after their last tile."""
    for t in range(o_ref.shape[0]):
        out, l_safe = _fwd_out(l_ref[t], acc_ref[t], segq, rate)
        o_ref[t] = out.astype(o_ref.dtype)
        lse_ref[t, 0, :] = (m_ref[t] + jnp.log(l_safe))[:, 0]


def _fwd_bh_kernel(ids, seed_ref, q_ref, k_ref, v_ref, bias_ref, segq_ref,
                   segk_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                   scale: float, blk_k: int, rate: float, has_bias: bool,
                   has_segments: bool, batch_of, causal: bool = False,
                   window: int = 0, ranges_ref=None, sel_ref=None):
    """One program per (grid row, q-block): the forward of its heads.
    `sel_ref`: the q block's packed selection (`_select_tile`), or None."""
    row, _, qi = ids
    hp, bq, _ = q_ref.shape
    s_len = k_ref.shape[1]
    nk = s_len // blk_k
    q0 = qi * bq if causal else 0   # first row of this tile (causal only)
    batch = batch_of(row)
    # what belongs to the q block alone, once for every head and k block
    segq = segq_ref[0, 0][:, None] if has_segments else None
    qrange = _block_range(ranges_ref, batch, qi)
    keep_rows = _keep_rows(qi * bq, bq) if rate > 0.0 else None
    _fwd_start(m_ref, l_ref, acc_ref)
    head = _fwd_heads(seed_ref, q_ref, k_ref, v_ref, bias_ref, m_ref, l_ref,
                      acc_ref, row=row, q0=q0, bk=blk_k, segq=segq,
                      keep_rows=keep_rows, scale=scale, rate=rate,
                      has_bias=has_bias, causal=causal, window=window)

    for j in range(nk):
        cols = slice(j * blk_k, (j + 1) * blk_k)
        # and to a k block alone, once for every head
        segk = (_seg_keys(segk_ref[0, 0, cols])[None, :]
                if has_segments else None)
        _each_head(hp, functools.partial(head, cols=cols, k0=j * blk_k,
                                         segk=segk, **_sel_kw(sel_ref, j)),
                   qrange, _block_range(ranges_ref, batch, s_len // bq + j),
                   _causal_live(causal, q0, bq, j * blk_k, blk_k, window))

    _fwd_finish(o_ref, lse_ref, m_ref, l_ref, acc_ref, segq, rate)


def _dropout_late(scale: float, rate: float) -> float:
    """The constant on a backward kernel's (blk, D) dq / dk results. With
    dropout the tile keeps where(keep, dp, 0) and where(keep, p, 0) as they
    are and the 1 / (1 - rate) of both rides here (and on dv), as the
    forward kernel's does on its output: ds * (1 - rate)
    = p * (where(keep, dp, 0) - delta * (1 - rate)), and `delta` comes in
    times (1 - rate) (_flash_bwd_rule)."""
    return scale / (1.0 - rate) if rate > 0.0 else scale


def _dq_heads(seed_ref, q_ref, k_ref, v_ref, bias_ref, lse_ref, delta_ref,
              do_ref, acc_ref, *, row, q0, bk: int, segq, keep_rows,
              scale: float, rate: float, has_bias: bool, causal: bool,
              window: int):
    """head(t, cols, k0, segk) of a dq program: what the keys and values at
    `cols` of k_ref / v_ref (as `_fwd_heads`), whose first position is
    `k0`, add to head t's dq."""
    hp, bq, _ = q_ref.shape
    out_scale = _dropout_late(scale, rate)

    def head(t, cols, k0, segk, sel=None):
        q, tile_scale = _scale_operand(q_ref[t], scale)
        kb = k_ref[_kv_at(k_ref, t), cols, :]
        vb = v_ref[_kv_at(v_ref, t), cols, :]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if tile_scale is not None:
            s = s * tile_scale
        if has_bias:
            s = s + bias_ref[0, 0, cols][None, :]
        s = _mask(s, *_causal_pos(causal, q0, k0, bq, bk), segq, segk,
                  window, None if sel is None else sel())
        p = jnp.exp(s - lse_ref[t, 0][:, None])
        dp = jax.lax.dot_general(
            do_ref[t], vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if rate > 0.0:
            keep = _keep_tile(
                keep_rows, _keep_cols(seed_ref[0], row * hp + t, k0, bk),
                rate)
            dp = jnp.where(keep, dp, 0.0)
        ds = p * (dp - delta_ref[t, 0][:, None])
        acc_ref[t] += jnp.dot(ds.astype(kb.dtype), kb,
                              preferred_element_type=jnp.float32) \
            * out_scale

    return head


def _dq_kernel(ids, seed_ref, q_ref, k_ref, v_ref, bias_ref, segq_ref,
               segk_ref, lse_ref, delta_ref, do_ref, dq_ref, acc_ref, *,
               scale: float, blk_k: int, rate: float, has_bias: bool,
               has_segments: bool, batch_of, causal: bool = False,
               window: int = 0, ranges_ref=None, sel_ref=None):
    """One program per (grid row, q-block): dq of its heads."""
    row, qi = ids
    hp, bq, _ = q_ref.shape
    s_len = k_ref.shape[1]
    nk = s_len // blk_k
    batch = batch_of(row)

    segq = segq_ref[0, 0][:, None] if has_segments else None
    qrange = _block_range(ranges_ref, batch, qi)
    keep_rows = _keep_rows(qi * bq, bq) if rate > 0.0 else None
    q0 = qi * bq if causal else 0
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    head = _dq_heads(seed_ref, q_ref, k_ref, v_ref, bias_ref, lse_ref,
                     delta_ref, do_ref, acc_ref, row=row, q0=q0, bk=blk_k,
                     segq=segq, keep_rows=keep_rows, scale=scale, rate=rate,
                     has_bias=has_bias, causal=causal, window=window)

    for j in range(nk):
        cols = slice(j * blk_k, (j + 1) * blk_k)
        segk = (_seg_keys(segk_ref[0, 0, cols])[None, :]
                if has_segments else None)
        _each_head(hp, functools.partial(head, cols=cols, k0=j * blk_k,
                                         segk=segk, **_sel_kw(sel_ref, j)),
                   qrange, _block_range(ranges_ref, batch, s_len // bq + j),
                   _causal_live(causal, q0, bq, j * blk_k, blk_k, window))

    dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_heads(seed_ref, q_ref, k_ref, v_ref, segq_ref, lse_ref, delta_ref,
               do_ref, dk_acc_ref, dv_acc_ref, *, row, kj, k0, bq: int, bias,
               segk, scale: float, rate: float, has_segments: bool,
               causal: bool, window: int):
    """head(t, rows, q0) of a dkv program: what the queries at `rows` of
    q_ref / do_ref and their statistics (a static slice of the panels, or
    the whole of a block), whose first position is `q0`, add to the dk and
    dv of head t's key/value head, k block `kj` (`k0`: its first position
    where attention is causal). `bias`: the k block's (1, bk) row, or
    None."""
    hp = q_ref.shape[0]
    bk = k_ref.shape[1]
    out_scale = _dropout_late(scale, rate)

    def head(t, rows, q0, sel=None):
        kv = _kv_at(k_ref, t)
        # the resident block takes the scale here: (q . k * scale); dk
        # needs the q blocks as they are
        ks, tile_scale = _scale_operand(k_ref[kv], scale)
        qb = q_ref[t, rows, :]
        dob = do_ref[t, rows, :]
        segq = segq_ref[0, 0, rows][:, None] if has_segments else None
        s = jax.lax.dot_general(
            qb, ks, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if tile_scale is not None:
            s = s * tile_scale
        if bias is not None:
            s = s + bias
        s = _mask(s, *_causal_pos(causal, q0, k0, bq, bk), segq, segk,
                  window, None if sel is None else sel())
        p = jnp.exp(s - lse_ref[t, 0, rows][:, None])
        if rate > 0.0:
            keep = _keep_tile(
                _keep_rows(q0, bq),
                _keep_cols(seed_ref[0], row * hp + t, kj * bk, bk), rate)
            p_keep = jnp.where(keep, p, 0.0)
        else:
            p_keep = p
        dv_acc_ref[kv] += jax.lax.dot_general(
            p_keep.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            dob, v_ref[kv], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if rate > 0.0:
            dp = jnp.where(keep, dp, 0.0)
        ds = p * (dp - delta_ref[t, 0, rows][:, None])
        dk_acc_ref[kv] += jax.lax.dot_general(
            ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * out_scale

    return head


def _dkv_finish(dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, rate: float):
    """A k block's dk and dv from the sums over its q blocks and heads."""
    dv = dv_acc_ref[...]
    if rate > 0.0:
        dv = dv / (1.0 - rate)
    dk_ref[...] = dk_acc_ref[...].astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _dkv_kernel(ids, seed_ref, q_ref, k_ref, v_ref, bias_ref, segq_ref,
                segk_ref, lse_ref, delta_ref, do_ref, dk_ref, dv_ref,
                dk_acc_ref, dv_acc_ref, *, scale: float, blk_q: int,
                rate: float, has_bias: bool, has_segments: bool, batch_of,
                causal: bool = False, window: int = 0, ranges_ref=None,
                sel_ref=None):
    """One program per (grid row, k-block): dk and dv of its heads'
    key/value heads (`sel_ref`: the k block's packed selection, transposed:
    `_select_tile` by q block). The query heads of a group add into the float32
    accumulators of their one key/value head, and ONE block a key/value
    head is written, in the parameters' dtype."""
    row, kj = ids
    hp = q_ref.shape[0]
    bk = k_ref.shape[1]
    s_len = q_ref.shape[1]
    nq = s_len // blk_q
    batch = batch_of(row)

    segk = _seg_keys(segk_ref[0, 0])[None, :] if has_segments else None
    krange = _block_range(ranges_ref, batch, nq + kj)
    k0 = kj * bk if causal else 0
    bias = bias_ref[0, 0][None, :] if has_bias else None    # (1, BLK_K)
    dk_acc_ref[...] = jnp.zeros(dk_acc_ref.shape, jnp.float32)
    dv_acc_ref[...] = jnp.zeros(dv_acc_ref.shape, jnp.float32)
    head = _dkv_heads(seed_ref, q_ref, k_ref, v_ref, segq_ref, lse_ref,
                      delta_ref, do_ref, dk_acc_ref, dv_acc_ref, row=row,
                      kj=kj, k0=k0, bq=blk_q, bias=bias, segk=segk,
                      scale=scale, rate=rate, has_segments=has_segments,
                      causal=causal, window=window)

    for i in range(nq):
        rows = slice(i * blk_q, (i + 1) * blk_q)
        _each_head(hp, functools.partial(head, rows=rows, q0=i * blk_q,
                                         **_sel_kw(sel_ref, i)),
                   _block_range(ranges_ref, batch, i), krange,
                   _causal_live(causal, i * blk_q, blk_q, k0, bk, window))

    _dkv_finish(dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, rate)


# The banded kernels (`_band_steps`): the same programs with the loop over
# the other side's blocks taken OUT of their text. The grid's last axis,
# sequential, walks the `nb` blocks of the band: step `d` of q block qi is k
# block j = qi - (nb - 1) + d, in ascending order as the unrolled loop ran
# its live tiles, so the sums are the same bit for bit (step d of k block kj
# is q block i = kj + d in the dkv kernel). k_ref / v_ref (q_ref, do_ref and
# the statistics in dkv) are BLOCKS fetched by an index map that clamps j at
# 0 (i at the row's last block): a step outside the row runs nothing and,
# its block index unchanged, fetches nothing. ONE tile body a kernel where
# the panel programs hold S / blk, and no (S, D) panel in VMEM.


def _fwd_band_kernel(ids, seed_ref, q_ref, k_ref, v_ref, bias_ref, segq_ref,
                     segk_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                     scale: float, nb: int, nblk: int, rate: float,
                     has_bias: bool, has_segments: bool, batch_of,
                     window: int, ranges_ref=None):
    """One program per (grid row, q-block, block of its band): the forward
    of its heads, begun at the band's first step and written at its last."""
    row, qi, step = ids
    hp, bq, _ = q_ref.shape
    bk = k_ref.shape[1]
    j = qi - (nb - 1) + step
    batch = batch_of(row)
    segq = segq_ref[0, 0][:, None] if has_segments else None
    segk = _seg_keys(segk_ref[0, 0])[None, :] if has_segments else None
    keep_rows = _keep_rows(qi * bq, bq) if rate > 0.0 else None
    pl.when(step == 0)(lambda: _fwd_start(m_ref, l_ref, acc_ref))
    head = _fwd_heads(seed_ref, q_ref, k_ref, v_ref, bias_ref, m_ref, l_ref,
                      acc_ref, row=row, q0=qi * bq, bk=bk, segq=segq,
                      keep_rows=keep_rows, scale=scale, rate=rate,
                      has_bias=has_bias, causal=True, window=window)
    _each_head(hp, functools.partial(head, cols=slice(None), k0=j * bk,
                                     segk=segk),
               _block_range(ranges_ref, batch, qi),
               _block_range(ranges_ref, batch,
                            nblk + jnp.maximum(j, 0)),
               j >= 0)
    pl.when(step == nb - 1)(lambda: _fwd_finish(
        o_ref, lse_ref, m_ref, l_ref, acc_ref, segq, rate))


def _dq_band_kernel(ids, seed_ref, q_ref, k_ref, v_ref, bias_ref, segq_ref,
                    segk_ref, lse_ref, delta_ref, do_ref, dq_ref, acc_ref, *,
                    scale: float, nb: int, nblk: int, rate: float,
                    has_bias: bool, has_segments: bool, batch_of,
                    window: int, ranges_ref=None):
    """One program per (grid row, q-block, block of its band): dq of its
    heads."""
    row, qi, step = ids
    hp, bq, _ = q_ref.shape
    bk = k_ref.shape[1]
    j = qi - (nb - 1) + step
    batch = batch_of(row)
    segq = segq_ref[0, 0][:, None] if has_segments else None
    segk = _seg_keys(segk_ref[0, 0])[None, :] if has_segments else None
    keep_rows = _keep_rows(qi * bq, bq) if rate > 0.0 else None

    @pl.when(step == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    head = _dq_heads(seed_ref, q_ref, k_ref, v_ref, bias_ref, lse_ref,
                     delta_ref, do_ref, acc_ref, row=row, q0=qi * bq, bk=bk,
                     segq=segq, keep_rows=keep_rows, scale=scale, rate=rate,
                     has_bias=has_bias, causal=True, window=window)
    _each_head(hp, functools.partial(head, cols=slice(None), k0=j * bk,
                                     segk=segk),
               _block_range(ranges_ref, batch, qi),
               _block_range(ranges_ref, batch,
                            nblk + jnp.maximum(j, 0)),
               j >= 0)

    @pl.when(step == nb - 1)
    def _():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_band_kernel(ids, seed_ref, q_ref, k_ref, v_ref, bias_ref, segq_ref,
                     segk_ref, lse_ref, delta_ref, do_ref, dk_ref, dv_ref,
                     dk_acc_ref, dv_acc_ref, *, scale: float, nb: int,
                     nblk: int, rate: float, has_bias: bool,
                     has_segments: bool, batch_of, window: int,
                     ranges_ref=None):
    """One program per (grid row, k-block, q block its band reaches): dk
    and dv of its heads' key/value heads."""
    row, kj, step = ids
    hp, bq, _ = q_ref.shape
    bk = k_ref.shape[1]
    i = kj + step
    batch = batch_of(row)
    segk = _seg_keys(segk_ref[0, 0])[None, :] if has_segments else None
    bias = bias_ref[0, 0][None, :] if has_bias else None

    @pl.when(step == 0)
    def _():
        dk_acc_ref[...] = jnp.zeros(dk_acc_ref.shape, jnp.float32)
        dv_acc_ref[...] = jnp.zeros(dv_acc_ref.shape, jnp.float32)

    head = _dkv_heads(seed_ref, q_ref, k_ref, v_ref, segq_ref, lse_ref,
                      delta_ref, do_ref, dk_acc_ref, dv_acc_ref, row=row,
                      kj=kj, k0=kj * bk, bq=bq, bias=bias, segk=segk,
                      scale=scale, rate=rate, has_segments=has_segments,
                      causal=True, window=window)
    _each_head(hp, functools.partial(head, rows=slice(None), q0=i * bq),
               _block_range(ranges_ref, batch, jnp.minimum(i, nblk - 1)),
               _block_range(ranges_ref, batch, nblk + kj),
               i < nblk)
    pl.when(step == nb - 1)(lambda: _dkv_finish(
        dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, rate))


def _dqkv_kernel(ids, seed_ref, q_ref, k_ref, v_ref, bias_ref, seg_ref,
                 lse_ref, delta_ref, do_ref, dq_ref, dk_ref, dv_ref, *,
                 scale: float, blk_q: int, blk_k: int, rate: float,
                 has_bias: bool, has_segments: bool, heads_per_prog: int,
                 heads_per_row: int, causal: bool = False):
    """Fused backward: one program per (row, head group) computes dq, dk
    and dv together for each of its heads, so the score tiles, softmax exp
    and dropout keep-masks are evaluated ONCE instead of once in _dq_kernel
    and again in _dkv_kernel. Same (rows, S, lanes) addressing as
    _fwd_kernel. The per-head accumulators live in VMEM — (S, D) fp32 x3 —
    which bounds this path to moderate S (_FUSED_BWD_MAX_PANEL); longer
    sequences take the split kernels."""
    row, group = ids
    s_len = q_ref.shape[1]
    d = q_ref.shape[2] // heads_per_prog
    nq = s_len // blk_q
    nk = s_len // blk_k
    out_scale = _dropout_late(scale, rate)
    skip = _tile_skip(has_segments, nq * nk)
    # what belongs to a q block or a k block alone, once for every head
    segqs = [seg_ref[0, 0, i * blk_q:(i + 1) * blk_q][:, None]
             if has_segments else None for i in range(nq)]
    segks = [_seg_keys(seg_ref[0, 0, j * blk_k:(j + 1) * blk_k])[None, :]
             if has_segments else None for j in range(nk)]
    qranges = [_seg_range(seg_ref[0, 0, i * blk_q:(i + 1) * blk_q][None, :])
               if skip else None for i in range(nq)]
    kranges = [_seg_range(segks[j]) if skip else None for j in range(nk)]
    keep_rows = [_keep_rows(i * blk_q, blk_q) if rate > 0.0 else None
                 for i in range(nq)]

    for t in range(heads_per_prog):
        lanes = slice(t * d, (t + 1) * d)
        bh = row * heads_per_row + group * heads_per_prog + t
        # per-k-block accumulators as plain Python lists — a (S, D)
        # functional scatter would lower to ops pallas rejects; disjoint
        # static blocks written once at the end need no scatter at all
        dk_blocks = [jnp.zeros((blk_k, d), jnp.float32) for _ in range(nk)]
        dv_blocks = [jnp.zeros((blk_k, d), jnp.float32) for _ in range(nk)]

        for i in range(nq):
            qb = q_ref[0, i * blk_q:(i + 1) * blk_q, lanes]
            # the scores take the scaled copy; dk needs q as it is
            qs, tile_scale = _scale_operand(qb, scale)
            dob = do_ref[0, i * blk_q:(i + 1) * blk_q, lanes]
            lse = lse_ref[0, 0, t, i * blk_q:(i + 1) * blk_q][:, None]
            delta = delta_ref[0, 0, t, i * blk_q:(i + 1) * blk_q][:, None]
            dq_i = jnp.zeros((blk_q, d), jnp.float32)
            for j in range(nk):
                if causal and j * blk_k > i * blk_q + blk_q - 1:
                    continue    # wholly above the diagonal (both static)

                def tile(carry, lanes=lanes, bh=bh, i=i, j=j, qb=qb, qs=qs,
                         dob=dob, lse=lse, delta=delta):
                    dq_i, dk_j, dv_j = carry
                    kb = k_ref[0, j * blk_k:(j + 1) * blk_k, lanes]
                    vb = v_ref[0, j * blk_k:(j + 1) * blk_k, lanes]
                    s = jax.lax.dot_general(
                        qs, kb, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    if tile_scale is not None:
                        s = s * tile_scale
                    if has_bias:
                        s = s + bias_ref[0, 0,
                                         j * blk_k:(j + 1) * blk_k][None, :]
                    s = _mask(s, *_causal_pos(causal, i * blk_q, j * blk_k,
                                              blk_q, blk_k),
                              segqs[i], segks[j])
                    p = jnp.exp(s - lse)
                    dp = jax.lax.dot_general(
                        dob, vb, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    if rate > 0.0:
                        keep = _keep_tile(
                            keep_rows[i],
                            _keep_cols(seed_ref[0], bh, j * blk_k, blk_k),
                            rate)
                        p_keep = jnp.where(keep, p, 0.0)
                        dp = jnp.where(keep, dp, 0.0)
                    else:
                        p_keep = p
                    ds = (p * (dp - delta)).astype(qb.dtype)
                    dq_i = dq_i + jnp.dot(
                        ds, kb, preferred_element_type=jnp.float32) \
                        * out_scale
                    dk_j = dk_j + jax.lax.dot_general(
                        ds, qb, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32) * out_scale
                    dv_j = dv_j + jax.lax.dot_general(
                        p_keep.astype(dob.dtype), dob,
                        (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    return dq_i, dk_j, dv_j

                dq_i, dk_blocks[j], dv_blocks[j] = _maybe_skip(
                    tile, (dq_i, dk_blocks[j], dv_blocks[j]),
                    qranges[i], kranges[j])
            dq_ref[0, i * blk_q:(i + 1) * blk_q, lanes] = dq_i.astype(
                dq_ref.dtype)

        for j in range(nk):
            rows = slice(j * blk_k, (j + 1) * blk_k)
            dv_j = dv_blocks[j] / (1.0 - rate) if rate > 0.0 else dv_blocks[j]
            dk_ref[0, rows, lanes] = dk_blocks[j].astype(dk_ref.dtype)
            dv_ref[0, rows, lanes] = dv_j.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# host-side wrappers
# ---------------------------------------------------------------------------

# Fused-backward bound on S * lanes of a program's (S, lanes) panels: its
# VMEM footprint is 8 double-buffered input/output panels plus 3 fp32 (S, D)
# accumulators per head. S=2048 with one D=64 head per program is the largest
# shape the v5e compiler accepts (tests/test_tpu_compile.py); two heads per
# program (native, D=64) or D=128 heads halve the admissible S. Beyond the
# bound the split dq / dkv kernels run, which exist in the bh layout only.
_FUSED_BWD_MAX_PANEL = 2048 * 64


# Beyond the fused backward's bound a program holds whole (S, D) panels of
# K and V (or Q and dO) beside its unrolled tiles: at S = 8192 that passes
# Mosaic's default 16 MiB of scoped VMEM (17.6 MB asked for the dq kernel
# at one head a program). Such calls ask for this much instead (a v5e core
# has 128 MiB); shorter sequences pass no parameter and compile as they
# always did.
_LONG_SEQ_VMEM_BYTES = 64 * 1024 * 1024
# What a bh-layout program takes of it beside its heads' panels
# (`_bh_heads_per_prog`): ONE head's tile (float32 (512, 512) scores,
# probabilities, their bf16 copies), the q / dO / output blocks and the
# heads' accumulators. Compiled for a described v5e: kimi's dq kernel at four
# heads asks 56.4 MiB, 48 of them panels.
_TILE_VMEM_BYTES = 16 * 1024 * 1024


def _long_seq_params(s: int, lanes: int, panels: int = 0) -> dict:
    """`panels`: the bytes of a bh-layout program's resident panels
    (`_panel_bytes` times its heads). A group of query heads is a program
    whatever its size (`_bh_heads_per_prog`), so where its panels leave less
    than `_TILE_VMEM_BYTES` of `_LONG_SEQ_VMEM_BYTES` (seven heads of 128 at
    S = 16,384: the dkv kernel's Q and dO panels are 56 MiB) the call asks
    for the panels plus that much; every other call asks what it always
    did."""
    if s * lanes <= _FUSED_BWD_MAX_PANEL:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=max(_LONG_SEQ_VMEM_BYTES,
                             panels + _TILE_VMEM_BYTES))}


def _panel_spec(block: tuple, index_map, long_seq: dict) -> pl.BlockSpec:
    """BlockSpec of a program's whole-sequence panels. Their block index
    changes once per S / blk programs, so where they are long
    (`_long_seq_params`: the bh layout alone) they are held in one buffer,
    not the pipeline's two."""
    if long_seq:
        return pl.BlockSpec(block, index_map, pipeline_mode=pl.Buffered(1))
    return pl.BlockSpec(block, index_map)


def _band_params(s: int, lanes: int) -> dict:
    """Compiler parameters of a banded call (`_band_steps`): the grid's
    last axis carries the accumulators from step to step, and a long row's
    program asks for a tile and its blocks, no panel."""
    from jax.experimental.pallas import tpu as pltpu

    vmem = ({} if s * lanes <= _FUSED_BWD_MAX_PANEL
            else {"vmem_limit_bytes": _TILE_VMEM_BYTES})
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"), **vmem)}


def _to_bh(x):
    """(B, S, H, D) -> (B*H, S, D)."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bh(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _heads_per_prog(h: int, d: int) -> int:
    """Heads that share one 128-lane block of the (B, S, H*D) view, or 0
    when the shape cannot be tiled that way. Mosaic wants a block's lane
    dim to be a multiple of 128, so D=64 heads go two to a program (static
    64-lane slices inside the kernel), D>=128 heads one."""
    if d % 128 == 0:
        return 1
    if 128 % d == 0 and h % (128 // d) == 0:
        return 128 // d
    return 0


def _use_native(s: int, h: int, d: int) -> bool:
    """Layout choice, by shape alone: native wherever heads tile into
    128-lane blocks and the fused backward fits them."""
    hp = _heads_per_prog(h, d)
    return hp > 0 and s * hp * d <= _FUSED_BWD_MAX_PANEL


class _Layout(NamedTuple):
    """How (B, S, H, D) operands are presented to the kernels: native, the
    (B, S, H * D) view walked by a (B, lane blocks, ...) grid; bh, the
    (B * H, S, D) view walked by a (B * H // heads_per_prog, 1, ...) grid
    whose blocks take `heads_per_prog` of its rows."""
    native: bool
    heads: int           # H
    rows: int            # grid rows
    groups: int          # lane blocks per row: H // heads_per_prog, or 1
    heads_per_prog: int

    @property
    def heads_per_row(self) -> int:
        """Stride of the kernels' dropout counter (row * this + head)."""
        return self.heads if self.native else self.heads_per_prog

    def pack(self, x):
        if self.native:
            b, s, h, d = x.shape
            return x.reshape(b, s, h * d)  # row-major view: moves no bytes
        return _to_bh(x)

    def unpack(self, x, b, s, d):
        if self.native:
            return x.reshape(b, s, self.heads, d)
        return _from_bh(x, b, x.shape[0] // b)

    def batch(self, row):
        """Grid row -> batch index (for the per-batch bias / segment
        operands)."""
        return row if self.native else row // (self.heads
                                               // self.heads_per_prog)

    def block(self, length: int, width: int, heads: int = 0) -> tuple:
        """Block shape of `heads` heads (default: a program's) over
        `length` positions."""
        n = heads or self.heads_per_prog
        return (1, length, n * width) if self.native else (n, length, width)

    def row_sums(self, x, s: int):
        """Float32 sums over the width of each head's rows of a packed
        array, in the layout of the forward kernel's logsumexp: native
        (B, groups, heads_per_prog, S), bh (B * H, 1, S)."""
        if self.native:
            return jnp.sum(x.reshape(self.rows, s, self.groups,
                                     self.heads_per_prog, -1),
                           axis=-1).transpose(0, 2, 3, 1)
        return jnp.sum(x, axis=-1)[:, None]

    def one_head(self) -> "_Layout":
        """The fused backward's grid: in the bh layout ONE head a program,
        whose (S, D) accumulators fill its VMEM bound."""
        if self.native:
            return self
        return self._replace(rows=self.rows * self.heads_per_prog,
                             heads_per_prog=1)


# Most heads of one bh-layout program (`_bh_heads_per_prog`; why a program
# owns several: the module docstring). Four is the most a cell has measured
# (lfm2: a group; kimi: 2 -> 4 heads took another fifth off its kernels).
_MAX_HEADS_PER_PROG = 4


def _panel_bytes(s: int, d: int, dv: int) -> int:
    """VMEM of ONE head's resident (S, D) and (S, Dv) panels (K and V in
    the forward and dq kernels, Q and dO in the dkv kernel): bf16, lanes
    padded to 128, in one buffer each (`_panel_spec`)."""
    pad = lambda w: -(-w // 128) * 128  # noqa: E731
    return 2 * s * (pad(d) + pad(dv))


def _bh_heads_per_prog(s: int, h: int, d: int, dv: int, group: int) -> int:
    """Heads of a bh-layout program, by shape alone: the query heads of a
    key/value head where heads are grouped (they read ONE K/V panel, and the
    dkv kernel adds their dk and dv in VMEM); otherwise the largest divisor
    of H up to `_MAX_HEADS_PER_PROG` whose resident panels leave
    `_TILE_VMEM_BYTES` of `_LONG_SEQ_VMEM_BYTES` to a tile."""
    if group > 1:
        return group
    for hp in range(min(h, _MAX_HEADS_PER_PROG), 1, -1):
        if h % hp == 0 and (hp * _panel_bytes(s, d, dv)
                            <= _LONG_SEQ_VMEM_BYTES - _TILE_VMEM_BYTES):
            return hp
    return 1


def _layout(b: int, s: int, h: int, d: int, group: int = 1,
            dv: int = 0, window: int = 0, select: bool = False) -> _Layout:
    """`group` query heads to a key/value head: grouped heads take the bh
    layout, where a program owns the group and its one key/value head; so do
    values of another width `dv` than the keys' (latent attention), a
    band (`window`) and a selection (`select`)."""
    dv = dv or d
    if (group == 1 and dv == d and not window and not select
            and _use_native(s, h, d)):
        hp = _heads_per_prog(h, d)
        return _Layout(True, h, b, h // hp, hp)
    hp = _bh_heads_per_prog(s, h, d, dv, group)
    return _Layout(False, h, b * h // hp, 1, hp)


def _seg_operand(segment_ids, b, s):
    """(B, S) int segment ids -> the (B, 1, S) kernel operand (mirrors the
    bias2 flattening so both layouts index it identically), or a (1, 1, 1)
    dummy when packing is off."""
    if segment_ids is None:
        return jnp.zeros((1, 1, 1), jnp.int32)
    return segment_ids.reshape(b, 1, s).astype(jnp.int32)


def _seed_operand(seed):
    return (jnp.zeros((1,), jnp.int32) if seed is None
            else jnp.asarray(seed, jnp.int32).reshape(1))


_DUMMY_BLOCK = (1, 1, 1)


def _per_batch_spec(present: bool, width: int, index_map):
    """BlockSpec of a (B, 1, S)-shaped per-batch operand (bias, segment
    ids), or of its (1, 1, 1) dummy when the operand is absent."""
    if present:
        return pl.BlockSpec((1, 1, width), index_map)
    return pl.BlockSpec(_DUMMY_BLOCK, lambda *_: (0, 0, 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def flash_attention(q, k, v, bias=None, segment_ids=None, dropout_seed=None,
                    dropout_rate: float = 0.0, interpret: bool = False,
                    causal: bool = False, window: int = 0):
    """q: (B, S, H, D); k: (B, S, Hkv, D), v: (B, S, Hkv, Dv) with H a
    multiple of Hkv (query head i reads key/value head i // (H // Hkv)) and
    Dv = D but for latent attention; `causal`: a query attends
    to positions <= its own; `window` (with `causal`; 0: none): and to the
    last `window` positions only, its own among them. bias: (B, 1, 1, S)
    additive or None;
    segment_ids: (B, S) int32 packing segments (1..n, 0 = pad) or None —
    attention is restricted to q_seg == k_seg blocks, the packed-sequence
    block-diagonal mask. dropout_seed: () or (1,) int32 array (traced OK);
    required when dropout_rate > 0. Returns (B, S, H, Dv) in q.dtype.

    NOTE: bias is treated as NON-differentiable (its cotangent is zero) —
    it exists for padding masks, which are data, not parameters. A trainable
    additive bias (e.g. relative-position bias) must use the XLA attention
    path, which differentiates through the bias correctly. segment_ids are
    integer data (zero/float0 cotangent), like the seed."""
    out, _ = _flash_fwd(q, k, v, bias, segment_ids, dropout_seed,
                        dropout_rate, interpret, causal, window)
    return out


def _kernel_name(name: str, d: int, dv: int, window: int = 0,
                 select: bool = False) -> str:
    """The kernels at values of a width of their own (latent attention:
    keys 192, values 128), the banded kernels (`flash_win_fwd`, ...) and
    those under a selection (`flash_sel_fwd`, ...) carry names of their own
    in the HLO and the device trace, so that what reads `flash_fwd` never
    reads them."""
    if window:
        name = name.replace("flash_", "flash_win_", 1)
    if select:
        name = name.replace("flash_", "flash_sel_", 1)
    return name if d == dv else "mla_" + name


def _scratch(*shapes) -> list:
    """float32 VMEM scratch of a bh-layout kernel: its heads' running
    results."""
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM(shape, jnp.float32) for shape in shapes]


def _band_kw(causal: bool, window: int) -> dict:
    """The kernels' `causal` and `window` keywords, each only where set, so
    that the bidirectional kernels, and the causal ones without a band,
    trace as they always did."""
    if window and not causal:
        raise ValueError("flash_attention: a window needs causal=True (the "
                         "bidirectional kernels have no band)")
    if window < 0:
        raise ValueError(f"flash_attention: window {window}")
    return dict({"causal": True} if causal else {},
                **({"window": int(window)} if window else {}))


# The block specs of a banded call (`_band_steps`), whose grid is (rows,
# blocks of the row, nb). `at(block, step)` is the operand's block along the
# row: `_near`, the block the program owns (its q block in fwd and dq, its k
# block in dkv), or the other side's block that the step walks to, clamped
# into the row (`_far_k`, `_far_q`).


def _near(block, step):
    return block


def _far_k(nb: int):
    """Step `step` of q block `block` is k block block - (nb - 1) + step."""
    return lambda block, step: jnp.maximum(block - (nb - 1) + step, 0)


def _far_q(nblk: int):
    """Step `step` of k block `block` is q block block + step."""
    return lambda block, step: jnp.minimum(block + step, nblk - 1)


def _band_block(heads: int, blk: int, width: int, at) -> pl.BlockSpec:
    return pl.BlockSpec((heads, blk, width),
                        lambda r, a, st: (r, at(a, st), 0))


def _band_stat(heads: int, blk: int, at) -> pl.BlockSpec:
    """A block of the heads' row statistics, (rows, 1, S) arrays."""
    return pl.BlockSpec((heads, 1, blk), lambda r, a, st: (r, 0, at(a, st)))


def _band_batch(present: bool, blk: int, lay: _Layout, at) -> pl.BlockSpec:
    return _per_batch_spec(
        present, blk, lambda r, a, st: (lay.batch(r), 0, at(a, st)))


def _band_fwd_call(seed_arr, qx, kx, vx, bias2, seg2, *, lay, nb, blk, kvh,
                   dv, interpret, has_bias, has_segments, window, **kw):
    """The banded forward: `_fwd_band_kernel` over (rows, q blocks, nb)."""
    hp, (_, s, d) = lay.heads_per_prog, qx.shape
    nblk = s // blk
    far = _far_k(nb)
    rng_spec, rng = _block_ranges(seg2, blk, blk,
                                  _tile_skip(has_segments, nblk * nblk))
    scratch = _scratch((hp, blk, 1), (hp, blk, 1), (hp, blk, dv))
    kernel = functools.partial(
        _fwd_band_kernel, nb=nb, nblk=nblk, batch_of=lay.batch,
        has_bias=has_bias, has_segments=has_segments, window=window, **kw)
    return pl.pallas_call(
        _program(kernel, 3, 2, None, bool(rng), len(scratch)),
        grid=(lay.rows, nblk, nb),
        in_specs=rng_spec + [
            pl.BlockSpec((1,), lambda r, qi, st: (0,)),      # seed
            _band_block(hp, blk, d, _near),
            _band_block(kvh, blk, d, far),
            _band_block(kvh, blk, dv, far),
            _band_batch(has_bias, blk, lay, far),
            _band_batch(has_segments, blk, lay, _near),
            _band_batch(has_segments, blk, lay, far),
        ],
        out_specs=[_band_block(hp, blk, dv, _near),
                   _band_stat(hp, blk, _near)],
        out_shape=[jax.ShapeDtypeStruct(qx.shape[:2] + (dv,), qx.dtype),
                   jax.ShapeDtypeStruct((qx.shape[0], 1, s), jnp.float32)],
        scratch_shapes=scratch,
        name=_kernel_name("flash_fwd", d, dv, window),
        interpret=interpret,
        **_band_params(s, hp * d),
    )(*rng, seed_arr, qx, kx, vx, bias2, seg2, seg2)


def _band_bwd_calls(seed_arr, qx, kx, vx, bias2, seg2, lse, delta, gx, *,
                    lay, nb, blk, kvh, interpret, has_bias, has_segments,
                    window, **kw):
    """The banded backward: `_dq_band_kernel` over (rows, q blocks, nb) and
    `_dkv_band_kernel` over (rows, k blocks, nb). -> dq, dk, dv."""
    hp, (_, s, d), dv = lay.heads_per_prog, qx.shape, vx.shape[2]
    nblk = s // blk
    rng_spec, rng = _block_ranges(seg2, blk, blk,
                                  _tile_skip(has_segments, nblk * nblk))
    operands = (*rng, seed_arr, qx, kx, vx, bias2, seg2, seg2, lse, delta,
                gx)
    kw = dict(kw, nb=nb, nblk=nblk, batch_of=lay.batch, has_bias=has_bias,
              has_segments=has_segments, window=window)
    common = dict(grid=(lay.rows, nblk, nb), interpret=interpret,
                  **_band_params(s, hp * d))
    seed_spec = pl.BlockSpec((1,), lambda r, a, st: (0,))

    def specs(q_at, k_at):
        """The ten operands after the ranges, q-side blocks at `q_at` and
        k-side blocks at `k_at`."""
        return rng_spec + [
            seed_spec,
            _band_block(hp, blk, d, q_at),
            _band_block(kvh, blk, d, k_at),
            _band_block(kvh, blk, dv, k_at),
            _band_batch(has_bias, blk, lay, k_at),
            _band_batch(has_segments, blk, lay, q_at),
            _band_batch(has_segments, blk, lay, k_at),
            _band_stat(hp, blk, q_at), _band_stat(hp, blk, q_at),
            _band_block(hp, blk, dv, q_at),
        ]

    dq = pl.pallas_call(
        _program(functools.partial(_dq_band_kernel, **kw), 3, 1, None,
                 bool(rng), 1),
        in_specs=specs(_near, _far_k(nb)),
        out_specs=_band_block(hp, blk, d, _near),
        out_shape=jax.ShapeDtypeStruct(qx.shape, qx.dtype),
        scratch_shapes=_scratch((hp, blk, d)),
        name=_kernel_name("flash_bwd_dq", d, dv, window),
        **common,
    )(*operands)
    dk, dvx = pl.pallas_call(
        _program(functools.partial(_dkv_band_kernel, **kw), 3, 2, None,
                 bool(rng), 2),
        in_specs=specs(_far_q(nblk), _near),
        out_specs=[_band_block(kvh, blk, d, _near),
                   _band_block(kvh, blk, dv, _near)],
        out_shape=[jax.ShapeDtypeStruct(kx.shape, kx.dtype),
                   jax.ShapeDtypeStruct(vx.shape, vx.dtype)],
        scratch_shapes=_scratch((kvh, blk, d), (kvh, blk, dv)),
        name=_kernel_name("flash_bwd_dkv", d, dv, window),
        **common,
    )(*operands)
    return dq, dk, dvx


def select_blocks(s: int) -> tuple:
    """(blk_q, blk_k, planes of the q blocks' words, planes of the k
    blocks'): the tiles a selection over rows of `s` positions is packed
    for (the module's comment at `_select_tile`)."""
    blk_q, blk_k = _pick_block(s, DEFAULT_BLK_Q), _pick_block(s, DEFAULT_BLK_K)
    return (blk_q, blk_k, -(-(s // blk_k) // SELECT_WORD),
            -(-(s // blk_q) // SELECT_WORD))


def _select_operand(words, lay: _Layout, block: tuple, at) -> tuple:
    """(in_specs, operands) of a packed selection: `words` (B, W, ., .)
    int32, a program's block all W planes of the `block` (rows, columns)
    that `at(block index)` places."""
    planes = words.shape[1]
    return [pl.BlockSpec(
        (1, planes) + block,
        lambda r, *ids: (lay.batch(r), 0) + at(ids[-1]))], [words]


def _check_select(select, b: int, s: int) -> None:
    blk_q, blk_k, wk, wq = select_blocks(s)
    want = ((b, wk, s, blk_k), (b, wq, blk_q, s))
    got = tuple(tuple(x.shape) for x in select)
    if got != want or any(x.dtype != jnp.int32 for x in select):
        raise ValueError(
            f"flash_select_attention: the selection packed by q block and "
            f"by k block has to be int32 of shapes {want}, got {got}")


def _flash_fwd(q, k, v, bias, segment_ids, seed, rate, interpret,
               causal=False, window=0, select=None):
    b, s, h, d = q.shape
    hkv, dv = k.shape[2], v.shape[3]
    if h % hkv or k.shape[:3] != v.shape[:3]:
        raise ValueError(f"flash_attention: {h} query heads over k"
                         f"{tuple(k.shape)} v{tuple(v.shape)}")
    ckw = _band_kw(causal, window)
    blk_q = _pick_block(s, DEFAULT_BLK_Q)
    blk_k = _pick_block(s, DEFAULT_BLK_K)
    scale = 1.0 / (d ** 0.5)
    has_bias = bias is not None
    has_segments = segment_ids is not None
    lay = _layout(b, s, h, d, h // hkv, dv, window, select is not None)
    hp = lay.heads_per_prog
    kvh = hp * hkv // h     # key/value heads of a program's heads
    # shared by both layouts: the cross-layout bit-parity contract depends
    # on identical bias flattening and seed packing, so they are built once
    bias2 = (bias.reshape(b, 1, s).astype(jnp.float32) if has_bias
             else jnp.zeros(_DUMMY_BLOCK, jnp.float32))
    seg2 = _seg_operand(segment_ids, b, s)
    qx, kx, vx = lay.pack(q), lay.pack(k), lay.pack(v)

    tiles = (s // blk_q) * (s // blk_k)
    nb = _band_steps(s, blk_q, blk_k, window)
    if nb:
        out, lse = _band_fwd_call(
            _seed_operand(seed), qx, kx, vx, bias2, seg2, lay=lay, nb=nb,
            blk=blk_q, kvh=kvh, dv=dv, interpret=interpret, scale=scale,
            rate=rate, has_bias=has_bias, has_segments=has_segments,
            window=int(window))
        out = checkpoint_name(out, "flash_out")
        lse = checkpoint_name(lse, "flash_lse")
        return lay.unpack(out, b, s, dv), (qx, kx, vx, bias2, seg2, lse, out)
    skip_rows = _skip_pad_rows(has_segments, tiles)
    live_spec, live = _live_rows(seg2, skip_rows)
    kw = dict(scale=scale, blk_k=blk_k, rate=rate, has_bias=has_bias,
              has_segments=has_segments, **ckw)
    params = _long_seq_params(s, hp * d, hp * _panel_bytes(s, d, dv))
    if lay.native:
        rng_spec, rng, scratch = [], [], []
        kernel = functools.partial(_fwd_kernel, heads_per_prog=hp,
                                   heads_per_row=lay.heads_per_row, **kw)
        lse_bs = pl.BlockSpec((1, 1, hp, blk_q),
                              lambda r, g, qi: (r, g, 0, qi))
        lse_shape = (lay.rows, lay.groups, hp, s)
    else:
        rng_spec, rng = _block_ranges(seg2, blk_q, blk_k,
                                      _tile_skip(has_segments, tiles))
        scratch = _scratch((hp, blk_q, 1), (hp, blk_q, 1), (hp, blk_q, dv))
        kernel = functools.partial(_fwd_bh_kernel, batch_of=lay.batch, **kw)
        lse_bs = pl.BlockSpec((hp, 1, blk_q), lambda r, g, qi: (r, 0, qi))
        lse_shape = (b * h, 1, s)
    sel_spec, sel = [], []
    if select is not None:
        sel_spec, sel = _select_operand(select[0], lay, (blk_q, blk_k),
                                        lambda qi: (qi, 0))
        kernel = _with_select(kernel)
    out, lse = pl.pallas_call(
        _program(kernel, 3, 2, lay.batch if skip_rows else None, bool(rng),
                 len(scratch)),
        grid=(lay.rows, lay.groups, s // blk_q),
        in_specs=live_spec + rng_spec + sel_spec + [
            pl.BlockSpec((1,), lambda r, g, qi: (0,)),      # seed
            pl.BlockSpec(lay.block(blk_q, d), lambda r, g, qi: (r, qi, g)),
            _panel_spec(lay.block(s, d, kvh), lambda r, g, qi: (r, 0, g),
                        params),
            _panel_spec(lay.block(s, dv, kvh), lambda r, g, qi: (r, 0, g),
                        params),
            _per_batch_spec(has_bias, s,
                            lambda r, g, qi: (lay.batch(r), 0, 0)),
            _per_batch_spec(has_segments, blk_q,
                            lambda r, g, qi: (lay.batch(r), 0, qi)),
            _per_batch_spec(has_segments, s,
                            lambda r, g, qi: (lay.batch(r), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec(lay.block(blk_q, dv), lambda r, g, qi: (r, qi, g)),
            lse_bs,
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qx.shape[:2] + (qx.shape[2] // d * dv,),
                                 q.dtype),
            jax.ShapeDtypeStruct(lse_shape, jnp.float32),
        ],
        scratch_shapes=scratch,
        name=_kernel_name("flash_fwd", d, dv, window, select is not None),
        interpret=interpret,
        **params,
    )(*live, *rng, *sel, _seed_operand(seed), qx, kx, vx, bias2, seg2, seg2)
    if causal:
        # names a rematerialising caller may keep (models/decoder.py
        # DENSE_SAVED): with both saved the backward pass finds the kernel's
        # results and does not run it again
        out = checkpoint_name(out, "flash_out")
        lse = checkpoint_name(lse, "flash_lse")
    return lay.unpack(out, b, s, dv), (qx, kx, vx, bias2, seg2, lse, out)


def _flash_fwd_rule(q, k, v, bias, segment_ids, seed, rate, interpret,
                    causal=False, window=0):
    out, res = _flash_fwd(q, k, v, bias, segment_ids, seed, rate, interpret,
                          causal, window)
    return out, (res, seed, q.shape, bias is not None,
                 segment_ids is not None)


def _flash_bwd_rule(rate, interpret, causal, window, saved, g, select=None):
    # residuals are in the kernel layout _flash_fwd chose (same
    # deterministic shape gate); lse is in `_Layout.row_sums`' layout
    (qx, kx, vx, bias2, seg2, lse, outx), seed, qshape, has_bias, \
        has_segments = saved
    b, s, h, d = qshape
    hkv = kx.size // (b * s * d)
    dv = vx.size // (b * s * hkv)
    blk_q = _pick_block(s, DEFAULT_BLK_Q)
    blk_k = _pick_block(s, DEFAULT_BLK_K)
    tiles = (s // blk_q) * (s // blk_k)
    skip_rows = _skip_pad_rows(has_segments, tiles)
    live_spec, live = _live_rows(seg2, skip_rows)
    scale = 1.0 / (d ** 0.5)
    lay = _layout(b, s, h, d, h // hkv, dv, window, select is not None)
    gx = lay.pack(g)
    # delta = rowsum(dO * O) per head (cheap elementwise — jnp, not a kernel)
    delta = lay.row_sums(gx.astype(jnp.float32) * outx.astype(jnp.float32),
                         s)
    if rate > 0.0:
        # the kernels subtract it from the UNscaled dp of the kept pairs and
        # put the dropout rescale on their (blk, D) results (_dropout_late)
        delta = delta * (1.0 - rate)
    seed_arr = _seed_operand(seed)
    kw = dict(scale=scale, rate=rate, has_bias=has_bias,
              has_segments=has_segments, **_band_kw(causal, window))

    one = lay.one_head()
    if (s * one.heads_per_prog * d <= _FUSED_BWD_MAX_PANEL and dv == d
            and hkv == h and not window and select is None):
        # fused dq/dk/dv kernel: scores, exp and dropout masks evaluated
        # once instead of twice
        hp = one.heads_per_prog
        lanes = hp * d
        qkv_bs = pl.BlockSpec((1, s, lanes), lambda r, g: (r, 0, g))
        stat_bs = pl.BlockSpec((1, 1, hp, s), lambda r, g: (r, g, 0, 0))
        stat_shape = (one.rows, one.groups, hp, s)
        per_batch = lambda r, g: (one.batch(r), 0, 0)  # noqa: E731
        dq, dk, dvx = pl.pallas_call(
            _program(
                functools.partial(_dqkv_kernel, blk_q=blk_q, blk_k=blk_k,
                                  heads_per_prog=hp,
                                  heads_per_row=one.heads_per_row, **kw),
                2, 3, one.batch if skip_rows else None),
            grid=(one.rows, one.groups),
            in_specs=live_spec + [
                pl.BlockSpec((1,), lambda r, g: (0,)),
                qkv_bs, qkv_bs, qkv_bs,
                _per_batch_spec(has_bias, s, per_batch),
                _per_batch_spec(has_segments, s, per_batch),
                stat_bs, stat_bs, qkv_bs,
            ],
            out_specs=[qkv_bs, qkv_bs, qkv_bs],
            out_shape=[jax.ShapeDtypeStruct(qx.shape, qx.dtype)] * 3,
            name="flash_bwd_dqkv",
            interpret=interpret,
        )(*live, seed_arr, qx, kx, vx, bias2, seg2,
          lse.reshape(stat_shape), delta.reshape(stat_shape), gx)
    elif nb := _band_steps(s, blk_q, blk_k, window):
        dq, dk, dvx = _band_bwd_calls(
            seed_arr, qx, kx, vx, bias2, seg2, lse, delta, gx, lay=lay,
            nb=nb, blk=blk_q, kvh=lay.heads_per_prog * hkv // h,
            interpret=interpret, scale=scale, rate=rate, has_bias=has_bias,
            has_segments=has_segments, window=int(window))
    else:
        # split kernels, bh layout only (_use_native excludes these shapes;
        # grouped heads and values of a width of their own take them at any
        # length)
        hp = lay.heads_per_prog
        kvh = hp * hkv // h
        params = _long_seq_params(s, hp * d, hp * _panel_bytes(s, d, dv))
        rng_spec, rng = _block_ranges(seg2, blk_q, blk_k,
                                      _tile_skip(has_segments, tiles))
        batch_of = lay.batch if skip_rows else None
        per_batch = lambda r, i: (lay.batch(r), 0, 0)  # noqa: E731
        per_batch_blk = lambda r, i: (lay.batch(r), 0, i)  # noqa: E731
        whole = lambda r, i: (r, 0, 0)  # noqa: E731
        blk = lambda r, i: (r, i, 0)  # noqa: E731

        q_blk_bs = pl.BlockSpec((hp, blk_q, d), blk)
        stat_blk_bs = pl.BlockSpec((hp, 1, blk_q), lambda r, qi: (r, 0, qi))
        dq_kernel = functools.partial(_dq_kernel, blk_k=blk_k,
                                      batch_of=lay.batch, **kw)
        dkv_kernel = functools.partial(_dkv_kernel, blk_q=blk_q,
                                       batch_of=lay.batch, **kw)
        selq_spec, selq, selk_spec, selk = [], [], [], []
        if select is not None:
            selq_spec, selq = _select_operand(
                select[0], lay, (blk_q, blk_k), lambda qi: (qi, 0))
            selk_spec, selk = _select_operand(
                select[1], lay, (blk_q, blk_k), lambda kj: (0, kj))
            dq_kernel = _with_select(dq_kernel)
            dkv_kernel = _with_select(dkv_kernel)
        named = functools.partial(_kernel_name, d=d, dv=dv, window=window,
                                  select=select is not None)
        dq = pl.pallas_call(
            _program(dq_kernel, 2, 1, batch_of, bool(rng), 1),
            grid=(lay.rows, s // blk_q),
            in_specs=live_spec + rng_spec + selq_spec + [
                pl.BlockSpec((1,), lambda r, qi: (0,)),
                q_blk_bs,
                _panel_spec((kvh, s, d), whole, params),
                _panel_spec((kvh, s, dv), whole, params),
                _per_batch_spec(has_bias, s, per_batch),
                _per_batch_spec(has_segments, blk_q, per_batch_blk),
                _per_batch_spec(has_segments, s, per_batch),
                stat_blk_bs, stat_blk_bs,
                pl.BlockSpec((hp, blk_q, dv), blk),
            ],
            out_specs=q_blk_bs,
            out_shape=jax.ShapeDtypeStruct(qx.shape, qx.dtype),
            scratch_shapes=_scratch((hp, blk_q, d)),
            name=named("flash_bwd_dq"),
            interpret=interpret,
            **params,
        )(*live, *rng, *selq, seed_arr, qx, kx, vx, bias2, seg2, seg2, lse,
          delta, gx)

        # ONE dk / dv block a key/value head, in the parameters' dtype: the
        # query heads of a group are added in the kernel's accumulators
        k_blk_bs = pl.BlockSpec((kvh, blk_k, d), blk)
        v_blk_bs = pl.BlockSpec((kvh, blk_k, dv), blk)
        stat_bs = _panel_spec((hp, 1, s), whole, params)
        dk, dvx = pl.pallas_call(
            _program(dkv_kernel, 2, 2, batch_of, bool(rng), 2),
            grid=(lay.rows, s // blk_k),
            in_specs=live_spec + rng_spec + selk_spec + [
                pl.BlockSpec((1,), lambda r, kj: (0,)),
                _panel_spec((hp, s, d), whole, params),
                k_blk_bs, v_blk_bs,
                _per_batch_spec(has_bias, blk_k, per_batch_blk),
                _per_batch_spec(has_segments, s, per_batch),
                _per_batch_spec(has_segments, blk_k, per_batch_blk),
                stat_bs, stat_bs,
                _panel_spec((hp, s, dv), whole, params),
            ],
            out_specs=[k_blk_bs, v_blk_bs],
            out_shape=[jax.ShapeDtypeStruct(kx.shape, kx.dtype),
                       jax.ShapeDtypeStruct(vx.shape, vx.dtype)],
            scratch_shapes=_scratch((kvh, blk_k, d), (kvh, blk_k, dv)),
            name=named("flash_bwd_dkv"),
            interpret=interpret,
            **params,
        )(*live, *rng, *selk, seed_arr, qx, kx, vx, bias2, seg2, seg2, lse,
          delta, gx)

    # bias is non-differentiable by contract (zero cotangent; see the
    # flash_attention docstring), segment ids and seed likewise — the
    # integer primals get float0 cotangents per JAX's convention (int32
    # zeros trip stricter custom_vjp aval checking)
    dbias = jnp.zeros((b, 1, 1, s), bias2.dtype) if has_bias else None
    dseg = None if not has_segments else jax.custom_derivatives \
        .zero_from_primal(seg2.reshape(b, s))
    dseed = None if seed is None else jax.custom_derivatives \
        .zero_from_primal(jnp.asarray(seed, jnp.int32))
    return (lay.unpack(dq, b, s, d), lay.unpack(dk, b, s, d),
            lay.unpack(dvx, b, s, dv), dbias, dseg, dseed)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def flash_select_attention(q, k, v, segment_ids, select_by_q, select_by_k,
                           interpret: bool = False):
    """Causal attention over the keys each query SELECTS (a learned-sparse
    layer, models/keye.py): `flash_attention(causal=True)` with one more
    condition in the tiles' mask, through the same bh tile bodies under
    kernel names of their own (`flash_sel_fwd`, `flash_sel_bwd_dq`,
    `flash_sel_bwd_dkv`). The selection is ONE a query token, shared by its
    heads, packed by block (`select_blocks`; ops/sparse_index.py packs it):
    `select_by_q` (B, W, S, blk_k) for the forward and dq kernels,
    `select_by_k` (B, W, blk_q, S) for the dkv kernel. A selected pair that
    is later than its query or of another document stays masked (the
    causal and segment conditions stand). Integer data: zero cotangents.
    No bias, no dropout, no band.

    -> (the context (B, S, H, D), lse (B, H, S) float32): the second is the
    forward kernel's residual, each head's log-sum-exp of its scaled scores
    over the query's selected keys (`_fwd_finish`), handed out for the
    caller that reads the same scores again (ops/sparse_index.index_kl's
    probabilities). It is data: a cotangent given to it is dropped."""
    return _flash_select_fwd_rule(q, k, v, segment_ids, select_by_q,
                                  select_by_k, interpret)[0]


def _flash_select_fwd_rule(q, k, v, segment_ids, select_by_q, select_by_k,
                           interpret):
    _check_select((select_by_q, select_by_k), q.shape[0], q.shape[1])
    out, res = _flash_fwd(q, k, v, None, segment_ids, None, 0.0, interpret,
                          True, 0, (select_by_q, select_by_k))
    # a selection takes the bh layout: the residual is (B * H, 1, S)
    *_, lse, _ = res
    lse = lse.reshape(q.shape[0], q.shape[2], q.shape[1])
    return (out, lse), (res, q.shape, segment_ids is not None, select_by_q,
                        select_by_k)


def _flash_select_bwd_rule(interpret, saved, cts):
    res, qshape, has_segments, by_q, by_k = saved
    dq, dk, dv, _, dseg, _ = _flash_bwd_rule(
        0.0, interpret, True, 0, (res, None, qshape, False, has_segments),
        cts[0], select=(by_q, by_k))
    zero = jax.custom_derivatives.zero_from_primal
    return dq, dk, dv, dseg, zero(by_q), zero(by_k)


flash_select_attention.defvjp(_flash_select_fwd_rule, _flash_select_bwd_rule)
