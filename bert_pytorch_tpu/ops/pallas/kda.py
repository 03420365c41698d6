"""Pallas TPU kernels of the gated delta-rule recurrence (ops/kda.py): the
chunks of a block one after another with the carried state in VMEM.

`kda_fwd` does what `lax.scan(_chunk_fwd)` does, and `kda_bwd` what the
backward pass's second forward scan and `lax.scan(_chunk_bwd, reverse=True)`
do, on what `_prepare` returns for a block of chunks, chunk-major: W, U',
the triangle P, Q and K decayed from and to the chunk's ends, and the
state's decay over the chunk. A scan sends the (heads, Dk, Dv) float32
state, and its cotangent on the way back, from HBM into every product of
every chunk and back, and the backward pass stacks the state of every chunk
in HBM to read it again in reverse; here a program owns a group of heads,
walks the block's chunks in order (the grid's last axis, "arbitrary") and
keeps its heads' state in the output block that it hands on when the block
ends. A backward program walks them twice, forward with every chunk's start
state kept in VMEM scratch and then in reverse with the state's cotangent.
So HBM sees a chunk's operands once a walk, its results once, a state once
a call, and the chunks' states never.

The same arithmetic as the scans: a product takes `mm_dtype` operands where
`_mm` casts them (cast here before the call, where they are `_prepare`'s
results, so that a program reads them at that width) and accumulates in
float32; the state, its cotangent, U', the decay and every sum are float32.
Only the order of float32 sums differs.

The state is carried TRANSPOSED, (B, H, Dv, Dk): the decay, one factor per
key channel, then multiplies along lanes, and S^T's products with a chunk's
(C, Dk) operands contract both last axes (the MXU's native A B^T). It goes
in and comes out of a call in that form and nothing outside the kernels
reads it (ops/kda.py starts a row from zeros and keeps one start state a
block). The products that contract over a chunk's tokens (K^T U for the
state; Q^T dO and W^T dU for its cotangent) take one operand transposed in
the kernel, in float32 before the cast; P^T comes transposed from outside
(`kda_bwd` says why).

What the chip taught (PERF.md section 6, PR 36). The loop over the chunks of
a grid step is rolled (`lax.fori_loop`: a body compiled once, fetched once,
PR 34), and the heads of a program run side by side inside it: a head's
chain of dependent products of M = 64 leaves the MXUs waiting, eight
independent chains fill them (the forward kernel: 3.7 ms a row at two heads
a pass, 2.1 at eight, 1.6 for its DMAs alone). Several chunks a grid step,
so that a step's DMAs are megabytes. And around the calls: XLA:TPU assigns
layouts backwards from a custom call's fixed ones, so what a call takes and
what leaves the scan it stands in decide how the plain-XLA algebra beside it
is laid out (`ops/kda._row_major`, P^T below).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# head-chunks (a head's tile of one chunk) a grid step: 32 of them are 3-6 MB
# of operands and results at a head of 128 and a chunk of 64
_TILES_PER_STEP = 32
# heads a program: the decay's (heads, Dk) tile wants 8 sublanes
_HEADS_PER_PROGRAM = 8
# heads of one pass of the rolled loop over a program's heads, side by side
# (all eight of a program: the loop then has one pass): their chains of
# products are independent, which is all the scheduler has to hide a
# product's latency behind (the forward kernel alone on the chip, ms a row
# of 256 chunks: 3.98 / 3.74 / 3.63 / 2.08 at 1 / 2 / 4 / 8 heads a pass,
# against 1.59 for its DMAs alone)
_HEADS_UNROLLED = 8
_VMEM_MARGIN_BYTES = 16 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))      # a (m, k), b (n, k) -> (m, n)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    return jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)


def _tiles(heads: int, chunks: int) -> tuple:
    """(heads a program, chunks a grid step)."""
    hp = _HEADS_PER_PROGRAM if heads % _HEADS_PER_PROGRAM == 0 else heads
    ck = max(1, min(chunks, _TILES_PER_STEP // hp))
    while chunks % ck:
        ck -= 1
    return hp, ck


def _each(chunks: int, heads: int, body, reverse: bool = False) -> None:
    """body(c, t) for every chunk c of the grid step, in order (or in
    reverse), and every head t of the program: the chunks a rolled loop,
    the heads `_HEADS_UNROLLED` a pass of a rolled loop inside it (no loop
    where that is all of them)."""
    side = _HEADS_UNROLLED if heads % _HEADS_UNROLLED == 0 else 1

    def chunk(i, _):
        c = chunks - 1 - i if reverse else i

        def heads_of_pass(j, _):
            for t in range(side):
                body(c, j * side + t)

        if heads == side:
            heads_of_pass(0, None)
        else:
            jax.lax.fori_loop(0, heads // side, heads_of_pass, None)

    jax.lax.fori_loop(0, chunks, chunk, None)


def _fwd_kernel(state_ref, w_ref, u_ref, k_ref, decay_ref, p_ref, q_ref,
                out_ref, new_ref, *, chunks, heads, mm_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _():
        new_ref[...] = state_ref[...]

    def body(c, t):
        st = new_ref[0, t]                                  # (Dv, Dk)
        sb = st.astype(mm_dtype)
        u = u_ref[c, 0, t] - _dot_nt(w_ref[c, 0, t], sb)    # (C, Dv)
        out_ref[c, 0, t] = (_dot_nt(q_ref[c, 0, t], sb)
                            + _dot(p_ref[c, 0, t], u.astype(mm_dtype)))
        new_ref[0, t] = (decay_ref[c, 0, pl.ds(t, 1), :] * st
                         + _dot(u.T.astype(mm_dtype), k_ref[c, 0, t]))

    _each(chunks, heads, body)


def _bwd_kernel(state_ref, dstate_ref, w_ref, u_ref, k_ref, decay_ref,
                pt_ref, q_ref, dout_ref, dw_ref, du_ref, dp_ref, dq_ref,
                dk_ref, ddecay_ref, dnew_ref, st_ref, states_ref, *, steps,
                chunks, heads, mm_dtype):
    """Two walks over the block's chunks, the grid's last axis 2 x `steps`
    long: forward, keeping in VMEM (`states_ref`) the state every chunk
    starts from (what the backward pass's second forward scan stacks in
    HBM); then in reverse, carrying the state's cotangent."""
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        st_ref[...] = state_ref[0]

    @pl.when(i == steps)
    def _():
        dnew_ref[...] = dstate_ref[...]

    def forward(c, t):
        st = st_ref[t]                                      # (Dv, Dk)
        states_ref[i * chunks + c, t] = st
        u = u_ref[c, 0, t] - _dot_nt(w_ref[c, 0, t], st.astype(mm_dtype))
        st_ref[t] = (decay_ref[c, 0, pl.ds(t, 1), :] * st
                     + _dot(u.T.astype(mm_dtype), k_ref[c, 0, t]))

    def backward(c, t):
        st = states_ref[(2 * steps - 1 - i) * chunks + c, t]
        ds = dnew_ref[0, t]                                 # (Dv, Dk)
        sb, dsb = st.astype(mm_dtype), ds.astype(mm_dtype)
        w, q, k = w_ref[c, 0, t], q_ref[c, 0, t], k_ref[c, 0, t]
        dout = dout_ref[c, 0, t]                            # (C, Dv)
        ub = (u_ref[c, 0, t] - _dot_nt(w, sb)).astype(mm_dtype)
        du = _dot(pt_ref[c, 0, t], dout) + _dot_nt(k, dsb)  # (C, Dv)
        dub = du.astype(mm_dtype)
        dw_ref[c, 0, t] = -_dot(dub, sb)
        du_ref[c, 0, t] = du
        dp_ref[c, 0, t] = _dot_nt(dout, ub)
        dq_ref[c, 0, t] = _dot(dout, sb)
        dk_ref[c, 0, t] = _dot(ub, dsb)
        ddecay_ref[c, 0, pl.ds(t, 1), :] = jnp.sum(ds * st, axis=0,
                                                   keepdims=True)
        dout_t = dout.astype(jnp.float32).T.astype(mm_dtype)
        dnew_ref[0, t] = (_dot(dout_t, q)
                          + decay_ref[c, 0, pl.ds(t, 1), :] * ds
                          - _dot(du.T.astype(mm_dtype), w))

    pl.when(i < steps)(lambda: _each(chunks, heads, forward))
    pl.when(i >= steps)(lambda: _each(chunks, heads, backward,
                                      reverse=True))


def _call(kernel, name, grid, in_blocks, out_blocks, operands, out_dtypes,
          interpret, scratch=()):
    """One pallas_call over (batch, head groups, chunk steps): blocks are
    (block shape, index map), and the call asks for the VMEM of its blocks
    twice over (the pipeline's two buffers) and a margin for the body."""
    from jax.experimental.pallas import tpu as pltpu

    def nbytes(block, dtype):
        n = jnp.dtype(dtype).itemsize
        for dim in block[0]:
            n *= dim
        return n

    need = 2 * (sum(nbytes(b, x.dtype) for b, x in zip(in_blocks, operands))
                + sum(nbytes(b, d[1]) for b, d in zip(out_blocks,
                                                      out_dtypes)))
    need += sum(nbytes((shape,), jnp.float32) for shape in scratch)
    return pl.pallas_call(
        kernel, grid=grid,
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in scratch],
        in_specs=[pl.BlockSpec(*b) for b in in_blocks],
        out_specs=[pl.BlockSpec(*b) for b in out_blocks],
        out_shape=[jax.ShapeDtypeStruct(s, d) for s, d in out_dtypes],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=need + _VMEM_MARGIN_BYTES),
        name=name, interpret=interpret)(*operands)


def _block_specs(shape, hp, ck, at=lambda i: i):
    """The block shapes and index maps of a call's operands, by kind, for
    chunk-major arrays (chunks, B, H, ...) of `shape` = (chunks, B, H, C,
    Dk, Dv) and the (B, H, Dv, Dk) state; `at`: the chunk step of a grid
    step."""
    _, _, _, c, dk, dv = shape

    def per_chunk(*tail):
        zeros = (0,) * len(tail)
        return ((ck, 1, hp) + tail, lambda b, g, i: (at(i), b, g) + zeros)

    return {"state": ((1, hp, dv, dk), lambda b, g, i: (b, g, 0, 0)),
            "k": per_chunk(c, dk), "v": per_chunk(c, dv),
            "p": per_chunk(c, c),
            # (ck, 1, hp, Dk): no token axis
            "decay": ((ck, 1, hp, dk), lambda b, g, i: (at(i), b, g, 0))}


def _once_a_signature(fn):
    """fn jitted on its arrays: a step calls each kernel once a KDA layer
    and pass at one signature, and a jitted function is traced and lowered
    once a signature. The kernels' bodies hold eight heads' operations:
    0.9 s a call site to trace and lower otherwise, 10.7 s of the kimi
    cell's set-up on the chip's host."""
    return jax.jit(fn, static_argnames=("mm_dtype", "interpret"))


def _fwd_block(state, w, u, p, q_start, k_end, decay, *, mm_dtype,
            interpret: bool):
    """A block's chunks forward. state (B, H, Dv, Dk) float32, the carried
    state transposed; w, q_start, k_end (chunks, B, H, C, Dk), u (chunks, B,
    H, C, Dv), p (chunks, B, H, C, C), decay (chunks, B, H, Dk), as
    `_prepare` returns them. Returns (the state after the block, the
    outputs (chunks, B, H, C, Dv) float32)."""
    n, b, h, c, dk = w.shape
    dv = u.shape[-1]
    hp, ck = _tiles(h, n)
    blk = _block_specs((n, b, h, c, dk, dv), hp, ck)
    f32 = jnp.float32
    w, p, q_start, k_end = (x.astype(mm_dtype)
                            for x in (w, p, q_start, k_end))
    out, new = _call(
        functools.partial(_fwd_kernel, chunks=ck, heads=hp,
                          mm_dtype=mm_dtype),
        "kda_fwd", (b, h // hp, n // ck),
        [blk["state"], blk["k"], blk["v"], blk["k"], blk["decay"], blk["p"],
         blk["k"]],
        [blk["v"], blk["state"]],
        (state, w, u.astype(f32), k_end, decay.astype(f32), p, q_start),
        [((n, b, h, c, dv), f32), (state.shape, f32)], interpret)
    return new, out


def _bwd_block(state, dstate, w, u, p, q_start, k_end, decay, dout, *, mm_dtype,
            interpret: bool):
    """The same chunks forward again from `state`, then in reverse. dstate
    (B, H, Dv, Dk) the cotangent of the state the block hands on
    (transposed like the state), dout (chunks, B, H, C, Dv) the outputs'
    cotangent. Returns (the cotangent of the state the block started from,
    those of w, u, p, q_start, k_end, decay, float32). The state every chunk
    starts from lives in VMEM between the two walks: (chunks, heads a
    program, Dv, Dk) float32, 16 MiB at 32 chunks of 8 heads of 128."""
    n, b, h, c, dk = w.shape
    dv = u.shape[-1]
    hp, ck = _tiles(h, n)
    steps = n // ck
    shape = (n, b, h, c, dk, dv)
    # both walks read these: forward, then back
    both = _block_specs(
        shape, hp, ck, lambda i: jnp.where(i < steps, i, 2 * steps - 1 - i))
    # the reverse walk's alone: they wait at its first step through the
    # forward walk (one fetch; an output block is written back when its
    # index moves on, after the reverse walk has filled it)
    back = _block_specs(
        shape, hp, ck, lambda i: 2 * steps - 1 - jnp.maximum(i, steps))
    # P goes in transposed: the backward reads P^T alone, and a call that
    # holds P itself to row-major order drags the inverse's chain of (C, C)
    # products, which XLA:TPU computes in the transposed domain, into six
    # transposing copies a block (0.8 ms each a row on the chip)
    w, pt, q_start, k_end, dout = (x.astype(mm_dtype) for x in (
        w, jnp.swapaxes(p, -1, -2), q_start, k_end, dout))
    f32 = jnp.float32
    *dprep, dnew = _call(
        functools.partial(_bwd_kernel, steps=steps, chunks=ck, heads=hp,
                          mm_dtype=mm_dtype),
        "kda_bwd", (b, h // hp, 2 * steps),
        [both["state"], both["state"], both["k"], both["v"], both["k"],
         both["decay"], back["p"], back["k"], back["v"]],
        [back["k"], back["v"], back["p"], back["k"], back["k"],
         back["decay"], back["state"]],
        (state, dstate, w, u.astype(f32), k_end, decay.astype(f32), pt,
         q_start, dout),
        [((n, b, h, c, dk), f32), ((n, b, h, c, dv), f32),
         ((n, b, h, c, c), f32), ((n, b, h, c, dk), f32),
         ((n, b, h, c, dk), f32), ((n, b, h, dk), f32), (dstate.shape, f32)],
        interpret, scratch=((hp, dv, dk), (n, hp, dv, dk)))
    return dnew, tuple(dprep)


kda_fwd = _once_a_signature(_fwd_block)
kda_bwd = _once_a_signature(_bwd_block)
