"""Kimi Delta Attention's recurrence: the gated delta rule with a decay per
channel, computed in chunks, forward and a hand-written backward.

Per head, with a state S (Dk x Dv) that is zero before the first token of
each document (and at every padding slot),

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

(g_t <= 0 the log-decay per key channel, beta_t in (0, 1)). Writing
u_t = beta_t (v_t - S_{t-1}^T (exp(g_t) * k_t)), the state is a decayed sum
of k_i u_i^T, so inside a chunk of C tokens that starts from S_0, with
G_t = g_1 + ... + g_t:

    A_ti = beta_t (k_t * exp(G_t)) . (k_i * exp(-G_i))      (i < t)
    (I + A) U = beta * V - (beta * K * exp(G)) S_0           (the WY form)
    O = (Q * exp(G)) S_0 + tril(Q exp(G) (K exp(-G))^T) U    (i <= t)
    S_C = exp(G_C) * S_0 + (K * exp(G_C - G))^T U

`T = (I + A)^-1` of the unit lower-triangular system is the product
(I - A)(I + A^2)(I + A^4)... (A is nilpotent), in float32 at the highest
matmul precision. A document boundary is a mask: pairs of different
documents leave A and the triangle of Q K^T, and a token past a boundary
in its chunk does not see S_0 (nor does S_C, past one). exp(G) and exp(-G)
are taken about the chunk's middle token where they multiply each other,
which keeps both finite while a channel's log-decay over half a chunk
stays above -88 (float32); beyond that the chunk size is too long for the
decay and the results are not finite.

What runs where: the chunk-local algebra (`_prepare`) over a block of
`block` chunks at once, as batched matrix products in plain XLA; the carried
state through the block's chunks one after another (`_chunks_fwd`); blocks
one after another (an outer scan, one start state kept a block), so that
the temporaries are one block's. The backward pass is written here, not
derived: per block, from the state at its start, the chunks' start states
again and then the chunks in reverse carrying dS (`_chunks_bwd`), then the
chunk-local algebra's cotangents (`jax.vjp` of `_prepare`, whose inverse
has its own rule, dA = -T^T dT T^T). Matrix products take `mm_dtype`
operands and accumulate in float32; decays, their sums, beta, the inverse
and the carried state are float32.

The chunks of a block are walked in one of two ways, chosen by what can be
observed when the call is traced (`kernel_mode`; no flag):
- on a TPU backend (elsewhere only under BPT_PALLAS_INTERPRET=1, in
  interpret mode), on one device, where the head widths are multiples of 128
  and the chunk a multiple of 8: the Pallas kernels `kda_fwd` / `kda_bwd`
  (ops/pallas/kda.py), one call a block and pass, the state, its cotangent
  and (backward) every chunk's start state in VMEM, so that no loop over
  chunks is left in the program;
- otherwise `_chunk_fwd` / `_chunk_bwd` under `lax.scan`: every CPU run, the
  toy widths of the tests, a mesh, and the kernels' oracle (the same
  products, float32 sums in another order).
models/kimi_linear.py counts the tokens of the first way as
`kda_kernel_tokens` beside `kda_tokens`.

Scope `kda/scan` (training/pretrain.LM_STEP_SCOPES), opened in every body
and around the kernels' calls: inside a scan or a custom rule an operation
keeps the scopes of the body. Under it (training/pretrain.STEP_SUBSCOPES)
`kda/scan/prepare` around every call of `_prepare` and of its pullback, and
`kda/scan/prepare/inverse` around `unit_lower_inverse` and in both of its
rules; each is opened by its whole path, because a transform's wrapper
(`transpose(jvp(...))`) comes between a scope opened outside it and one
opened inside. What stays directly under `kda/scan` is the chunk-major
transposes, the outer scan's slices and the chunks' walk (the kernels, or
the two scans).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

SCOPE = "kda/scan"
PREPARE_SCOPE = SCOPE + "/prepare"
INVERSE_SCOPE = PREPARE_SCOPE + "/inverse"
_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(eq: str, a, b, dtype):
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def _mm32(a, b):
    return jnp.matmul(a, b, precision=_HIGHEST)


@jax.custom_vjp
def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """(I + a)^-1 for strictly lower-triangular a (..., C, C), float32:
    (I - a)(I + a^2)(I + a^4)..., exact because a^C = 0."""
    with jax.named_scope(INVERSE_SCOPE):
        c = a.shape[-1]
        t = jnp.eye(c, dtype=a.dtype) - a
        power, n = a, 2
        while n < c:
            power = _mm32(power, power)         # a^n
            t = t + _mm32(t, power)
            n *= 2
        return t


def _inverse_fwd(a):
    t = unit_lower_inverse(a)
    return t, t


def _inverse_bwd(t, dt):
    with jax.named_scope(INVERSE_SCOPE):
        tt = jnp.swapaxes(t, -1, -2)
        return (-_mm32(_mm32(tt, dt), tt),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _prepare(q, k, v, g, beta, rid, prev, mm_dtype):
    """The chunk-local algebra of any number of chunks at once. q, k, v, g
    (..., H, C, D), beta (..., H, C), rid (..., C) the tokens' document
    runs, prev (...) the run of the token before the chunk. Returns what
    the recurrence reads: W, U' (the solved right-hand sides for K and V),
    the masked triangle P of Q K^T, Q and K decayed from and to the chunk's
    ends, and the state's decay over the chunk."""
    c = q.shape[-2]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    same = (rid[..., :, None] == rid[..., None, :])[..., None, :, :]
    seen = (rid == prev[..., None])[..., None, :, None]     # sees S_0
    to_end = same[..., c - 1, :, None]                      # reaches S_C
    lower = jnp.tril(jnp.ones((c, c), bool), -1)
    cum = jnp.cumsum(g.astype(jnp.float32), axis=-2)
    about = cum - jax.lax.stop_gradient(cum[..., c // 2:c // 2 + 1, :])
    up, down = jnp.exp(about), jnp.exp(-about)
    from_start = jnp.exp(cum)
    k_down = k * down
    bk = beta[..., None]
    # masked BEFORE beta multiplies: above the diagonal the product of the
    # two exponentials may be inf or nan, and 0 * inf in beta's cotangent
    # is not 0
    a = bk * jnp.where(same & lower, _mm("...td,...id->...ti", k * up,
                                         k_down, mm_dtype), 0.0)
    t = unit_lower_inverse(a)
    w = _mm("...ti,...id->...td", t,
            jnp.where(seen, bk * k * from_start, 0.0), mm_dtype)
    u = _mm("...ti,...id->...td", t, bk * v, mm_dtype)
    p = jnp.where(same & (lower | jnp.eye(c, dtype=bool)),
                  _mm("...td,...id->...ti", q * up, k_down, mm_dtype), 0.0)
    q_start = jnp.where(seen, q * from_start, 0.0)
    k_end = jnp.where(to_end, k * jnp.exp(cum[..., c - 1:, :] - cum), 0.0)
    decay = jnp.where(seen[..., c - 1, :], from_start[..., c - 1, :], 0.0)
    return w, u, p, q_start, k_end, decay


def _chunk_fwd(mm_dtype, state, chunk):
    """One chunk of every head: state (B, H, Dk, Dv) float32 in, (state
    out, (outputs (B, H, C, Dv), the state it started from))."""
    with jax.named_scope(SCOPE):
        w, u, p, q_start, k_end, decay = chunk
        u = u - _mm("bhck,bhkv->bhcv", w, state, mm_dtype)
        out = (_mm("bhck,bhkv->bhcv", q_start, state, mm_dtype)
               + _mm("bhct,bhtv->bhcv", p, u, mm_dtype))
        new = decay[..., None] * state + _mm("bhck,bhcv->bhkv", k_end, u,
                                             mm_dtype)
        return new, (out, state)


def _chunk_bwd(mm_dtype, dstate, chunk):
    """The same chunk in reverse: dstate is the cotangent of the state it
    hands on; returns that of the state it started from and of everything
    `_prepare` gave it."""
    with jax.named_scope(SCOPE):
        (w, u, p, q_start, k_end, decay), state, dout = chunk
        u = u - _mm("bhck,bhkv->bhcv", w, state, mm_dtype)
        du = (_mm("bhct,bhcv->bhtv", p, dout, mm_dtype)
              + _mm("bhck,bhkv->bhcv", k_end, dstate, mm_dtype))
        dprep = (-_mm("bhcv,bhkv->bhck", du, state, mm_dtype), du,
                 _mm("bhcv,bhtv->bhct", dout, u, mm_dtype),
                 _mm("bhcv,bhkv->bhck", dout, state, mm_dtype),
                 _mm("bhcv,bhkv->bhck", u, dstate, mm_dtype),
                 jnp.sum(dstate * state, axis=-1))
        dstate = (_mm("bhck,bhcv->bhkv", q_start, dout, mm_dtype)
                  + decay[..., None] * dstate
                  - _mm("bhck,bhcv->bhkv", w, du, mm_dtype))
        return dstate, dprep


def kernel_mode(head_dim: int, value_dim: int, chunk: int, block: int):
    """How the chunks of a block are walked, by what can be observed: None,
    the XLA scans (`_chunk_fwd` / `_chunk_bwd`); else the Pallas kernels of
    ops/pallas/kda.py, and the value is their `interpret` argument: False on
    a TPU backend, True elsewhere under BPT_PALLAS_INTERPRET=1 (the
    convention of ops/attention.py and ops/layernorm.py). The kernels want
    head widths that fill the lanes (multiples of 128), a chunk of whole
    sublanes (a multiple of 8), a block whose chunk states fit VMEM beside
    the tiles (`kda_bwd` keeps eight heads' between its two walks: 16 MiB
    at 32 chunks of 128 x 128; up to 64 MiB), and one device: a mesh cannot
    split them."""
    from bert_pytorch_tpu.ops.attention import _pallas_interpret, active_mesh

    on_tpu = jax.default_backend() == "tpu"
    if (head_dim % 128 or value_dim % 128 or chunk % 8
            or block * 8 * head_dim * value_dim * 4 > 64 * 1024 * 1024
            or not (on_tpu or _pallas_interpret())
            or active_mesh() is not None):
        return None
    return not on_tpu


def _row_major(x):
    """x, held to the layout its shape spells. The kernels take and return
    chunk-major arrays in that layout; the stacked gradients that leave the
    backward pass's scan over blocks are free, and with the kernels in its
    body XLA:TPU's layout assignment lays them out tokens-major for the
    transposition that follows the scan, and every fusion of the body with
    them: transposing copies and strided fusions around each call (the
    compiled step then holds 1,188 arrays in such layouts where the scans'
    step holds 20, and the kernels' gain goes to them)."""
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


def _chunks_fwd(mm_dtype, kernels, state, prep):
    """The chunks of a block one after another from `state`: (the state
    after the last, the outputs). The state is (B, H, Dk, Dv) under the
    scans and its transpose under the kernels (ops/pallas/kda.py: nothing
    else reads it)."""
    if kernels is None:
        new, (out, _) = jax.lax.scan(
            functools.partial(_chunk_fwd, mm_dtype), state, prep)
        return new, out
    from bert_pytorch_tpu.ops.pallas.kda import kda_fwd

    with jax.named_scope(SCOPE):
        return kda_fwd(state, *prep, mm_dtype=mm_dtype, interpret=kernels)


def _chunks_bwd(mm_dtype, kernels, state, dstate, prep, dout):
    """The same chunks again from `state`, for the state each starts from,
    then in reverse from the cotangent of the state they hand on: (that of
    the state they started from, those of `prep`)."""
    if kernels is None:
        _, (_, states) = jax.lax.scan(
            functools.partial(_chunk_fwd, mm_dtype), state, prep)
        return jax.lax.scan(functools.partial(_chunk_bwd, mm_dtype), dstate,
                            (prep, states, dout), reverse=True)
    from bert_pytorch_tpu.ops.pallas.kda import kda_bwd

    with jax.named_scope(SCOPE):
        return kda_bwd(state, dstate, *prep, dout, mm_dtype=mm_dtype,
                       interpret=kernels)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _blocks(q, k, v, g, beta, rid, prev, mm_dtype, kernels):
    """Blocks of chunks, chunk-major: q, k, v, g (blocks, chunks, B, H, C,
    D), beta (blocks, chunks, B, H, C), rid (blocks, chunks, B, C), prev
    (blocks, chunks, B) -> outputs like v, float32. `kernels`:
    `kernel_mode`'s answer."""
    return _blocks_fwd(q, k, v, g, beta, rid, prev, mm_dtype, kernels)[0]


def _blocks_fwd(q, k, v, g, beta, rid, prev, mm_dtype, kernels):
    b, h, d, dv = q.shape[2], q.shape[3], q.shape[5], v.shape[5]

    def body(state, block):
        with jax.named_scope(PREPARE_SCOPE):
            prep = _prepare(*block, mm_dtype)
        new, out = _chunks_fwd(mm_dtype, kernels, state, prep)
        return new, (out, state)

    _, (out, starts) = jax.lax.scan(
        body, jnp.zeros((b, h, d, dv) if kernels is None else (b, h, dv, d),
                        jnp.float32),
        (q, k, v, g, beta, rid, prev))
    return out, (q, k, v, g, beta, rid, prev, starts)


def _blocks_bwd(mm_dtype, kernels, saved, dout):
    q, k, v, g, beta, rid, prev, starts = saved

    def body(dstate, block):
        state, dout, *inputs = block
        with jax.named_scope(PREPARE_SCOPE):
            prep, pull = jax.vjp(
                lambda *x: _prepare(*x, *inputs[5:], mm_dtype), *inputs[:5])
        dstate, dprep = _chunks_bwd(mm_dtype, kernels, state, dstate, prep,
                                    dout)
        with jax.named_scope(PREPARE_SCOPE):
            grads = pull(dprep)
        if kernels is not None:
            with jax.named_scope(SCOPE):
                grads = tuple(_row_major(x) for x in grads)
        return dstate, grads

    _, grads = jax.lax.scan(
        body, jnp.zeros_like(starts[0]),
        (starts, dout, q, k, v, g, beta, rid, prev), reverse=True)
    zero = jax.custom_derivatives.zero_from_primal
    return (*grads, zero(rid), zero(prev))


_blocks.defvjp(_blocks_fwd, _blocks_bwd)


def chunk_counts(seq_len: int, chunk: int, block: int) -> Tuple[int, int]:
    """(chunks a row is computed in, chunks a block): the row is padded to
    whole blocks."""
    n = -(-seq_len // chunk)
    per_block = min(block, n)
    return -(-n // per_block) * per_block, per_block


def kda_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, starts: jax.Array, chunk: int = 64,
             block: int = 32, mm_dtype=jnp.bfloat16) -> jax.Array:
    """The recurrence of the module docstring over rows: q, k (B, S, H, Dk),
    v (B, S, H, Dv), g (B, S, H, Dk) float32 log-decays, beta (B, S, H),
    starts (B, S) bool, true where the state restarts BEFORE the token (a
    document's first token; every padding slot). Returns o (B, S, H, Dv)
    float32. `chunk` tokens a chunk, `block` chunks a block (memory: one
    block's temporaries are alive)."""
    with jax.named_scope(SCOPE):
        b, s, h, _ = q.shape
        n, per_block = chunk_counts(s, chunk, block)
        pad = n * chunk - s

        def chunked(x, fill=0):
            # (B, S, ...) -> (blocks, chunks, B, ..., C, trailing)
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2),
                        constant_values=fill)
            x = x.reshape((b, n // per_block, per_block, chunk)
                          + x.shape[2:])
            return jnp.moveaxis(x, 0, 2)

        def heads_first(x):     # (..., B, C, H, D) -> (..., B, H, C, D)
            return jnp.swapaxes(chunked(x), 3, 4)

        # a padded slot restarts the state: it touches nothing before it
        run = jnp.cumsum(jnp.pad(starts, ((0, 0), (0, pad)),
                                 constant_values=True), axis=1,
                         dtype=jnp.int32)
        before = jnp.pad(run, ((0, 0), (1, 0)))[:, :-1:chunk]   # (B, n)
        rid = jnp.moveaxis(run.reshape(b, n // per_block, per_block, chunk),
                           0, 2)
        prev = jnp.moveaxis(before.reshape(b, n // per_block, per_block),
                            0, 2)
        out = _blocks(heads_first(q), heads_first(k), heads_first(v),
                      heads_first(g),
                      jnp.swapaxes(chunked(beta.astype(jnp.float32)), 3, 4),
                      rid, prev, mm_dtype,
                      kernel_mode(q.shape[-1], v.shape[-1], chunk,
                                  per_block))
        # (blocks, chunks, B, H, C, Dv) -> (B, S, H, Dv)
        out = jnp.moveaxis(jnp.swapaxes(out, 3, 4), 2, 0)
        return out.reshape(b, n * chunk, h, -1)[:, :s]
