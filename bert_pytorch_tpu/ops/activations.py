"""Activation functions.

Parity targets: the reference's erf-based gelu / bias_gelu / swish and its
ACT2FN registry (reference src/modeling.py:118-139). On TPU, XLA fuses the
bias-add + activation into the preceding matmul's epilogue, so `bias_gelu`
exists mainly to keep the "fused bias+act" call-shape of the reference's
LinearActivation (src/modeling.py:141-180) available to model code.

What `gelu` keeps for its backward pass: ONE array of its input's shape and
dtype, the derivative gelu'(x). Autodiff of `jax.nn.gelu(x,
approximate=False)` keeps three (0.5*x, the erfc and an exp), and in the
encoder each is a (B, S, F) array that the forward layer scan stacks over L
layers and the backward scan slices out again, beside the activation's
output that the next matmul keeps for its weight gradient: four wide arrays
a layer where two say everything (PERF.md section 6, PR 29).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_INV_SQRT_2PI = float(1.0 / np.sqrt(2.0 * np.pi))


def _gelu_and_erfc(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """`jax.nn.gelu(x, approximate=False)` as JAX 0.9 writes it, in x's
    dtype, and the erfc it evaluated (tests/test_model.py holds the output
    to jax.nn.gelu's bit for bit, so a release that changes the formula is
    caught there)."""
    sqrt_half = np.sqrt(0.5).astype(x.dtype)
    e = lax.erfc(-x * sqrt_half)
    return jnp.array(0.5 * x * e, dtype=x.dtype), e


@jax.custom_vjp
def gelu(x: jax.Array) -> jax.Array:
    """Exact (erf) GELU — matches the reference's non-approximate formula
    (src/modeling.py:118-123), not the tanh approximation."""
    return _gelu_and_erfc(x)[0]


def _gelu_fwd(x: jax.Array) -> tuple[jax.Array, tuple[jax.Array]]:
    y, e = _gelu_and_erfc(x)
    # gelu'(x) = Phi(x) + x * phi(x), with Phi(x) = erfc(-x / sqrt 2) / 2 from
    # the erfc the output used (about a hundred float32 operations an
    # element: evaluated once), in float32 and rounded once to x's dtype.
    # The barrier makes "the erfc the output used" hold in the compiled
    # program too: XLA:TPU clones an elementwise producer into every fusion
    # that reads it, and evaluated the polynomial once for the next
    # matmul's input and once more where the residuals are written
    # (PERF.md section 6, PR 29). Where the derivative is not wanted (the
    # first forward under nn.remat) the barrier is dead code and goes.
    x32 = x.astype(jnp.float32)
    phi = jnp.exp(-0.5 * x32 * x32) * _INV_SQRT_2PI
    e32 = lax.optimization_barrier(e).astype(jnp.float32)
    d = (0.5 * e32 + x32 * phi).astype(x.dtype)
    return y, (d,)


def _gelu_bwd(res: tuple[jax.Array], dy: jax.Array) -> tuple[jax.Array]:
    (d,) = res
    return (dy * d,)


gelu.defvjp(_gelu_fwd, _gelu_bwd)


def bias_gelu(bias: jax.Array, y: jax.Array) -> jax.Array:
    """Fused bias-add + exact GELU (reference src/modeling.py:126-131)."""
    return gelu(y + bias)


def swish(x: jax.Array) -> jax.Array:
    return x * jax.nn.sigmoid(x)


def relu(x: jax.Array) -> jax.Array:
    return jax.nn.relu(x)


def tanh(x: jax.Array) -> jax.Array:
    return jnp.tanh(x)


ACT2FN = {
    "gelu": gelu,
    "bias_gelu": bias_gelu,
    "relu": relu,
    "swish": swish,
    "tanh": tanh,
}
