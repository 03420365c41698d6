"""The rotation's kernels (ops/pallas/rotary.py: `rotary_fwd` / `rotary_bwd`,
in interpret mode on the CPU) against the plain function of
ops/decoder_ops.py: the forward against the function, the hand-written rule
against `jax.vjp` of it, over the forms the decoder families call it in; and
the dispatch: which calls take the kernels, and that the calls which do not
trace what they traced before."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bert_pytorch_tpu.ops import decoder_ops
from bert_pytorch_tpu.ops.decoder_ops import rotary, rotary_table

D = 128
S = 96          # three tiles of 32 rows
# documents that end inside a tile (rows 0-31, 32-63, 64-95), a padded tail
CUTS = [[0, 9, 70, 90], [0, 96]]
YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
# (table: inverse frequencies or None for theta alone, rotated width, factor)
TABLES = {
    "whole": (None, D, 1.0),
    "whole_factor": (None, D, 1.4158883),
    "half": (1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64.0), 64, 1.0),
    "half_factor": (1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64.0), 64,
                    1.4158883),
    "yarn": rotary_table(D, YARN),
}


@pytest.fixture
def kernels(monkeypatch):
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")


def _positions():
    pos = np.zeros((len(CUTS), S), np.int32)
    for r, cuts in enumerate(CUTS):
        for a, b in zip(cuts[:-1], cuts[1:]):
            pos[r, a:b] = np.arange(b - a)
    return jnp.asarray(pos)


def _plain(x, pos, table):
    inv_freq, rotated, factor = table
    return decoder_ops._rotary_plain(x, pos, 10000.0, inv_freq, rotated,
                                     factor)


def _kernel(x, pos, table, **kw):
    inv_freq, rotated, factor = table
    return rotary(x, pos, 10000.0, inv_freq=inv_freq, rotated=rotated,
                  factor=factor, **kw)


def _ulps_bf16(got, want):
    """The largest gap between two bfloat16 arrays, in units of the last
    place of the larger of each pair."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(
        np.maximum(np.abs(got), np.abs(want)), 1e-30))) - 7)
    return float(np.max(np.abs(got - want) / ulp))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("heads", [72, 56, 32, 36])
@pytest.mark.parametrize("table", sorted(TABLES))
def test_kernels_against_the_plain_function(kernels, table, heads, dtype):
    """Forward: the result in bfloat16 within one unit of the last place of
    the plain function's (equal before the rounding up to float32's own
    last places); the rule: dx against jax.vjp of the plain function, in
    float32 to 1e-6 of the cotangent's scale."""
    rng = np.random.default_rng(heads)
    x = jnp.asarray(rng.standard_normal((2, S, heads, D)), dtype)
    dy = jnp.asarray(rng.standard_normal((2, S, heads, D)), jnp.float32)
    pos = _positions()
    want, pull = jax.vjp(lambda u: _plain(u.astype(jnp.float32), pos,
                                          TABLES[table]), x)
    got32, pull_kernel = jax.vjp(lambda u: _kernel(u, pos, TABLES[table]), x)
    assert got32.dtype == jnp.float32 and got32.shape == want.shape
    np.testing.assert_allclose(np.asarray(got32), np.asarray(want),
                               rtol=0, atol=1e-6 * 8)
    got = _kernel(x, pos, TABLES[table], out_dtype=jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    assert _ulps_bf16(got, want.astype(jnp.bfloat16)) <= 1.0
    (dx,), (dx_kernel,) = pull(dy), pull_kernel(dy)
    assert dx_kernel.dtype == x.dtype and dx_kernel.shape == x.shape
    if dtype == jnp.float32:
        scale = float(jnp.max(jnp.abs(dx)))
        np.testing.assert_allclose(np.asarray(dx_kernel), np.asarray(dx),
                                   rtol=0, atol=1e-6 * scale)
    else:
        assert _ulps_bf16(dx_kernel, dx) <= 1.0


@pytest.mark.parametrize("first,heads", [(0, 48), (48, 8), (28, 4)])
def test_heads_read_from_the_fused_projection(kernels, first, heads):
    """`heads=`: the heads' columns of a wider matrix, read where they lie;
    the cotangent is the matrix's, zero in its other columns."""
    rng = np.random.default_rng(first)
    width = (first + heads + 8) * D
    x = jnp.asarray(rng.standard_normal((2, S, width)), jnp.bfloat16)
    dy = jnp.asarray(rng.standard_normal((2, S, heads, D)), jnp.bfloat16)
    pos = _positions()
    table = TABLES["yarn"]

    def cut(u):
        return u[..., first * D:(first + heads) * D].reshape(2, S, heads, D)

    want, pull = jax.vjp(lambda u: _plain(cut(u).astype(jnp.float32), pos,
                                          table).astype(jnp.bfloat16), x)
    got, pull_kernel = jax.vjp(lambda u: _kernel(
        u, pos, table, out_dtype=jnp.bfloat16, heads=(first, heads, D)), x)
    assert got.shape == (2, S, heads, D)
    assert _ulps_bf16(got, want) <= 1.0
    (dx,), (dx_kernel,) = pull(dy), pull_kernel(dy)
    assert dx_kernel.shape == x.shape and dx_kernel.dtype == x.dtype
    assert _ulps_bf16(dx_kernel, dx) <= 1.0
    outside = np.ones(width, bool)
    outside[first * D:(first + heads) * D] = False
    assert not np.asarray(dx_kernel, np.float32)[..., outside].any()
    # the plain function takes the same argument
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("BPT_PALLAS_INTERPRET", "0")
        plain = _kernel(x, pos, table, out_dtype=jnp.bfloat16,
                        heads=(first, heads, D))
    np.testing.assert_array_equal(np.asarray(plain, np.float32),
                                  np.asarray(want, np.float32))


def test_which_calls_take_the_kernels(monkeypatch):
    """By what the call can see: heads of 128 lanes, rows that tile, a TPU
    backend or the test switch. Heads of 64 (lfm2's, keye's index heads),
    rows that do not tile and every call on the CPU keep the plain
    function."""
    pos = jnp.zeros((1, S), jnp.int32)
    wide = jnp.zeros((1, S, 4, D), jnp.bfloat16)
    narrow = jnp.zeros((1, S, 4, 64), jnp.bfloat16)
    ragged = jnp.zeros((1, 90, 4, D), jnp.bfloat16)

    def call(x):
        return rotary(x, pos[:, :x.shape[1]], 10000.0)

    def _kernel_calls(fn, x) -> int:
        # a function of its own a call: a traced one is not traced again
        return str(jax.make_jaxpr(lambda u: fn(u))(x)).count("rotary_fwd")

    assert _kernel_calls(call, wide) == 0           # the CPU, no switch
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    assert _kernel_calls(call, wide) == 1
    assert _kernel_calls(call, narrow) == 0
    assert _kernel_calls(call, ragged) == 0
    text = str(jax.make_jaxpr(jax.grad(lambda x: call(x).sum()))(
        wide.astype(jnp.float32)))
    assert text.count("rotary_fwd") == 1 and text.count("rotary_bwd") == 1


def _rotary_before(x, position_ids, theta):
    """ops/decoder_ops.rotary as it stood before the kernels (the whole
    head at theta), to the line."""
    d = x.shape[-1]
    r = d
    inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32)
                                / r))
    angles = position_ids.astype(jnp.float32)[:, :, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, :, None, :]
    x = x.astype(jnp.float32)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + half * sin


def test_lfm2_traces_what_it_traced_before(kernels):
    """Heads of 64: the call's jaxpr, forward and transposed, is the one the
    function had before the kernels, and a whole lfm2 layer stack under the
    test switch holds no kernel of the rotation."""
    from tests import test_lfm2_moe as toy
    from bert_pytorch_tpu.config import Lfm2MoeConfig
    from bert_pytorch_tpu.models import lfm2_moe

    pos = _positions()
    x = jnp.zeros((2, S, 4, 64), jnp.float32)

    def graph(fn):
        return str(jax.make_jaxpr(jax.value_and_grad(
            lambda u: fn(u, pos, 1e6).astype(jnp.bfloat16).astype(
                jnp.float32).sum()))(x))

    assert graph(rotary) == graph(_rotary_before)

    cfg = Lfm2MoeConfig.from_dict(toy.TOY).replace(dtype="float32")
    model = lfm2_moe.Lfm2MoeForCausalLM(cfg, dtype=jnp.float32)
    ids, seg, pos = (jnp.asarray(a) for a in toy._packed())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids, seg, pos)
    loss_fn = lfm2_moe.pretrain_loss_fn_builder(model)
    batch = {"input_ids": ids, "segment_ids": seg, "position_ids": pos}
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(p, batch, jax.random.PRNGKey(0), True)[0]))(
            params["params"]))
    assert "rotary_fwd" not in text and "rotary_bwd" not in text
    assert "cos" in text       # the rotation is there, as plain jax.numpy
