"""The banded form of the causal flash kernels (ops/pallas/flash_attention.py,
`_band_steps`): where a band spans fewer blocks than the row, `flash_win_fwd`
/ `flash_win_bwd_dq` / `flash_win_bwd_dkv` walk the band's blocks as a grid
axis over K / V (Q / dO) BLOCKS, one tile body a kernel, no (S, D) panel.

Which calls take it and how many tiles they visit (shapes alone); forward and
all three gradients against the XLA path in interpret mode over 128 x 128
tiles, at every width of band the index maps tell apart, at groups of 1, 7
and 8 heads, on packed rows with a boundary inside the band, with a bias and
with dropout; blocks outside the band are never read (NaN there reaches no
output); and calls without a band, or whose band spans the row, never reach
the banded calls."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bert_pytorch_tpu.ops.attention import dot_product_attention

NAMES = ("flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv")


@pytest.fixture
def fa(monkeypatch):
    """The kernels' module in interpret mode over 128 x 128 tiles."""
    monkeypatch.setenv("BPT_PALLAS_INTERPRET", "1")
    mod = importlib.import_module(
        "bert_pytorch_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(mod, "DEFAULT_BLK_Q", 128)
    monkeypatch.setattr(mod, "DEFAULT_BLK_K", 128)
    return mod


@pytest.mark.parametrize("window,nb,tiles", [
    (512, 2, 63),       # laguna: one tile wide, 63 of the row's 32 x 32
    (4096, 9, 252),     # smallthinker: today's live count, all of them live
    (1, 1, 32), (2, 2, 63), (513, 2, 63), (514, 3, 93),
])
def test_band_blocks_and_visited_tiles_at_the_cells_row(fa, window, nb,
                                                        tiles):
    """S = 16,384 over 512 x 512 tiles: the blocks of a q block's band, the
    (q block, k block) pairs a head's programs visit, each of them live by
    the test the panel-walking kernels make, and no live pair left out."""
    s, blk = 16384, 512
    assert fa.band_blocks(blk, window) == nb
    assert fa.band_tiles(s, blk, window) == tiles
    assert fa._band_steps(s, blk, blk, window) == nb
    live = {(qi, j) for qi in range(s // blk) for j in range(s // blk)
            if fa._causal_live(True, qi * blk, blk, j * blk, blk, window)}
    visited = {(qi, qi - (nb - 1) + d) for qi in range(s // blk)
               for d in range(nb) if qi - (nb - 1) + d >= 0}
    assert visited == live and len(live) == tiles
    # the dkv kernel's walk: step d of k block kj is q block kj + d
    assert {(kj + d, kj) for kj in range(s // blk) for d in range(nb)
            if kj + d < s // blk} == live


@pytest.mark.parametrize("s,blk_q,blk_k,window,steps", [
    (16384, 512, 512, 0, 0),            # no band
    (16384, 512, 512, 15362, 0),        # 32 blocks: the band spans the row
    (16384, 512, 512, 15361, 31),       # one block short of it
    (16384, 512, 512, 1 << 20, 0),      # wider than the row
    (16384, 512, 256, 512, 0),          # unequal blocks keep the panels
    (1024, 128, 128, 769, 7), (1024, 128, 128, 770, 0),
    (256, 128, 128, 40, 0),             # two blocks a row, two a band
])
def test_the_form_follows_the_shapes(fa, s, blk_q, blk_k, window, steps):
    assert fa._band_steps(s, blk_q, blk_k, window) == steps


def _case(s, group, hkv, d, packed, seed=0):
    h = hkv * group
    keys = jax.random.split(jax.random.PRNGKey(seed + s + group), 4)
    q = jax.random.normal(keys[0], (2, s, h, d))
    k = jax.random.normal(keys[1], (2, s, hkv, d))
    v = jax.random.normal(keys[2], (2, s, hkv, d))
    seg = None
    valid = jnp.ones((2, s), bool)
    if packed:
        # boundaries inside a band and off the tiles' edges (150, 300 + a
        # tile), a one-tile document, and a padded tail
        seg = np.zeros((2, s), np.int32)
        for g, (a, b) in enumerate(zip((0, 150, 428), (150, 428, s))):
            seg[0, a:b] = g + 1
        seg[1, :128], seg[1, 128:s - 90] = 1, 2
        seg = jnp.asarray(seg)
        valid = seg > 0
    weight = jax.random.normal(keys[3], (2, s, h, d)) \
        * valid[:, :, None, None]           # no loss term reads padding
    return q, k, v, seg, weight


CASES = [
    # id, S, window, band's blocks, group, key/value heads, D, packed, extra
    ("under-a-block-g1", 512, 40, 2, 1, 2, 64, True, None),
    ("one-block-g8", 512, 128, 2, 8, 1, 128, True, None),   # laguna's case
    ("blocks-and-a-remainder-g7", 1024, 300, 4, 7, 1, 64, True, None),
    ("under-the-panel-threshold-g1", 1024, 769, 7, 1, 2, 64, True, None),
    ("full-rows-g7", 512, 200, 3, 7, 1, 64, False, None),
    ("bias-g4", 512, 128, 2, 4, 1, 64, False, "bias"),
    ("dropout-g4", 512, 128, 2, 4, 1, 128, True, "dropout"),
]


def _mirror(fa, q, k, v, seg, bias, window, seed, rate):
    """Plain float32 attention under the band with the kernels' keep mask
    (the XLA path draws another)."""
    b, s, h, d = q.shape
    group = h // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d)
    pos = jnp.arange(s)
    dist = pos[:, None] - pos[None, :]
    allowed = ((dist >= 0) & (dist < window))[None, None]
    if seg is not None:
        allowed = allowed & (seg[:, None, :, None] == seg[:, None, None, :])
    if bias is not None:
        sc = sc + bias
    p = jax.nn.softmax(jnp.where(allowed, sc, -1e30), axis=-1)
    keep = jnp.stack([jnp.stack([
        fa._keep_mask(seed, bi * h + hh, 0, 0, s, s, rate)
        for hh in range(h)]) for bi in range(b)])
    out = jnp.einsum("bhqk,bkhd->bqhd",
                     jnp.where(keep, p / (1 - rate), 0.0), v)
    if seg is not None:
        out = out * (seg > 0)[:, :, None, None]
    return out


@pytest.mark.parametrize("name,s,window,nb,group,hkv,d,packed,extra", CASES,
                         ids=[c[0] for c in CASES])
def test_banded_form_matches_xla(fa, monkeypatch, name, s, window, nb, group,
                                 hkv, d, packed, extra):
    """Forward and all three gradients of the banded form against
    `dot_product_attention(impl="xla", causal=True, window=W)` (with
    dropout: against a mirror under the kernels' own keep mask), and
    against the panel-walking form of the same call, whose tiles it runs in
    the same order: bit for bit."""
    assert fa._band_steps(s, 128, 128, window) == nb
    q, k, v, seg, weight = _case(s, group, hkv, d, packed)
    bias = seed = None
    rate = 0.0
    if extra == "bias":
        bias = jnp.where(jnp.arange(s) % 5 == 3, -10000.0, 0.0) \
            * jnp.ones((2, 1, 1, 1))
    if extra == "dropout":
        seed, rate = jnp.asarray(11, jnp.int32), 0.2

    def kernels(q, k, v):
        out = fa.flash_attention(q, k, v, bias, seg, seed, rate, True, True,
                                 window)
        return jnp.sum(out * weight), out

    def xla(q, k, v):
        if extra == "dropout":
            out = _mirror(fa, q, k, v, seg, bias, window, seed, rate)
        else:
            out = dot_product_attention(q, k, v, bias=bias, segment_ids=seg,
                                        impl="xla", causal=True,
                                        window=window)
        return jnp.sum(out * weight), out

    grad = lambda f: jax.value_and_grad(  # noqa: E731
        f, argnums=(0, 1, 2), has_aux=True)
    (_, got), got_grads = grad(kernels)(q, k, v)
    (_, want), want_grads = grad(xla)(q, k, v)
    np.testing.assert_allclose(got, want, atol=3e-5)
    for a, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, w, atol=1e-4)
    text = str(jax.make_jaxpr(grad(kernels))(q, k, v))
    assert all(n in text for n in NAMES)
    assert not re.search(r"\bflash_(fwd|bwd)", text)

    monkeypatch.setattr(fa, "_band_steps", lambda *a: 0)
    (_, panel), panel_grads = grad(kernels)(q, k, v)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(panel))
    for a, w in zip(got_grads, panel_grads):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w))


def test_blocks_outside_the_band_are_never_read(fa):
    """S = 1,024 in eight blocks under a band of 200, three blocks wide.
    Forward and dq: NaN in every K and V block behind the band of the last
    three q blocks leaves their rows finite and the XLA path's. dkv: NaN in
    every Q and dO block beyond the reach of the first three k blocks
    leaves their dk and dv so. A visited block outside the band would run
    masked, and 0 x NaN poisons."""
    s, window, blk = 1024, 200, 128
    assert fa._band_steps(s, blk, blk, window) == 3
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(keys[0], (1, s, 8, 64))
    k = jax.random.normal(keys[1], (1, s, 1, 64))
    v = jax.random.normal(keys[2], (1, s, 1, 64))
    g = jax.random.normal(keys[3], (1, s, 8, 64))

    def vjp(impl, q, k, v, g):
        if impl == "xla":
            f = lambda q, k, v: dot_product_attention(  # noqa: E731
                q, k, v, impl="xla", causal=True, window=window)
        else:
            f = lambda q, k, v: fa.flash_attention(  # noqa: E731
                q, k, v, None, None, None, 0.0, True, True, window)
        out, pull = jax.vjp(f, q, k, v)
        return (out,) + pull(g)

    # q blocks 5..7 reach back to block 3: blocks 0..2 lie behind their band
    rows = slice(5 * blk, s)
    behind = slice(0, 3 * blk)
    g_rows = jnp.zeros_like(g).at[:, rows].set(g[:, rows])
    out, dq, _, _ = vjp("pallas", q, k.at[:, behind].set(jnp.nan),
                        v.at[:, behind].set(jnp.nan), g_rows)
    want_out, want_dq, _, _ = vjp("xla", q, k, v, g_rows)
    for got, want in ((out, want_out), (dq, want_dq)):
        assert np.isfinite(np.asarray(got[:, rows])).all()
        np.testing.assert_allclose(got[:, rows], want[:, rows], atol=5e-5)
    # and a k block's band ends two q blocks after its own: k blocks 0..2
    # are read by q blocks 0..4 and no later one
    cols = slice(0, 3 * blk)
    beyond = slice(5 * blk, s)
    _, _, dk, dv = vjp("pallas", q.at[:, beyond].set(jnp.nan), k, v,
                       g.at[:, beyond].set(jnp.nan))
    g_cols = jnp.zeros_like(g).at[:, :5 * blk].set(g[:, :5 * blk])
    _, _, want_dk, want_dv = vjp("xla", q, k, v, g_cols)
    for got, want in ((dk, want_dk), (dv, want_dv)):
        assert np.isfinite(np.asarray(got[:, cols])).all()
        np.testing.assert_allclose(got[:, cols], want[:, cols], atol=5e-5)
    assert np.isnan(np.asarray(dk[:, 3 * blk:])).any()


@pytest.mark.parametrize("window", [0, 1024, 1 << 20],
                         ids=["no-band", "band-spans-the-row", "wider"])
def test_calls_without_a_band_of_their_own_keep_the_panel_kernels(
        fa, monkeypatch, window):
    """`window=0` and a band that spans every block of the row never reach
    the banded calls: they trace the panel-walking kernels, whose text this
    PR left as it was (CHANGES.md, PR 42, has the hashes)."""
    def refuse(*a, **kw):
        raise AssertionError("the banded form was taken")

    monkeypatch.setattr(fa, "_band_fwd_call", refuse)
    monkeypatch.setattr(fa, "_band_bwd_calls", refuse)
    q, k, v, seg, weight = _case(1024, 4, 2, 64, True)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, None, seg, None, 0.0,
                                          True, True, window) * weight)

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    prefix = "flash_win_" if window else "flash_"
    for name in ("fwd", "bwd_dq", "bwd_dkv"):
        assert prefix + name in text
    # a panel program's grid has no axis for the band: (rows, 1, q blocks)
    # forward, (rows, blocks) backward
    grids = re.findall(r"grid=\(([^)]*)\)", text)
    assert grids and all(len(g.split(",")) <= 3 for g in grids)
    if window:
        with pytest.raises(AssertionError, match="banded form"):
            jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
                q, k, v, None, seg, None, 0.0, True, True, 200))(q, k, v)
