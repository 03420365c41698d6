"""The main path's Pallas kernels, compiled by the TPU's own compiler for a
described (not attached) v5e at BERT-Large shapes.

Interpret mode proves a kernel's arithmetic; it cannot show what Mosaic
refuses — a store it cannot lay out, a block that breaks the tiling rule,
more VMEM than a kernel may use. libtpu is installed with the test
environment and compiles for a topology that is only described
(jax.experimental.topologies), so these checks need no chip: each lowers
one jitted call on ShapeDtypeStructs placed on a described v5e device and
asserts the executable holds the expected kernels, by name
(analysis/hlo.kernel_counts over its `tpu_custom_call`s). Nothing runs,
so nothing here says a result or a time is right — tests/test_pallas.py and
chip_smoke.py do that.

Skipped only where the topology cannot be described (no libtpu).
"""

import importlib
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest

from bert_pytorch_tpu.analysis import hlo
from bert_pytorch_tpu.ops.pallas.layernorm import (
    add_dropout_layer_norm_pallas, layer_norm_pallas)

fa = importlib.import_module("bert_pytorch_tpu.ops.pallas.flash_attention")

H, D, E = 16, 64, 1024      # configs/bert_large_uncased_config.json

# kernels of one differentiated flash call, by backward variant
FUSED = {"flash_fwd": 1, "flash_bwd_dqkv": 1}
SPLIT = {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}


@pytest.fixture(scope="module")
def v5e():
    """Sharding that places an abstract array on one described v5e chip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / topology unknown to it
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """An executable compiled for a described chip can be written to the
    persistent cache but not read back without one: keep the cache off
    around these compiles, whatever the environment enabled."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _kernels(fn, *args) -> dict:
    """Compile fn(*args) for the described chip — raising what the chip's
    compiler would raise — and name the Mosaic kernels in the executable."""
    return hlo.kernel_counts(jax.jit(fn).lower(*args).compile().as_text())


def _flash_case(v5e, b, s, *, bias, segments, rate, grad):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)  # noqa: E731
    q = sds((b, s, H, D), jnp.bfloat16)
    bias_a = sds((b, 1, 1, s), jnp.float32) if bias else None
    seg_a = sds((b, s), jnp.int32) if segments else None
    seed_a = sds((), jnp.int32) if rate > 0 else None

    def fwd(q, k, v, bias, seg, seed):
        return fa.flash_attention(q, k, v, bias, seg, seed, rate, False)

    def bwd(q, k, v, bias, seg, seed):
        return jax.grad(
            lambda q, k, v: fwd(q, k, v, bias, seg, seed)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    return _kernels(bwd if grad else fwd, q, q, q, bias_a, seg_a, seed_a)


# (batch, seq) of the phase-1 / phase-2 recipes on one 16 GB chip
@pytest.mark.parametrize("b,s", [(64, 128), (16, 512)])
@pytest.mark.parametrize("bias,segments,rate", [
    (True, False, 0.1),     # padded batches: mask bias + dropout
    (False, True, 0.1),     # packed batches: segment ids + dropout
])
def test_flash_train_step_kernels_compile(v5e, b, s, bias, segments, rate):
    assert fa._use_native(s, H, D)
    assert _flash_case(v5e, b, s, bias=bias, segments=segments, rate=rate,
                       grad=True) == FUSED


@pytest.mark.parametrize("bias,segments", [(True, False), (False, True)])
def test_flash_serving_forward_compiles(v5e, bias, segments):
    # run_server's bucket 512 at its default batch_rows, packing on/off
    assert _flash_case(v5e, 8, 512, bias=bias, segments=segments, rate=0.0,
                       grad=False) == {"flash_fwd": 1}


@pytest.mark.parametrize("bias,segments", [(True, False), (False, True)])
def test_flash_split_backward_compiles(v5e, bias, segments,
                                       force_flash_path):
    """The split dq / dkv kernels (the long-sequence backward, bh layout)
    at the BERT-Large head shape."""
    force_flash_path("bh", "split")
    assert _flash_case(v5e, 16, 512, bias=bias, segments=segments, rate=0.1,
                       grad=True) == SPLIT


_MIB = 1024 * 1024


def _asked_vmem(text: str) -> list:
    """Bytes of scoped VMEM each Mosaic call of a compiled executable's text
    asked of the compiler (its `vmem_limit_bytes`; the default is 16 MiB)."""
    import re

    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "custom-call(" in ln]
    return [int(re.search(r'"scoped_memory_configs":\[\{"memory_space":"1",'
                          r'"offset":"\d+","size":"(\d+)"', ln).group(1))
            for ln in calls]


@pytest.mark.parametrize("cell,b,s,h,hkv,d,dv,heads,window,band,asked", [
    # lfm2-ep8-clm-8k-packed: a program owns a key/value head's four query
    # heads
    ("lfm2", 4, 8192, 32, 8, 64, 64, 4, 0, 0, 64),
    # kimi-linear-ep32-clm-16k-packed: four heads' K and V panels, 48 MiB in
    # one buffer each
    ("kimi", 1, 16384, 32, 32, 192, 128, 4, 0, 0, 64),
    # smallthinker-ep8-clm-16k-fullrow: a program owns the SEVEN query heads
    # of a key/value head of 128. Its full layers walk whole panels: the dkv
    # kernel's Q and dO panels are 56 MiB, so the calls ask 72 MiB
    # (`_long_seq_params`). Its layers banded at 4,096 walk the NINE blocks
    # of the band as a grid axis and hold blocks, no panel: 16 MiB asked
    # (`_band_params`)
    ("smallthinker-full", 1, 16384, 28, 4, 128, 128, 7, 0, 0, 72),
    ("smallthinker-band", 1, 16384, 28, 4, 128, 128, 7, 4096, 9, 16),
    # laguna-ep8-clm-16k-packed: a windowed layer's program owns the EIGHT
    # query heads of a key/value head of 128 under a band of 512, one tile
    # wide: TWO blocks a band, 16 MiB asked where whole panels asked 80; a
    # full layer's owns six
    ("laguna-band", 1, 16384, 64, 8, 128, 128, 8, 512, 2, 16),
    ("laguna-full", 1, 16384, 48, 8, 128, 128, 6, 0, 0, 64),
])
def test_flash_decoder_cells_split_kernels_compile(v5e, cell, b, s, h, hkv,
                                                   d, dv, heads, window,
                                                   band, asked):
    """The causal split kernels at the decoder cells' shapes, at the heads a
    program that `_layout` picks, in the form `_band_steps` picks (`band`
    steps of a grid axis over the band's blocks, or 0: the panel-walking
    programs), inside the VMEM the calls ask for (`asked` MiB: the compiler
    refuses a kernel that needs more): dynamic head indices into the blocks,
    the rolled loop over the heads, the scratch accumulators carried from
    grid step to grid step and the clamped block indices are what interpret
    mode cannot refuse."""
    assert fa._layout(b, s, h, d, h // hkv, dv,
                      window).heads_per_prog == heads
    assert fa._band_steps(s, 512, 512, window) == band
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)  # noqa: E731

    def bwd(q, k, v, seg):
        return jax.grad(lambda q, k, v: fa.flash_attention(
            q, k, v, None, seg, None, 0.0, False, True, window)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    name = lambda n: fa._kernel_name(n, d, dv, window)  # noqa: E731
    text = jax.jit(bwd).lower(
        sds((b, s, h, d), jnp.bfloat16), sds((b, s, hkv, d), jnp.bfloat16),
        sds((b, s, hkv, dv), jnp.bfloat16),
        sds((b, s), jnp.int32)).compile().as_text()
    assert hlo.kernel_counts(text) == {name(n): 1 for n in SPLIT}
    assert _asked_vmem(text) == [asked * _MIB] * 3


def test_flash_select_kernels_compile_at_the_keye_cell(v5e):
    """keye-ep8-clm-16k-fullrow: the causal split kernels under a selection
    (`flash_sel_*`), a program owning the EIGHT query heads of a key/value
    head of 128, the packed selection one more block of each program: a
    (1, 512, 512) int32 block of the q blocks' words in the forward and dq
    kernels, of the k blocks' in the dkv kernel, inside the 80 MiB the
    panel-walking calls ask at this group. The AND and compare on the
    words, under the rolled loop over the heads, are what interpret mode
    cannot refuse."""
    b, s, h, hkv, d = 1, 16384, 32, 4, 128
    assert fa._layout(b, s, h, d, h // hkv, d, 0, True).heads_per_prog == 8
    blk_q, blk_k, wk, wq = fa.select_blocks(s)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)  # noqa: E731

    def bwd(q, k, v, seg, by_q, by_k):
        return jax.grad(lambda q, k, v: fa.flash_select_attention(
            q, k, v, seg, by_q, by_k, False)[0].astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    args = (sds((b, s, h, d), jnp.bfloat16), sds((b, s, hkv, d), jnp.bfloat16),
            sds((b, s, hkv, d), jnp.bfloat16), sds((b, s), jnp.int32),
            sds((b, wk, s, blk_k), jnp.int32),
            sds((b, wq, blk_q, s), jnp.int32))
    fwd = jax.jit(lambda *a: fa.flash_select_attention(*a, False)).lower(
        *args).compile()
    assert hlo.kernel_counts(fwd.as_text()) == {"flash_sel_fwd": 1}
    ctx, lse = fwd.out_info
    assert (ctx.shape, lse.shape, lse.dtype) == ((b, s, h, d), (b, h, s),
                                                 jnp.float32)
    text = jax.jit(bwd).lower(*args).compile().as_text()
    assert hlo.kernel_counts(text) == {
        fa._kernel_name(n, d, d, 0, True): 1 for n in SPLIT}
    assert sorted(hlo.kernel_counts(text)) == [
        "flash_sel_bwd_dkv", "flash_sel_bwd_dq", "flash_sel_fwd"]
    assert _asked_vmem(text) == [80 * _MIB] * 3


def test_index_kernels_compile_at_the_keye_cell(v5e):
    """ops/pallas/sparse_index.py's three kernels for one chunk of 512
    queries of a 16,384-token row: 16 index heads of 64 on one key head,
    32 query heads of 128 on 4 key/value heads; the chunk's number as a
    prefetched scalar that clamps the key blocks' index maps; the rolled
    loops over the heads with dynamic first-axis indices; the lane-dense
    per-token vectors (the index's weights, the main attention's
    log-sum-exp, 32 rows of 512) turned into columns. `dsa_probs` is the
    one-walk form: a grid of the 32 key blocks alone."""
    from bert_pytorch_tpu.ops.pallas import sparse_index as ker

    c, s, j, di, h, hkv, d, blk = 512, 16384, 16, 64, 32, 4, 128, 512
    assert ker.supported(c, blk, di, d) and not ker.supported(c, blk, 8, 16)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)  # noqa: E731
    i, bf = sds((), jnp.int32), jnp.bfloat16
    q_idx, k_idx = sds((c, j, di), bf), sds((s, di), bf)
    w_idx, scores = sds((c, j), jnp.float32), sds((c, s), jnp.float32)
    assert _kernels(lambda i, q, k, w: ker.index_scores(
        i, q, k, w, blk, False), i, q_idx, k_idx, w_idx) == {
            "dsa_index_fwd": 1}
    assert _kernels(lambda i, q, k, w, g: ker.index_scores_grads(
        i, q, k, w, g, blk, False), i, q_idx, k_idx, w_idx, scores) == {
            "dsa_index_bwd": 1}
    probs = (lambda i, q, k, lse, words: ker.mean_probs(  # noqa: E731
        i, q, k, lse, words, blk, False))
    probs_args = (i, sds((c, h, d), bf), sds((s, hkv, d), bf),
                  sds((h, c), jnp.float32), sds((1, c, blk), jnp.int32))
    assert _kernels(probs, *probs_args) == {"dsa_probs": 1}
    call, = [e for e in jax.make_jaxpr(probs)(*probs_args).eqns
             if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (s // blk,)


@pytest.mark.parametrize("cell,first,heads,width,rotated,dtype", [
    ("laguna-window-q", 0, 64, 80, 128, jnp.bfloat16),
    ("laguna-window-k", 64, 8, 80, 128, jnp.bfloat16),
    ("laguna-full-q", 0, 48, 64, 64, jnp.bfloat16),
    ("laguna-full-k", 48, 8, 64, 64, jnp.bfloat16),
    ("smallthinker-q", 0, 28, 36, 128, jnp.bfloat16),
    ("smallthinker-k", 28, 4, 36, 128, jnp.bfloat16),
    ("keye-q", 0, 32, 32, 128, jnp.float32),
    ("keye-k", 0, 4, 4, 128, jnp.float32),
])
def test_rotary_kernels_compile_at_the_decoder_cells(v5e, cell, first, heads,
                                                     width, rotated, dtype):
    """ops/pallas/rotary.py's two kernels over a 16,384-token row at the
    cells' head counts: q's and k's heads read as column blocks of the
    fused projection's (1, S, width * 128) output (keye: the head norms'
    float32 output, all of it) and written by head, the rule reading by
    head and writing columns; the whole head and half of it (two different
    lane rolls)."""
    from bert_pytorch_tpu.ops.pallas import rotary as ker

    s, d = 16384, 128
    assert ker.supported(s, d) and not ker.supported(s, 64)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)  # noqa: E731
    x = sds((1, s, width * d), dtype)
    table = sds((1, s, d), jnp.float32)

    def both(x, c, sa, sb, dy):
        # the rotation is linear: its rule needs no forward pass, so ask
        # for the result beside the cotangent
        y, pull = jax.vjp(lambda u: ker.rotate(
            u, c, sa, sb, first, heads, rotated, jnp.bfloat16, False), x)
        return y, pull(dy)

    dy = sds((1, s, heads, d), jnp.bfloat16)
    assert _kernels(both, x, table, table, table, dy) == {
        "rotary_fwd": 1, "rotary_bwd": 1}


def test_kl_pass_compiles_at_the_keye_cell(v5e, monkeypatch):
    """One row of `ops/sparse_index.index_kl` with its gradient rule, as a
    keye layer runs it after the main attention's forward kernel: a scan
    over the row's 32 chunks whose body holds one `dsa_index_fwd` (the
    chunk's scores, again), one `dsa_probs` on the chunk's rows of the
    attention's log-sum-exp, and one `dsa_index_bwd`; nothing of size
    (S, S) in the program (a row's float32 scores would be 1 GiB)."""
    import re

    from bert_pytorch_tpu.ops import sparse_index

    # `_use_kernels` asks the live backend, which is the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    b, s, j, di, h, hkv, d = 1, 16384, 16, 64, 32, 4, 128
    _, blk_k, wk, _ = fa.select_blocks(s)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)  # noqa: E731
    bf = jnp.bfloat16

    def kl(q_idx, k_idx, w_idx, q, k, lse, by_q):
        return jax.value_and_grad(
            lambda *a: sparse_index.index_kl(*a, q, k, lse, by_q),
            argnums=(0, 1, 2))(q_idx, k_idx, w_idx)

    compiled = jax.jit(kl).lower(
        sds((b, s, j, di), bf), sds((b, s, di), bf),
        sds((b, s, j), jnp.float32), sds((b, s, h, d), bf),
        sds((b, s, hkv, d), bf), sds((b, h, s), jnp.float32),
        sds((b, wk, s, blk_k), jnp.int32)).compile()
    text = compiled.as_text()
    assert hlo.kernel_counts(text) == {"dsa_index_fwd": 1, "dsa_probs": 1,
                                       "dsa_index_bwd": 1}
    assert not re.search(r"\[16384,16384\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_kda_scan_kernels_compile_at_the_kimi_cell(v5e, monkeypatch):
    """A differentiated `ops/kda.kda_scan` at kimi-linear-ep32-clm-16k-packed's
    shape (a row of 16,384, 32 heads of 128, chunks of 64, 32 a block, bf16
    products): `kda_fwd` and `kda_bwd`, eight heads a program and four
    chunks a grid step, inside the VMEM the calls ask for (the backward's
    with 16.5 MiB of scratch for the block's states between its two walks);
    dynamic chunk indices into the blocks, the one-row slices of the decay's
    tile, the two walks of the backward's grid and the transposes in the
    rolled loop are what interpret mode cannot refuse. Both under the scope
    `kda/scan`, which the benchmark's `kda_scan_share.train` and
    `kda_scan_roofline` read; and the gradients that leave the backward
    pass's scan hold the layout their shapes spell (`ops/kda._row_major`):
    with the kernels in its body XLA:TPU otherwise lays them, and every
    fusion around the calls, out tokens-major."""
    import re

    from bert_pytorch_tpu.ops import kda
    from bert_pytorch_tpu.ops.pallas import kda as kda_kernels

    # `kernel_mode` asks the live backend, which is the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    b, s, h, d, chunk, block = 1, 16384, 32, 128, 64, 32
    assert kda.kernel_mode(d, d, chunk, block) is False
    assert kda.kernel_mode(d, d, chunk, 256) is None   # 128 MiB of states
    assert kda_kernels._tiles(h, block) == (8, 4)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e)  # noqa: E731
    x = sds((b, s, h, d), jnp.bfloat16)

    def bwd(q, k, v, g, beta, starts):
        return jax.grad(lambda *a: kda.kda_scan(
            *a, starts, chunk=chunk, block=block,
            mm_dtype=jnp.bfloat16).sum(), argnums=range(5))(q, k, v, g, beta)

    text = jax.jit(bwd).lower(
        x, x, x, sds((b, s, h, d), jnp.float32), sds((b, s, h), jnp.float32),
        sds((b, s), jnp.bool_)).compile().as_text()
    assert hlo.kernel_counts(text) == {"kda_fwd": 1, "kda_bwd": 1}
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and "custom-call(" in ln]
    assert len(calls) == 2 and all(
        re.search(r'op_name="[^"]*kda/scan/[^"]*"', ln) for ln in calls)
    # (chunks, B, H, C, D) with C major over the chunks and heads: some 300
    # such arrays without `_row_major`, 4 with it (and in the scans' program)
    assert len(re.findall(r"\{4,2,0,3,1|\{5,3,1,4,2,0|\{3,1,0,2", text)) < 20
    # and no transposing copy of the inverse's (C, C) matrices: six a block
    # where `kda_bwd` takes P and not its transpose
    assert not re.findall(r"%copy[.0-9]* = f32\[32,32,64,64\]", text)


@pytest.mark.slow
@pytest.mark.parametrize("b,s,split", [
    (32, 384, False),   # SQuAD finetune length (scripts/run_squad.sh)
    (8, 1024, False),   # largest native shape: two D=64 heads per program
    (4, 2048, False),   # bh layout, fused backward at its VMEM bound
    (2, 4096, True),    # beyond it: split backward
])
def test_flash_other_lengths_compile(v5e, b, s, split):
    want = SPLIT if split else FUSED
    assert _flash_case(v5e, b, s, bias=True, segments=False, rate=0.1,
                       grad=True) == want
    assert _flash_case(v5e, b, s, bias=False, segments=True, rate=0.1,
                       grad=True) == want


# rows x E of a seq-512 batch of 16 and a seq-128 batch of 64, as the model
# calls them (B, S, E)
@pytest.mark.parametrize("shape", [(16, 512, E), (64, 128, E)])
@pytest.mark.parametrize("fused_residual", [False, True])
def test_layernorm_kernels_compile(v5e, shape, fused_residual):
    sds = lambda shp, dt: jax.ShapeDtypeStruct(shp, dt, sharding=v5e)  # noqa: E731
    x = sds(shape, jnp.bfloat16)
    w = sds((E,), jnp.float32)
    seed = sds((), jnp.int32)

    if fused_residual:
        def f(x, res, scale, bias, seed):
            return add_dropout_layer_norm_pallas(
                x, res, scale, bias, seed, 0.1, 1e-12, False)
        args = (x, x, w, w, seed)
        diff = (0, 1, 2, 3)
    else:
        def f(x, scale, bias):
            return layer_norm_pallas(x, scale, bias, 1e-12, False)
        args = (x, w, w)
        diff = (0, 1, 2)

    def g(*a):
        return jax.grad(lambda *b: f(*b).astype(jnp.float32).sum(),
                        argnums=diff)(*a)

    name = "add_dropout_layernorm" if fused_residual else "layernorm"
    assert _kernels(f, *args) == {f"{name}_fwd": 1}
    assert _kernels(g, *args) == {f"{name}_fwd": 1, f"{name}_bwd": 1}
