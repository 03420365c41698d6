"""Model-layer tests: shapes, numerics, parity of LayerNorm/GELU with golden
numpy implementations, tied-decoder behavior, remat equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bert_pytorch_tpu.config import BertConfig
from bert_pytorch_tpu.models import (
    BertForMaskedLM,
    BertForPreTraining,
    BertForQuestionAnswering,
    BertForSequenceClassification,
    BertForTokenClassification,
    BertModel,
    losses,
)
from bert_pytorch_tpu.models.bert import _REMAT_POLICIES
from bert_pytorch_tpu.ops import bias_gelu, gelu, layer_norm

TINY = BertConfig(
    vocab_size=128, hidden_size=32, num_hidden_layers=2,
    num_attention_heads=4, intermediate_size=64,
    max_position_embeddings=64, next_sentence=True,
    dtype="float32", fused_ops=False, attention_impl="xla",
)


def _inputs(batch=2, seq=16, vocab=128, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
    types = rng.randint(0, 2, (batch, seq)).astype(np.int32)
    mask = np.ones((batch, seq), np.int32)
    mask[:, seq - 3:] = 0
    return jnp.array(ids), jnp.array(types), jnp.array(mask)


def test_layer_norm_matches_numpy():
    x = np.random.RandomState(0).randn(4, 10, 32).astype(np.float32)
    scale = np.random.RandomState(1).randn(32).astype(np.float32)
    bias = np.random.RandomState(2).randn(32).astype(np.float32)
    got = layer_norm(jnp.array(x), jnp.array(scale), jnp.array(bias))
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    want = (x - mean) / np.sqrt(var + 1e-12) * scale + bias
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_gelu_is_exact_erf():
    import math

    x = np.linspace(-4, 4, 101).astype(np.float32)
    want = np.array([0.5 * v * (1 + math.erf(v / math.sqrt(2))) for v in x])
    np.testing.assert_allclose(np.asarray(gelu(jnp.array(x))), want,
                               rtol=1e-5, atol=1e-6)


def _gelu_grid(dtype):
    """[-8, 8] with 0, both tails and the derivative's root near -0.75."""
    x = np.concatenate([np.linspace(-8, 8, 2049),
                        [0.0, -0.75, -0.7518, 1e-3, -1e-3]])
    return jnp.asarray(x, dtype)


def _erf_gelu_grad(x):
    """jax.grad of the textbook float32 formula at x (any dtype)."""
    def ref(v):
        return 0.5 * v * (1.0 + jax.lax.erf(v / np.sqrt(2.0)))
    return np.asarray(jax.vmap(jax.grad(ref))(x.astype(jnp.float32)))


def _grad_of(fn, x):
    y, vjp = jax.vjp(fn, x)
    return np.asarray(vjp(jnp.ones_like(y))[0], np.float32)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_gelu_forward_is_jax_nn_gelu_bit_for_bit(dtype):
    """ops.activations writes out jax.nn.gelu(approximate=False) to share
    its erfc with the derivative; a JAX release that changes that formula
    has to show here. Both the primal and the forward rule."""
    x = _gelu_grid(dtype)
    want = np.asarray(jax.nn.gelu(x, approximate=False))
    np.testing.assert_array_equal(np.asarray(gelu(x)), want)
    np.testing.assert_array_equal(np.asarray(jax.vjp(gelu, x)[0]), want)
    np.testing.assert_array_equal(
        np.asarray(bias_gelu(jnp.zeros((), dtype), x)), want)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_gelu_forward_rule_evaluates_one_erfc_and_backward_none():
    x = _gelu_grid(jnp.bfloat16)

    def primitives(fn):
        return [e.primitive.name for e in _eqns(jax.make_jaxpr(fn)(x).jaxpr)]

    fwd = primitives(lambda v: jax.vjp(gelu, v)[0])
    assert fwd.count("erfc") == 1 and fwd.count("erf") == 0
    assert fwd.count("exp") == 1
    # the whole of value-and-gradient: still one, and the residual is one
    # array of x's shape and dtype
    both = primitives(jax.value_and_grad(lambda u: gelu(u).sum()))
    assert both.count("erfc") == 1 and both.count("exp") == 1
    _, vjp = jax.vjp(gelu, x)
    res = jax.tree_util.tree_leaves(vjp)
    assert [(r.shape, r.dtype) for r in res] == [(x.shape, x.dtype)]


_both_gelus = pytest.mark.parametrize("fn", [gelu, lambda v: bias_gelu(
    jnp.zeros((), v.dtype), v)], ids=["gelu", "bias_gelu"])


@_both_gelus
def test_gelu_gradient_float32(fn):
    x = _gelu_grid(jnp.float32)
    np.testing.assert_allclose(_grad_of(fn, x), _erf_gelu_grad(x),
                               rtol=0, atol=4 * np.finfo(np.float32).eps)


@_both_gelus
def test_gelu_gradient_bfloat16_is_closer_than_autodiff(fn):
    """gelu'(x) = Phi(x) + x*phi(x) is built from the forward's bf16 erfc
    (whose argument the forward formula also rounds to bf16) and rounded
    once more, so the yardstick is the bf16 ulp of the largest of the
    derivative and its two terms: they cancel at the root near x = -0.75
    and in the negative tail. Over [-8, 8] the rule reads within 1.32 such
    ulps of float32 (two allowed); autodiff of jax.nn.gelu, which rounds
    after every operation, reads up to 34 (the negative tail)."""
    x = _gelu_grid(jnp.bfloat16)
    x64 = np.asarray(x, np.float64)
    want = _erf_gelu_grad(x)
    x_phi = x64 * np.exp(-0.5 * x64 * x64) / np.sqrt(2 * np.pi)
    largest = np.max(np.abs([want, x_phi, want - x_phi]), axis=0)
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(largest, 1e-300))) - 7)
    got = _grad_of(fn, x)
    assert np.isfinite(got).all()
    err = np.abs(got - want)
    # + float32's eps: the reference's own 1 + erf cancels in that tail
    bad = err > 2 * ulp + np.finfo(np.float32).eps
    assert not bad.any(), (x64[bad], err[bad], ulp[bad])
    parent = np.abs(_grad_of(
        lambda v: jax.nn.gelu(v, approximate=False), x) - want)
    assert err.max() <= parent.max() and err.mean() <= parent.mean()
    assert _grad_of(fn, jnp.zeros((1,), jnp.bfloat16))[0] == 0.5


def test_gelu_rule_under_vmap_and_remat():
    x = _gelu_grid(jnp.float32)[:64].reshape(8, 8)
    want = _grad_of(gelu, x)
    np.testing.assert_array_equal(
        np.asarray(jax.vmap(jax.grad(lambda r: gelu(r).sum()))(x)), want)
    np.testing.assert_array_equal(np.asarray(jax.grad(
        lambda v: jax.checkpoint(gelu)(v).sum())(x)), want)
    np.testing.assert_array_equal(np.asarray(jax.grad(
        lambda v: jax.checkpoint(
            gelu, policy=_REMAT_POLICIES["nothing"])(v).sum())(x)), want)


def test_bert_model_shapes():
    ids, types, mask = _inputs()
    model = BertModel(TINY, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), ids, types, mask)
    seq_out, pooled = model.apply(params, ids, types, mask)
    assert seq_out.shape == (2, 16, 32)
    assert pooled.shape == (2, 32)


def test_pretraining_head_shapes_and_loss():
    ids, types, mask = _inputs()
    model = BertForPreTraining(TINY, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), ids, types, mask)
    mlm_logits, nsp_logits = model.apply(params, ids, types, mask)
    assert mlm_logits.shape == (2, 16, 128) and mlm_logits.dtype == jnp.float32
    assert nsp_logits.shape == (2, 2)

    labels = np.full((2, 16), -1, np.int32)
    labels[0, 3] = 7
    labels[1, 5] = 11
    nsp_labels = np.array([0, 1], np.int32)
    loss = losses.pretraining_loss(mlm_logits, jnp.array(labels), nsp_logits,
                                   jnp.array(nsp_labels))
    assert np.isfinite(float(loss)) and float(loss) > 0


def test_no_nsp_config_drops_pooler_and_token_type():
    cfg = TINY.replace(next_sentence=False)
    ids, _, mask = _inputs()
    model = BertModel(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), ids, None, mask)
    flat = jax.tree_util.tree_leaves_with_path(params)
    names = [jax.tree_util.keystr(p) for p, _ in flat]
    assert not any("token_type" in n for n in names)
    assert not any("pooler" in n for n in names)
    seq_out, pooled = model.apply(params, ids, None, mask)
    assert pooled is None


def test_cross_entropy_matches_torch_semantics():
    import torch

    rng = np.random.RandomState(0)
    logits = rng.randn(4, 6, 11).astype(np.float32)
    labels = rng.randint(-1, 11, (4, 6)).astype(np.int64)
    got = losses.cross_entropy(jnp.array(logits), jnp.array(labels),
                               ignore_index=-1)
    want = torch.nn.functional.cross_entropy(
        torch.tensor(logits).reshape(-1, 11), torch.tensor(labels).reshape(-1),
        ignore_index=-1)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_tied_decoder_grads_flow_to_embedding():
    ids, types, mask = _inputs()
    model = BertForMaskedLM(TINY, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), ids, types, mask)
    labels = np.full((2, 16), -1, np.int32)
    labels[0, 0] = 5

    def loss_fn(p):
        logits = model.apply(p, ids, types, mask)
        return losses.cross_entropy(logits, jnp.array(labels))

    grads = jax.grad(loss_fn)(params)
    emb_grad = grads["params"]["bert"]["embeddings"]["word_embeddings"][
        "embedding"]
    emb_grad = emb_grad.unbox() if hasattr(emb_grad, "unbox") else emb_grad
    assert float(jnp.abs(emb_grad).sum()) > 0


def _out_and_grads(model, params, inputs, **kw):
    """A forward pass with dropout on, and the gradient of a scalar of it
    with respect to every parameter."""
    def loss(p):
        out = model.apply(p, *inputs, deterministic=False,
                          rngs={"dropout": jax.random.PRNGKey(7)}, **kw)[0]
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    (_, out), grads = jax.value_and_grad(loss, has_aux=True)(params)
    return out, grads


def assert_remat_matches(make_model, cfg, policy, inputs, **kw):
    """Recomputing changes what is kept, not what is computed: outputs AND
    gradients equal the un-rematted model's, dropout masks included."""
    base = make_model(cfg, dtype=jnp.float32)
    changes = dict(checkpoint_activations=True)
    if policy is not None:
        changes["remat_policy"] = policy
    remat = make_model(cfg.replace(**changes), dtype=jnp.float32)
    params = base.init(jax.random.PRNGKey(0), *inputs)
    want_out, want = _out_and_grads(base, params, inputs, **kw)
    got_out, got = _out_and_grads(remat, params, inputs, **kw)
    np.testing.assert_allclose(np.asarray(got_out), np.asarray(want_out),
                               rtol=1e-5, atol=1e-5)
    assert float(sum(jnp.abs(g).sum() for g in jax.tree.leaves(want))) > 0
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5), got, want)


# None: the field's default ("auto"), as --checkpoint_activations leaves it
REMAT_POLICIES = [None, "dense", "nothing"]


@pytest.mark.parametrize("policy", REMAT_POLICIES)
def test_remat_matches_no_remat(policy):
    assert_remat_matches(BertModel, TINY, policy, _inputs())


def test_scan_unroll_matches_scanned():
    """scan_unroll only changes the compiled loop structure (config.py);
    param tree stays stacked and outputs must match the while-loop scan."""
    ids, types, mask = _inputs()
    m1 = BertModel(TINY, dtype=jnp.float32)
    params = m1.init(jax.random.PRNGKey(0), ids, types, mask)
    out1, _ = m1.apply(params, ids, types, mask)
    for unroll in (2, 99):  # partial is clamped; 99 > L means full unroll
        m2 = BertModel(TINY.replace(scan_unroll=unroll), dtype=jnp.float32)
        out2, _ = m2.apply(params, ids, types, mask)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   rtol=1e-6, atol=1e-6)


def test_qa_and_classification_heads():
    ids, types, mask = _inputs()
    qa = BertForQuestionAnswering(TINY, dtype=jnp.float32)
    p = qa.init(jax.random.PRNGKey(0), ids, types, mask)
    start, end = qa.apply(p, ids, types, mask)
    assert start.shape == (2, 16) and end.shape == (2, 16)
    loss = losses.qa_loss(start, end, jnp.array([1, 2]), jnp.array([3, 4]))
    assert np.isfinite(float(loss))

    clf = BertForSequenceClassification(TINY, num_labels=3, dtype=jnp.float32)
    p = clf.init(jax.random.PRNGKey(0), ids, types, mask)
    logits = clf.apply(p, ids, types, mask)
    assert logits.shape == (2, 3)

    tok = BertForTokenClassification(TINY, num_labels=5, dtype=jnp.float32)
    p = tok.init(jax.random.PRNGKey(0), ids, types, mask)
    logits = tok.apply(p, ids, types, mask)
    assert logits.shape == (2, 16, 5)
    labels = np.full((2, 16), -100, np.int64)
    labels[:, :4] = 1
    l = losses.token_classification_loss(logits, jnp.array(labels))
    assert np.isfinite(float(l))


def test_attention_mask_effect():
    """Masked positions must not influence unmasked outputs."""
    ids, types, _ = _inputs()
    mask = np.ones((2, 16), np.int32)
    mask[:, 8:] = 0
    model = BertModel(TINY, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), ids, types, jnp.array(mask))
    out1, _ = model.apply(params, ids, types, jnp.array(mask))
    ids2 = np.asarray(ids).copy()
    ids2[:, 12] = (ids2[:, 12] + 1) % 128  # change a masked-out token
    out2, _ = model.apply(params, jnp.array(ids2), types, jnp.array(mask))
    np.testing.assert_allclose(np.asarray(out1[:, :8]),
                               np.asarray(out2[:, :8]), rtol=1e-5, atol=1e-5)


def test_gathered_mlm_head_matches_dense():
    """masked_positions gather: logits at the gathered positions and the
    resulting loss must match the dense (B, S, V) path exactly."""
    from bert_pytorch_tpu.training.pretrain import gather_masked_labels

    ids, types, mask = _inputs(batch=3, seq=16)
    model = BertForPreTraining(TINY, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), ids, types, mask)

    rng = np.random.RandomState(7)
    labels = np.full((3, 16), -1, np.int32)
    # rows with 3, 1, and 0 masked tokens; P=4 exercises the -1 fill tail
    labels[0, [2, 5, 9]] = rng.randint(0, 128, 3)
    labels[1, [11]] = rng.randint(0, 128)
    labels = jnp.asarray(labels)
    positions, glabels = gather_masked_labels(labels, 4)

    dense_logits, nsp = model.apply(params, ids, types, mask,
                                    deterministic=True)
    gath_logits, _ = model.apply(params, ids, types, mask,
                                 deterministic=True,
                                 masked_positions=positions)
    assert gath_logits.shape == (3, 4, TINY.vocab_size)
    want = jnp.take_along_axis(dense_logits, positions[..., None], axis=1)
    np.testing.assert_allclose(np.asarray(gath_logits), np.asarray(want),
                               rtol=1e-6, atol=1e-6)

    # gathered labels: tail fill positions carry -1 (ignored by the loss)
    assert int((glabels == -1).sum()) == 12 - 3 - 1

    nsl = jnp.asarray(rng.randint(0, 2, (3,)).astype(np.int32))
    dense_loss = losses.pretraining_loss(dense_logits, labels, nsp, nsl)
    gath_loss = losses.pretraining_loss(gath_logits, glabels, nsp, nsl)
    np.testing.assert_allclose(float(gath_loss), float(dense_loss),
                               rtol=1e-6)
