"""Distillation factory tests (training/distill.py, run_distill.py,
the DISTILL artifact chain, and the debug_taps layer contract).

The acceptance pins:

- packed distillation loss — KD + hard + layer-matched tap terms with
  width-bridging projections — equals the same examples
  one-example-per-row BIT-for-bit (the PR 13 standard, extended to the
  teacher-in-the-graph loss);
- the teacher runs under stop_gradient: student gradients with the
  teacher forward IN the graph are bit-identical to gradients against
  precomputed teacher logits (tree-exact);
- `debug_taps` sows keep their names and shapes under BOTH encoder
  layouts (stacked scan and unstacked) — the contract the distillation
  layer map rides;
- the strict serving restore names expected-vs-found encoder depth and
  points at run_distill.py's student model_config.json on a
  student-checkpoint-under-teacher-config mismatch;
- the jax-free artifact chain: loadtest --assemble --kind distill
  computes accuracy deltas + vs_teacher_per_chip, and its
  `--check_distill` trips on a student below the accuracy floor (and
  passes a student that beats its teacher).
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bert_pytorch_tpu.config import (  # noqa: E402
    BertConfig, is_student_preset, student_config)
from tests.test_finetune_packing import (  # noqa: E402
    _examples, _pack_both)


def _teacher_config(**kw):
    base = dict(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, fused_ops=False,
        attention_impl="xla", debug_taps=True)
    base.update(kw)
    return BertConfig(**base)


# -- student presets ----------------------------------------------------------


def test_student_presets():
    teacher = _teacher_config(hidden_size=768, num_hidden_layers=12,
                              num_attention_heads=12,
                              intermediate_size=3072)
    s6 = student_config("student_6l_768", teacher)
    assert (s6.num_hidden_layers, s6.hidden_size,
            s6.num_attention_heads, s6.intermediate_size) \
        == (6, 768, 12, 3072)
    s4 = student_config("student_4l_512", teacher)
    assert (s4.num_hidden_layers, s4.hidden_size,
            s4.num_attention_heads, s4.intermediate_size) \
        == (4, 512, 8, 2048)
    # everything not depth/width related is inherited from the teacher
    assert s4.vocab_size == teacher.vocab_size
    assert s4.max_position_embeddings == teacher.max_position_embeddings
    # head count divides the hidden size even for odd widths
    s = student_config("student_2l_100", teacher)
    assert s.hidden_size % s.num_attention_heads == 0
    assert is_student_preset("student_6l_768")
    assert not is_student_preset("bert_base")
    with pytest.raises(ValueError, match="student_<L>l_<H>"):
        student_config("student_768", teacher)


def test_layer_map():
    from bert_pytorch_tpu.training import distill

    assert distill.default_layer_map(6, 12) == (
        (0, 1), (1, 3), (2, 5), (3, 7), (4, 9), (5, 11))
    assert distill.default_layer_map(2, 2) == ((0, 0), (1, 1))
    assert distill.parse_layer_map("0:0,1:11", 2, 12) == ((0, 0), (1, 11))
    assert distill.parse_layer_map(None, 6, 12) \
        == distill.default_layer_map(6, 12)
    with pytest.raises(ValueError, match="out of range"):
        distill.parse_layer_map("0:12", 2, 12)
    with pytest.raises(ValueError, match="student:teacher"):
        distill.parse_layer_map("0-3", 2, 12)


# -- debug_taps layout contract (the layer map's substrate) -------------------


@pytest.mark.parametrize("stacked", [True, False],
                         ids=["stacked", "unstacked"])
def test_debug_taps_names_and_shapes_both_layouts(stacked):
    """Pin the sow names and shapes the distillation tap losses consume,
    under both encoder layouts: per layer {attention_out, mlp_out} of
    (B, S, H), plus the trunk-level embeddings_out/pooled sows."""
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.models import BertForSequenceClassification
    from bert_pytorch_tpu.training.distill import layer_taps

    cfg = _teacher_config(stacked_params=stacked)
    model = BertForSequenceClassification(cfg, num_labels=2,
                                          max_segments=4,
                                          dtype=jnp.float32)
    x = jnp.zeros((2, 16), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), x, x, x)
    _, vs = model.apply({"params": variables["params"]}, x, x, x,
                        deterministic=True, mutable=["debug_taps"])
    taps = vs["debug_taps"]["bert"]

    def leaf(v):
        return v[0] if isinstance(v, (tuple, list)) else v

    assert leaf(taps["embeddings_out"]).shape == (2, 16, 32)
    assert leaf(taps["pooled"]).shape == (2, 32)
    enc = taps["encoder"]
    if stacked:
        per = enc["layers"]["layer"]
        assert set(per) == {"attention_out", "mlp_out"}
        for v in per.values():
            assert leaf(v).shape == (2, 2, 16, 32)  # (L, B, S, H)
    else:
        assert set(enc) == {"layer_0", "layer_1"}
        for layer in enc.values():
            assert set(layer) == {"attention_out", "mlp_out"}
            for v in layer.values():
                assert leaf(v).shape == (2, 16, 32)

    layers = layer_taps(vs["debug_taps"], cfg)
    assert len(layers) == cfg.num_hidden_layers
    for lt in layers:
        assert set(lt) == {"attention_out", "mlp_out"}
        assert lt["attention_out"].shape == (2, 16, 32)
        assert lt["mlp_out"].shape == (2, 16, 32)


def test_debug_taps_cross_layout_parity():
    """The same weights produce the same per-layer tap values under both
    layouts (convert_tree_layout), so a layer map trained against one
    layout means the same thing against the other."""
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.models import BertForSequenceClassification
    from bert_pytorch_tpu.models.pretrained import convert_tree_layout
    from bert_pytorch_tpu.training.distill import layer_taps

    cfg_s = _teacher_config(stacked_params=True)
    cfg_u = cfg_s.replace(stacked_params=False)
    m_s = BertForSequenceClassification(cfg_s, num_labels=2,
                                        max_segments=4, dtype=jnp.float32)
    m_u = BertForSequenceClassification(cfg_u, num_labels=2,
                                        max_segments=4, dtype=jnp.float32)
    x = jnp.zeros((2, 16), jnp.int32)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 5, 64)
    mask = jnp.ones((2, 16), jnp.int32)
    p_s = m_s.init(jax.random.PRNGKey(0), x, x, x)["params"]
    p_u = convert_tree_layout(p_s, stacked=False)
    _, vs_s = m_s.apply({"params": p_s}, ids, x, mask,
                        deterministic=True, mutable=["debug_taps"])
    _, vs_u = m_u.apply({"params": p_u}, ids, x, mask,
                        deterministic=True, mutable=["debug_taps"])
    for ls, lu in zip(layer_taps(vs_s["debug_taps"], cfg_s),
                      layer_taps(vs_u["debug_taps"], cfg_u)):
        for k in ("attention_out", "mlp_out"):
            np.testing.assert_allclose(np.asarray(ls[k]),
                                       np.asarray(lu[k]),
                                       rtol=1e-5, atol=1e-5)


# -- the distillation loss: packed bit-equality + stop_gradient ---------------


def _distill_setup(alpha_hidden=1.0, alpha_attn=0.5):
    """(student_model, teacher_model, student_params+proj,
    teacher_params, dcfg) on a width-differing pair so the projections
    are exercised."""
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.models import BertForSequenceClassification
    from bert_pytorch_tpu.training import distill

    t_cfg = _teacher_config()
    s_cfg = student_config("student_1l_16", t_cfg)
    dcfg = distill.DistillConfig(
        temperature=2.0, alpha_kd=1.0, alpha_ce=0.5,
        alpha_hidden=alpha_hidden, alpha_attn=alpha_attn,
        layer_map=distill.default_layer_map(1, 2), max_segments=4)
    teacher = BertForSequenceClassification(t_cfg, num_labels=2,
                                            max_segments=4,
                                            dtype=jnp.float32)
    student = BertForSequenceClassification(s_cfg, num_labels=2,
                                            max_segments=4,
                                            dtype=jnp.float32)
    x = jnp.zeros((1, 48), jnp.int32)
    t_params = teacher.init(jax.random.PRNGKey(0), x, x, x)["params"]
    s_params = dict(student.init(jax.random.PRNGKey(1), x, x, x)["params"])
    proj = distill.init_projections(jax.random.PRNGKey(2), dcfg,
                                    s_cfg, t_cfg)
    if proj:
        s_params["distill_proj"] = proj
    return student, teacher, s_params, t_params, dcfg


def test_packed_distill_loss_bit_equal():
    """The tentpole pin: the full distillation mix (KD + hard + both tap
    terms through a width-bridging projection) on a multi-segment packed
    batch equals the one-example-per-row baseline bit-for-bit."""
    import jax

    from bert_pytorch_tpu.tasks.classify import pack_labels
    from bert_pytorch_tpu.training import distill

    student, teacher, s_params, t_params, dcfg = _distill_setup()
    proj = distill.init_projections(jax.random.PRNGKey(2), dcfg,
                                    student.config, teacher.config)
    assert proj, "fixture must exercise the projection path"

    arrays, _ = _examples()
    arrays["labels"] = np.array([0, 1, 1, 0, 1], np.int32)
    multi, single, _ = _pack_both(arrays, pack_labels)

    loss_fn = distill.make_distill_loss_builder(
        teacher_model=teacher, teacher_params=t_params, dcfg=dcfg,
        output_kind="segment", packed=True,
        label_ignore={"labels": -1})(student)
    rng = jax.random.PRNGKey(3)
    l_multi, _ = loss_fn(s_params, multi, rng, deterministic=True)
    l_single, _ = loss_fn(s_params, single, rng, deterministic=True)
    assert float(l_multi) == float(l_single)  # BIT-equal
    assert np.isfinite(float(l_multi)) and float(l_multi) > 0.0


def test_teacher_stop_gradient_precomputed_equivalence():
    """Teacher-under-stop_gradient proven: student grads with the
    teacher forward in the SAME graph are bit-identical (tree-exact) to
    grads against precomputed teacher logits — i.e. the teacher
    contributes values, never gradients."""
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.tasks.classify import pack_labels
    from bert_pytorch_tpu.training import distill

    student, teacher, s_params, t_params, dcfg = _distill_setup(
        alpha_hidden=0.0, alpha_attn=0.0)  # tap-free: logits-only KD
    s_params.pop("distill_proj", None)

    arrays, _ = _examples()
    arrays["labels"] = np.array([0, 1, 1, 0, 1], np.int32)
    multi, _, _ = _pack_both(arrays, pack_labels)

    loss_fn = distill.make_distill_loss_builder(
        teacher_model=teacher, teacher_params=t_params, dcfg=dcfg,
        output_kind="segment", packed=True,
        label_ignore={"labels": -1})(student)
    rng = jax.random.PRNGKey(3)

    def loss(params, batch):
        return loss_fn(params, batch, rng, deterministic=True)[0]

    g_ingraph = jax.grad(loss)(s_params, multi)

    t_logits = teacher.apply(
        {"params": t_params}, jnp.asarray(multi["input_ids"]),
        jnp.asarray(multi["token_type_ids"]),
        jnp.asarray(multi["attention_mask"]), deterministic=True,
        position_ids=jnp.asarray(multi["position_ids"]),
        segment_ids=jnp.asarray(multi["segment_ids"]))
    pre = dict(multi)
    pre["teacher_logits"] = t_logits
    g_pre = jax.grad(loss)(s_params, pre)

    flat_a = jax.tree_util.tree_leaves_with_path(g_ingraph)
    flat_b = jax.tree_util.tree_leaves_with_path(g_pre)
    assert len(flat_a) == len(flat_b)
    nonzero = 0.0
    for (pa, a), (pb, b) in zip(flat_a, flat_b):
        assert pa == pb
        assert np.array_equal(np.asarray(a), np.asarray(b)), pa
        nonzero += float(jnp.abs(a).sum())
    assert nonzero > 0.0, "degenerate fixture: all-zero gradients"


# -- strict restore: depth-mismatch error (satellite 1) -----------------------


def test_strict_merge_depth_mismatch_hint():
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.models import BertForSequenceClassification
    from bert_pytorch_tpu.serving.engine import _strict_merge
    from bert_pytorch_tpu.training.state import unbox

    def params_for(layers, stacked):
        cfg = _teacher_config(num_hidden_layers=layers, debug_taps=False,
                              stacked_params=stacked)
        m = BertForSequenceClassification(cfg, num_labels=2,
                                          max_segments=4,
                                          dtype=jnp.float32)
        x = jnp.zeros((1, 16), jnp.int32)
        return unbox(m.init(jax.random.PRNGKey(0), x, x, x)["params"])

    for stacked in (True, False):
        teacher_tree = params_for(2, stacked)
        student_tree = params_for(1, stacked)
        with pytest.raises(ValueError) as ei:
            _strict_merge(teacher_tree, student_tree)
        msg = str(ei.value)
        assert "expects 2 encoder layer(s)" in msg, msg
        assert "carries 1" in msg, msg
        assert "--student" in msg and "model_config.json" in msg, msg
        if stacked:
            # reverse direction: under the stacked layout the scanned
            # leaves' leading axis mis-shapes, and the error names the
            # reverse counts. (Unstacked, a DEEPER checkpoint restores
            # into a shallower model fine — every model leaf exists and
            # extra checkpoint subtrees are ignored by contract.)
            with pytest.raises(ValueError,
                               match=r"expects 1 encoder layer"):
                _strict_merge(student_tree, teacher_tree)


# -- jax-free artifact chain: loadtest assemble + accuracy-floor gate ---------


def _mode_doc(label, tag, dtype, rps, n_chips=1):
    return {"schema_version": 1, "kind": "serve_mode", "label": label,
            "time_unix": 5.0,
            "rates": {"10": {"p50_ms": 4.0, "p95_ms": 8.0, "p99_ms": 20.0,
                             "req_per_sec": rps,
                             "real_tokens_per_sec": 900.0,
                             "batch_occupancy": 0.8, "n": 300,
                             "n_2xx": 300, "n_err": 0,
                             "duration_s": 30.0,
                             "cost_per_1k_tokens": 0.01}},
            "meta": {"model_tag": tag, "dtype": dtype,
                     "n_chips": n_chips},
            "saturation": {"req_per_sec": rps, "at_rate": 10.0,
                           "p99_ms": 20.0, "cost_per_1k_tokens": 0.01}}


def _write_distill_artifact(tmp_path, accuracies):
    from tools.loadtest import assemble, validate_serve

    paths = []
    legs = [("teacher_f32", "teacher", "f32", 10.0),
            ("s6_f32", "student_6l_768", "f32", 21.0),
            ("s6_int8", "student_6l_768", "int8", 30.0),
            ("s4_f32", "student_4l_512", "f32", 40.0, 2)]
    for leg in legs:
        p = tmp_path / f"{leg[0]}.json"
        p.write_text(json.dumps(_mode_doc(*leg)))
        paths.append(str(p))
    doc = assemble(paths, kind="distill", accuracies=accuracies)
    assert validate_serve(doc) == []
    out = tmp_path / "DISTILL_test.json"
    out.write_text(json.dumps(doc, sort_keys=True))
    return doc, out


def test_loadtest_distill_assemble(tmp_path):
    doc, _ = _write_distill_artifact(
        tmp_path, {"teacher": 0.92, "student_6l_768": 0.90,
                   "student_4l_512": 0.93})
    assert doc["kind"] == "distill"
    m = doc["modes"]
    assert m["teacher_f32"]["accuracy"] == 0.92
    assert m["teacher_f32"]["accuracy_delta"] == 0.0
    assert m["s6_f32"]["accuracy_delta"] == pytest.approx(0.02)
    # student beating the teacher yields a NEGATIVE delta
    assert m["s4_f32"]["accuracy_delta"] == pytest.approx(-0.01)
    # per-chip ratio vs the same-dtype teacher leg; int8 student falls
    # back to the f32 teacher (only teacher available); s4 runs on 2
    # chips so its per-chip ratio halves
    assert m["s6_f32"]["saturation"]["vs_teacher_per_chip"] == 2.1
    assert m["s6_int8"]["saturation"]["vs_teacher_per_chip"] == 3.0
    assert m["s4_f32"]["saturation"]["vs_teacher_per_chip"] == 2.0
    assert "vs_teacher_per_chip" not in m["teacher_f32"]["saturation"]


def test_loadtest_distill_accuracy_gate(tmp_path):
    from tools import loadtest

    doc, artifact = _write_distill_artifact(
        tmp_path, {"teacher": 0.92, "student_6l_768": 0.90,
                   "student_4l_512": 0.93})
    # the accuracy floor: 0.02 passes at 0.05, trips at 0.01; the
    # teacher-beating student never trips; rc via the CLI path
    assert loadtest.main(["--check_distill", str(artifact), "0.05"]) == 0
    assert loadtest.main(["--check_distill", str(artifact), "0.01"]) == 1
    failures, notes = loadtest.validate_distill(doc, 0.01)
    assert [f for f in failures if "s6" in f]
    assert not [f for f in failures if "s4_f32" in f]
    assert [n for n in notes if "s4_f32" in n and "beats teacher" in n]
    # an unmeasured student fails loudly
    doc2, _ = _write_distill_artifact(tmp_path, {"teacher": 0.92})
    failures, _ = loadtest.validate_distill(doc2, 0.5)
    assert failures and "no accuracy_delta" in " ".join(failures)
    # a serve artifact is not a distill artifact
    failures, _ = loadtest.validate_distill(dict(doc, kind="serve"), 0.5)
    assert failures and "not a distill artifact" in failures[0]
