"""Packed-finetune tests: per-task packed-vs-unpadded loss parity (the
acceptance pin — BIT-equal for all five registered tasks), the finetune
packer's layout contract, length-bucketed eval, and the shared driver
end-to-end on the three new heads (run_finetune.py --packing with
real_tokens_per_sec perf records).

"Unpadded" is the degenerate packing — every example in its own row of
the SAME packed program (exactly how the serving scheduler defines
packing off); the single-segment baseline is built in the multi-segment
batch's row-major traversal order so the ordered-sum loss reductions
(models/losses._ordered_sum) see identical partial-sum sequences.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bert_pytorch_tpu.data.packing import first_fit  # noqa: E402
from bert_pytorch_tpu.training.finetune import (  # noqa: E402
    bucketed_eval_batches, eval_buckets, pack_finetune_batch)

VOCAB = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"] + (
    "the cat sat on mat a dog did run in park bert serves packed "
    "rows red blue green fast slow").split()


def _tiny_config():
    from bert_pytorch_tpu.config import BertConfig

    return BertConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, fused_ops=False,
        attention_impl="xla")


def _examples(n=5, seq=48, group=1, seed=0):
    """Varied-length synthetic examples: (unit, [group]) arrays with a
    real-token prefix per sub-row."""
    rng = np.random.RandomState(seed)
    shape = (n, seq) if group == 1 else (n, group, seq)
    arrays = {
        "input_ids": np.zeros(shape, np.int32),
        "token_type_ids": np.zeros(shape, np.int32),
        "attention_mask": np.zeros(shape, np.int32),
    }
    lens = 4 + rng.randint(0, 10, (n, group))
    for i in range(n):
        for c in range(group):
            ln = int(lens[i, c])
            row = (i,) if group == 1 else (i, c)
            arrays["input_ids"][row][:ln] = rng.randint(5, 64, ln)
            arrays["token_type_ids"][row][ln // 2:ln] = 1
            arrays["attention_mask"][row][:ln] = 1
    return arrays, lens


def _pack_both(arrays, pack_labels, group=1, seq=48, max_segments=4):
    """(multi-segment packed batch, single-segment baseline) with the
    baseline's units in the multi batch's row-major traversal order, so
    ordered reductions see the same value sequence."""
    n = len(arrays["input_ids"])
    multi, placements = pack_finetune_batch(
        arrays, list(range(n)), n_rows=2, seq_len=seq,
        max_segments=max_segments, group_size=group)
    assert len(placements) == n, "fixture must fully pack"
    multi.update(pack_labels(arrays, placements, 2, seq, max_segments))
    order = [p.unit for p in sorted(placements,
                                    key=lambda p: (p.row, p.seg0))]
    single, sp = pack_finetune_batch(
        arrays, order, n_rows=n, seq_len=seq, max_segments=group,
        group_size=group)
    assert len(sp) == n and all(p.seg0 == 0 for p in sp)
    # label arrays keep the MULTI batch's G so both batches run the
    # SAME compiled program (one example per row = degenerate packing,
    # exactly the serving scheduler's packing-off mode)
    single.update(pack_labels(arrays, sp, n, seq, max_segments))
    return multi, single, order


def _apply(model, params, batch, extract=None):
    import jax.numpy as jnp

    out = model.apply(
        {"params": params}, jnp.asarray(batch["input_ids"]),
        jnp.asarray(batch["token_type_ids"]),
        jnp.asarray(batch["attention_mask"]), deterministic=True,
        position_ids=jnp.asarray(batch["position_ids"]),
        segment_ids=jnp.asarray(batch["segment_ids"]))
    return out if extract is None else extract(out)


# -- per-task parity: packed loss == unpadded loss, bit for bit ---------------


def test_parity_classify():
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.models import (BertForSequenceClassification,
                                         losses)
    from bert_pytorch_tpu.tasks.classify import pack_labels

    cfg = _tiny_config()
    arrays, _ = _examples()
    arrays["labels"] = np.array([0, 1, 1, 0, 1], np.int32)
    multi, single, order = _pack_both(arrays, pack_labels)

    model4 = BertForSequenceClassification(cfg, num_labels=2,
                                           max_segments=4,
                                           dtype=jnp.float32)
    s = jnp.zeros((1, 48), jnp.int32)
    params = model4.init(jax.random.PRNGKey(0), s, s, s)["params"]
    l_multi = float(losses.segment_classification_loss(
        _apply(model4, params, multi), jnp.asarray(multi["labels"])))
    l_single = float(losses.segment_classification_loss(
        _apply(model4, params, single), jnp.asarray(single["labels"])))
    assert l_multi == l_single  # BIT-equal, the acceptance pin
    # and the plain (no packing fields at all) path agrees to fp noise
    plain = model4.apply(
        {"params": params}, jnp.asarray(arrays["input_ids"]),
        jnp.asarray(arrays["token_type_ids"]),
        jnp.asarray(arrays["attention_mask"]), deterministic=True)
    l_plain = float(losses.segment_classification_loss(
        plain, jnp.asarray(arrays["labels"])))
    assert l_multi == pytest.approx(l_plain, abs=1e-6)


def test_parity_embed():
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.models import BertForSentenceEmbedding, losses
    from bert_pytorch_tpu.tasks.embed import pack_labels

    cfg = _tiny_config()
    arrays, _ = _examples(seed=1)
    arrays["labels"] = np.array([1, 0, 1, 0, 0], np.int32)
    multi, single, order = _pack_both(arrays, pack_labels)

    model4 = BertForSentenceEmbedding(cfg, num_labels=2, max_segments=4,
                                      dtype=jnp.float32)
    s = jnp.zeros((1, 48), jnp.int32)
    params = model4.init(jax.random.PRNGKey(0), s, s, s)["params"]
    take = lambda out: out[1]
    l_multi = float(losses.segment_classification_loss(
        _apply(model4, params, multi, take),
        jnp.asarray(multi["labels"])))
    l_single = float(losses.segment_classification_loss(
        _apply(model4, params, single, take),
        jnp.asarray(single["labels"])))
    assert l_multi == l_single
    # packed and single-segment embeddings are bit-equal row for row
    # (same (B, G, S) einsum structure, values merely offset); the
    # plain (B, 1, S) program agrees to fp noise and stays unit-norm
    emb_multi = np.asarray(_apply(model4, params, multi, lambda o: o[0]))
    emb_single = np.asarray(_apply(model4, params, single,
                                   lambda o: o[0]))
    seg_of = {}
    for row in range(multi["segment_ids"].shape[0]):
        for g in sorted(set(multi["segment_ids"][row]) - {0}):
            seg_of[(row, g)] = emb_multi[row, g - 1]
    flat = [seg_of[k] for k in sorted(seg_of)]  # traversal order
    assert len(flat) == 5
    # the un-normalized mean (and so the probe LOSS above) is bit-equal;
    # the final L2-norm reduces over E with a batch-shape-dependent
    # grouping, so cross-shape embeddings agree to last-bit noise only
    # (same-shape packed-vs-single bit-identity is pinned through the
    # serving demux in tests/test_task_registry.py)
    for i in range(5):
        np.testing.assert_allclose(flat[i], emb_single[i, 0],
                                   atol=1e-6, rtol=0)
    emb_plain, _ = model4.apply(
        {"params": params}, jnp.asarray(arrays["input_ids"]),
        jnp.asarray(arrays["token_type_ids"]),
        jnp.asarray(arrays["attention_mask"]), deterministic=True)
    emb_plain = np.asarray(emb_plain)
    for unit_emb in emb_plain:
        assert abs(np.linalg.norm(unit_emb) - 1.0) < 1e-5
    np.testing.assert_allclose(
        emb_single[:, 0], emb_plain[order], atol=1e-6, rtol=0)


def test_parity_choice():
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.models import BertForMultipleChoice, losses
    from bert_pytorch_tpu.tasks.choice import make_pack_labels

    cfg = _tiny_config()
    C = 2
    arrays, _ = _examples(n=4, group=C, seed=2)
    arrays["labels"] = np.array([1, 0, 0, 1], np.int32)
    multi, single, order = _pack_both(arrays, make_pack_labels(C),
                                      group=C)

    model4 = BertForMultipleChoice(cfg, num_choices=C, max_segments=4,
                                   dtype=jnp.float32)
    s = jnp.zeros((1, C, 48), jnp.int32)
    params = model4.init(jax.random.PRNGKey(0), s, s, s)["params"]
    l_multi = float(losses.choice_loss(
        _apply(model4, params, multi), jnp.asarray(multi["labels"]), C))
    l_single = float(losses.choice_loss(
        _apply(model4, params, single), jnp.asarray(single["labels"]), C))
    assert l_multi == l_single
    # the reference-shaped (B, C, S) path agrees to fp noise
    plain = model4.apply(
        {"params": params}, jnp.asarray(arrays["input_ids"]),
        jnp.asarray(arrays["token_type_ids"]),
        jnp.asarray(arrays["attention_mask"]), deterministic=True)
    l_plain = float(losses.choice_loss(plain, jnp.asarray(arrays["labels"]),
                                       C))
    assert l_multi == pytest.approx(l_plain, abs=1e-6)


def test_parity_squad():
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.models import BertForQuestionAnswering, losses
    from bert_pytorch_tpu.tasks.squad_task import pack_labels

    cfg = _tiny_config()
    arrays, lens = _examples(seed=3)
    rng = np.random.RandomState(3)
    n = len(arrays["input_ids"])
    arrays["start_positions"] = np.array(
        [rng.randint(1, lens[i, 0] - 1) for i in range(n)], np.int32)
    arrays["end_positions"] = np.minimum(
        arrays["start_positions"] + 2, lens[:, 0] - 1).astype(np.int32)
    multi, single, order = _pack_both(arrays, pack_labels)

    model = BertForQuestionAnswering(cfg, dtype=jnp.float32)
    s = jnp.zeros((1, 48), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), s, s, s)["params"]

    def loss(batch, G):
        start, end = _apply(model, params, batch)
        return float(losses.packed_qa_loss(
            start, end, jnp.asarray(batch["start_positions"]),
            jnp.asarray(batch["end_positions"]),
            jnp.asarray(batch["segment_ids"]), G))

    assert loss(multi, 4) == loss(single, 4)


def test_parity_ner():
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.data.ner import IGNORE_LABEL
    from bert_pytorch_tpu.models import BertForTokenClassification, losses
    from bert_pytorch_tpu.tasks.ner_task import pack_labels

    cfg = _tiny_config()
    arrays, lens = _examples(seed=4)
    rng = np.random.RandomState(4)
    n, seq = arrays["input_ids"].shape
    labels = np.full((n, seq), IGNORE_LABEL, np.int32)
    for i in range(n):
        labels[i, 1:lens[i, 0] - 1] = rng.randint(1, 4, lens[i, 0] - 2)
    arrays["labels"] = labels
    multi, single, order = _pack_both(arrays, pack_labels)

    model = BertForTokenClassification(cfg, num_labels=4,
                                       dtype=jnp.float32)
    s = jnp.zeros((1, 48), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), s, s, s)["params"]

    def loss(batch, G):
        logits = _apply(model, params, batch)
        return float(losses.packed_token_loss(
            logits, jnp.asarray(batch["labels"]),
            jnp.asarray(batch["segment_ids"]), G,
            ignore_index=IGNORE_LABEL))

    assert loss(multi, 4) == loss(single, 4)


# -- packer + bucketed eval mechanics -----------------------------------------


def test_first_fit_group_costs():
    # groups of 2 segments: 3 units of length 10 into rows of capacity
    # 24 with max_segments 4 -> two per row by segment quota
    bins = first_fit([10, 10, 10], n_bins=2, capacity=24,
                     max_segments=4, segs_per_unit=2)
    assert bins == [[0, 1], [2]]
    with pytest.raises(ValueError, match="capacity"):
        first_fit([30], n_bins=1, capacity=24, max_segments=4)


def test_pack_finetune_batch_layout():
    arrays, lens = _examples(n=4, seq=32, seed=5)
    batch, placements = pack_finetune_batch(
        arrays, [0, 1, 2, 3], n_rows=2, seq_len=32, max_segments=4)
    assert sorted(p.unit for p in placements) == [0, 1, 2, 3]
    for p in placements:
        ln = int(lens[p.unit, 0])
        sl = slice(p.offsets[0], p.offsets[0] + ln)
        np.testing.assert_array_equal(
            batch["input_ids"][p.row, sl],
            arrays["input_ids"][p.unit, :ln])
        np.testing.assert_array_equal(
            batch["segment_ids"][p.row, sl], p.seg0 + 1)
        np.testing.assert_array_equal(
            batch["position_ids"][p.row, sl], np.arange(ln))
    # mask == segment > 0 everywhere
    np.testing.assert_array_equal(batch["attention_mask"],
                                  (batch["segment_ids"] > 0).astype(np.int32))


def test_bucketed_eval_batches_trim_and_pad():
    arrays, lens = _examples(n=7, seq=48, seed=6)
    arrays["labels"] = np.arange(7, dtype=np.int32)
    buckets = eval_buckets(48, floor=8)
    seen = []
    for batch, idx, bucket in bucketed_eval_batches(
            arrays, 4, buckets, label_ignore={"labels": -1}):
        assert batch["input_ids"].shape == (4, bucket)
        assert int(lens[idx, 0].max()) <= bucket
        if len(idx) < 4:  # padded tail rows carry ignored labels
            assert (batch["labels"][len(idx):] == -1).all()
        seen.extend(int(i) for i in idx)
    assert sorted(seen) == list(range(7))


# -- driver e2e on the new heads ----------------------------------------------


@pytest.fixture
def finetune_env(tmp_path):
    vocab = tmp_path / "vocab.txt"
    vocab.write_text("\n".join(VOCAB) + "\n")
    cfg = {
        "vocab_size": len(VOCAB), "hidden_size": 32,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "intermediate_size": 64, "max_position_embeddings": 64,
        "hidden_dropout_prob": 0.0, "attention_probs_dropout_prob": 0.0,
        "fused_ops": False, "attention_impl": "xla", "lowercase": True,
        "tokenizer": "wordpiece", "vocab_file": str(vocab),
    }
    cfg_path = tmp_path / "model_config.json"
    cfg_path.write_text(json.dumps(cfg))

    rng = np.random.RandomState(0)
    words = [w for w in VOCAB if not w.startswith("[")]
    sent = lambda n: " ".join(rng.choice(words, n))
    cls_files = {}
    for split, n in (("train", 32), ("test", 12)):
        path = tmp_path / f"cls_{split}.tsv"
        with open(path, "w") as f:
            for i in range(n):
                lab = i % 2
                marker = "cat cat cat" if lab else "dog dog dog"
                f.write(f"{'positive' if lab else 'negative'}\t"
                        f"{marker} {sent(2 + i % 8)}\n")
        cls_files[split] = str(path)
    mc_path = tmp_path / "mc_train.jsonl"
    with open(mc_path, "w") as f:
        for i in range(16):
            lab = i % 2
            choices = [sent(2 + i % 4), sent(2 + (i + 1) % 4)]
            choices[lab] = "cat cat " + choices[lab]
            f.write(json.dumps({"question": sent(2), "choices": choices,
                                "label": lab}) + "\n")
    return tmp_path, str(cfg_path), cls_files, str(mc_path)


def _perf_records(path):
    return [json.loads(line) for line in
            open(path, encoding="utf-8").read().splitlines()
            if json.loads(line).get("tag") == "perf"]


def test_run_finetune_classify_packed_e2e(finetune_env):
    """The new-head acceptance pin: classification trains through
    run_finetune.py with --packing, LEARNS the marker task, and its perf
    records carry real_tokens_per_sec / pad_fraction end to end (plus
    the --perf_artifact summary)."""
    import run_finetune

    from bert_pytorch_tpu.telemetry import PERF_RECORD_CORE_KEYS

    tmp_path, cfg_path, cls_files, _ = finetune_env
    out = tmp_path / "out_cls"
    artifact = tmp_path / "FINETUNE_test.json"
    results = run_finetune.main([
        "--task", "classify",
        "--train_file", cls_files["train"],
        "--test_file", cls_files["test"],
        "--model_config_file", cfg_path,
        "--output_dir", str(out), "--epochs", "14", "--lr", "1e-3",
        "--batch_size", "8", "--max_seq_len", "32", "--dtype", "float32",
        "--packing", "--packing_max_segments", "4",
        "--perf_artifact", str(artifact)])
    assert results["test_accuracy"] > 0.8, results

    perf = _perf_records(out / "classify_log.jsonl")
    assert perf, "no perf records reached the classify jsonl sink"
    rec = perf[-1]
    assert set(PERF_RECORD_CORE_KEYS) <= set(rec), rec
    for key in ("real_tokens_per_sec", "pad_fraction",
                "packing_efficiency"):
        assert key in rec, key
    assert 0.0 < rec["packing_efficiency"] <= 1.0

    doc = json.loads(artifact.read_text())
    assert doc["kind"] == "finetune"
    task_rec = doc["tasks"]["classify"]
    assert task_rec["packing"] is True
    assert task_rec["real_tokens_per_sec"] > 0
    assert 0.0 <= task_rec["pad_fraction"] < 1.0

    # the saved checkpoint restores through the serving path (strict)
    import jax.numpy as jnp

    from bert_pytorch_tpu.config import BertConfig, pad_vocab_size
    from bert_pytorch_tpu.models import BertForSequenceClassification
    from bert_pytorch_tpu.serving.engine import restore_serving_params

    config = BertConfig.from_json_file(cfg_path)
    config = config.replace(vocab_size=pad_vocab_size(config.vocab_size, 8))
    model = BertForSequenceClassification(config, num_labels=2,
                                          max_segments=4,
                                          dtype=jnp.float32)
    _params, step = restore_serving_params(
        str(out / "ckpt"), model, 32, log=lambda m: None)
    assert step > 0


def test_run_finetune_embed_and_choice_packed_smoke(finetune_env):
    """The other two new heads through the same driver: short packed
    runs, perf records + artifact rows present (learning quality is
    classify's job — these pin the wiring)."""
    import run_finetune

    tmp_path, cfg_path, cls_files, mc_path = finetune_env
    artifact = tmp_path / "FINETUNE_test2.json"
    results = run_finetune.main([
        "--task", "embed", "--train_file", cls_files["train"],
        "--model_config_file", cfg_path,
        "--output_dir", str(tmp_path / "out_emb"),
        "--epochs", "1", "--lr", "1e-3", "--batch_size", "8",
        "--max_seq_len", "32", "--dtype", "float32", "--packing",
        "--perf_artifact", str(artifact)])
    assert results["embedding_norm_err"] < 1e-4

    run_finetune.main([
        "--task", "choice", "--train_file", mc_path,
        "--model_config_file", cfg_path, "--num_choices", "2",
        "--output_dir", str(tmp_path / "out_mc"),
        "--epochs", "1", "--lr", "1e-3", "--batch_size", "4",
        "--max_seq_len", "32", "--dtype", "float32", "--packing",
        "--packing_max_segments", "4",
        "--perf_artifact", str(artifact)])

    doc = json.loads(artifact.read_text())
    assert set(doc["tasks"]) == {"embed", "choice"}
    for rec in doc["tasks"].values():
        assert rec["real_tokens_per_sec"] > 0
        assert rec["packing"] is True
