"""Shared finetune driver: one loop, N registered tasks.

Before this module, run_squad.py and run_ner.py each carried a private
copy of the same machinery — featurize, shuffle/batch, jitted step,
StepWatch perf records, preemption guard + emergency save, watchdog,
checkpoint save, eval loop. Five registered tasks (tasks/registry.py)
would have meant five copies. This driver owns the loop once; a task
contributes only what is genuinely task-shaped (model head, loss,
featurizer, eval/predict) through the `TaskRun` contract its
`TaskSpec.setup` returns.

What every task inherits from the loop, for free:

- telemetry via the single `init_run(phase=<task>)` wiring path —
  jsonl/csv sinks, live /metrics + /healthz, CompileWatch, and StepWatch
  perf records carrying `real_tokens_per_sec` / `pad_fraction` /
  `packing_efficiency` end to end;
- the survival kit (docs/RESILIENCE.md): SIGTERM/SIGINT emergency
  checkpoint of the in-progress state, optional hung-step watchdog;
- **packed training** (`--packing`): the greedy first-fit packer
  (data/packing.first_fit generalized to multi-segment units) assembles
  fixed-shape rows from several short examples, with per-segment labels
  for span/token/classification heads — finetune corpora pad far worse
  than pretraining ones ("Boosting Distributed Training Performance of
  the Unpadded BERT Model", PAPERS.md 2208.08124). Packed loss is
  pinned bit-equal to the same examples one-segment-per-row
  (tests/test_finetune_packing.py);
- **length-bucketed eval**: eval batches ride the smallest bucket that
  fits their longest example instead of always padding to
  max_seq_length — a handful of compiles, most of the pad FLOPs gone;
- a final orbax checkpoint (`<output_dir>/ckpt`) in the finetune save
  layout run_server.py restores, and an optional finetune perf summary
  (`--perf_artifact`; no tool reads it yet — ROADMAP.md Design).
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

# -- default eval buckets: powers of two up to the task's max_seq_len ---------


def eval_buckets(max_seq_len: int, floor: int = 32) -> Tuple[int, ...]:
    """Length buckets for eval batching: 32/64/128/... up to (and always
    including) max_seq_len."""
    out = []
    b = int(floor)
    while b < max_seq_len:
        out.append(b)
        b *= 2
    out.append(int(max_seq_len))
    return tuple(sorted(set(out)))


# -- shared CLI pieces --------------------------------------------------------


def add_common_finetune_flags(p) -> None:
    """Flags every task's parser carries (run_squad/run_ner append these
    to their historical CLIs; the base parser below includes them)."""
    p.add_argument("--packing", action="store_true",
                   help="pack several short examples per row with "
                        "segment-aware attention and per-segment labels "
                        "(data/packing.py; packed loss is bit-equal to "
                        "one-example-per-row — docs/TASKS.md)")
    p.add_argument("--packing_max_segments", type=int, default=8,
                   help="max packed examples (segments) per row")
    p.add_argument("--perf_artifact", type=str, default=None,
                   help="merge this run's finetune perf summary "
                        "(real_tokens_per_sec, pad_fraction, ...) into "
                        "the given JSON file (one entry per task)")


def base_finetune_parser(description: str):
    """The shared CLI for registry tasks without a historical entry
    point (classify / choice / embed): run_ner-style flags plus the
    common packing/perf knobs."""
    import argparse

    p = argparse.ArgumentParser(description=description)
    p.add_argument("--train_file", type=str, default=None)
    p.add_argument("--val_file", type=str, default=None)
    p.add_argument("--test_file", type=str, default=None)
    p.add_argument("--model_config_file", type=str, required=True)
    p.add_argument("--init_checkpoint", type=str, default=None,
                   help="pretraining checkpoint dir (orbax), TF release, "
                        "or reference torch save; optional")
    p.add_argument("--vocab_file", default=None, type=str)
    p.add_argument("--uppercase", action="store_true", default=None,
                   help="force cased tokenization (default: follow the "
                        "model config's `lowercase`, exactly like the "
                        "serving tokenizer — run_server.py)")
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--lr", type=float, default=3e-5)
    p.add_argument("--warmup_proportion", type=float, default=0.1)
    p.add_argument("--clip_grad", type=float, default=1.0)
    p.add_argument("--batch_size", type=int, default=16)
    p.add_argument("--max_seq_len", type=int, default=128)
    p.add_argument("--max_steps", type=int, default=-1,
                   help="cap total optimization steps (benchmarking)")
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--output_dir", type=str, required=True)
    p.add_argument("--log_prefix", type=str, default=None)
    p.add_argument("--metrics_port", type=int, default=None)
    p.add_argument("--dtype", type=str, default="bfloat16",
                   choices=["bfloat16", "float32"])
    p.add_argument("--watchdog_timeout", type=float, default=0.0)
    p.add_argument("--watchdog_action", type=str, default="abort",
                   choices=["abort", "warn"])
    add_common_finetune_flags(p)
    return p


# -- shared task-setup scaffolding (classify / choice / embed) ----------------


def resolve_tokenizer(args, config):
    """The finetune-side tokenizer, case-matched to the serving side:
    run_server.py builds `uppercase=not config.lowercase`, so when
    --uppercase is unset the training featurizer follows the model config
    too — a cased checkpoint must not lowercase its training data while
    live traffic keeps case (they would hit different wordpiece ids)."""
    from bert_pytorch_tpu.data.tokenization import get_wordpiece_tokenizer

    vocab_file = args.vocab_file or config.vocab_file
    if not vocab_file:
        raise SystemExit("vocab_file required (CLI or model config)")
    upper = getattr(args, "uppercase", None)
    if upper is None:
        upper = not config.lowercase
    return get_wordpiece_tokenizer(vocab_file, uppercase=upper)


def dataset_splits(args, build) -> Dict[str, Dict[str, np.ndarray]]:
    """{split: build(path).arrays()} over the train/val/test CLI flags."""
    return {split: build(path)
            for split, path in (("train", args.train_file),
                                ("val", args.val_file),
                                ("test", args.test_file)) if path}


def epoch_steps(train: Optional[Dict[str, np.ndarray]], args,
                group_size: int = 1) -> Tuple[int, int]:
    """(steps_per_epoch, total_steps) with the --max_steps cap applied.

    Packed runs count the actual per-epoch first-fit stream
    (packed_epoch_step_counts) so total_steps — and therefore the LR
    schedule built over it — matches the steps that really execute; the
    unpacked batch count would be ~avg_segments× too large."""
    if train is None:
        return 0, 0
    if getattr(args, "packing", False):
        counts = packed_epoch_step_counts(
            train, n_rows=args.batch_size, seq_len=args.max_seq_len,
            max_segments=getattr(args, "packing_max_segments", 8),
            seed=args.seed, epochs=args.epochs, group_size=group_size)
        steps_per_epoch = counts[0] if counts else 0
        total_steps = sum(counts)
    else:
        steps_per_epoch = max(1, -(-len(train["input_ids"])
                                   // args.batch_size))
        total_steps = steps_per_epoch * args.epochs
    if args.max_steps and args.max_steps > 0:
        total_steps = min(total_steps, int(args.max_steps))
    return steps_per_epoch, total_steps


def finetune_optimizer(args, total_steps: int):
    """(schedule, tx): linear-warmup fused_adam + optional global-norm
    clip — the one finetune recipe every registry task trains with."""
    import optax

    from bert_pytorch_tpu.optim import schedulers
    from bert_pytorch_tpu.optim.adam import fused_adam
    from bert_pytorch_tpu.optim.lamb import default_weight_decay_mask

    sched = schedulers.linear_warmup_schedule(
        args.lr, max(total_steps, 1), warmup=args.warmup_proportion)
    tx = fused_adam(sched, weight_decay=0.01,
                    weight_decay_mask=default_weight_decay_mask,
                    bias_correction=False)
    if args.clip_grad and args.clip_grad > 0:
        tx = optax.chain(optax.clip_by_global_norm(args.clip_grad), tx)
    return sched, tx


def accuracy_evals(datasets, batch_size: int, buckets: Sequence[int],
                   logits_fn) -> Dict[str, Callable]:
    """{split: run(params) -> accuracy} for the val/test splits present.
    `logits_fn(params, feats)` returns the (N, ...) per-example scores
    argmaxed against the 'labels' field (length-bucketed batching)."""
    from bert_pytorch_tpu.data import glue

    def make(split):
        arrays = datasets[split]

        def run(params):
            import jax.numpy as jnp

            outs, labels = [], []
            for batch, idx, _bucket in bucketed_eval_batches(
                    arrays, batch_size, buckets,
                    label_ignore={"labels": -1}):
                feats = {k: jnp.asarray(v) for k, v in batch.items()
                         if k != "labels"}
                outs.append(np.asarray(logits_fn(params, feats))[:len(idx)])
                labels.append(arrays["labels"][idx])
            return glue.accuracy(np.concatenate(outs),
                                 np.concatenate(labels))

        return run

    return {s: make(s) for s in ("val", "test") if s in datasets}


def eval_closures(evals: Dict[str, Callable], tel, metric: str = "accuracy"
                  ) -> Tuple[Optional[Callable], Callable]:
    """(epoch_eval, finalize) over accuracy_evals' split runners —
    epoch_eval logs val accuracy per epoch (None when no val split),
    finalize logs/returns test accuracy."""

    def epoch_eval(params, epoch):
        acc = evals["val"](params)
        tel.logger.log("val", epoch, epoch=epoch, **{metric: acc})
        return {"val_accuracy": acc}

    def finalize(params, results):
        out = {}
        if "test" in evals:
            acc = evals["test"](params)
            tel.logger.log("test", 0, **{metric: acc})
            out["test_accuracy"] = acc
        return out

    return (epoch_eval if "val" in evals else None), finalize


# -- checkpoint seeding (moved from run_squad.py; run_ner/run_squad alias it) --


def _is_tf_source(path: str) -> bool:
    """Does `path` name an external weight source — a Google TF release
    (registry name, URL, zip, extracted dir, bare ckpt prefix) or a
    reference torch checkpoint (ckpt_*.pt) — rather than one of this
    framework's orbax checkpoints?"""
    from bert_pytorch_tpu.models.pretrained import PRETRAINED_ARCHIVE_MAP

    if path in PRETRAINED_ARCHIVE_MAP or "://" in path \
            or path.endswith((".zip", ".ckpt", ".pt", ".pth", ".bin")):
        return True
    if os.path.isdir(path):
        for _root, _dirs, files in os.walk(path):
            if "bert_config.json" in files \
                    or any(f.endswith(".ckpt.index") for f in files):
                return True
        return False
    return os.path.exists(path + ".index")


def load_pretrained_params(init_checkpoint: str, current_params,
                           log=None):
    """Load encoder weights from a pretraining checkpoint — this framework's
    orbax checkpoints, a Google TF BERT release (zip / URL / extracted dir /
    registry name), or a reference torch save — returning the FINAL param
    tree: loaded leaves replace current ones (placed with their
    dtype/sharding), everything else keeps its current init. Tolerant of
    missing/extra heads
    (reference loads ckpt['model'] with strict=False, run_squad.py:961; TF
    import parity: src/modeling.py:58-116).

    Every subtree that does NOT come from the checkpoint is reported loudly:
    a wrong --init_checkpoint must not silently train from scratch. Raises if
    nothing at all matches (that checkpoint is certainly not a BERT encoder
    for this config)."""
    import jax

    if _is_tf_source(init_checkpoint):
        from bert_pytorch_tpu.models.pretrained import from_pretrained

        vocab = int(np.shape(jax.tree.leaves(
            current_params["bert"]["embeddings"]["word_embeddings"])[0])[0])
        _, src = from_pretrained(init_checkpoint, next_sentence=True,
                                 vocab_pad_multiple=1)
        # re-pad the release vocab to this model's padded size
        emb = src["bert"]["embeddings"]["word_embeddings"]["embedding"]
        if emb.shape[0] < vocab:
            from bert_pytorch_tpu.models.pretrained import (
                PADDED_VOCAB_BIAS, _pad_vocab)

            src["bert"]["embeddings"]["word_embeddings"]["embedding"] = \
                _pad_vocab(emb, vocab, 0.0)
            src["cls_predictions"]["bias"] = _pad_vocab(
                src["cls_predictions"]["bias"], vocab, PADDED_VOCAB_BIAS)
        step = ("torch-ckpt" if init_checkpoint.endswith(
            (".pt", ".pth", ".bin")) else "tf-release")
    else:
        from bert_pytorch_tpu.training.checkpoint import CheckpointManager

        # 'dir@step' selects a specific checkpoint step (finetune curves
        # against intermediate pretraining checkpoints); bare dir = latest
        want_step = None
        ckpt_dir = init_checkpoint
        if "@" in init_checkpoint:
            head, _, tail = init_checkpoint.rpartition("@")
            if tail.isdigit():
                ckpt_dir, want_step = head, int(tail)
        mgr = CheckpointManager(ckpt_dir)
        state, step = mgr.restore_raw(step=want_step)
        mgr.close()
        src = state["params"]

    # align the source's encoder layer layout (scan-stacked vs per-layer)
    # with the target model's before the path-wise merge — a stacked-era
    # checkpoint must seed an unstacked model and vice versa
    from bert_pytorch_tpu.models.pretrained import (convert_tree_layout,
                                                    tree_layout)

    want_layout = tree_layout(current_params)
    if want_layout is not None and tree_layout(src) not in (None, want_layout):
        src = convert_tree_layout(src, stacked=(want_layout == "stacked"))

    loaded, fresh = [], []

    def merge(dst, src_tree, path=()):
        out = {}
        for k, v in dst.items():
            child_path = path + (k,)
            if isinstance(v, dict):
                out[k] = merge(v, src_tree.get(k, {}) if isinstance(
                    src_tree, dict) else {}, child_path)
            else:
                cand = src_tree.get(k) if isinstance(src_tree, dict) else None
                name = "/".join(child_path)
                if cand is not None and tuple(np.shape(cand)) == tuple(v.shape):
                    out[k] = jax.numpy.asarray(cand, v.dtype)
                    loaded.append(name)
                else:
                    out[k] = None  # keep fresh init
                    fresh.append(name + ("" if cand is None
                                         else f" (shape {np.shape(cand)} != "
                                              f"{tuple(v.shape)})"))
        return out

    merged = merge(current_params, src)
    emit = log if log is not None else print
    emit(f"init_checkpoint step {step}: loaded {len(loaded)} param leaves, "
         f"{len(fresh)} fresh-initialized")
    if fresh:
        emit("WARNING: fresh-initialized (not found in checkpoint or shape "
             "mismatch): " + ", ".join(sorted(fresh)))
    if not loaded:
        raise ValueError(
            f"checkpoint {init_checkpoint} (step {step}) shares no "
            "same-shaped parameters with this model — wrong checkpoint?")

    # apply the merge here so every caller gets final params: a loaded leaf
    # is placed with the current leaf's dtype/sharding, a fresh leaf IS the
    # current (initialized) leaf object
    def take(cur, new):
        if new is None:
            return cur
        if isinstance(cur, jax.Array) and hasattr(cur, "sharding"):
            return jax.device_put(new, cur.sharding)
        return new

    return jax.tree.map(take, current_params, merged)


# -- packed finetune batch assembly -------------------------------------------


@dataclass(frozen=True)
class UnitPlacement:
    """Where one training unit landed in a packed batch. A unit is one
    example — `group_size` sub-rows (1 for single-sequence tasks, C for
    multiple choice, whose C choices must stay CONSECUTIVE segments of
    one row so the loss can regroup (B, G) -> (B, G/C, C))."""

    unit: int                 # index into the per-example arrays
    row: int                  # packed batch row
    seg0: int                 # first segment slot (0-based)
    offsets: Tuple[int, ...]  # per-sub-row token offset within the row
    lengths: Tuple[int, ...]  # per-sub-row real token count


def _unit_lengths(attention_mask: np.ndarray) -> np.ndarray:
    """(N, S) or (N, C, S) masks -> (N,) total real tokens per unit."""
    mask = np.asarray(attention_mask, np.int64)
    return mask.sum(axis=tuple(range(1, mask.ndim)))


def segment_scalar_pack_labels(arrays: Dict[str, np.ndarray],
                               placements: Sequence[UnitPlacement],
                               n_rows: int, seq_len: int,
                               max_segments: int) -> Dict[str, np.ndarray]:
    """Per-segment scalar labels for pooled heads: (n_rows, G), -1 = empty
    slot. The `pack_labels` hook for any task whose label is one int per
    example (classify, embed)."""
    labels = np.full((n_rows, max_segments), -1, np.int32)
    for p in placements:
        labels[p.row, p.seg0] = arrays["labels"][p.unit]
    return {"labels": labels}


def pack_finetune_batch(arrays: Dict[str, np.ndarray],
                        unit_indices: Sequence[int],
                        n_rows: int, seq_len: int, max_segments: int,
                        group_size: int = 1
                        ) -> Tuple[Dict[str, np.ndarray],
                                   List[UnitPlacement]]:
    """First-fit `unit_indices` (arrival order) into an (n_rows, seq_len)
    packed batch. Returns the base packed fields (data/packing.py
    contract: input_ids / token_type_ids / attention_mask / segment_ids /
    position_ids) plus the placements a task's label packer consumes;
    units that did not fit are simply not placed (their indices stay
    pending with the caller)."""
    from bert_pytorch_tpu.data.packing import first_fit

    ids = arrays["input_ids"]
    types = arrays.get("token_type_ids")
    lengths = _unit_lengths(arrays["attention_mask"])
    sub_lengths = np.asarray(arrays["attention_mask"], np.int64).sum(axis=-1)

    # the ONE greedy first-fit packer — the same function the pretraining
    # loader and the serving batcher bin with, so training and serving
    # packing cannot drift; segs_per_unit packs whole C-segment
    # multiple-choice groups as one unit
    bins = first_fit([lengths[i] for i in unit_indices],
                     n_bins=n_rows, capacity=seq_len,
                     max_segments=max_segments,
                     segs_per_unit=group_size)
    batch = {k: np.zeros((n_rows, seq_len), np.int32)
             for k in ("input_ids", "token_type_ids", "attention_mask",
                       "segment_ids", "position_ids")}
    placements: List[UnitPlacement] = []
    for row, members in enumerate(bins):
        cursor, seg = 0, 0
        for local in members:
            unit = int(unit_indices[local])
            offsets, lens = [], []
            for c in range(group_size):
                if group_size == 1:
                    row_ids = ids[unit]
                    row_types = None if types is None else types[unit]
                    ln = int(sub_lengths[unit])
                else:
                    row_ids = ids[unit, c]
                    row_types = None if types is None else types[unit, c]
                    ln = int(sub_lengths[unit, c])
                sl = slice(cursor, cursor + ln)
                batch["input_ids"][row, sl] = row_ids[:ln]
                if row_types is not None:
                    batch["token_type_ids"][row, sl] = row_types[:ln]
                batch["attention_mask"][row, sl] = 1
                batch["segment_ids"][row, sl] = seg + 1
                batch["position_ids"][row, sl] = np.arange(ln,
                                                           dtype=np.int32)
                offsets.append(cursor)
                lens.append(ln)
                cursor += ln
                seg += 1
            placements.append(UnitPlacement(
                unit=unit, row=row, seg0=seg - group_size,
                offsets=tuple(offsets), lengths=tuple(lens)))
    return batch, placements


# -- plain + packed training batch iterators ----------------------------------


def plain_train_batches(arrays: Dict[str, np.ndarray], batch_per_step: int,
                        accum_steps: int, shuffle: bool, seed: int,
                        label_ignore: Optional[Dict[str, int]] = None):
    """Fixed-shape per-step batches, tail padded to full by repeating
    index 0 with its labels forced to the ignore value (so duplicated
    rows contribute zero loss — the run_squad pad_to_full convention).
    Yields ((accum, micro, ...) stacked batch, real_token_count,
    real_example_count)."""
    from bert_pytorch_tpu.training.pretrain import stack_microbatches

    n = len(arrays["input_ids"])
    order = (np.random.RandomState(seed).permutation(n) if shuffle
             else np.arange(n))
    for lo in range(0, n, batch_per_step):
        idx = order[lo:lo + batch_per_step]
        pad = batch_per_step - len(idx)
        full = (np.concatenate([idx, np.zeros(pad, np.int64)]) if pad
                else idx)
        batch = {k: np.asarray(v[full]).copy() for k, v in arrays.items()}
        if pad:
            for fld, ign in (label_ignore or {}).items():
                batch[fld][len(idx):] = ign
        real = int(np.asarray(
            arrays["attention_mask"][idx], np.int64).sum())
        yield stack_microbatches(batch, accum_steps), real, len(idx)


def _packable_lengths(arrays: Dict[str, np.ndarray],
                      seq_len: int) -> np.ndarray:
    """(N,) per-unit token counts, validated to fit one packed row."""
    lengths = _unit_lengths(arrays["attention_mask"])
    too_long = [int(i) for i in np.nonzero(lengths > seq_len)[0]]
    if too_long:
        raise ValueError(
            f"{len(too_long)} unit(s) exceed seq_len {seq_len} (e.g. unit "
            f"{too_long[0]}: {int(lengths[too_long[0]])} tokens) — a "
            "multi-choice group must fit one row to pack; raise "
            "--max_seq_len or disable --packing")
    return lengths


def packed_epoch_step_counts(arrays: Dict[str, np.ndarray], n_rows: int,
                             seq_len: int, max_segments: int, seed: int,
                             epochs: float,
                             group_size: int = 1) -> List[int]:
    """Per-epoch step counts `packed_train_batches` will dispatch.

    The epoch-e shuffle is a pure function of seed+e, so the first-fit
    stream can be replayed placement-only BEFORE training: total_steps
    and the LR schedule built over it are sized to the packed stream. A
    packed step consumes ~n_rows*avg_segments examples, so sizing from
    the unpacked batch count instead would leave epoch-bound runs ending
    near peak LR and step-bound runs training avg_segments× the data
    passes. A fractional final epoch contributes round(frac * count).
    """
    from bert_pytorch_tpu.data.packing import first_fit

    n = len(arrays["input_ids"])
    if n == 0 or epochs <= 0:
        return []
    lengths = _packable_lengths(arrays, seq_len)
    window = max(1, n_rows * max_segments * 2)
    full = int(epochs)
    frac = float(epochs) - full
    counts: List[int] = []
    for e in range(full + (1 if frac > 0 else 0)):
        pending = list(np.random.RandomState(seed + e).permutation(n))
        steps = 0
        while pending:
            head = pending[:window]
            bins = first_fit([lengths[i] for i in head], n_bins=n_rows,
                             capacity=seq_len, max_segments=max_segments,
                             segs_per_unit=group_size)
            placed = {int(head[local]) for b in bins for local in b}
            if not placed:
                raise RuntimeError("packer failed to place the head unit")
            pending = [i for i in pending if i not in placed]
            steps += 1
        counts.append(steps)
    if frac > 0:
        counts[-1] = max(1, int(round(frac * counts[-1])))
    return counts


def packed_train_batches(arrays: Dict[str, np.ndarray], n_rows: int,
                         seq_len: int, max_segments: int,
                         pack_labels: Callable, shuffle: bool, seed: int,
                         group_size: int = 1):
    """Packed per-step batches: shuffle once, then first-fit the pending
    stream in arrival order; units that do not fit a batch stay pending
    for the next (continuous packing, the data/packing.py discipline).
    Yields ((1, n_rows, ...) stacked packed batch, real_token_count,
    placed_example_count)."""
    n = len(arrays["input_ids"])
    _packable_lengths(arrays, seq_len)  # reject units that cannot fit
    order = (np.random.RandomState(seed).permutation(n) if shuffle
             else np.arange(n))
    pending: List[int] = list(order)
    window = max(1, n_rows * max_segments * 2)
    while pending:
        batch, placements = pack_finetune_batch(
            arrays, pending[:window], n_rows, seq_len, max_segments,
            group_size=group_size)
        if not placements:  # cannot happen (head always fits an empty row)
            raise RuntimeError("packer failed to place the head unit")
        labels = pack_labels(arrays, placements, n_rows, seq_len,
                             max_segments)
        batch.update(labels)
        placed = {p.unit for p in placements}
        pending = [i for i in pending if i not in placed]
        real = int(sum(sum(p.lengths) for p in placements))
        yield ({k: v[None] for k, v in batch.items()}, real,
               len(placements))


# -- length-bucketed eval -----------------------------------------------------


def bucketed_eval_batches(arrays: Dict[str, np.ndarray], batch_size: int,
                          buckets: Sequence[int],
                          label_ignore: Optional[Dict[str, int]] = None):
    """Length-bucketed eval batching: examples group by the smallest
    bucket that fits their longest sub-row, every sequence-shaped field
    is TRIMMED to the bucket, and tails pad to full batch_size by
    repeating index 0 with ignored labels. Pad keys beyond a real
    example's length carry the exact-zero attention bias either way, so
    trimming changes FLOPs, not answers. Yields
    (np_batch, real_indices, bucket)."""
    mask = np.asarray(arrays["attention_mask"], np.int64)
    sub_len = mask.sum(axis=-1)
    max_len = sub_len.max(axis=-1) if sub_len.ndim > 1 else sub_len
    buckets = sorted(set(int(b) for b in buckets))
    by_bucket: Dict[int, List[int]] = {}
    for i, ln in enumerate(max_len):
        for b in buckets:
            if ln <= b:
                by_bucket.setdefault(b, []).append(i)
                break
        else:
            by_bucket.setdefault(buckets[-1], []).append(i)
    seq_fields = {k for k, v in arrays.items()
                  if np.asarray(v).ndim >= 2
                  and np.asarray(v).shape[-1] == mask.shape[-1]}
    for bucket in sorted(by_bucket):
        idx_all = by_bucket[bucket]
        for lo in range(0, len(idx_all), batch_size):
            idx = np.asarray(idx_all[lo:lo + batch_size])
            pad = batch_size - len(idx)
            full = (np.concatenate([idx, np.zeros(pad, np.int64)]) if pad
                    else idx)
            batch = {}
            for k, v in arrays.items():
                picked = np.asarray(v[full]).copy()
                if k in seq_fields:
                    picked = picked[..., :bucket].copy()
                batch[k] = picked
            if pad:
                for fld, ign in (label_ignore or {}).items():
                    batch[fld][len(idx):] = ign
            yield batch, idx, bucket


# -- the TaskRun contract + the loop ------------------------------------------


@dataclass
class TaskRun:
    """Everything task-shaped the driver loop needs, built by a
    TaskSpec.setup(args, config, tel). `train_arrays=None` skips
    training (predict/eval-only invocations)."""

    model: Any
    tx: Any
    init_fn: Callable                     # rng -> model variables
    schedule: Callable[[int], float]      # lr metric (optimizer owns its own)
    seq_len: int
    batch_size: int                       # units per optimization step
    accum_steps: int = 1
    total_steps: int = 0
    epochs: Optional[int] = None          # None = loop until total_steps
    train_arrays: Optional[Dict[str, np.ndarray]] = None
    loss_builder: Optional[Callable] = None         # plain batches
    packed_loss_builder: Optional[Callable] = None  # --packing batches
    pack_labels: Optional[Callable] = None
    group_size: int = 1                   # sub-rows per unit (MC: C)
    label_ignore: Dict[str, int] = field(default_factory=dict)
    rows_per_step: Optional[int] = None   # FLOPs basis (MC: batch*C)
    log_every: int = 50
    perf_log_freq: int = 50
    init_checkpoint: Optional[str] = None
    epoch_eval: Optional[Callable] = None  # (params, epoch) -> dict|None
    finalize: Optional[Callable] = None    # (params, results) -> dict|None
    log_epoch_metrics: bool = False        # per-epoch train record (run_ner)


def write_finetune_artifact(path: str, task: str,
                            record: Dict[str, Any]) -> None:
    """Merge one task's finetune perf summary into the JSON artifact at
    path (several tasks accumulate into one file)."""
    doc: Dict[str, Any] = {"schema_version": 1, "kind": "finetune",
                           "tasks": {}}
    try:
        with open(path, encoding="utf-8") as f:
            prev = json.load(f)
        if isinstance(prev, dict) and isinstance(prev.get("tasks"), dict):
            doc = prev
    except (OSError, ValueError):
        pass
    doc["schema_version"] = 1
    doc["kind"] = "finetune"
    doc["time_unix"] = round(time.time(), 3)
    doc["tasks"][task] = record
    os.makedirs(os.path.dirname(os.path.abspath(path)) or ".",
                exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True, allow_nan=False)
        f.write("\n")


def run_task(spec, args) -> Dict[str, Any]:
    """The shared finetune entry body: telemetry + survival kit + train
    loop (plain or packed) + checkpoint + per-task eval, for any
    registered TaskSpec. run_finetune.py (and the run_squad.py /
    run_ner.py aliases) call this."""
    if not getattr(args, "output_dir", None):
        raise SystemExit("--output_dir is required")
    os.makedirs(args.output_dir, exist_ok=True)

    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.compile_cache import enable_compile_cache
    from bert_pytorch_tpu.config import BertConfig, pad_vocab_size
    from bert_pytorch_tpu.parallel import dist
    from bert_pytorch_tpu.resilience import PreemptionGuard
    from bert_pytorch_tpu.resilience.preemption import \
        finetune_emergency_save
    from bert_pytorch_tpu.resilience.watchdog import arm_watchdog
    from bert_pytorch_tpu.telemetry import (collect_provenance,
                                            device_peak_flops,
                                            flops_per_seq, init_run)
    from bert_pytorch_tpu.training import TrainState, make_sharded_state
    from bert_pytorch_tpu.training.checkpoint import CheckpointManager
    from bert_pytorch_tpu.training.pretrain import build_pretrain_step

    np.random.seed(args.seed)
    enable_compile_cache()
    config = BertConfig.from_json_file(args.model_config_file)
    config = config.replace(vocab_size=pad_vocab_size(config.vocab_size, 8))

    log_prefix = getattr(args, "log_prefix", None) or f"{spec.name}_log"
    tel = init_run(phase=spec.name,
                   log_prefix=os.path.join(args.output_dir, log_prefix),
                   verbose=dist.is_main_process(), jsonl=True,
                   metrics_port=getattr(args, "metrics_port", None))
    logger = tel.logger
    compile_watch = tel.compile_watch
    guard = PreemptionGuard(registry=tel.registry, log=logger.info)
    guard.install()
    watchdog = None
    survival: Dict[str, Any] = {}
    try:
        tel.log_header(**collect_provenance())
        run: TaskRun = spec.setup(args, config, tel)
        packing = bool(getattr(args, "packing", False))
        if packing and run.pack_labels is None:
            raise SystemExit(f"task '{spec.name}' does not support "
                             "--packing")
        if packing and run.accum_steps > 1:
            raise SystemExit(
                "--packing is incompatible with gradient accumulation "
                f"(accum_steps={run.accum_steps}): the packer owns the "
                "per-step example budget, so accumulation would silently "
                "change the effective batch and LR-schedule basis. Drop "
                "one of the two flags.")
        results: Dict[str, Any] = {}
        last_perf: Optional[Dict[str, float]] = None

        do_train = run.train_arrays is not None and run.total_steps > 0
        if do_train:
            loss_builder = (run.packed_loss_builder if packing
                            else run.loss_builder)
            accum = run.accum_steps
            step_fn = build_pretrain_step(
                run.model, run.tx, schedule=run.schedule,
                accum_steps=accum, loss_fn_builder=loss_builder)
            state, _ = make_sharded_state(jax.random.PRNGKey(args.seed),
                                          run.init_fn, run.tx)
            if run.init_checkpoint:
                params = load_pretrained_params(run.init_checkpoint,
                                                state.params,
                                                log=logger.info)
                state = TrainState(step=state.step, params=params,
                                   opt_state=state.opt_state)
                logger.info(f"loaded pretrained weights from "
                            f"{run.init_checkpoint}")
            jit_step = jax.jit(step_fn, donate_argnums=(0,))

            # StepWatch's flops/slot basis is DEVICE ROWS per step: a
            # packed step dispatches exactly batch_size rows (accum > 1
            # is rejected with --packing above),
            # a plain step batch*accum*group rows (multiple choice
            # computes C rows per example). Getting this wrong skews the
            # reported MFU/pad_fraction (seq_per_sec therefore
            # counts rows, not examples; results[
            # "training_sequences_per_second"] below counts examples
            # actually consumed, both modes).
            if packing:
                rows = run.batch_size
            else:
                rows = run.rows_per_step or (
                    run.batch_size * run.accum_steps * run.group_size)
            peak = device_peak_flops(
                jax.devices()[0],
                dtype=getattr(args, "dtype", None) or config.dtype)
            sw = tel.make_stepwatch(
                flops_per_step=flops_per_seq(
                    config, run.seq_len, config.vocab_size, 0) * rows,
                seqs_per_step=rows,
                seq_len=run.seq_len,
                peak_flops=peak and peak * jax.device_count(),
                log_freq=run.perf_log_freq,
                n_devices=jax.device_count())
            watchdog = arm_watchdog(
                getattr(args, "watchdog_timeout", 0.0),
                getattr(args, "watchdog_action", "abort"), sw,
                registry=tel.registry, log=logger.info,
                out_dir=args.output_dir)

            logger.info(
                f"finetune[{spec.name}]: {run.total_steps} step(s), "
                f"batch {run.batch_size} x accum {run.accum_steps}, "
                f"seq {run.seq_len}, packing "
                f"{'on' if packing else 'off'}"
                + (f" (max_segments "
                   f"{getattr(args, 'packing_max_segments', 8)})"
                   if packing else ""))

            rng = jax.random.PRNGKey(args.seed)
            t0 = time.time()
            step, epoch, examples_done = 0, 0, 0
            metrics = None
            while step < run.total_steps:
                if packing:
                    batches = packed_train_batches(
                        run.train_arrays, n_rows=run.batch_size,
                        seq_len=run.seq_len,
                        max_segments=getattr(args, "packing_max_segments",
                                             8),
                        pack_labels=run.pack_labels, shuffle=True,
                        seed=args.seed + epoch,
                        group_size=run.group_size)
                else:
                    batches = plain_train_batches(
                        run.train_arrays,
                        run.batch_size * run.accum_steps,
                        run.accum_steps, shuffle=True,
                        seed=args.seed + epoch,
                        label_ignore=run.label_ignore)
                for batch_np, real_tokens, n_examples in batches:
                    if step >= run.total_steps:
                        break
                    with sw.phase("data_prep"):
                        batch = {k: jnp.asarray(v)
                                 for k, v in batch_np.items()}
                        sw.note_tokens(float(real_tokens))
                    rng, srng = jax.random.split(rng)
                    with sw.phase("dispatch"):
                        state, metrics = jit_step(state, batch, srng)
                    step += 1
                    examples_done += n_examples
                    survival["state"], survival["step"] = state, step
                    if not run.log_epoch_metrics and (
                            step % run.log_every == 0
                            or step == run.total_steps):
                        with sw.phase("metric_flush"):
                            tel.log_train(
                                step, loss=float(metrics["loss"]),
                                learning_rate=float(
                                    metrics["learning_rate"]))
                    perf = sw.step_done()
                    if perf is not None:
                        tel.log_perf(step, perf)
                        last_perf = perf
                if run.log_epoch_metrics and metrics is not None:
                    with sw.phase("metric_flush"):
                        tel.log_train(step, epoch=epoch,
                                      loss=float(metrics["loss"]),
                                      learning_rate=float(
                                          metrics["learning_rate"]))
                if run.epoch_eval is not None and step > 0:
                    with sw.pause():  # eval must not pollute the interval
                        extra = run.epoch_eval(state.params, epoch)
                    if extra:
                        results.update(extra)
                epoch += 1
                if run.epochs is not None and epoch >= run.epochs:
                    break
            perf = sw.flush()  # partial interval: short runs still get one
            if perf is not None:
                tel.log_perf(step, perf)
                last_perf = perf
            train_time = time.time() - t0
            results["e2e_train_time"] = train_time
            # examples ACTUALLY consumed: a packed step trains a
            # data-dependent number of examples (never batch*accum — the
            # packed path forces accum to 1) and a plain tail batch pads
            # with zero-loss repeats that must not count
            results["training_sequences_per_second"] = (
                examples_done / max(train_time, 1e-9))

            mgr = CheckpointManager(os.path.join(args.output_dir, "ckpt"))
            mgr.save(step, state, extra={"task": spec.name,
                                         "config": config.to_dict()})
            mgr.close()
            final_params = state.params

            artifact = getattr(args, "perf_artifact", None)
            if artifact and last_perf is not None:
                rec = {k: last_perf[k] for k in
                       ("real_tokens_per_sec", "pad_fraction",
                        "packing_efficiency", "seq_per_sec",
                        "step_time_ms", "mfu") if k in last_perf}
                rec["packing"] = packing
                rec["steps"] = step
                write_finetune_artifact(artifact, spec.name, rec)
                logger.info(f"finetune[{spec.name}]: perf artifact -> "
                            f"{artifact}")
        else:
            state, _ = make_sharded_state(jax.random.PRNGKey(args.seed),
                                          run.init_fn, run.tx)
            if run.init_checkpoint:
                final_params = load_pretrained_params(
                    run.init_checkpoint, state.params, log=logger.info)
            else:
                final_params = state.params

        if run.finalize is not None:
            extra = run.finalize(final_params, results)
            if extra:
                results.update(extra)

        if results:
            logger.log("final", 0, **{
                k: v for k, v in results.items()
                if isinstance(v, (int, float))})
        logger.info(json.dumps(results, default=str))
        logger.info(f"compiles: {compile_watch.snapshot()}")
        return results
    except BaseException as exc:
        # preemption-safe finetuning: SIGTERM/SIGINT mid-epoch saves the
        # in-progress state (the reference lost the whole finetune run)
        finetune_emergency_save(guard, exc, survival,
                                os.path.join(args.output_dir, "ckpt"),
                                spec.name, registry=tel.registry,
                                log=logger.info)
        raise
    finally:
        for closeable in (watchdog, guard):
            if closeable is not None:
                try:
                    closeable.close()
                except Exception:
                    pass
        tel.close()
