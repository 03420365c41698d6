"""`squad` task: SQuAD v1.1/v2.0 extractive question answering.

The run_squad.py entry point's task-shaped half, registered: CLI parity
with the reference run_squad.py (:729-859), featurize/train/predict/
n-best/eval through tasks/squad.py, serving on POST /v1/squad. The
training and eval loop itself lives in training/finetune.py (run_squad.py is
a thin alias of run_finetune.py --task squad).

Packed training (--packing): spans shift by each segment's packing
offset and the packed QA loss softmaxes per segment
(losses.packed_qa_loss) — a full-row softmax would mix denominators
across co-packed strangers. Prediction rides length-bucketed eval
batches (windows grouped by real length instead of always padding to
--max_seq_length).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict

import numpy as np

from bert_pytorch_tpu.tasks import registry


def parse_arguments(argv=None):
    import argparse

    from bert_pytorch_tpu.training.finetune import add_common_finetune_flags

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config_file", default=None, type=str)
    p.add_argument("--bert_model", default="bert-large-uncased", type=str)
    p.add_argument("--output_dir", required=False, default=None, type=str)
    p.add_argument("--train_file", default=None, type=str)
    p.add_argument("--predict_file", default=None, type=str)
    p.add_argument("--init_checkpoint", default=None, type=str,
                   help="pretraining checkpoint dir (orbax) or none")
    p.add_argument("--model_config_file", default=None, type=str)
    p.add_argument("--vocab_file", default=None, type=str)
    p.add_argument("--do_train", action="store_true")
    p.add_argument("--do_predict", action="store_true")
    p.add_argument("--do_eval", action="store_true")
    p.add_argument("--do_lower_case", action="store_true", default=True)
    p.add_argument("--max_seq_length", default=384, type=int)
    p.add_argument("--doc_stride", default=128, type=int)
    p.add_argument("--max_query_length", default=64, type=int)
    p.add_argument("--train_batch_size", default=32, type=int)
    p.add_argument("--predict_batch_size", default=8, type=int)
    p.add_argument("--learning_rate", default=3e-5, type=float,
                   help="peak LR. The finetune optimizer keeps apex "
                        "FusedAdam's bias_correction=False semantics "
                        "(reference run_squad.py:982-988), which amplifies "
                        "early updates ~(1/sqrt(1-b2))x; measured on v5e, "
                        "3e-4 diverges the encoder to chance while 5e-5 "
                        "reaches 100 F1 on an overfit probe — stay near the "
                        "reference's 3e-5 scale")
    p.add_argument("--num_train_epochs", default=2.0, type=float)
    p.add_argument("--max_steps", default=-1.0, type=float,
                   help="early exit for benchmarking (reference :1070-1073)")
    p.add_argument("--warmup_proportion", default=0.1, type=float)
    p.add_argument("--n_best_size", default=20, type=int)
    p.add_argument("--max_answer_length", default=30, type=int)
    p.add_argument("--verbose_logging", action="store_true")
    p.add_argument("--seed", type=int, default=42)
    p.add_argument("--gradient_accumulation_steps", type=int, default=1)
    p.add_argument("--version_2_with_negative", action="store_true")
    p.add_argument("--null_score_diff_threshold", type=float, default=0.0)
    p.add_argument("--max_grad_norm", type=float, default=1.0)
    p.add_argument("--dtype", type=str, default="bfloat16",
                   choices=["bfloat16", "float32"])
    p.add_argument("--log_prefix", type=str, default="squad_log")
    p.add_argument("--watchdog_timeout", type=float, default=0.0,
                   help="hung-step watchdog (resilience/watchdog.py): a "
                        "host phase exceeding this many seconds dumps "
                        "all-thread stacks and acts per "
                        "--watchdog_action; 0 = off (docs/RESILIENCE.md)")
    p.add_argument("--watchdog_action", type=str, default="abort",
                   choices=["abort", "warn"])
    p.add_argument("--metrics_port", type=int, default=None,
                   help="serve live /metrics + /healthz on this port while "
                        "the run is alive (telemetry/exporter.py; 0 = "
                        "ephemeral). Default: off")
    p.add_argument("--eval_script", default=None, type=str,
                   help="unused (in-process eval); kept for CLI parity")
    add_common_finetune_flags(p)

    from bert_pytorch_tpu.config import merge_args_with_config

    return merge_args_with_config(p, argv)


def build_serving_model(config, dtype, opts: Dict[str, Any]):
    from bert_pytorch_tpu.models import BertForQuestionAnswering

    return BertForQuestionAnswering(config, dtype=dtype)


def make_service(scheduler, tokenizer, opts: Dict[str, Any]):
    from bert_pytorch_tpu.serving.frontend import SquadService
    from bert_pytorch_tpu.tasks import squad

    return SquadService(
        scheduler, tokenizer,
        answer_cfg=opts.get("answer_cfg") or squad.AnswerConfig(),
        doc_stride=int(opts.get("doc_stride", 128)),
        max_query_length=int(opts.get("max_query_length", 64)),
        tok_lock=opts.get("tok_lock"))


def _forward_builder(model):
    from bert_pytorch_tpu.tasks import predict

    return predict.build_qa_forward(model)


def pack_labels(arrays, placements, n_rows, seq_len, max_segments):
    """Per-segment ABSOLUTE span positions: (n_rows, G) start/end, -1 for
    empty slots and for answers clamped out of the window (the qa_loss
    convention, reference run_squad.py:1080-1092)."""
    out = {k: np.full((n_rows, max_segments), -1, np.int32)
           for k in ("start_positions", "end_positions")}
    for p in placements:
        ln, off = p.lengths[0], p.offsets[0]
        for k in ("start_positions", "end_positions"):
            pos = int(arrays[k][p.unit])
            if 0 <= pos < ln:
                out[k][p.row, p.seg0] = pos + off
    return out


def setup(args, config, tel):
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.data.tokenization import get_wordpiece_tokenizer
    from bert_pytorch_tpu.models import BertForQuestionAnswering, losses
    from bert_pytorch_tpu.optim import schedulers
    from bert_pytorch_tpu.optim.adam import fused_adam
    from bert_pytorch_tpu.optim.lamb import default_weight_decay_mask
    from bert_pytorch_tpu.tasks import predict, squad
    from bert_pytorch_tpu.training.finetune import (TaskRun,
                                                    bucketed_eval_batches,
                                                    eval_buckets)

    vocab_file = args.vocab_file or config.vocab_file
    compute_dtype = (jnp.bfloat16 if args.dtype == "bfloat16"
                     else jnp.float32)
    model = BertForQuestionAnswering(config, dtype=compute_dtype)
    tokenizer = get_wordpiece_tokenizer(vocab_file,
                                        uppercase=not config.lowercase)
    logger = tel.logger

    train_arrays = None
    total_steps = 0
    if args.do_train:
        examples = squad.read_squad_examples(
            args.train_file, is_training=True,
            version_2_with_negative=args.version_2_with_negative)
        cache = os.path.join(
            args.output_dir,
            f"train_feats_{args.max_seq_length}_{args.doc_stride}.pkl")
        feats = squad.cached_features(cache, lambda: (
            squad.convert_examples_to_features(
                examples, tokenizer, args.max_seq_length,
                args.doc_stride, args.max_query_length,
                is_training=True)))
        train_arrays = squad.features_to_arrays(feats, is_training=True)
        train_arrays.pop("unique_ids", None)
        if getattr(args, "packing", False):
            # a packed step consumes a data-dependent number of examples;
            # count the actual per-epoch first-fit stream so total_steps
            # (and the schedule) cover num_train_epochs real data passes
            from bert_pytorch_tpu.training.finetune import (
                packed_epoch_step_counts)

            total_steps = sum(packed_epoch_step_counts(
                train_arrays, n_rows=args.train_batch_size,
                seq_len=args.max_seq_length,
                max_segments=getattr(args, "packing_max_segments", 8),
                seed=args.seed, epochs=args.num_train_epochs))
        else:
            # optimizer steps per epoch: each step consumes batch*accum
            # examples (reference divides num_train_optimization_steps
            # the same way, run_squad.py:966-970)
            examples_per_step = (args.train_batch_size
                                 * args.gradient_accumulation_steps)
            steps_per_epoch = len(feats) // examples_per_step
            total_steps = int(steps_per_epoch * args.num_train_epochs)
        if args.max_steps > 0:
            total_steps = min(total_steps, int(args.max_steps))

    sched = schedulers.linear_warmup_schedule(
        args.learning_rate, max(total_steps, 1),
        warmup=args.warmup_proportion)
    import optax

    # two param groups: wd 0.01 everywhere except bias/LayerNorm
    # (reference run_squad.py:974-986)
    tx = fused_adam(sched, weight_decay=0.01,
                    weight_decay_mask=default_weight_decay_mask,
                    bias_correction=False)
    if args.max_grad_norm and args.max_grad_norm > 0:
        # reference GradientClipper global-norm clip before the step
        # (run_squad.py:703-725,1104)
        tx = optax.chain(
            optax.clip_by_global_norm(args.max_grad_norm), tx)

    sample_ids = jnp.zeros((2, args.max_seq_length), jnp.int32)
    init_fn = lambda r: model.init(r, sample_ids, sample_ids, sample_ids)

    def loss_builder(model):
        def loss_fn(params, batch, rng, deterministic=False):
            start, end = model.apply(
                {"params": params}, batch["input_ids"],
                batch["token_type_ids"], batch["attention_mask"],
                deterministic=deterministic,
                rngs=None if deterministic else {"dropout": rng})
            loss = losses.qa_loss(start, end,
                                  batch["start_positions"],
                                  batch["end_positions"])
            return loss, {}
        return loss_fn

    max_segments = args.packing_max_segments

    def packed_loss_builder(model):
        def loss_fn(params, batch, rng, deterministic=False):
            start, end = model.apply(
                {"params": params}, batch["input_ids"],
                batch["token_type_ids"], batch["attention_mask"],
                deterministic=deterministic,
                position_ids=batch["position_ids"],
                segment_ids=batch["segment_ids"],
                rngs=None if deterministic else {"dropout": rng})
            loss = losses.packed_qa_loss(
                start, end, batch["start_positions"],
                batch["end_positions"], batch["segment_ids"],
                max_segments)
            return loss, {}
        return loss_fn

    def finalize(params, results):
        out: Dict[str, Any] = {}
        if not args.do_predict:
            return out
        eval_examples = squad.read_squad_examples(
            args.predict_file, is_training=False,
            version_2_with_negative=args.version_2_with_negative)
        eval_feats = squad.convert_examples_to_features(
            eval_examples, tokenizer, args.max_seq_length,
            args.doc_stride, args.max_query_length, is_training=False)
        eval_arrays = squad.features_to_arrays(eval_feats,
                                               is_training=False)
        uids_all = eval_arrays.pop("unique_ids")

        # the SAME pure forward + RawResult assembly the serving engine
        # compiles (tasks/predict.py), dispatched over length-bucketed
        # batches: each window rides the smallest bucket that fits it
        predict_step = jax.jit(predict.build_qa_forward(model))
        buckets = eval_buckets(args.max_seq_length)

        raw_results = []
        t0 = time.time()
        for batch, idx, _bucket in bucketed_eval_batches(
                eval_arrays, args.predict_batch_size, buckets):
            feats_dev = {k: jnp.asarray(v) for k, v in batch.items()}
            start, end = predict_step(params, feats_dev)
            raw_results.extend(predict.qa_raw_results(
                uids_all[idx], start, end, len(idx)))
        infer_time = time.time() - t0
        out["e2e_inference_time"] = infer_time
        out["inference_sequences_per_second"] = (
            len(eval_feats) / max(infer_time, 1e-9))

        answers, nbest = squad.get_answers(
            eval_examples, eval_feats, raw_results,
            squad.AnswerConfig(
                n_best_size=args.n_best_size,
                max_answer_length=args.max_answer_length,
                do_lower_case=config.lowercase,
                version_2_with_negative=args.version_2_with_negative,
                null_score_diff_threshold=args.null_score_diff_threshold,
                verbose_logging=args.verbose_logging))
        pred_file = os.path.join(args.output_dir, "predictions.json")
        with open(pred_file, "w", encoding="utf-8") as f:
            json.dump(answers, f, indent=2)
        with open(os.path.join(args.output_dir,
                               "nbest_predictions.json"),
                  "w", encoding="utf-8") as f:
            json.dump(nbest, f, indent=2)

        if args.do_eval:
            # v1.1 runs the official evaluate-v1.1 math; v2 needs the
            # no-answer-aware metric (the reference's --do_eval only ever
            # shells out to the v1.1 script, run_squad.py:1197-1204)
            eval_fn = (squad.evaluate_v2 if args.version_2_with_negative
                       else squad.evaluate_v1)
            out.update(eval_fn(args.predict_file, answers))
        logger.info(f"predict: wrote {pred_file}")
        return out

    return TaskRun(
        model=model, tx=tx, init_fn=init_fn, schedule=sched,
        seq_len=args.max_seq_length,
        batch_size=args.train_batch_size,
        accum_steps=args.gradient_accumulation_steps,
        total_steps=total_steps, epochs=None,
        train_arrays=train_arrays,
        loss_builder=loss_builder,
        packed_loss_builder=packed_loss_builder,
        pack_labels=pack_labels,
        label_ignore={"start_positions": -1, "end_positions": -1},
        log_every=50, perf_log_freq=50,
        init_checkpoint=args.init_checkpoint,
        finalize=finalize)


registry.register(registry.TaskSpec(
    name="squad",
    title="SQuAD v1.1/v2.0 extractive question answering",
    head="BertForQuestionAnswering",
    output_kind="token",
    metric="f1",
    request_schema={"question": "str (required)",
                    "context": "str (required)"},
    parse_arguments=parse_arguments,
    setup=setup,
    build_serving_model=build_serving_model,
    forward_builder=_forward_builder,
    make_service=make_service,
    serving_defaults={"doc_stride": 128, "max_query_length": 64},
    reference_heads=("BertForQuestionAnswering",),
))
