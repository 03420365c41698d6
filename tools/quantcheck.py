#!/usr/bin/env python
"""int8 serving quantization accuracy gate (offline CLI).

`run_server.py --serve_dtype int8` quantizes weights at restore time and
refuses to serve past `--int8_max_delta`; this tool runs the SAME check
offline so an operator can qualify a checkpoint before rollout — and
prove the gate actually trips on a broken quantization:

    # qualify: quantize each checkpoint, compare the int8 decode against
    # the f32 reference on a deterministic probe batch, gate the delta
    python tools/quantcheck.py --model_config_file cfg.json \
        --task_checkpoint squad=out/squad_ckpt \
        --task_checkpoint classify=out/classify_ckpt \
        --class_names 0 1 --max_delta 0.1

    # negative control: corrupt one leaf's scales — MUST exit nonzero
    python tools/quantcheck.py ... --inject broken_scale

Exit 0 = every task under the gate; exit 1 = at least one task over it
(or, with --inject, the corruption somehow slipped under the gate —
which would mean the gate is broken). --out writes the per-task report
as JSON for CI artifacts.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model_config_file", required=True, type=str)
    p.add_argument("--task_checkpoint", action="append", required=True,
                   metavar="TASK=DIR")
    p.add_argument("--labels", type=str, nargs="+", default=None)
    p.add_argument("--class_names", type=str, nargs="+",
                   default=["negative", "positive"])
    p.add_argument("--num_choices", type=int, default=2)
    p.add_argument("--embed_labels", type=int, default=2)
    p.add_argument("--max_segments", type=int, default=4)
    p.add_argument("--max_delta", type=float, default=0.1,
                   help="gate: max relative decode delta vs f32")
    p.add_argument("--bucket", type=int, default=64,
                   help="probe batch sequence length")
    p.add_argument("--batch_rows", type=int, default=2)
    p.add_argument("--vocab_pad_multiple", type=int, default=8)
    p.add_argument("--inject", type=str, default="none",
                   choices=["none", "broken_scale"],
                   help="broken_scale: corrupt one quantized leaf's "
                        "scales — the gate MUST trip (negative control)")
    p.add_argument("--out", type=str, default=None,
                   help="write the per-task JSON report here")
    p.add_argument("--force_cpu", action="store_true")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.force_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # read when jax is imported
    import threading

    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.config import BertConfig, pad_vocab_size
    from bert_pytorch_tpu.serving import quantize as quant_lib
    from bert_pytorch_tpu.serving.engine import restore_serving_params
    from bert_pytorch_tpu.tasks import registry

    checkpoints = {}
    for entry in args.task_checkpoint:
        task, sep, ckpt = entry.partition("=")
        if not sep or task not in registry.all_tasks():
            raise SystemExit(f"--task_checkpoint wants TASK=DIR with a "
                             f"registered task, got {entry!r}")
        checkpoints[task] = ckpt

    config = BertConfig.from_json_file(args.model_config_file)
    config = config.replace(vocab_size=pad_vocab_size(
        config.vocab_size, args.vocab_pad_multiple))
    bucket = min(args.bucket, config.max_position_embeddings)
    serve_opts = {
        "tok_lock": threading.Lock(),
        "labels": args.labels,
        "class_names": args.class_names,
        "num_choices": args.num_choices,
        "embed_labels": args.embed_labels,
        "max_segments": args.max_segments,
    }
    probe = quant_lib.probe_batch(args.batch_rows, bucket,
                                  config.vocab_size,
                                  max_segments=min(2, args.max_segments))

    report, failed = {}, []
    for task in sorted(checkpoints):
        spec = registry.get(task)
        ref_model = spec.build_serving_model(config, jnp.float32,
                                             serve_opts)
        params, step = restore_serving_params(
            checkpoints[task], ref_model, bucket, log=lambda m: None)
        qparams, stats = quant_lib.quantize_tree(jax.device_get(params))
        if args.inject == "broken_scale":
            qparams = quant_lib.corrupt_scales(qparams)
        serve_model = spec.build_serving_model(config, jnp.bfloat16,
                                               serve_opts)
        q_forward = quant_lib.wrap_forward(
            spec.forward_builder(serve_model), jnp.bfloat16)
        delta = quant_lib.decode_delta(
            spec.forward_builder(ref_model), params, q_forward, qparams,
            probe)
        ok = delta["rel_delta"] <= args.max_delta
        if not ok:
            failed.append(task)
        report[task] = {
            "checkpoint": checkpoints[task], "step": step,
            "quantized_leaves": stats["quantized_leaves"],
            "bytes_before": stats["bytes_before"],
            "bytes_after": stats["bytes_after"],
            "inject": args.inject, "ok": ok,
            **{k: float(v) for k, v in delta.items()},
        }
        print(f"quantcheck[{task}]: rel_delta {delta['rel_delta']:.4f} "
              f"(gate {args.max_delta:g}) argmax_agreement "
              f"{delta['argmax_agreement']:.4f} "
              f"{stats['bytes_before'] / 1e6:.1f}->"
              f"{stats['bytes_after'] / 1e6:.1f} MB "
              + ("OK" if ok else "FAIL"))

    doc = {"schema_version": 1, "kind": "quantcheck",
           "max_delta": args.max_delta, "inject": args.inject,
           "tasks": report, "ok": not failed}
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1, sort_keys=True, allow_nan=False)
            f.write("\n")
    if failed:
        print(f"quantcheck: FAIL — task(s) over the gate: "
              f"{', '.join(failed)}")
        return 1
    print("quantcheck: all tasks under the gate")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
