#!/usr/bin/env python
"""Build a tiny self-contained serving fixture: vocab + model config +
params-only checkpoints for EVERY registered task.

scripts/check_serve.sh and the serving tests need checkpoints the
server can restore WITHOUT a training run — this writes them in seconds
by iterating tasks/registry.py (a newly registered task automatically
joins the fixture, and therefore the check_serve CI gate): a
randomly-initialized tiny BERT per task head (structure-faithful: same
heads, padded vocab, either encoder layout) saved under the serving
checkpoint contract ({"params": tree}, which `restore_serving_params`
loads through `restore_either_layout`). Random weights serve garbage
answers but real latency — exactly what a load test measures.

    python scripts/make_serving_fixture.py --out /tmp/fixture
    # -> /tmp/fixture/{vocab.txt, model_config.json, <task>_ckpt/...,
    #    serve_args.txt}

`--model_config_file configs/bert_large_uncased_config.json` builds the
fixture at a real model's width and depth instead of the tiny default
(chip_smoke.py serves BERT-Large this way; `--tasks` keeps that to the
heads it needs — a BERT-Large checkpoint is 1.3 GB per task).

`serve_args.txt` holds the ready-made run_server.py argument list for
the whole battery (one token per line; check_serve.sh consumes it).
The NER head is sized for the canonical 5-label CoNLL set
(`--labels B-PER I-PER B-LOC I-LOC O` on run_server.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

NER_LABELS = ["B-PER", "I-PER", "B-LOC", "I-LOC", "O"]
CLASS_NAMES = ["negative", "positive"]
NUM_CHOICES = 2

_VOCAB = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"] + (
    "the cat sat on mat a dog did run in park who what where when how "
    "why fast slow red blue green bert serves packed rows thing to of "
    "and is was . , ?").split()


def build(out_dir: str, hidden: int = 32, layers: int = 2, heads: int = 4,
          max_pos: int = 128, stacked_params: bool = True,
          max_segments: int = 8, model_config_file: str = None,
          tasks=None, seed: int = 0) -> dict:
    """Write the fixture under out_dir; returns {name: path}. The model is
    `model_config_file`'s when given (its vocab_file replaced by the
    fixture's tiny vocab — the embedding table keeps the file's
    vocab_size), else the tiny hidden/layers/heads/max_pos one. `tasks`
    restricts the battery (default: every registered task)."""
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.config import BertConfig, pad_vocab_size
    from bert_pytorch_tpu.tasks import registry
    from bert_pytorch_tpu.training.checkpoint import CheckpointManager
    from bert_pytorch_tpu.training.state import unbox

    os.makedirs(out_dir, exist_ok=True)
    vocab_path = os.path.join(out_dir, "vocab.txt")
    with open(vocab_path, "w", encoding="utf-8") as f:
        f.write("\n".join(_VOCAB) + "\n")

    if model_config_file:
        with open(model_config_file, encoding="utf-8") as f:
            model_cfg = json.load(f)
        if model_cfg["vocab_size"] < len(_VOCAB):
            raise SystemExit(
                f"{model_config_file}: vocab_size {model_cfg['vocab_size']} "
                f"< the fixture vocab's {len(_VOCAB)} entries")
        model_cfg.update(vocab_file=vocab_path, tokenizer="wordpiece",
                         stacked_params=stacked_params)
        max_pos = model_cfg["max_position_embeddings"]
    else:
        model_cfg = {
            "vocab_size": len(_VOCAB), "hidden_size": hidden,
            "num_hidden_layers": layers, "num_attention_heads": heads,
            "intermediate_size": hidden * 2,
            "max_position_embeddings": max_pos,
            "hidden_dropout_prob": 0.0, "attention_probs_dropout_prob": 0.0,
            "tokenizer": "wordpiece", "vocab_file": vocab_path,
            "fused_ops": False, "attention_impl": "xla",
            "stacked_params": stacked_params,
        }
    cfg_path = os.path.join(out_dir, "model_config.json")
    with open(cfg_path, "w", encoding="utf-8") as f:
        json.dump(model_cfg, f, indent=1, sort_keys=True)
        f.write("\n")

    # mirror run_server.py's model construction exactly (padded vocab,
    # same serve_opts the server will derive from its CLI defaults)
    config = BertConfig.from_json_file(cfg_path)
    config = config.replace(vocab_size=pad_vocab_size(config.vocab_size, 8))
    serve_opts = {"labels": NER_LABELS, "class_names": CLASS_NAMES,
                  "num_choices": NUM_CHOICES, "embed_labels": 2,
                  "max_segments": max_segments}
    sample = jnp.zeros((1, min(64, max_pos)), jnp.int32)
    out = {"vocab": vocab_path, "model_config": cfg_path}
    serve_args = ["--model_config_file", cfg_path,
                  "--vocab_file", vocab_path,
                  "--labels", *NER_LABELS,
                  "--class_names", *CLASS_NAMES,
                  "--num_choices", str(NUM_CHOICES)]
    for task in tasks or registry.all_tasks():
        spec = registry.get(task)
        model = spec.build_serving_model(config, jnp.float32, serve_opts)
        params = unbox(jax.jit(model.init)(jax.random.PRNGKey(seed),
                                           sample, sample, sample)["params"])
        ckpt_dir = os.path.join(out_dir, f"{task}_ckpt")
        mgr = CheckpointManager(ckpt_dir)
        mgr.save(0, {"params": params})
        mgr.close()
        out[f"{task}_ckpt"] = ckpt_dir
        serve_args += ["--task_checkpoint", f"{task}={ckpt_dir}"]
    args_path = os.path.join(out_dir, "serve_args.txt")
    with open(args_path, "w", encoding="utf-8") as f:
        f.write("\n".join(serve_args) + "\n")
    out["serve_args"] = args_path
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", required=True)
    ap.add_argument("--model_config_file", default=None,
                    help="BertConfig JSON to build the fixture at (width, "
                         "depth, heads, positions, kernels); replaces "
                         "--hidden/--layers/--heads/--max_pos")
    ap.add_argument("--tasks", nargs="+", default=None,
                    help="registered tasks to build (default: all)")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed of the random weights")
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--max_pos", type=int, default=128)
    ap.add_argument("--max_segments", type=int, default=8)
    ap.add_argument("--unstacked", action="store_true",
                    help="write the fixture in the unstacked encoder "
                         "layout (exercises the cross-layout restore)")
    args = ap.parse_args(argv)
    paths = build(args.out, hidden=args.hidden, layers=args.layers,
                  heads=args.heads, max_pos=args.max_pos,
                  stacked_params=not args.unstacked,
                  max_segments=args.max_segments,
                  model_config_file=args.model_config_file,
                  tasks=args.tasks, seed=args.seed)
    for k, v in sorted(paths.items()):
        print(f"fixture: {k}: {v}")
    print(f"fixture: ner labels: {' '.join(NER_LABELS)}")
    return 0


if __name__ == "__main__":
    # the fixture is built on the host CPU, whatever accelerator is attached
    # (a server may own it); read by jax when main() imports it
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.exit(main())
