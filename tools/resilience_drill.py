#!/usr/bin/env python
"""Resilience drills: prove the survival kit end-to-end, bit for bit.

Two drills (scripts/check_resilience.sh runs both as a CI gate;
tests/test_resilience.py drives the same functions in tier-1):

1. `sigkill` (the headline): a pretraining run is SIGKILLed mid-interval
   (--chaos sigkill_at_step — the un-catchable death), tools/supervise.py
   restarts it, auto-resume restores the last checkpoint, and the
   resumed run's FINAL PARAMS and METRIC STREAM are bit-identical to an
   uninterrupted run's. Runs on the offline (sharded-HDF5) and streaming
   (tokenize-on-the-fly) data planes, --packing on — the full
   deterministic-resume surface (sampler cursor, packer carry-over,
   stream cursor, per-step fold_in dropout keys) under the worst death.

2. `corrupt`: the run dies right after its newest checkpoint is
   byte-flipped (--chaos corrupt_newest_ckpt); the supervised restart
   must QUARANTINE the corrupt step (renamed `<step>.corrupt`, warning
   naming the failed item), fall back to the next-newest, and STILL
   converge to the bit-identical final state.

"Bit-identical metric stream" means: collect every per-step `train`
record from both runs' jsonl (the killed run's stream spans two process
lifetimes and may log an overlap region twice — once pre-kill, once
replayed after resume); for every step, all logged `step_loss` values
must agree exactly, and the two runs must cover the same steps with the
same values. Timestamps/averages legitimately differ; the training
trajectory may not.

Subprocess sessions force the CPU backend and an 8-device host platform
so the drill exercises the real sharded path deterministically anywhere.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import tempfile
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SEED = 42
MAX_STEPS = 5
CKPT_EVERY = 2
# Mid-interval, and far enough past the step-2 boundary that the ASYNC
# step-2 save has committed before the kill lands (a kill racing the
# very first commit leaves no checkpoint — the restart then legitimately
# starts fresh, which is survival but not the resume path this drill
# must prove).
KILL_AT = 5

# As small as the model can be while still exercising every resume
# surface (packing, NSP, MLM head, checkpointed cursors): the drill's
# cost is dominated by per-session XLA compiles on a one-core CI box,
# and compile time scales with graph size
MODEL_CFG = {
    "vocab_size": 64, "hidden_size": 16, "num_hidden_layers": 1,
    "num_attention_heads": 2, "intermediate_size": 32,
    "max_position_embeddings": 64, "next_sentence": True,
    "hidden_dropout_prob": 0.0, "attention_probs_dropout_prob": 0.0,
    "tokenizer": "wordpiece", "fused_ops": False, "attention_impl": "xla",
}

_WORDS = ["alpha", "bravo", "charlie", "delta", "echo", "foxtrot",
          "golf", "hotel", "india", "juliet", "kilo", "lima", "mike",
          "november", "oscar", "papa"]
_SPECIALS = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"]


def _log(msg: str) -> None:
    print(f"resilience_drill: {msg}", file=sys.stderr, flush=True)


# -- fixtures ----------------------------------------------------------------


def write_model_config(workdir: str) -> str:
    path = os.path.join(workdir, "model_config.json")
    if not os.path.exists(path):
        with open(path, "w", encoding="utf-8") as f:
            json.dump(MODEL_CFG, f)
    return path


def write_offline_shards(workdir: str, n: int = 48, seq: int = 32) -> str:
    """Varied-length HDF5 shards (the corpus shape --packing exists for);
    same schema as pipeline/encode.py output."""
    import h5py
    import numpy as np

    data = os.path.join(workdir, "data")
    if os.path.isdir(data):
        return data
    os.makedirs(data)
    for s in range(2):
        rng = np.random.RandomState(s)
        # token ids stay below MODEL_CFG["vocab_size"]
        ids = rng.randint(5, 60, (n, seq)).astype(np.int32)
        ids[:, 0] = 1  # [CLS]
        specials = np.zeros((n, 3), np.int32)
        for i in range(n):
            last = rng.randint(7, seq - 1)
            sep1 = rng.randint(2, last - 2)
            ids[i, sep1] = 2
            ids[i, last] = 2
            ids[i, last + 1:] = 0
            specials[i] = [0, sep1, last]
        labels = rng.randint(0, 2, (n,)).astype(np.int8)
        with h5py.File(os.path.join(data, f"shard_{s}.hdf5"), "w") as f:
            f.create_dataset("input_ids", data=ids)
            f.create_dataset("special_token_positions", data=specials)
            f.create_dataset("next_sentence_labels", data=labels)
    return data


def write_stream_corpus(workdir: str, n_docs: int = 80) -> Dict[str, str]:
    """Raw-text corpus + vocab for the streaming plane (data/streaming.py
    FileSource contract: blank-line-delimited documents)."""
    import numpy as np

    corpus = os.path.join(workdir, "corpus")
    vocab = os.path.join(workdir, "vocab.txt")
    if not os.path.isdir(corpus):
        os.makedirs(corpus)
        rng = np.random.RandomState(0)
        for fi in range(2):
            lines = []
            for _ in range(n_docs // 2):
                for _ in range(rng.randint(2, 6)):
                    lines.append(" ".join(
                        rng.choice(_WORDS, rng.randint(3, 12))))
                lines.append("")
            with open(os.path.join(corpus, f"c{fi}.txt"), "w",
                      encoding="utf-8") as fh:
                fh.write("\n".join(lines))
    if not os.path.exists(vocab):
        with open(vocab, "w", encoding="utf-8") as f:
            f.write("\n".join(_SPECIALS + _WORDS) + "\n")
    return {"corpus": corpus, "vocab": vocab}


def drill_argv(plane: str, workdir: str, out_dir: str,
               extra: Optional[List[str]] = None) -> List[str]:
    """run_pretraining argv for one drill session (packing on, tiny
    model, checkpoint every CKPT_EVERY steps)."""
    cfg = write_model_config(workdir)
    argv = ["--model_config_file", cfg, "--output_dir", out_dir,
            "--dtype", "float32", "--vocab_pad_multiple", "8",
            "--packing", "--packing_max_segments", "4",
            "--learning_rate", "1e-3", "--global_batch_size", "16",
            "--local_batch_size", "2", "--max_predictions_per_seq", "5",
            "--max_steps", str(MAX_STEPS), "--seed", str(SEED),
            "--num_steps_per_checkpoint", str(CKPT_EVERY),
            "--log_freq", "1", "--log_prefix", "drill",
            # startup dominates these 15s sessions: skip the ~4s
            # torch.utils.tensorboard (tensorflow/keras) import
            "--tensorboard", "off"]
    if plane == "offline":
        argv += ["--input_dir", write_offline_shards(workdir),
                 "--mask_token_index", "3"]
    elif plane == "stream":
        fx = write_stream_corpus(workdir)
        argv += ["--stream_dir", fx["corpus"], "--stream_vocab",
                 fx["vocab"], "--stream_seq_len", "32"]
    else:
        raise ValueError(f"plane {plane!r}: want offline|stream")
    return argv + list(extra or [])


def subprocess_env() -> Dict[str, str]:
    """Child env: CPU backend, 8-device host platform (matching
    tests/conftest.py so every session compiles the identical sharded
    program), repo importable. NOTE: deliberately no persistent
    compilation cache (run_pretraining enables one by default; it is
    switched off here through JAX's own setting, whatever directory the
    environment names) — a SIGKILLed session can tear the cache entry it
    was writing and the restarted session segfaults loading it (the
    drill found its own torn-write failure in that layer)."""
    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", "")).strip()
    flags = re.sub(r"--xla_backend_optimization_level=\d+", "",
                   flags).strip()
    # optimization level 0: the drill's correctness claims are about
    # BIT-IDENTITY between sessions compiled with the SAME flags, so the
    # cheapest compile wins — 2.6s vs 7.6s of XLA time per session, and
    # every session (reference included) runs under this env so the
    # comparisons never cross program families
    env["XLA_FLAGS"] = \
        (flags + " --xla_force_host_platform_device_count=8"
         " --xla_backend_optimization_level=0").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_ENABLE_COMPILATION_CACHE"] = "0"
    return env


def run_session(argv: List[str], env: Optional[Dict[str, str]] = None
                ) -> int:
    """One run_pretraining subprocess session; returns its exit code."""
    cmd = [sys.executable, os.path.join(REPO, "run_pretraining.py"),
           "--force_cpu"] + argv
    return subprocess.call(cmd, env=env or subprocess_env(), cwd=REPO)


# -- comparators -------------------------------------------------------------


def _ensure_cpu8() -> None:
    """The comparator restores 8-device-sharded checkpoints, so the
    PARENT needs the same faked 8-device CPU platform the sessions used
    (tests/conftest.py recipe). No-op when already configured (pytest)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"


def final_params(out_dir: str) -> Dict[str, "object"]:
    """{leaf path: numpy array} of the params in the NEWEST checkpoint."""
    _ensure_cpu8()
    import jax

    from bert_pytorch_tpu.training.checkpoint import CheckpointManager

    mgr = CheckpointManager(os.path.join(out_dir, "pretrain_ckpts"))
    try:
        state, step = mgr.restore_raw()
    finally:
        mgr.close()
    params = state["params"] if isinstance(state, dict) else state.params
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): v
            for path, v in jax.tree_util.tree_flatten_with_path(params)[0]}
    flat["__step__"] = step
    return flat


def compare_params(a: Dict, b: Dict) -> List[str]:
    import numpy as np

    errors = []
    if set(a) != set(b):
        errors.append(f"param trees differ: only-in-a="
                      f"{sorted(set(a) - set(b))[:3]} only-in-b="
                      f"{sorted(set(b) - set(a))[:3]}")
        return errors
    for k in sorted(a):
        if k == "__step__":
            if a[k] != b[k]:
                errors.append(f"final checkpoint step differs: "
                              f"{a[k]} vs {b[k]}")
            continue
        av, bv = np.asarray(a[k]), np.asarray(b[k])
        if av.shape != bv.shape or av.dtype != bv.dtype:
            errors.append(f"{k}: shape/dtype {av.shape}/{av.dtype} vs "
                          f"{bv.shape}/{bv.dtype}")
        elif av.tobytes() != bv.tobytes():
            d = np.max(np.abs(av.astype("float64")
                              - bv.astype("float64")))
            errors.append(f"{k}: NOT bit-identical (max abs diff {d:g})")
    return errors


def metric_stream(out_dir: str, prefix: str = "drill"
                  ) -> Dict[int, float]:
    """{step: step_loss} from the jsonl train records; raises on
    self-contradiction (the same step logged twice with different
    values — a killed+resumed run logs the replayed overlap twice, and
    those MUST agree bit-for-bit)."""
    path = os.path.join(out_dir, f"{prefix}.jsonl")
    out: Dict[int, float] = {}
    with open(path, encoding="utf-8") as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("tag") != "train" or "step_loss" not in rec:
                continue
            step, loss = int(rec["step"]), rec["step_loss"]
            if step in out and out[step] != loss:
                raise AssertionError(
                    f"{path}: step {step} logged twice with DIFFERENT "
                    f"step_loss ({out[step]!r} vs {loss!r}) — the "
                    "replayed overlap is not bit-identical")
            out[step] = loss
    return out


def compare_streams(a: Dict[int, float], b: Dict[int, float]
                    ) -> List[str]:
    errors = []
    if set(a) != set(b):
        errors.append(f"metric streams cover different steps: "
                      f"{sorted(set(a) ^ set(b))}")
    for step in sorted(set(a) & set(b)):
        if a[step] != b[step]:
            errors.append(f"step {step}: step_loss {a[step]!r} vs "
                          f"{b[step]!r} — not bit-identical")
    return errors


# -- drills ------------------------------------------------------------------


def run_reference(plane: str, workdir: str) -> str:
    """The uninterrupted control run — a subprocess under the SAME env
    as every other drill session (subprocess_env), so the compiled
    program, and therefore every bit of the result, is comparable."""
    out = os.path.join(workdir, f"ref_{plane}")
    rc = run_session(drill_argv(plane, workdir, out))
    if rc != 0:
        raise RuntimeError(f"reference {plane} run failed rc={rc}")
    return out


def run_supervised_chaos(plane: str, workdir: str, chaos: str,
                         chaos_step: int, tag: str) -> str:
    """One chaos session + supervised restart(s) to completion."""
    from tools.supervise import supervise

    out = os.path.join(workdir, f"{tag}_{plane}")
    argv = drill_argv(plane, workdir, out,
                      extra=["--chaos", chaos,
                             "--chaos_step", str(chaos_step)])
    cmd = [sys.executable, os.path.join(REPO, "run_pretraining.py"),
           "--force_cpu"] + argv
    rc = supervise(cmd, os.path.join(out, "pretrain_ckpts"),
                   max_restarts=3, crash_loop_tolerance=3,
                   backoff_base=0.1, backoff_max=0.5,
                   env=subprocess_env(), log=_log)
    if rc != 0:
        raise RuntimeError(
            f"supervised {chaos} {plane} drill did not converge (rc={rc})")
    return out


def verify_bit_identical(ref_out: str, drill_out: str) -> List[str]:
    errors = compare_params(final_params(ref_out), final_params(drill_out))
    errors += compare_streams(metric_stream(ref_out),
                              metric_stream(drill_out))
    return errors


def drill_sigkill(plane: str, workdir: str,
                  ref_out: Optional[str] = None) -> List[str]:
    """Headline drill on one data plane; returns verification errors.
    `ref_out` reuses an existing uninterrupted control run (same
    drill_argv config + subprocess_env) instead of running a fresh one —
    the tier-1 test shares one reference between this drill and the
    SIGTERM e2e."""
    if ref_out is None:
        _log(f"[sigkill/{plane}] reference run ...")
        ref = run_reference(plane, workdir)
    else:
        ref = ref_out
    _log(f"[sigkill/{plane}] SIGKILL at step {KILL_AT} + supervise ...")
    out = run_supervised_chaos(plane, workdir, "sigkill_at_step",
                               KILL_AT, "sigkill")
    errors = verify_bit_identical(ref, out)
    # the drill must actually have died once: the supervisor's lineage
    # env shows up in the resumed session's auto-resume log line
    log = open(os.path.join(out, "drill.txt"), encoding="utf-8").read()
    if "auto-resumed from step" not in log:
        errors.append("drill log never auto-resumed — the kill or the "
                      "restart did not happen")
    return errors


def drill_corrupt(plane: str, workdir: str) -> List[str]:
    """Corrupt-newest drill: die right after corrupting the freshest
    checkpoint; the restart must quarantine + fall back + still converge
    bit-identically."""
    _log(f"[corrupt/{plane}] reference run ...")
    ref = run_reference(plane, workdir)
    _log(f"[corrupt/{plane}] corrupt newest ckpt at step {CKPT_EVERY * 2} "
         "+ SIGKILL + supervise ...")
    out = run_supervised_chaos(plane, workdir, "corrupt_newest_ckpt",
                               CKPT_EVERY * 2, "corrupt")
    errors = verify_bit_identical(ref, out)
    log = open(os.path.join(out, "drill.txt"), encoding="utf-8").read()
    if "is CORRUPT" not in log or "Quarantined" not in log:
        errors.append("drill log shows no quarantine warning")
    ckpts = os.path.join(out, "pretrain_ckpts")
    if not any(name.endswith(".corrupt") for name in os.listdir(ckpts)):
        errors.append(f"no quarantined *.corrupt dir under {ckpts}")
    return errors


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--drill", default="all",
                   choices=["sigkill", "corrupt", "all"])
    p.add_argument("--plane", default="both",
                   choices=["offline", "stream", "both"])
    p.add_argument("--workdir", default=None,
                   help="scratch dir (default: a fresh temp dir)")
    args = p.parse_args(argv)

    workdir = args.workdir or tempfile.mkdtemp(prefix="resilience_drill_")
    os.makedirs(workdir, exist_ok=True)
    planes = (["offline", "stream"] if args.plane == "both"
              else [args.plane])
    failures = 0
    for plane in planes:
        if args.drill in ("sigkill", "all"):
            errors = drill_sigkill(plane, workdir)
            _log(f"[sigkill/{plane}] "
                 + ("PASS — SIGKILLed+supervised run is bit-identical "
                    "to the uninterrupted run" if not errors
                    else "FAIL:\n  " + "\n  ".join(errors)))
            failures += bool(errors)
        if args.drill in ("corrupt", "all"):
            errors = drill_corrupt(plane, workdir)
            _log(f"[corrupt/{plane}] "
                 + ("PASS — corrupt newest quarantined, fallback resumed "
                    "bit-identically" if not errors
                    else "FAIL:\n  " + "\n  ".join(errors)))
            failures += bool(errors)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
