#!/usr/bin/env python
"""Benchmark: BERT-Large MLM pretraining throughput on one chip, at both
phase-1 (seq 128) and phase-2 (seq 512) recipes.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "seq/s/chip", "vs_baseline": N,
   "seq512_value": N, "seq512_mfu": N, ...}

The reference publishes no measured numbers (README Performance section is
empty; BASELINE.md), so vs_baseline is reported against the north-star
contract in BASELINE.json: >=50% MFU. vs_baseline = achieved_MFU / 0.50 —
1.0 means the 50% target is met exactly; >1.0 beats it. The headline value
is the phase-1 (seq128) number; the phase-2 (seq512,
max_predictions_per_seq=80, reference phase2 config:3-10) result rides along
in the same line as seq512_*.

Methodology matches the reference's training_seq_per_sec (global_batch x
steps / train_time, run_pretraining.py:578-580) measured over the full jitted
train step (fwd + bwd + LAMB update), steady-state after warmup, on a
directly attached TPU. The parent process never touches the JAX backend: the
chip belongs to one process at a time, so each candidate runs in a fresh
child that owns it (an OOM attempt then cannot poison the next one's device
heap either). Dispatch is asynchronous, so every timing window ends by
fetching the loss the timed program returns — a host read of any output of
a program waits for the whole program (block_until_ready would do the same).

The device is not optional. A platform probe that fails, or finds anything
but a TPU, is an error; `--cpu` asks for the tiny CPU smoke of the harness
by name (its JSON says `bench_smoke_cpu` and carries no MFU). `--multichip`
likewise needs `--devices` real chips unless `--cpu` asks for the forced
host-device mesh.

Harness contract (round-5): the sweep ALWAYS lands a parsed JSON line.
Candidates are ordered best-known-first, a wall-clock budget
(BENCH_BUDGET_S, default 2100 s) gates every child launch, and SIGTERM /
SIGALRM handlers flush the final JSON from whatever has been measured so
far — a truncated sweep still reports its best. (Round 4 lost its headline
to an external timeout that arrived mid-grid, BENCH_r04.json rc=124.)
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

# FLOPs model + peak table live in telemetry/stepwatch.py — ONE source of
# truth shared with run_pretraining's live MFU, so the bench headline and
# the training-time number can never drift apart.
from bert_pytorch_tpu.telemetry.stepwatch import (  # noqa: E402
    device_peak_flops, flops_per_seq)

# Phase recipes (reference config/bert_pretraining_phase{1,2}_config.json).
PHASES = {
    128: {"max_pred": 20, "lr": 6e-3, "total_steps": 7038, "warmup": 0.2843},
    512: {"max_pred": 80, "lr": 4e-3, "total_steps": 1563, "warmup": 0.128},
}
MASK_FRACTION = 0.15  # reference masked_token_fraction, shared by children


def _bench_base_config(seq_len: int, on_tpu: bool):
    """Child-process setup shared by the grid candidates and the packing
    pair: BERT-Large config (shrunk under the --cpu smoke), padded vocab,
    the phase recipe, and the BENCH_RNG PRNG selection. Keeping this in ONE
    place is what makes the packing-pair numbers comparable with the grid
    numbers in the same JSON."""
    import jax

    from bert_pytorch_tpu.config import BertConfig, pad_vocab_size

    phase = PHASES[seq_len] if seq_len in PHASES else PHASES[128]
    max_pred = phase["max_pred"]
    here = os.path.dirname(os.path.abspath(__file__))
    cfg = BertConfig.from_json_file(
        os.path.join(here, "configs/bert_large_uncased_config.json"))
    if not on_tpu:  # --cpu smoke: shrink so the harness runs in seconds
        cfg = cfg.replace(num_hidden_layers=2, hidden_size=256,
                          intermediate_size=1024, num_attention_heads=4)
        max_pred = min(max_pred, 20)
    cfg = cfg.replace(vocab_size=pad_vocab_size(cfg.vocab_size, 128))
    # threefry2x32 = run_pretraining's default: the headline must measure
    # the configuration a user actually gets. rbg was a measured ~10%
    # step-time win on v5e pre-r5 (threefry bit generation dominated
    # nn.Dropout); with counter-hash dropout everywhere the PRNG only
    # draws one 32-bit seed per dropout site per step, so the gap is gone
    # and production keeps threefry's cross-version bit-stream stability.
    # BENCH_RNG=rbg reproduces the old opt-in measurement.
    jax.config.update("jax_default_prng_impl",
                      os.environ.get("BENCH_RNG", "threefry2x32"))
    return cfg, phase, max_pred


def _bench_lamb(phase: dict):
    """The phase-recipe schedule + LAMB pair every bench child measures."""
    from bert_pytorch_tpu.optim import schedulers
    from bert_pytorch_tpu.optim.lamb import (lamb, default_weight_decay_mask,
                                             default_trust_batch_axes)

    sched = schedulers.poly_warmup_schedule(
        phase["lr"], total_steps=phase["total_steps"],
        warmup=phase["warmup"])
    tx = lamb(sched, weight_decay=0.01,
              weight_decay_mask=default_weight_decay_mask,
              trust_batch_axes=default_trust_batch_axes)
    return sched, tx


def run_candidate(batch: int, seq_len: int, steps: int, on_tpu: bool,
                  attn: str, remat: str, unroll: int,
                  accum: int = 1, stacked: bool = True) -> dict:
    """Measure one config; called in the child process. `remat` is a
    checkpoint-policy name ("dots", "mlp_only", "nothing") or "none" for an
    un-rematted stack. `stacked` is the encoder parameter layout
    (config.stacked_params): False kills the scan-backward wgrad
    dynamic-update-slice writes (per-layer param leaves, always fully
    unrolled)."""
    # overlap flag pack (parallel/xla_flags.py) before the backend comes up:
    # single-chip it is inert (no collectives to schedule), but the headline
    # must measure the same runtime configuration run_pretraining ships.
    # BENCH_OVERLAP=0 opts out for A/B.
    if os.environ.get("BENCH_OVERLAP", "1") == "1":
        from bert_pytorch_tpu.parallel.xla_flags import apply_overlap_flags

        apply_overlap_flags()
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.compile_cache import enable_compile_cache
    from bert_pytorch_tpu.models import BertForPreTraining
    from bert_pytorch_tpu.telemetry.run import init_run
    from bert_pytorch_tpu.training import build_pretrain_step, make_sharded_state
    from bert_pytorch_tpu.training.pretrain import stack_microbatches

    # compile accounting rides into the result record: a candidate whose
    # measured window recompiled is NOT a steady-state number. Wired
    # through the same init_run path as the entry points (verbose=False:
    # the child's stdout belongs to its JSON result protocol)
    enable_compile_cache()
    tel = init_run(phase="bench", verbose=False)
    compile_watch = tel.compile_watch

    cfg, phase, max_pred = _bench_base_config(seq_len, on_tpu)

    # BENCH_* env knobs for perf experiments without editing the file:
    # BENCH_FUSED=0 (XLA LayerNorm instead of Pallas), BENCH_RNG,
    # BENCH_DROPOUT=0, BENCH_OPT=sgd. The attention impl / batch / unroll /
    # remat policy are per-candidate child CLI flags (--attn etc.).
    fused = os.environ.get("BENCH_FUSED", "1") == "1"
    cfg = cfg.replace(attention_impl=attn, fused_ops=fused,
                      checkpoint_activations=(remat != "none"),
                      remat_policy=(remat if remat != "none" else "dots"),
                      scan_unroll=unroll, stacked_params=stacked)
    if os.environ.get("BENCH_DROPOUT", "1") == "0":
        cfg = cfg.replace(hidden_dropout_prob=0.0,
                          attention_probs_dropout_prob=0.0)
    if os.environ.get("BENCH_FUSED_DROPOUT", "1") == "0":
        cfg = cfg.replace(fused_dropout_ln=False)  # nn.Dropout + LN ablation
    # finer ablations for the perf budget map: attention-kernel dropout and
    # hidden (residual) dropout cost measured independently
    if os.environ.get("BENCH_ATTN_DROPOUT", "1") == "0":
        cfg = cfg.replace(attention_probs_dropout_prob=0.0)
    if os.environ.get("BENCH_HIDDEN_DROPOUT", "1") == "0":
        cfg = cfg.replace(hidden_dropout_prob=0.0)
    model = BertForPreTraining(cfg, dtype=jnp.bfloat16)

    rng = np.random.RandomState(0)
    n_rows = batch * accum
    ids = rng.randint(5, cfg.vocab_size, (n_rows, seq_len)).astype(np.int32)
    # exactly max_pred masked positions per row, like a full phase sample
    labels = np.full((n_rows, seq_len), -1, np.int64)
    for b in range(n_rows):
        pos = rng.choice(seq_len, max_pred, replace=False)
        labels[b, pos] = ids[b, pos]
    batch_np = {
        "input_ids": ids,
        "token_type_ids": np.zeros_like(ids),
        "attention_mask": np.ones_like(ids),
        "masked_lm_labels": labels.astype(np.int32),
        "next_sentence_labels": rng.randint(0, 2, (n_rows,)).astype(np.int32),
    }
    micro_batch = {k: jnp.asarray(v) for k, v in
                   stack_microbatches(batch_np, accum).items()}

    sched, tx = _bench_lamb(phase)
    if os.environ.get("BENCH_OPT") == "sgd":  # optimizer-cost diagnosis only
        import optax

        tx = optax.sgd(sched)
    grad_dtype = (None if os.environ.get("BENCH_GRAD_DTYPE") == "f32"
                  else jnp.bfloat16)
    step_fn = build_pretrain_step(model, tx, schedule=sched,
                                  accum_steps=accum,
                                  max_predictions=max_pred,
                                  grad_dtype=grad_dtype)

    def init_fn(r):
        return model.init(r, micro_batch["input_ids"][0],
                          micro_batch["token_type_ids"][0],
                          micro_batch["attention_mask"][0])

    state, _ = make_sharded_state(jax.random.PRNGKey(0), init_fn, tx)

    # Device-side K-step loop: the host dispatches ONE program for the whole
    # measured window (training/pretrain.chain_steps — the same inner loop
    # run_pretraining exposes as --steps_per_loop), so the window measures
    # the device and not the host's per-step dispatch.
    from bert_pytorch_tpu.training.pretrain import chain_steps

    multi_fn = jax.jit(chain_steps(step_fn, steps), donate_argnums=(0,))
    single = jax.jit(step_fn, donate_argnums=(0,))
    state, metrics = single(state, micro_batch, jax.random.PRNGKey(0))
    float(metrics["loss"])  # host read of an output = the program finished
    state, metrics = multi_fn(state, micro_batch, jax.random.PRNGKey(1))
    float(metrics["loss"])  # compile + warmup of the chained program
    compile_watch.mark_steady()  # compiles past here taint the measurement
    profile_dir = os.environ.get("BENCH_PROFILE_DIR")
    if profile_dir:  # trace exactly the steady-state measured window
        jax.profiler.start_trace(profile_dir)
    t0 = time.time()
    state, metrics = multi_fn(state, micro_batch, jax.random.PRNGKey(2))
    loss = float(metrics["loss"])  # ends the window: waits for the program
    dt = time.time() - t0
    if profile_dir:
        jax.profiler.stop_trace()

    dev = jax.devices()[0]
    # effective flash kernel-grid layout, only when a flash kernel actually
    # runs ("auto" resolves to pallas beyond seq 256) — derived through the
    # same gate the kernel dispatch uses, so the record cannot lie about
    # which path was measured
    flash_layout = None
    if attn == "pallas" or (attn == "auto" and seq_len > 256):
        from bert_pytorch_tpu.ops.pallas.flash_attention import _use_native

        flash_layout = ("native" if _use_native(
            seq_len, cfg.num_attention_heads, cfg.head_dim) else "bh")
    seqs_per_sec = batch * accum * steps / dt
    fps = flops_per_seq(cfg, seq_len, cfg.vocab_size, max_pred)
    # single-chip bench always computes in bf16 (model built with
    # jnp.bfloat16 above) — quote MFU against the bf16 peak explicitly.
    # None under the --cpu smoke (no MFU on the CPU backend); a TPU the
    # peak table does not know raises
    peak = device_peak_flops(dev, dtype="bf16")
    mfu = round(seqs_per_sec * fps / peak, 4) if peak else None
    cw = compile_watch.snapshot()
    info = {"device": dev.device_kind, "platform": dev.platform,
            "batch": batch, "seq": seq_len,
            "attn": attn, "remat": remat, "unroll": unroll,
            "accum": accum, "stacked": stacked, "steps": steps,
            "mfu": mfu,
            "loss": round(loss, 3), "dt_s": round(dt, 3),
            "compiles": cw["compiles"],
            "compile_secs": cw["compile_secs"],
            "recompiles_in_window": cw["recompiles_after_warmup"]}
    if flash_layout is not None:
        info["flash_layout"] = flash_layout
    tel.close()
    return {
        "seqs_per_sec": round(seqs_per_sec, 2),
        "mfu": mfu,
        "_info": info,
    }


def run_packing_candidate(seq_len: int, steps: int, on_tpu: bool,
                          packed: bool, batch: int) -> dict:
    """Measure one member of the packed-vs-padded pair (child process).

    Both members train on the SAME deterministically generated example set
    (varied lengths, seed 0) — the same global token budget — so their
    real_tokens_per_sec ratio is the packing speedup and nothing else:
    `packed` first-fits the examples into `batch` rows of seq_len with
    block-diagonal segment attention; `padded` feeds them one per row,
    dense-padded to seq_len, exactly like the pre-round-9 pipeline."""
    if os.environ.get("BENCH_OVERLAP", "1") == "1":
        from bert_pytorch_tpu.parallel.xla_flags import apply_overlap_flags

        apply_overlap_flags()
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.compile_cache import enable_compile_cache
    from bert_pytorch_tpu.data import packing as packing_lib
    from bert_pytorch_tpu.models import BertForPreTraining
    from bert_pytorch_tpu.training import (build_pretrain_step,
                                           make_sharded_state)
    from bert_pytorch_tpu.training.pretrain import (chain_steps,
                                                    stack_microbatches)

    enable_compile_cache()
    max_segments = 8
    cfg, phase, max_pred = _bench_base_config(seq_len, on_tpu)
    cfg = cfg.replace(attention_impl="auto", next_sentence=True,
                      fused_ops=os.environ.get("BENCH_FUSED", "1") == "1")
    model = BertForPreTraining(cfg, dtype=jnp.bfloat16 if on_tpu
                               else jnp.float32)

    # deterministic varied-length corpus: mean length ~0.62*S, the regime
    # where packing fits 1-3 examples per row
    rng = np.random.RandomState(0)
    n_candidates = batch * 3
    lengths = rng.randint(seq_len // 4, seq_len + 1, n_candidates)
    ids = rng.randint(5, cfg.vocab_size, (n_candidates, seq_len)) \
        .astype(np.int32)
    attention_mask = (np.arange(seq_len)[None, :]
                      < lengths[:, None]).astype(np.int32)
    ids *= attention_mask
    labels = np.full((n_candidates, seq_len), -1, np.int64)
    for i in range(n_candidates):
        n_mask = max(1, min(max_pred, int(lengths[i] * MASK_FRACTION)))
        pos = rng.choice(lengths[i], n_mask, replace=False)
        labels[i, pos] = ids[i, pos]
    examples = {
        "input_ids": ids,
        "token_type_ids": np.zeros_like(ids),
        "attention_mask": attention_mask,
        "masked_lm_labels": labels,
        "next_sentence_labels": rng.randint(0, 2, (n_candidates,))
        .astype(np.int32),
    }
    bins = packing_lib.first_fit(lengths, batch, seq_len, max_segments)
    placed = sorted(i for members in bins for i in members)
    kept = {k: v[placed] for k, v in examples.items()}
    n_examples = len(placed)
    real_tokens = int(kept["attention_mask"].sum())

    if packed:
        remap = {old: new for new, old in enumerate(placed)}
        bins = [[remap[i] for i in members] for members in bins]
        batch_np = packing_lib.pack_examples(kept, bins, seq_len,
                                             max_segments)
        # same per-row gathered-head budget formula as run_pretraining.py
        max_pred_row = min(seq_len, max_segments * max_pred,
                           int(seq_len * MASK_FRACTION) + max_segments)
        rows = batch
    else:
        batch_np = dict(kept)
        batch_np["masked_lm_labels"] = \
            batch_np["masked_lm_labels"].astype(np.int32)
        max_pred_row = max_pred
        rows = n_examples

    micro = {k: jnp.asarray(v) for k, v in
             stack_microbatches(batch_np, 1).items()}
    sched, tx = _bench_lamb(phase)
    step_fn = build_pretrain_step(model, tx, schedule=sched, accum_steps=1,
                                  max_predictions=max_pred_row,
                                  grad_dtype=jnp.bfloat16 if on_tpu
                                  else None)

    def init_fn(r):
        return model.init(r, micro["input_ids"][0],
                          micro["token_type_ids"][0],
                          micro["attention_mask"][0])

    state, _ = make_sharded_state(jax.random.PRNGKey(0), init_fn, tx)
    multi_fn = jax.jit(chain_steps(step_fn, steps), donate_argnums=(0,))
    state, metrics = multi_fn(state, micro, jax.random.PRNGKey(1))
    float(metrics["loss"])  # compile + warmup; the host read waits for it
    t0 = time.time()
    state, metrics = multi_fn(state, micro, jax.random.PRNGKey(2))
    loss = float(metrics["loss"])  # ends the window: waits for the program
    dt = time.time() - t0

    return {
        "mode": "packed" if packed else "padded",
        "seq": seq_len,
        "rows_per_step": rows,
        "examples_per_step": n_examples,
        "real_tokens_per_step": real_tokens,
        "packing_efficiency": round(real_tokens / (rows * seq_len), 4),
        "real_tokens_per_sec": round(real_tokens * steps / dt, 1),
        "seqs_per_sec": round(rows * steps / dt, 2),
        "loss": round(loss, 3),
        "dt_s": round(dt, 3),
    }


def _measure_packing_pair(seq_len: int, steps: int, on_tpu: bool,
                          batch: int) -> None:
    """Run the packed and padded children (same token budget) and record
    the pair + speedup for the final JSON. Budget-gated like the grids."""
    here = os.path.abspath(__file__)
    pair = {}
    for mode in ("packed", "padded"):
        remaining = DEADLINE[0] - time.time()
        if remaining < EST_COST[0]:
            print(f"# budget: skipping packing pair ({mode})",
                  file=sys.stderr)
            SKIPPED[0] = True
            return
        cmd = [sys.executable, here, "--packing-child", "--mode", mode,
               "--seq", str(seq_len), "--steps", str(steps),
               "--batch", str(batch)]
        if not on_tpu:
            cmd.append("--cpu")
        res = _run_child(cmd, min(900.0, remaining - 15.0))
        if res is None:
            print(f"# packing pair {mode} timed out; skipping pair",
                  file=sys.stderr)
            SKIPPED[0] = True
            return
        stdout, stderr, rc = res
        for line in stdout.splitlines():
            if line.startswith("BENCH_RESULT "):
                pair[mode] = json.loads(line[len("BENCH_RESULT "):])
        if mode not in pair:
            print(stderr[-2000:], file=sys.stderr)
            print(f"# packing pair {mode} failed rc={rc}; skipping pair",
                  file=sys.stderr)
            SKIPPED[0] = True
            return
        print(f"# packing pair measured {pair[mode]}", file=sys.stderr)
    PACKING_PAIR.update(pair)
    PACKING_PAIR["speedup_real_tokens_per_sec"] = round(
        pair["packed"]["real_tokens_per_sec"]
        / max(pair["padded"]["real_tokens_per_sec"], 1e-9), 4)


# Candidate grids: (batch, attn, remat_policy, unroll, accum, stacked),
# ordered BEST-KNOWN-FIRST so a budget-truncated sweep still lands the
# headline. "none" = un-rematted stack; "mlp_only" recomputes only the
# (B, S, 4E) wide-MLP activations (models/bert.py remat policies), trading
# cheap MLP recompute for batch headroom. attention "xla_checkpoint" frees
# the (B, H, S, S) probs; "auto" resolves to the Pallas flash kernel.
# stacked=False is the unstacked per-layer parameter layout
# (config.stacked_params): wgrads write into per-layer leaves instead of
# dynamic_update_slice into the (L, ...) stack — the 9.4% DUS bucket in the
# seq512 trace (docs/PERF.md) — and at seq512 it pairs with the flash
# kernel's native layout (no transpose pass, the 4.9% bucket).
# accum > 1 measures the reference RECIPE configuration (phase global
# batches are 65536/32768 — far above one chip's micro batch,
# config/bert_pretraining_phase{1,2}_config.json:3), so the
# once-per-optimization-step LAMB cost amortizes over the microbatches
# exactly as it does in real training.
CANDIDATES_128 = [
    # unstacked first: the r5 winner config minus its scan-wgrad DUS writes
    # (same batch/accum; the stack was already fully unrolled, so the only
    # delta is the parameter layout).
    (64, "xla", "none", 24, 32, False),
    # r5 winner family: fused residual-dropout-LN kernel (measured 65.1-65.3%
    # MFU at accum 32; r4's 53.0% was the same config with nn.Dropout).
    # Batch expansion via remat is measured dead: b80/b96 mlp_only OOM at
    # 17.3/20.4G vs 15.75G HBM. No accum 64: accum 32 already amortizes the
    # once-per-step LAMB update (r4 measured ~0.2 points between them),
    # which is not worth a candidate's share of the budget.
    (64, "xla", "none", 24, 32, True),
    (64, "xla", "none", 24, 16, False),
    (16, "xla", "dots", 1, 1, True),    # fit-anywhere floor (small HBM)
]
CANDIDATES_512 = [
    # unstacked + native-layout flash: attacks the two structural buckets
    # left in the r5 seq512 trace (9.4% DUS + 4.9% layout copies)
    (16, "auto", "none", 24, 32, False),
    (16, "auto", "none", 24, 32, True),  # r5: 50.7% with fused dropout-LN
    # no accum 64 here either (accum 32 already amortizes LAMB fully).
    # b24/b32 mlp_only OOM (19.0/24.8G); b20 un-rematted measured 49.9% —
    # b16 stays the knee.
    (16, "auto", "none", 24, 16, False),
    (16, "auto", "none", 24, 8, True),
    (4, "xla_checkpoint", "dots", 1, 1, True),  # fit-anywhere floor
]
OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Ran out of memory",
               "Exceeded hbm", "out of memory")

# --- always-land-the-JSON machinery (round-5, VERDICT item 1) ---
BEST: dict = {}          # seq_len -> best measured result, updated live
PACKING_PAIR: dict = {}  # packed-vs-padded pair + speedup (round 9)
ON_TPU = [False]
_EMITTED = [False]
_CHILD = [None]          # live child Popen, killed on signal
DEADLINE = [None]        # wall-clock emit deadline
# per-candidate cost estimate, shared across grids: a cold-compile guess
# (compile + 3 measurement windows), then the most recent child's observed
# wall time x1.2 — grows after slow/hung children
EST_COST = [240.0]


SKIPPED = [False]        # any candidate skipped/timed out -> truncated_sweep
FAILED: list = []        # candidates that died of anything but OOM: errors


def emit_final(partial: bool = False, signal_safe: bool = False) -> None:
    """Print the one JSON line from BEST. Idempotent. With signal_safe,
    bypasses buffered stdio (a SIGTERM landing mid-print would otherwise
    hit CPython's BufferedWriter reentrancy guard and kill the process
    before the JSON gets out)."""
    if _EMITTED[0]:
        return
    _EMITTED[0] = True
    if 128 not in BEST:
        msg = "# no seq128 result measured before the deadline\n"
        os.write(2, msg.encode()) if signal_safe else sys.stderr.write(msg)
        return
    out = {
        "metric": ("bert_large_mlm_seq128_train_throughput" if ON_TPU[0]
                   else "bench_smoke_cpu"),
        "value": BEST[128]["seqs_per_sec"],
        "unit": "seq/s/chip",
        # MFU (and so vs_baseline) exists on a TPU only
        "vs_baseline": (round(BEST[128]["mfu"] / 0.50, 4) if ON_TPU[0]
                        else None),
        "compiles": BEST[128]["_info"].get("compiles"),
        "recompiles_in_window": BEST[128]["_info"].get(
            "recompiles_in_window"),
    }
    if 512 in BEST:
        out["seq512_value"] = BEST[512]["seqs_per_sec"]
        out["seq512_mfu"] = BEST[512]["mfu"]
        out["seq512_vs_baseline"] = round(BEST[512]["mfu"] / 0.50, 4)
        out["seq512_compiles"] = BEST[512]["_info"].get("compiles")
    if PACKING_PAIR:
        # packed-vs-padded over the identical example set (same global
        # token budget): the real_tokens_per_sec ratio IS the packing win
        out["packing"] = PACKING_PAIR
    if partial or SKIPPED[0]:
        out["truncated_sweep"] = True
    if FAILED:
        out["failed_candidates"] = list(FAILED)
    if not signal_safe:
        # self-describing artifact (ISSUE 3 provenance satellite). Skipped
        # on the signal path: collect() shells out to git, which is not
        # async-signal-safe. device=False — the parent process must never
        # initialize the TPU backend (children own the device).
        try:
            from bert_pytorch_tpu.telemetry.provenance import collect

            # the PARENT env's pack state is reported; the measurement
            # children apply the overlap pack themselves iff BENCH_OVERLAP=1
            # (run_candidate), so record that intent alongside
            out["provenance"] = collect(device=False, extra={
                "bench_overlap": os.environ.get("BENCH_OVERLAP", "1")})
        except Exception:
            pass
    line = json.dumps(out) + "\n"
    if signal_safe:
        os.write(1, line.encode())
    else:
        sys.stdout.write(line)
        sys.stdout.flush()


def _signal_flush(signum, frame):
    """External timeout (SIGTERM) or our own alarm: flush JSON and exit 0
    so the driver parses a real result instead of recording rc=124. Only
    async-signal-tolerant calls here: os.write, no buffered prints."""
    os.write(2, f"# signal {signum}: flushing partial result\n".encode())
    child = _CHILD[0]
    if child is not None and child.poll() is None:
        child.kill()
    emit_final(partial=True, signal_safe=True)
    # exit 0 only if there is a headline to parse
    os._exit(0 if 128 in BEST else 1)


def _run_child(cmd, timeout_s: float):
    """Popen wrapper that records the live child so the signal handler can
    kill it; returns (stdout, stderr, rc) or None on timeout."""
    child = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                             stderr=subprocess.PIPE, text=True)
    _CHILD[0] = child
    try:
        out, err = child.communicate(timeout=timeout_s)
        return out, err, child.returncode
    except subprocess.TimeoutExpired:
        child.kill()
        child.communicate()
        return None
    finally:
        _CHILD[0] = None


def _measure_grid(seq_len: int, candidates, steps: int, on_tpu: bool):
    """Run candidates best-first in fresh subprocesses, respecting the
    wall-clock deadline: a child is only launched if the remaining budget
    plausibly covers it, and its timeout is clipped to the budget. Updates
    BEST[seq_len] after every measurement so a signal flush mid-grid still
    reports the best so far.

    A candidate runs once. Running out of device memory is an expected
    outcome of a grid that probes batch sizes; any other failure (a kernel
    the compiler refuses, a crash) is recorded in FAILED and fails the
    sweep — nothing is retried under another configuration."""
    here = os.path.abspath(__file__)
    n_measured = 0
    for batch, attn, remat, unroll, accum, stacked in candidates:
        remaining = DEADLINE[0] - time.time()
        if remaining < EST_COST[0]:
            print(f"# budget: {remaining:.0f}s left < {EST_COST[0]:.0f}s "
                  f"estimate; skipping rest of seq{seq_len} grid",
                  file=sys.stderr)
            SKIPPED[0] = True
            break
        # measurement window ~48 optimizer-equivalent steps regardless of
        # accumulation depth so every candidate gets a comparable timing run
        c_steps = max(6, steps // accum) if accum > 1 else steps
        cmd = [sys.executable, here, "--child", "--batch", str(batch),
               "--steps", str(c_steps), "--seq", str(seq_len),
               "--attn", attn, "--unroll", str(unroll),
               "--accum", str(accum), "--remat", remat,
               "--stacked", "1" if stacked else "0"]
        if not on_tpu:
            cmd.append("--cpu")
        label = f"b={batch} {attn} remat={remat} seq={seq_len}"
        t_start = time.time()
        child_budget = min(900.0, DEADLINE[0] - time.time() - 15.0)
        if child_budget < 60.0:
            SKIPPED[0] = True
            break
        res = _run_child(cmd, child_budget)
        if res is None:
            elapsed = time.time() - t_start
            print(f"# candidate {label} timed out after {elapsed:.0f}s; "
                  "skipping", file=sys.stderr)
            # a hung child proves candidates can cost this much: raise
            # the estimate so the gate stops launching doomed ones
            EST_COST[0] = max(EST_COST[0], elapsed * 1.2)
            SKIPPED[0] = True
            continue
        stdout, stderr, rc = res
        result = None
        for line in stdout.splitlines():
            if line.startswith("BENCH_RESULT "):
                result = json.loads(line[len("BENCH_RESULT "):])
        if result is not None:
            print(f"# measured {result['_info']}", file=sys.stderr)
            n_measured += 1
            EST_COST[0] = max(180.0, (time.time() - t_start) * 1.2)
            if (seq_len not in BEST
                    or result["seqs_per_sec"]
                    > BEST[seq_len]["seqs_per_sec"]):
                BEST[seq_len] = result
        elif any(m in stderr for m in OOM_MARKERS):
            print(f"# candidate {label} OOM", file=sys.stderr)
        else:
            print(stderr[-2000:], file=sys.stderr)
            print(f"# candidate {label} FAILED (rc={rc})", file=sys.stderr)
            FAILED.append(label)
    if not n_measured and candidates:
        print(f"# seq{seq_len}: nothing measured in this block",
              file=sys.stderr)


# --- measured multichip scaling bench (round 7) -------------------------
# Sweeps {pure-DP, DP+ZeRO-1, fsdp} over an n-device mesh plus a 1-device
# baseline, and reports per-variant step time, seq/s/chip, and scaling
# efficiency (seq/s/chip / single-chip seq/s). Upgrades MULTICHIP_r*.json
# from a dryrun-only artifact to a perf trajectory. It needs n real chips;
# `--cpu` asks instead for the forced n-device CPU mesh — the relative
# DP-vs-ZeRO-1 cost is still real there (a replicated LAMB update is
# executed once per device; the sharded one 1/n per device), absolute seq/s
# is not TPU-comparable and the JSON records the platform.
#
# The model is deliberately optimizer-heavy (big vocab embedding, thin
# trunk, accum=1, gathered MLM head): the quantity under test is the
# once-per-step update + collective path, not the matmul throughput the
# single-chip headline already measures.

MULTICHIP_MODEL = dict(vocab_size=32768, hidden_size=128,
                       num_hidden_layers=2, num_attention_heads=4,
                       intermediate_size=512, max_position_embeddings=64)
MULTICHIP_SEQ = 32
MULTICHIP_BATCH_PER_SHARD = 2
MULTICHIP_MAX_PRED = 4


def _mc_packed_batch(cfg, batch_global: int, seq: int, max_pred: int,
                     max_segments: int = 4):
    """Synthetic PACKED batch through the production packer: two
    half-row-length examples per row (deterministic bins — the quantity
    under test is the packed step's collective/compute profile, not the
    packer), exactly `max_pred` masked positions per example."""
    from bert_pytorch_tpu.data.packing import pack_examples

    rng = np.random.RandomState(0)
    n = batch_global * 2
    ln = seq // 2
    ids = rng.randint(5, cfg.vocab_size, (n, seq)).astype(np.int32)
    mask = np.zeros((n, seq), np.int32)
    mask[:, :ln] = 1
    labels = np.full((n, seq), -1, np.int32)
    for b in range(n):
        pos = rng.choice(ln, max_pred, replace=False)
        labels[b, pos] = ids[b, pos]
    ex = {
        "input_ids": ids,
        "token_type_ids": np.zeros_like(ids),
        "attention_mask": mask,
        "masked_lm_labels": labels,
        "next_sentence_labels": rng.randint(0, 2, (n,)).astype(np.int32),
    }
    bins = [[2 * i, 2 * i + 1] for i in range(batch_global)]
    return pack_examples(ex, bins, seq, max_segments)


def _mc_time_variant(label, mesh, cfg, steps: int, reps: int,
                     zero1: bool = False, overlap: bool = False,
                     packed: bool = False, fsdp_overlap: bool = False,
                     rs: bool = False, trace_dir=None):
    """Measure one mesh/variant in-process; returns the per-variant record.

    `overlap` = gather-on-use ZeRO-1 (params rest 1/N-sharded, re-gathered
    per leaf at the point of use). `fsdp_overlap` = gather-on-use for the
    fsdp axis (parallel/zero.make_fsdp_plan — explicit per-leaf gathers
    instead of GSPMD's implicit re-materialization). `packed` runs a
    2-segments/row packed batch through the segment-aware attention; the
    dp_seq_packing_overlap variant composes packed + ring + zero1-overlap
    — the `production` mesh_config, measured rather than assumed.
    `trace_dir` additionally captures one traced window per variant and
    lands its collective/compute/host breakdown — incl. the round-15
    per-KIND collective split (telemetry/trace.py collective_kind_ms) —
    in the record, the attribution behind the scaling-efficiency
    numbers. `rs` (round 16, implies zero1+overlap and a data-only mesh)
    routes gradients through the reduce-scatter region with coalesced
    trust-ratio norms: the per-kind split is the gate target — all-reduce
    ms down, reduce-scatter ms up."""
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.optim import schedulers
    from bert_pytorch_tpu.optim.lamb import (lamb, default_weight_decay_mask,
                                             default_trust_batch_axes)
    from bert_pytorch_tpu.models import BertForPreTraining
    from bert_pytorch_tpu.parallel import mesh as mesh_lib
    from bert_pytorch_tpu.parallel.zero import make_zero1_plan
    from bert_pytorch_tpu.telemetry.run import init_run
    from bert_pytorch_tpu.training import build_pretrain_step, make_sharded_state
    from bert_pytorch_tpu.training.pretrain import (chain_steps,
                                                    stack_microbatches)

    import __graft_entry__ as graft

    # same init_run wiring path as the entry points (phase label 'bench')
    tel = init_run(phase="bench", verbose=False)
    compile_watch = tel.compile_watch

    n_shards = mesh_lib.data_shard_count(mesh)
    n_dev = mesh.devices.size
    batch_global = MULTICHIP_BATCH_PER_SHARD * n_shards
    max_pred_row = MULTICHIP_MAX_PRED * (2 if packed else 1)
    if packed:
        batch_np = _mc_packed_batch(cfg, batch_global, MULTICHIP_SEQ,
                                    MULTICHIP_MAX_PRED)
    else:
        # the dryrun's synthetic-batch builder (same premasked-width
        # contract as the gathered MLM head: exactly max_pred masked
        # positions per row)
        batch_np = graft._make_batch(cfg, 1, batch_global, MULTICHIP_SEQ,
                                     MULTICHIP_MAX_PRED)
    stacked = stack_microbatches(batch_np, 1)

    model = BertForPreTraining(cfg, dtype=jnp.float32
                               if jax.devices()[0].platform == "cpu"
                               else jnp.bfloat16)
    sched = schedulers.poly_warmup_schedule(1e-3, total_steps=1000,
                                            warmup=0.1)
    tx = lamb(sched, weight_decay=0.01,
              weight_decay_mask=default_weight_decay_mask,
              trust_batch_axes=default_trust_batch_axes)

    def init_fn(r):
        return model.init(r, jnp.asarray(stacked["input_ids"][0]),
                          jnp.asarray(stacked["token_type_ids"][0]),
                          jnp.asarray(stacked["attention_mask"][0]))

    with mesh_lib.logical_rules():
        state, shardings = make_sharded_state(
            jax.random.PRNGKey(0), init_fn, tx, mesh=mesh, zero1=zero1,
            zero1_params=overlap)
    plan = (make_zero1_plan(state.params, shardings.params, mesh,
                            gather_on_use=overlap, reduce_scatter=rs,
                            warn_skipped=False)
            if zero1 else None)
    if fsdp_overlap:
        from bert_pytorch_tpu.parallel.zero import make_fsdp_plan

        fplan = make_fsdp_plan(state.params, shardings.params, mesh,
                               zero1=plan is not None, warn_skipped=False)
        if fplan is not None:
            plan = fplan
    norm_reducer = None
    if rs and plan is not None:
        # coalesced trust-ratio norms are what keep the rs program's
        # all-reduce count at O(buckets) instead of O(leaves) — without
        # them the per-leaf norm reductions hand back most of the
        # all-reduces the scatter path just removed
        from bert_pytorch_tpu.parallel.coalesce import NormReducer

        norm_reducer = NormReducer(plan.grad_shardings, mesh)
        tx = lamb(sched, weight_decay=0.01,
                  weight_decay_mask=default_weight_decay_mask,
                  trust_batch_axes=default_trust_batch_axes,
                  norm_reducer=norm_reducer)
    step_fn = build_pretrain_step(model, tx, schedule=sched, accum_steps=1,
                                  max_predictions=max_pred_row,
                                  zero1=plan, norm_reducer=norm_reducer)
    from bert_pytorch_tpu.training.pretrain import StepProgram

    # StepProgram = same one compile jit would do, but the executable's
    # HLO stays reachable — the collective inventory below is the static
    # counterpart of the traced time_breakdown
    chained = StepProgram(chain_steps(step_fn, steps))
    batch = mesh_lib.host_to_device_batch(mesh, stacked)
    breakdown = None
    inventory = None
    with mesh, mesh_lib.logical_rules():
        state, metrics = chained(state, batch, jax.random.PRNGKey(1))
        float(metrics["loss"])  # compile + warmup; the host read waits
        hlo_text = chained.as_text()
        if hlo_text is not None:
            from bert_pytorch_tpu.analysis.hlo import collective_inventory

            inventory = collective_inventory(hlo_text)
            # per-STEP counts read better next to step_time_ms than
            # whole-chunk totals (the chunk is `steps` identical bodies)
            inventory["steps_per_program"] = steps
        dts = []
        for rep in range(reps):
            t0 = time.time()
            state, metrics = chained(state, batch,
                                     jax.random.PRNGKey(2 + rep))
            loss = float(metrics["loss"])  # ends the window (see docstring)
            dts.append(time.time() - t0)
        if trace_dir is not None:
            # one EXTRA traced window after the timed reps (tracing costs;
            # the wall-clock numbers above stay untainted), summarized into
            # the collective/compute/host buckets per variant
            from bert_pytorch_tpu.telemetry.trace import summarize_trace

            tdir = os.path.join(trace_dir, label)
            jax.profiler.start_trace(tdir)
            try:
                state, m = chained(state, batch, jax.random.PRNGKey(99))
                float(m["loss"])
            finally:
                jax.profiler.stop_trace()
            try:
                breakdown = summarize_trace(tdir, steps=steps,
                                            n_devices=n_dev)
                breakdown.pop("trace_file", None)  # tempdir path: noise
            except Exception as e:  # a missing trace must not kill the sweep
                breakdown = {"error": f"{type(e).__name__}: {e}"}
    dt = min(dts)
    seqs_per_sec = batch_global * steps / dt
    cw = compile_watch.snapshot()
    tel.close()
    rec = {
        "label": label,
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "n_devices": int(n_dev),
        "zero1": bool(zero1 and plan is not None),
        "zero1_overlap": bool(zero1 and plan is not None and overlap),
        "zero1_rs": bool(rs and plan is not None
                         and getattr(plan, "reduce_scatter", False)),
        "fsdp_overlap": bool(fsdp_overlap and plan is not None
                             and plan.axis == "fsdp"),
        "packed": bool(packed),
        "batch_global": int(batch_global),
        "step_time_ms": round(dt / steps * 1e3, 3),
        "seqs_per_sec": round(seqs_per_sec, 2),
        "seqs_per_sec_per_chip": round(seqs_per_sec / n_dev, 2),
        "loss": round(loss, 3),
        "compiles": cw["compiles"],
        "compile_secs": cw["compile_secs"],
    }
    if breakdown is not None:
        rec["time_breakdown"] = breakdown
    if inventory is not None:
        # the static collective inventory next to the measured breakdown:
        # WHAT the program moves, beside WHERE the time went
        rec["collectives"] = inventory
    # bf16 on TPU (see the BertForPreTraining construction above); None on
    # the --cpu mesh, where absolute MFU would be fiction — omitted
    peak = device_peak_flops(jax.devices()[0], dtype="bf16")
    if peak is not None:
        fps = flops_per_seq(cfg, MULTICHIP_SEQ, cfg.vocab_size,
                            max_pred_row)
        rec["mfu"] = round(seqs_per_sec * fps / (peak * n_dev), 4)
    if zero1 and plan is not None:
        # record that the moments genuinely live sharded (the thing ZeRO-1
        # claims), so the JSON cannot report a silently-replicated run
        mu_leaves = jax.tree.leaves(state.opt_state.mu)
        rec["moment_shards"] = max(
            len(l.sharding.device_set) if not l.sharding.is_fully_replicated
            else 1 for l in mu_leaves)
    if overlap and plan is not None:
        # ...and that the PARAMS genuinely rest sharded between steps (the
        # thing gather-on-use claims)
        p_leaves = jax.tree.leaves(state.params)
        rec["param_shards_at_rest"] = max(
            len(l.sharding.device_set) if not l.sharding.is_fully_replicated
            else 1 for l in p_leaves)
    return rec


def multichip_measure(n_devices: int, out_path=None, budget_s=None,
                      steps: int = 10, reps: int = 3) -> dict:
    """Run the multichip sweep in a process that already exposes >=
    n_devices devices. Writes `out_path` incrementally after every variant
    (a killed run still leaves the variants measured so far on disk) and
    prints one final `MULTICHIP_BENCH {json}` line."""
    import jax

    from bert_pytorch_tpu.config import BertConfig
    from bert_pytorch_tpu.parallel import mesh as mesh_lib

    if jax.device_count() < n_devices:
        raise RuntimeError(
            f"{jax.device_count()} devices visible, need {n_devices}")
    deadline = time.time() + budget_s if budget_s else None
    est = [150.0]

    cfg = BertConfig(next_sentence=True, dtype="float32", fused_ops=False,
                     attention_impl="xla", hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0, **MULTICHIP_MODEL)
    # the seq-sharded variants need an impl the ring dispatch serves
    # (ops/attention.py routes impl in {ring, pallas} to ring_sharded when
    # the ambient mesh has seq>1; impl='xla' is the documented opt-out)
    cfg_ring = cfg.replace(attention_impl="ring")
    devs = jax.devices()[:n_devices]
    half = max(1, n_devices // 2)
    # (label, mesh, variant kwargs) — ordered so the round-11 quantities
    # under test (overlap ZeRO-1, seq-axis composition) land before the
    # budget can truncate the tail
    plan = [
        ("single", mesh_lib.make_mesh({"data": 1}, devices=devs[:1]),
         dict()),
        ("dp", mesh_lib.make_mesh({"data": n_devices}, devices=devs),
         dict()),
        ("dp_zero1", mesh_lib.make_mesh({"data": n_devices}, devices=devs),
         dict(zero1=True)),
        ("dp_zero1_overlap",
         mesh_lib.make_mesh({"data": n_devices}, devices=devs),
         dict(zero1=True, overlap=True)),
        # round 16: grads leave the step through psum_scatter instead of
        # all-reduce-then-slice (half the gradient bytes on the wire),
        # with coalesced trust-ratio norms. Data-only meshes by
        # construction (parallel/zero.rs_supported); production_rs is the
        # production composition minus the seq axis — packing + ZeRO-1
        # overlap + rs — so the packed loss path is measured on the
        # scatter region too
        ("dp_zero1_rs",
         mesh_lib.make_mesh({"data": n_devices}, devices=devs),
         dict(zero1=True, overlap=True, rs=True)),
        ("production_rs",
         mesh_lib.make_mesh({"data": n_devices}, devices=devs),
         dict(packed=True, zero1=True, overlap=True, rs=True)),
        ("fsdp", mesh_lib.make_mesh({"fsdp": n_devices}, devices=devs),
         dict()),
        # gather-on-use for the fsdp axis (--fsdp_overlap): the implicit
        # GSPMD re-materialization above vs explicit per-leaf gathers the
        # scheduler can overlap — the round-15 tentpole, measured
        ("fsdp_overlap",
         mesh_lib.make_mesh({"fsdp": n_devices}, devices=devs),
         dict(fsdp_overlap=True)),
    ]
    if n_devices >= 2:  # the seq axis needs 2 devices; 'single' covers n=1
        plan[4:4] = [
            ("dp_seq", mesh_lib.make_mesh({"data": half, "seq": 2},
                                          devices=devs[:half * 2]),
             dict(cfg=cfg_ring)),
            ("dp_seq_packing", mesh_lib.make_mesh({"data": half, "seq": 2},
                                                  devices=devs[:half * 2]),
             dict(cfg=cfg_ring, packed=True)),
            # the `production` mesh_config composition (packing + ring
            # attention + ZeRO-1 overlap on one mesh) — gated so the
            # default is measured, not assumed
            ("dp_seq_packing_overlap",
             mesh_lib.make_mesh({"data": half, "seq": 2},
                                devices=devs[:half * 2]),
             dict(cfg=cfg_ring, packed=True, zero1=True, overlap=True)),
        ]
    from bert_pytorch_tpu.telemetry.provenance import collect

    out = {
        "n_devices": n_devices,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "measured": True,
        "model": dict(MULTICHIP_MODEL, seq=MULTICHIP_SEQ,
                      batch_per_shard=MULTICHIP_BATCH_PER_SHARD,
                      max_predictions=MULTICHIP_MAX_PRED, accum=1),
        "steps_per_window": steps,
        "provenance": collect(),  # backend already up in this child
        "variants": {},
    }

    def flush():
        if out_path:
            tmp = out_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(out, f, indent=1, sort_keys=True)
            os.replace(tmp, out_path)

    # write the empty skeleton BEFORE the first (minutes-long) compile: a
    # signal landing in that window must flush THIS run's (empty) record,
    # not a stale previous MULTICHIP json left at the same path
    flush()

    import shutil
    import tempfile

    trace_root = tempfile.mkdtemp(prefix="multichip_traces_")
    for label, mesh, opts in plan:
        if deadline is not None and time.time() + est[0] > deadline:
            print(f"# multichip: budget exhausted before {label}; truncating",
                  file=sys.stderr)
            out["truncated"] = True
            break
        t0 = time.time()
        rec = _mc_time_variant(label, mesh, opts.pop("cfg", cfg), steps,
                               reps, trace_dir=trace_root, **opts)
        est[0] = max(60.0, (time.time() - t0) * 1.2)
        single = out["variants"].get("single")
        if single and label != "single":
            rec["scaling_efficiency"] = round(
                rec["seqs_per_sec_per_chip"] / single["seqs_per_sec"], 4)
        out["variants"][label] = rec
        print(f"# multichip measured {label}: "
              f"{rec['step_time_ms']} ms/step, "
              f"{rec['seqs_per_sec_per_chip']} seq/s/chip",
              file=sys.stderr)
        flush()

    dp = out["variants"].get("dp")
    dpz = out["variants"].get("dp_zero1")
    dpo = out["variants"].get("dp_zero1_overlap")
    if dp and dpz:
        out["zero1_step_time_ratio_vs_dp"] = round(
            dpz["step_time_ms"] / dp["step_time_ms"], 4)
    if dpz and dpo:
        # the round-11 headline: gather-on-use vs the blocking all-gather
        out["zero1_overlap_step_time_ratio_vs_zero1"] = round(
            dpo["step_time_ms"] / dpz["step_time_ms"], 4)
    dprs = out["variants"].get("dp_zero1_rs")
    if dpo and dprs:
        # the round-16 headline: reduce-scatter grads + coalesced norms
        # vs the all-reduce-then-slice overlap step
        out["zero1_rs_step_time_ratio_vs_overlap"] = round(
            dprs["step_time_ms"] / dpo["step_time_ms"], 4)
    fs = out["variants"].get("fsdp")
    fso = out["variants"].get("fsdp_overlap")
    if fs and fso:
        # the round-15 headline: explicit gather-on-use vs GSPMD's
        # implicit fsdp re-materialization
        out["fsdp_overlap_step_time_ratio_vs_fsdp"] = round(
            fso["step_time_ms"] / fs["step_time_ms"], 4)
    flush()
    # the breakdowns are extracted into the json; the raw traces are
    # ~100 MB/sweep and would otherwise accumulate in /tmp across CI runs
    shutil.rmtree(trace_root, ignore_errors=True)
    print("MULTICHIP_BENCH " + json.dumps(out, sort_keys=True), flush=True)
    return out


_MC_CHILD = [None]
_MC_OUT = [None]


def _mc_signal_flush(signum, frame):
    """SIGTERM/SIGALRM during the multichip sweep: kill the child and emit
    whatever the incremental file already holds — same always-land-the-JSON
    contract the single-chip sweep gives the headline."""
    os.write(2, f"# signal {signum}: flushing partial multichip result\n"
             .encode())
    child = _MC_CHILD[0]
    if child is not None and child.poll() is None:
        child.kill()
    path = _MC_OUT[0]
    try:
        with open(path) as f:
            data = f.read()
        payload = json.loads(data)
        payload["truncated"] = True
        os.write(1, ("MULTICHIP_BENCH " + json.dumps(payload, sort_keys=True)
                     + "\n").encode())
        os._exit(0)
    except Exception:
        os._exit(1)


def multichip_main():
    """`bench.py --multichip [--devices N] [--cpu]`: run multichip_measure
    in a child process over N real chips, or — only when `--cpu` asks for
    it — over a forced-CPU virtual mesh of N host devices."""
    def arg(name, default=None):
        return (sys.argv[sys.argv.index(name) + 1]
                if name in sys.argv else default)

    n = int(arg("--devices", "8"))
    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.environ.get(
        "MULTICHIP_OUT", os.path.join(here, "MULTICHIP_r09.json"))
    budget = float(os.environ.get("MULTICHIP_BUDGET_S", "2400"))
    _MC_OUT[0] = out_path

    import __graft_entry__ as graft

    env = dict(os.environ, MULTICHIP_OUT=out_path,
               MULTICHIP_BUDGET_S=str(budget - 60))
    if "--cpu" in sys.argv:
        graft.force_virtual_cpu_mesh(env, n)
    else:
        have = graft.accelerator_count()
        if have < n:
            raise SystemExit(
                f"bench.py --multichip --devices {n}: {have} accelerator "
                "chip(s) found; pass --cpu to measure the forced "
                f"{n}-device CPU mesh instead")

    signal.signal(signal.SIGTERM, _mc_signal_flush)
    signal.signal(signal.SIGINT, _mc_signal_flush)
    signal.signal(signal.SIGALRM, _mc_signal_flush)
    signal.alarm(int(budget) + 60)

    cmd = [sys.executable, os.path.abspath(__file__), "--multichip-child",
           "--devices", str(n)]
    child = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                             stderr=subprocess.PIPE, text=True, env=env,
                             cwd=here)
    _MC_CHILD[0] = child
    try:
        stdout, stderr = child.communicate(timeout=budget)
    except subprocess.TimeoutExpired:
        child.kill()
        child.communicate()
        return _mc_signal_flush(signal.SIGALRM, None)
    finally:
        _MC_CHILD[0] = None
    sys.stderr.write(graft.filter_known_noise(stderr))
    sys.stdout.write(stdout)
    sys.stdout.flush()
    if child.returncode != 0:
        raise SystemExit(f"multichip child failed rc={child.returncode}")


def main():
    if "--multichip-child" in sys.argv:
        if os.environ.get("BENCH_OVERLAP", "1") == "1":  # same A/B knob as
            from bert_pytorch_tpu.parallel.xla_flags import \
                apply_overlap_flags  # the single-chip candidates honor

            apply_overlap_flags()
        from bert_pytorch_tpu.compile_cache import enable_compile_cache

        enable_compile_cache()
        n = int(sys.argv[sys.argv.index("--devices") + 1]
                if "--devices" in sys.argv else 8)
        budget = os.environ.get("MULTICHIP_BUDGET_S")
        multichip_measure(n, out_path=os.environ.get("MULTICHIP_OUT"),
                          budget_s=float(budget) if budget else None)
        return
    if "--multichip" in sys.argv:
        return multichip_main()
    if "--packing-child" in sys.argv:
        def arg(name, default=None):
            return (sys.argv[sys.argv.index(name) + 1]
                    if name in sys.argv else default)

        result = run_packing_candidate(
            seq_len=int(arg("--seq", "128")),
            steps=int(arg("--steps", "8")),
            on_tpu="--cpu" not in sys.argv,
            packed=arg("--mode", "packed") == "packed",
            batch=int(arg("--batch", "16")),
        )
        print("BENCH_RESULT " + json.dumps(result), flush=True)
        return
    if "--child" in sys.argv:
        def arg(name, default=None):
            return (sys.argv[sys.argv.index(name) + 1]
                    if name in sys.argv else default)

        result = run_candidate(
            batch=int(arg("--batch")),
            seq_len=int(arg("--seq", "128")),
            steps=int(arg("--steps")),
            on_tpu="--cpu" not in sys.argv,
            attn=arg("--attn", "auto"),
            remat=arg("--remat", "none"),
            unroll=int(arg("--unroll", "1")),
            accum=int(arg("--accum", "1")),
            stacked=arg("--stacked", "1") == "1",
        )
        print("BENCH_RESULT " + json.dumps(result), flush=True)
        return

    budget = float(os.environ.get("BENCH_BUDGET_S", "2100"))
    DEADLINE[0] = time.time() + budget
    signal.signal(signal.SIGTERM, _signal_flush)
    signal.signal(signal.SIGINT, _signal_flush)
    signal.signal(signal.SIGALRM, _signal_flush)
    signal.alarm(int(budget) + 60)  # backstop if skip logic miscounts

    # The parent stays off the JAX backend (a process that touched it holds
    # the chip and the children could not attach), so the platform is
    # probed in a child. Anything but a TPU is an error unless the CPU
    # smoke was asked for by name.
    if "--cpu" in sys.argv:
        on_tpu = False
    else:
        import __graft_entry__ as graft

        platform, _ = graft.probe_devices()
        if platform != "tpu":
            raise SystemExit(
                f"bench.py: no TPU (jax platform {platform!r}); pass --cpu "
                "for the tiny CPU smoke of the harness")
        on_tpu = True
    ON_TPU[0] = on_tpu

    steps = 48 if on_tpu else 3
    if on_tpu:
        # known winners FIRST, across both grids: even a slow/flaky sweep
        # lands both headline numbers before any budget goes to exploration
        work = [(128, CANDIDATES_128[:1]), (512, CANDIDATES_512[:1]),
                (128, CANDIDATES_128[1:]), (512, CANDIDATES_512[1:])]
    else:
        work = [(128, [(8, "xla", "none", 1, 1, False)])]

    for seq_len, candidates in work:
        _measure_grid(seq_len, candidates, steps, on_tpu)
    # packed-vs-padded pair (round 9): measured after both headline grids
    # so a truncated sweep still lands them first. Phase-2 recipe on TPU
    # (seq 512 is where the flash kernel + block skipping carry the win);
    # the CPU smoke runs a tiny pair so the JSON field always exists.
    if on_tpu:
        _measure_packing_pair(512, steps=24, on_tpu=True, batch=16)
    else:
        _measure_packing_pair(128, steps=2, on_tpu=False, batch=4)
    for seq_len in sorted(BEST):
        print(f"# best seq{seq_len}: {BEST[seq_len]['_info']}",
              file=sys.stderr)

    if 128 not in BEST:
        raise SystemExit("no seq128 benchmark configuration measured")
    emit_final()
    if FAILED:
        raise SystemExit(f"bench.py: candidate(s) failed: {FAILED}")


if __name__ == "__main__":
    main()
