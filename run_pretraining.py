#!/usr/bin/env python
"""BERT/RoBERTa pretraining entry point, TPU-native.

Capability parity with the reference's run_pretraining.py (CLI surface
:70-167, setup :170-221, train loop :453-581) on the SPMD execution model:
no torch.distributed.launch fan-out, no DDP wrapper, no GradScaler — one
process per TPU-VM host, one jitted train step over a (data, fsdp, model,
seq) mesh, gradients reduced by compiler-inserted collectives over ICI.

Telemetry (bert_pytorch_tpu/telemetry/, docs/OBSERVABILITY.md): an in-graph
health pack (non-finite counts, grad-spike z-score, --nonfinite_action
policy), per-interval StepWatch records (step time, data-wait vs dispatch,
seq/s, tokens/s, MFU), compile counting with loud recompile warnings, HBM
snapshots, and provenance-stamped log headers.

Usage (mirrors the reference):
  python run_pretraining.py --config_file configs/bert_pretraining_phase1_config.json \
      --input_dir data/encoded/seq128 --output_dir results/phase1
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time
from pathlib import Path

import numpy as np


def parse_arguments(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    # Optional json run config overriding defaults (CLI > config > defaults,
    # reference run_pretraining.py:152-166)
    parser.add_argument("--config_file", default=None, type=str,
                        help="JSON run config overriding defaults")
    parser.add_argument("--input_dir", default=None, type=str,
                        help="dir containing .hdf5 shards")
    parser.add_argument("--output_dir", default=None, type=str,
                        help="dir for checkpoints and logs")
    parser.add_argument("--model_config_file", default=None, type=str,
                        help="BERT model config JSON")
    # dynamic masking (reference :86-91)
    parser.add_argument("--masked_token_fraction", type=float, default=0.2)
    parser.add_argument("--max_predictions_per_seq", type=int, default=80)
    parser.add_argument("--init_checkpoint", type=str, default="",
                        help="seed model weights (not optimizer state) from "
                             "an external checkpoint before step 0: a "
                             "reference torch save (ckpt_*.pt), a Google TF "
                             "release, or a framework orbax dir[@step]. "
                             "Ignored when output_dir already holds a "
                             "resumable checkpoint (auto-resume wins). The "
                             "migration path for continuing a GPU-pretrained "
                             "run on TPU, e.g. phase 2 from a reference "
                             "phase-1 ckpt_7038.pt")
    # training configuration (reference :93-108)
    parser.add_argument("--num_steps_per_checkpoint", type=int, default=200)
    parser.add_argument("--keep_checkpoints", type=int, default=3,
                        help="rolling checkpoint window size (reference kept "
                             "3, run_pretraining.py:513-516); raise to keep "
                             "intermediate checkpoints for finetune curves")
    parser.add_argument("--prefetch_batches", type=int, default=2,
                        help="host batches assembled ahead on an executor "
                             "thread (gather + dynamic masking overlap the "
                             "device step; 0 = assemble synchronously). The "
                             "reference used 4 DataLoader workers for the "
                             "same overlap (run_pretraining.py:384)")
    parser.add_argument("--steps_per_loop", type=int, default=1,
                        help="optimization steps per host dispatch: >1 runs "
                             "a device-side lax.fori_loop over that many "
                             "steps (host only feeds data / logs at loop "
                             "boundaries) — amortizes dispatch latency; "
                             "metrics are logged once per loop from its "
                             "final step (health/anomaly flags are "
                             "max-accumulated across the loop so nothing "
                             "is lost)")
    parser.add_argument("--skip_checkpoint", action="store_true")
    parser.add_argument("--checkpoint_activations", action="store_true")
    parser.add_argument("--log_prefix", type=str, default="logfile")
    parser.add_argument("--seed", type=int, default=42)
    # hyperparameters (reference :110-126)
    parser.add_argument("--learning_rate", default=5e-5, type=float)
    parser.add_argument("--lr_decay", default="poly", type=str,
                        choices=["poly", "linear", "cosine", "constant"])
    parser.add_argument("--warmup_proportion", default=0.01, type=float)
    parser.add_argument("--global_batch_size", default=2 ** 16, type=int)
    parser.add_argument("--local_batch_size", default=8, type=int,
                        help="per-data-shard microbatch size (reference: per-GPU)")
    parser.add_argument("--max_steps", default=1000, type=int)
    parser.add_argument("--steps", default=None, type=int,
                        help="steps to perform this session (default: to max_steps)")
    parser.add_argument("--previous_phase_end_step", default=0, type=int)
    # K-FAC (reference :128-144)
    parser.add_argument("--kfac", action="store_true", default=False)
    parser.add_argument("--kfac_inv_interval", type=int, default=10)
    parser.add_argument("--kfac_factor_interval", type=int, default=1)
    parser.add_argument("--kfac_stat_decay", type=float, default=0.95)
    parser.add_argument("--kfac_damping", type=float, default=0.003)
    parser.add_argument("--kfac_kl_clip", type=float, default=0.001)
    parser.add_argument("--kfac_stats_dtype", type=str, default="f32",
                        choices=["f32", "bf16"],
                        help="dtype of the per-microbatch K-FAC factor "
                             "STATISTICS on the wire (optim/kfac.py "
                             "stats_dtype): bf16 halves the factor-psum "
                             "bytes; the EMA accumulator and resting "
                             "factors stay f32 either way (the reduction "
                             "upcasts before summing). f32 is the exact "
                             "round-15 program, bit for bit")
    parser.add_argument("--kfac_skip_layers", nargs="+", type=str,
                        default=["cls_predictions", "embeddings"])
    # TPU-native knobs (no reference equivalent)
    parser.add_argument("--mesh", type=str, default="",
                        help="mesh axis sizes, e.g. 'data=8,fsdp=1,model=1,seq=1'; "
                             "empty = all devices on data")
    parser.add_argument("--dtype", type=str, default="bfloat16",
                        choices=["bfloat16", "float32"])
    parser.add_argument("--grad_dtype", type=str, default="auto",
                        choices=["auto", "bfloat16", "float32"],
                        help="gradient accumulation dtype; auto follows "
                             "--dtype (bf16 grads against fp32 masters, the "
                             "apex-O2-equivalent default)")
    parser.add_argument("--mask_token_index", type=int, default=None,
                        help="[MASK] id; default: looked up in vocab_file")
    parser.add_argument("--vocab_pad_multiple", type=int, default=128,
                        help="pad vocab for the MXU (reference padded to 8)")
    parser.add_argument("--optimizer", type=str, default="lamb",
                        choices=["lamb", "bert_adam", "fused_adam"])
    parser.add_argument("--profile_steps", type=str, default=None,
                        help="'start,stop' step range to capture a jax.profiler "
                             "trace. Host loop phases carry TraceAnnotations "
                             "(data_wait/data_prep/h2d/dispatch/metric_flush) "
                             "and the model is named_scope-annotated "
                             "(embeddings/attention/mlp/mlm_head), so the "
                             "trace maps time to code, not fused-op soup")
    # telemetry (docs/OBSERVABILITY.md)
    parser.add_argument("--log_freq", type=int, default=10,
                        help="optimization steps per StepWatch interval "
                             "record (tag 'perf': step_time_ms, seq_per_sec, "
                             "tokens_per_sec, MFU, data_wait/dispatch "
                             "breakdown, compile counts, HBM peak). Per-step "
                             "'train' records are unaffected")
    parser.add_argument("--health_pack", type=str, default="on",
                        choices=["on", "off"],
                        help="in-graph health pack (telemetry/health.py): "
                             "non-finite counts for loss and per-group "
                             "grads, grad-norm EMA + z-score spike flag, "
                             "param-norm drift — all returned through the "
                             "non-blocking metrics readback")
    parser.add_argument("--nonfinite_action", type=str, default="log",
                        choices=["log", "skip", "halt"],
                        help="policy when the health pack flags a non-finite "
                             "loss/grad step: 'log' warns loudly and trains "
                             "on; 'skip' drops the update IN-GRAPH (params/"
                             "optimizer state stay bit-identical — the host "
                             "only learns one step later, too late to "
                             "intervene); 'halt' stops the run after "
                             "logging. Requires --health_pack=on")
    parser.add_argument("--stacked_params", type=str, default="auto",
                        choices=["auto", "true", "false"],
                        help="encoder parameter layout: 'true' = one nn.scan "
                             "stack with a leading (L, ...) axis (O(1) "
                             "compile time), 'false' = per-layer modules "
                             "(no scan-wgrad dynamic-update-slice traffic "
                             "in backward — faster at BERT-Large when the "
                             "stack is fully unrolled anyway, O(L) compile "
                             "time). 'auto' keeps the model config's value. "
                             "Checkpoints resume across either choice "
                             "(layout converted losslessly on restore)")
    parser.add_argument("--zero1", type=str, default="auto",
                        choices=["auto", "true", "false"],
                        help="ZeRO-1 optimizer-state sharding over the data "
                             "mesh axis (parallel/zero.py): moments stored "
                             "1/N per chip, gradient reduce-scatter + "
                             "shard-local LAMB update + param all-gather — "
                             "the apex DistributedFusedLAMB analog. 'auto' "
                             "enables it whenever the data axis is >1; "
                             "checkpoints of sharded moments save/restore "
                             "transparently (orbax is sharding-native)")
    parser.add_argument("--zero1_overlap", action="store_true",
                        help="gather-on-use ZeRO-1 (requires --zero1): "
                             "params rest in the 1/N shard layout between "
                             "steps and are re-gathered leaf-by-leaf at the "
                             "point of use, so the all-gathers become "
                             "per-layer ops the latency-hiding scheduler "
                             "overlaps with forward compute instead of one "
                             "blocking constraint after the update. "
                             "Bit-identical values; only the collective "
                             "schedule changes")
    parser.add_argument("--zero1_rs", action="store_true",
                        help="reduce-scatter ZeRO-1 gradients (requires "
                             "--zero1; forces --zero1_overlap): the grad "
                             "tree exits the backward through psum_scatter "
                             "into the exact 1/N shard the update owns, "
                             "instead of a full all-reduce every device "
                             "then slices — half the gradient bytes on "
                             "the wire. Bit-identical values (pinned in "
                             "tests against the all-reduce arm of the "
                             "same program); needs a data-only mesh "
                             "(every non-data axis trivial)")
    parser.add_argument("--fsdp_overlap", action="store_true",
                        help="gather-on-use for fsdp-RESIDENT params "
                             "(parallel/zero.make_fsdp_plan): each param's "
                             "point-of-use all-gather becomes an explicit, "
                             "independent per-leaf node the latency-hiding "
                             "scheduler can interleave with forward compute "
                             "— instead of wherever (and fused however) "
                             "GSPMD implicitly re-materializes the leaf. "
                             "No-op when the mesh's fsdp axis is trivial; "
                             "with --zero1 it forces --zero1_overlap (the "
                             "resting layout must match the update's "
                             "output pin)")
    parser.add_argument("--mesh_config", type=str, default="auto",
                        choices=["auto", "production", "base"],
                        help="named feature config from the rules table "
                             "(parallel/rules.py CONFIG_OVERRIDES): "
                             "'production' turns on the collective-time "
                             "pack the mesh qualifies for — packing, "
                             "ZeRO-1 overlap (data>1), fsdp gather-on-use "
                             "(fsdp>1), ring attention (seq>1). "
                             "'auto' selects production on real "
                             "accelerators when the mesh has a non-trivial "
                             "parallel axis (forced-CPU harness meshes "
                             "keep 'base' so test programs only "
                             "change when asked); 'base' keeps every "
                             "feature at its own flag's default")
    parser.add_argument("--coalesce_reductions", type=str, default="off",
                        choices=["on", "off"],
                        help="bucket the cross-device reduction storm "
                             "(parallel/coalesce.py): LAMB per-tensor "
                             "trust norms, the pre-normalization global "
                             "norm and the logged grad_norm compile to a "
                             "handful of vector all-reduces instead of "
                             "two scalars per parameter leaf; with --kfac "
                             "the factor statistics reduce in "
                             "size-capped buckets too (--kfac_bucket_mb). "
                             "Values bit-identical for the norm paths; "
                             "K-FAC factor state allclose to the "
                             "per-site program (tests/test_kfac.py)")
    parser.add_argument("--kfac_bucket_mb", type=float, default=4.0,
                        help="bucket size cap (MB) for coalesced K-FAC "
                             "factor reductions (--coalesce_reductions); "
                             "the deterministic assignment is recorded in "
                             "the run header")
    parser.add_argument("--kfac_factor_sync_freq", type=int, default=1,
                        help="sync (reduce + EMA) K-FAC factor statistics "
                             "only every N steps — they are EMA-smoothed, "
                             "so off-steps skip the factor collectives "
                             "entirely under --coalesce_reductions. 1 "
                             "(default) compiles the exact legacy "
                             "program; parity at freq=1 is test-pinned")
    parser.add_argument("--h2d_prefetch", type=int, default=1,
                        help="batches kept device-resident ahead of dispatch "
                             "(data/sharded.py DevicePrefetcher): the next "
                             "batch's host->device transfer is issued before "
                             "the current step dispatches, so the copy rides "
                             "the wire under device compute and the h2d "
                             "StepWatch bucket measures only the issue. 0 "
                             "disables (synchronous put, the pre-round-11 "
                             "behavior). Ignored when --steps_per_loop>1 "
                             "(chunks already amortize the put)")
    parser.add_argument("--overlap_flags", type=str, default="on",
                        choices=["on", "off"],
                        help="apply the libtpu async-collective + "
                             "latency-hiding-scheduler flag pack "
                             "(parallel/xla_flags.py) so grad reduce-scatter "
                             "/ param all-gather overlap compute; no-op off "
                             "TPU. 'off' leaves LIBTPU_INIT_ARGS untouched")
    parser.add_argument("--rng_impl", type=str, default="threefry2x32",
                        choices=["rbg", "unsafe_rbg", "threefry2x32"],
                        help="PRNG for dropout keys. threefry (JAX default) "
                             "gives stable bit-streams across versions and "
                             "backends; pass 'rbg' for ~10%% faster steps on "
                             "v5e at the cost of that stability guarantee "
                             "(rbg streams are not version-portable)")
    parser.add_argument("--packing", action="store_true",
                        help="sequence packing (data/packing.py): assemble "
                             "each batch row from multiple short examples "
                             "with block-diagonal segment attention, "
                             "per-segment positions and per-segment NSP — "
                             "the padded FLOPs the perf record's "
                             "pad_fraction measures become real work. "
                             "Default off; resume-compatible (the packer "
                             "buffer checkpoints with the sampler cursor)")
    parser.add_argument("--packing_max_segments", type=int, default=8,
                        help="max examples packed into one row (bounds the "
                             "static per-segment NSP arrays)")
    parser.add_argument("--packing_lookahead", type=int, default=4,
                        help="batches of examples the packer may look ahead "
                             "when filling rows; higher = better packing "
                             "efficiency, more host RAM in flight")
    # flight recorder (docs/OBSERVABILITY.md "Postmortem debugging")
    parser.add_argument("--flight_recorder", type=str, default="on",
                        choices=["on", "off"],
                        help="black-box ring of the last --recorder_window "
                             "batches + RNG keys + metric records "
                             "(telemetry/flight_recorder.py); dumps a "
                             "self-contained repro bundle under "
                             "<output_dir>/repro_bundles when the health "
                             "pack flags a non-finite step or the process "
                             "dies (signal/exception). tools/replay.py "
                             "re-executes the offending step from the "
                             "bundle + the matching checkpoint")
    parser.add_argument("--recorder_window", type=int, default=8,
                        help="optimization steps of loader output the "
                             "flight recorder holds (host RAM bound: "
                             "window * host batch bytes). Replaying a bad "
                             "step needs a checkpoint at most this many "
                             "steps behind it — size against "
                             "--num_steps_per_checkpoint when full "
                             "replayability matters. Auto-raised to "
                             "2x --steps_per_loop (the metric readback "
                             "lags one dispatch)")
    parser.add_argument("--metrics_port", type=int, default=None,
                        help="serve live Prometheus-text /metrics and a "
                             "/healthz JSON (last step, last health-pack "
                             "flags, compile count) on this port while "
                             "the run is alive (telemetry/exporter.py; "
                             "0 = ephemeral port, logged at startup). "
                             "Default: off")
    parser.add_argument("--inject_nonfinite_step", type=int, default=None,
                        help="fault-injection drill: poison layer 0's "
                             "attention output kernel with one NaN at "
                             "exactly this global step (in-graph, "
                             "deterministic — replays from the bundle), "
                             "to fire-drill the alarm -> recorder -> "
                             "replay -> bisect pipeline on a real run")
    # streaming data plane (data/streaming.py, docs/DATA.md): tokenize raw
    # text on the fly instead of reading offline-encoded HDF5 shards
    parser.add_argument("--stream_dir", default=None, type=str,
                        help="STREAM MODE: directory (or glob) of raw .txt "
                             "corpus files (blank-line-delimited documents, "
                             "pipeline/format.py contract) tokenized on the "
                             "fly by a worker pool — no offline encode "
                             "cycle. Mutually exclusive with --input_dir. "
                             "Deterministic multi-host record sharding, "
                             "resumable checkpointed cursors (resume is "
                             "bit-identical, masks included), composes "
                             "with --packing / --prefetch_batches / "
                             "--h2d_prefetch unchanged")
    parser.add_argument("--stream_vocab", default=None, type=str,
                        help="vocab file for the streaming tokenizer "
                             "(default: the model config's vocab_file)")
    parser.add_argument("--stream_tokenizer", default="wordpiece", type=str,
                        choices=["wordpiece", "bpe"],
                        help="tokenizer family for stream mode (native C++ "
                             "encoder used automatically when built)")
    parser.add_argument("--stream_seq_len", default=128, type=int,
                        help="example length in stream mode (records chunk "
                             "into [CLS] + stream_seq_len-2 tokens + [SEP]); "
                             "the offline plane reads this off the shards "
                             "instead")
    parser.add_argument("--stream_workers", default=2, type=int,
                        help="tokenize worker threads; results are consumed "
                             "in submission order so worker count changes "
                             "pacing only, never the batch stream")
    parser.add_argument("--stream_queue_batches", default=4, type=int,
                        help="bounded example-queue depth in batches: full "
                             "queue stalls the tokenize workers (bounded "
                             "RAM), empty queue surfaces as the data_wait "
                             "StepWatch bucket; live depth exported as "
                             "bert_stream_queue_depth")
    parser.add_argument("--tensorboard", type=str, default="on",
                        choices=["on", "off"],
                        help="tensorboard metric sink. 'off' skips the "
                             "torch.utils.tensorboard import (~4s of "
                             "tensorflow/keras pulled in at startup) — "
                             "worth it for short-lived drill/CI sessions "
                             "where startup dominates")
    parser.add_argument("--force_cpu", action="store_true",
                        help="run on the CPU backend (CI/drill harness): "
                             "sets JAX_PLATFORMS=cpu before jax is "
                             "imported, like run_server.py")
    # resilience / survival kit (bert_pytorch_tpu/resilience/,
    # docs/RESILIENCE.md): preemption-safe checkpointing is always on
    # (SIGTERM -> emergency checkpoint of the last completed step);
    # these flags configure the watchdog and the chaos drills
    parser.add_argument("--watchdog_timeout", type=float, default=0.0,
                        help="hung-step watchdog (resilience/watchdog.py): "
                             "if any host phase (dispatch/readback/h2d/"
                             "checkpoint/data_wait) exceeds this many "
                             "seconds, dump all-thread stacks + a "
                             "flight-recorder bundle and act per "
                             "--watchdog_action. Device-side stalls exit "
                             "72 (device hang), data_wait stalls exit 73 "
                             "(input starvation) — tools/supervise.py "
                             "retries only the latter. 0 = off (default); "
                             "set to several multiples of your worst "
                             "legitimate step/checkpoint time")
    parser.add_argument("--watchdog_action", type=str, default="abort",
                        choices=["abort", "warn"],
                        help="on a watchdog trip: 'abort' hard-exits with "
                             "the distinct code (supervisor-friendly); "
                             "'warn' logs + dumps once per stall and "
                             "keeps waiting (drills, soak runs)")
    parser.add_argument("--chaos", type=str, default=None,
                        choices=["sigkill_at_step", "sigterm_at_step",
                                 "corrupt_newest_ckpt", "stall_dispatch"],
                        help="fault-injection drill (resilience/chaos.py): "
                             "SIGKILL/SIGTERM self before --chaos_step, "
                             "corrupt the newest checkpoint at the first "
                             "save boundary at/after it (then SIGKILL), "
                             "or stall the dispatch phase there. Fires "
                             "only in the first supervised incarnation "
                             "(BERT_SUPERVISOR_RESTARTS==0) so the "
                             "restarted run survives the drill")
    parser.add_argument("--chaos_step", type=int, default=None,
                        help="global step the --chaos fault fires at "
                             "(required with --chaos)")
    parser.add_argument("--chaos_stall_secs", type=float, default=3.0,
                        help="stall length for --chaos stall_dispatch "
                             "(pick > --watchdog_timeout to trip it)")
    parser.add_argument("--slo_config", type=str, default=None,
                        help="SLO spec file (configs/slo.json): evaluate "
                             "the train-phase specs (step-time ceiling, "
                             "checkpoint freshness, non-finite rate) live "
                             "through the burn-rate engine — alerts land "
                             "in the log + /healthz status when "
                             "--metrics_port is on (docs/OBSERVABILITY.md)")
    parser.add_argument("--slo_eval_interval_s", type=float, default=5.0,
                        help="burn-rate engine evaluation period")
    parser.add_argument("--slo_action", type=str, default="log",
                        choices=["log", "halt"],
                        help="on a sustained page-severity train SLO "
                             "breach: 'log' keeps going; 'halt' exits "
                             "with the DISTINCT code EXIT_SLO_BREACH (76) "
                             "— retryable, tools/supervise.py restarts it "
                             "(unlike 71/72 a fresh process often clears "
                             "a stuck input pipeline or straggler)")
    parser.add_argument("--slo_halt_after_s", type=float, default=60.0,
                        help="how long a page alert must stay firing "
                             "before --slo_action=halt pulls the plug")
    parser.add_argument("--stream_inject", default=None, type=str,
                        choices=["slow_producer", "corrupt_record",
                                 "worker_crash"],
                        help="streaming fault drill: slow_producer sleeps "
                             "in the workers (starves the consumer -> "
                             "data_wait), corrupt_record poisons every 7th "
                             "owned record (skipped-and-counted, "
                             "bert_stream_records_dropped_total), "
                             "worker_crash kills a tokenize task once per "
                             "5th record (detected + restarted with its "
                             "cursor intact — the stream stays "
                             "bit-identical)")

    from bert_pytorch_tpu.config import merge_args_with_config

    args = merge_args_with_config(parser, argv)
    validate_stream_args(parser, args, argv)
    if args.chaos and args.chaos_step is None:
        parser.error("--chaos requires --chaos_step (the global step the "
                     "fault fires at)")
    return args


# stream flags that only make sense with --stream_dir; a half-configured
# CLI mix fails at argparse time, not deep inside the loader (satellite:
# CLI validation bugfix)
_STREAM_DEPENDENT_FLAGS = ("stream_vocab", "stream_tokenizer",
                           "stream_seq_len", "stream_workers",
                           "stream_queue_batches", "stream_inject")


def validate_stream_args(parser, args, argv=None) -> None:
    """Argparse-time validation of the stream/offline mode split: the two
    planes' flags must conflict loudly, not fail deep in the loader.

    Explicit-flag detection shares config.explicit_cli_keys with the
    CLI-wins config merge (value-vs-default comparison would miss an
    explicitly-passed default and misreport run-config keys as CLI
    flags). Run-config JSON keys for the OTHER plane are deliberately
    tolerated — a shared config may carry settings for both planes; an
    explicit CLI mode flag overrides the config's plane, and only an
    unresolvable mix (both modes from the same precedence level) errors."""
    from bert_pytorch_tpu.config import explicit_cli_keys

    explicit = None  # computed at most once, only when needed

    def cli(flag: str) -> bool:
        nonlocal explicit
        if explicit is None:
            explicit = explicit_cli_keys(parser, argv)
        return flag in explicit

    if args.stream_dir and args.input_dir:
        # an explicit CLI plane choice beats a config-sourced one (the
        # CLI-wins precedence the config merge already implements)
        if cli("stream_dir") and not cli("input_dir"):
            args.input_dir = None
        elif cli("input_dir") and not cli("stream_dir"):
            args.stream_dir = None
        else:
            parser.error(
                "--stream_dir (streaming plane) and --input_dir (offline "
                "sharded-HDF5 plane) are mutually exclusive — pick one "
                "data plane per run")
    if not args.stream_dir:
        stray = [f for f in _STREAM_DEPENDENT_FLAGS if cli(f)]
        if stray:
            parser.error(
                "--" + " --".join(sorted(stray)) + " require --stream_dir "
                "(they configure the streaming plane; --input_dir reads "
                "offline shards and ignores them)")


def parse_mesh_arg(mesh_arg: str):
    if not mesh_arg:
        return None
    out = {}
    for part in mesh_arg.split(","):
        k, v = part.split("=")
        out[k.strip()] = int(v)
    return out


def find_mask_token_index(args, config) -> int:
    if args.mask_token_index is not None:
        return args.mask_token_index
    # stream_vocab is consulted ONLY in stream mode: an offline run whose
    # shared run-config carries a streaming vocab must keep reading the
    # [MASK] id of the vocab its shards were encoded with
    stream_vocab = (getattr(args, "stream_vocab", None)
                    if getattr(args, "stream_dir", None) else None)
    vocab_file = stream_vocab or getattr(config, "vocab_file", None)
    if vocab_file and os.path.exists(vocab_file):
        from bert_pytorch_tpu.data.tokenization import load_vocab

        vocab = load_vocab(vocab_file)
        if "[MASK]" in vocab:
            return vocab["[MASK]"]
        if "<mask>" in vocab:
            return vocab["<mask>"]
    return 103  # [MASK] in the standard BERT vocab


class NonFiniteHalt(RuntimeError):
    """--nonfinite_action=halt tripped: a non-finite loss/gradient step was
    flagged by the in-graph health pack."""


class SLOBreachHalt(RuntimeError):
    """--slo_action=halt tripped: a page-severity train SLO stayed firing
    past --slo_halt_after_s. Exits EXIT_SLO_BREACH (76) — retryable."""


def make_optimizer(name: str, schedule, norm_reducer=None):
    """The pretraining optimizer zoo, keyed by --optimizer. Module-level so
    tools/replay.py rebuilds the exact same transformation chain from a
    flight-recorder manifest — one construction site, no drift.
    `norm_reducer` (parallel/coalesce.NormReducer, --coalesce_reductions)
    buckets LAMB's trust-norm/global-norm all-reduces; the other
    optimizers have no per-tensor norms to coalesce."""
    from bert_pytorch_tpu.optim import adam
    from bert_pytorch_tpu.optim.lamb import (lamb,
                                             default_weight_decay_mask,
                                             default_trust_batch_axes)

    if name == "lamb":
        return lamb(schedule, weight_decay=0.01,
                    weight_decay_mask=default_weight_decay_mask,
                    trust_batch_axes=default_trust_batch_axes,
                    norm_reducer=norm_reducer)
    if name == "bert_adam":
        return adam.bert_adam(schedule, weight_decay=0.01,
                              weight_decay_mask=default_weight_decay_mask)
    return adam.fused_adam(schedule)


def main(argv=None):
    entered = time.perf_counter()   # the set-up account starts here
    args = parse_arguments(argv)
    if not (args.input_dir or args.stream_dir) or not args.output_dir:
        raise SystemExit("--output_dir and one data plane (--input_dir for "
                         "offline shards, --stream_dir for raw-text "
                         "streaming) are required")

    # must land in the env before the first backend touch (libtpu reads
    # LIBTPU_INIT_ARGS once, at initialization)
    overlap_added = []
    if args.overlap_flags == "on":
        from bert_pytorch_tpu.parallel.xla_flags import apply_overlap_flags

        overlap_added = apply_overlap_flags()

    if args.force_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_default_prng_impl", args.rng_impl)
    import jax.numpy as jnp

    from bert_pytorch_tpu.compile_cache import enable_compile_cache
    from bert_pytorch_tpu.config import load_model_config, pad_vocab_size
    from bert_pytorch_tpu.data.sharded import (
        HostShardSampler, PretrainingDataLoader, ShardIndex)
    from bert_pytorch_tpu.models.families import family_name, family_of
    from bert_pytorch_tpu.optim import schedulers
    from bert_pytorch_tpu.parallel import dist, mesh as mesh_lib
    from bert_pytorch_tpu.telemetry import (
        HealthConfig, SetupWatch, collect_provenance,
        hbm_snapshot, device_peak_flops, init_run, init_telemetry_state)
    from bert_pytorch_tpu.resilience import ChaosMonkey, PreemptionGuard
    from bert_pytorch_tpu.resilience.preemption import (emergency_save,
                                                        is_preemption_exit)
    from bert_pytorch_tpu.resilience.watchdog import arm_watchdog
    from bert_pytorch_tpu.training import (
        CheckpointManager, build_pretrain_step, make_sharded_state)
    from bert_pytorch_tpu.analysis.hlo import stale_scopes_warning
    from bert_pytorch_tpu.training.pretrain import (StepProgram,
                                                    stack_microbatches,
                                                    chain_steps,
                                                    resolve_remat_policy,
                                                    step_subscopes)

    # set-up spans (telemetry/stepwatch.SetupWatch): backend | data | state
    # | lower | first_step, closed by the first step's loss on the host;
    # 'backend' has been open since main()'s entry
    setup = SetupWatch(start=entered)
    compile_cache_dir = enable_compile_cache()
    dist.initialize()
    np.random.seed(args.seed + dist.get_rank())

    mesh = mesh_lib.make_mesh(parse_mesh_arg(args.mesh))
    data_shards = mesh_lib.data_shard_count(mesh)
    n_hosts = dist.get_world_size()

    # accumulation math (reference :208-218): global batch realized as
    # accum_steps microbatches of local_batch per data shard
    micro_global = args.local_batch_size * data_shards
    accum_steps = max(1, math.ceil(args.global_batch_size / micro_global))
    host_step_batch = accum_steps * micro_global // n_hosts

    os.makedirs(args.output_dir, exist_ok=True)
    # ONE telemetry wiring path (telemetry/run.py): logger + compile watch
    # + registry (+ /metrics server and the multi-host perf fold when
    # enabled) come from init_run — the same call run_finetune/run_server
    # make, so every phase emits identically-shaped records
    tel = init_run(
        phase="pretrain",
        log_prefix=os.path.join(args.output_dir, args.log_prefix),
        verbose=dist.is_main_process(),
        tensorboard=(args.tensorboard == "on"), jsonl=True,
        metrics_port=args.metrics_port,
        multihost_dir=(os.path.join(args.output_dir, "metrics_hosts")
                       if n_hosts > 1 else None),
        process_index=dist.get_rank(), process_count=n_hosts)
    tel.attach_setup(setup)
    logger = tel.logger
    compile_watch = tel.compile_watch
    # every resource created below is released in the finally block, on the
    # success AND exception paths (logger/trace/loader/manager leak fix)
    loader = manager = recorder = None
    crash_flush = None  # bound once the loop-scope pieces exist
    emergency_ckpt = None  # bound once state/manager exist (preemption)
    guard = watchdog = slo_eval = None
    trace_active = False
    try:
        prov = collect_provenance(mesh=mesh)
        tel.log_header(**prov)
        logger.info(f"devices={jax.device_count()} hosts={n_hosts} "
                    f"mesh={dict(mesh.shape)} accumulation_steps={accum_steps} "
                    f"effective_global_batch={accum_steps * micro_global}")
        logger.info(f"compile cache: {compile_cache_dir}")
        # -- named mesh config (parallel/rules.py CONFIG_OVERRIDES) ---------
        # 'production' = the round-15 collective-time pack; 'auto' selects
        # it on real accelerators whenever the mesh has a non-trivial
        # parallel axis. Forced-CPU meshes (the test harness) stay on
        # 'base' under auto so harness programs only change when asked.
        from bert_pytorch_tpu.parallel import rules as rules_lib

        production = (args.mesh_config == "production"
                      or (args.mesh_config == "auto"
                          and jax.devices()[0].platform != "cpu"
                          and rules_lib.production_qualifies(mesh)))
        mesh_config_name = (rules_lib.PRODUCTION_CONFIG if production
                            else rules_lib.mesh_config(mesh))
        prod_features = {}
        if production:
            prod_features = rules_lib.production_features(mesh)
            if prod_features["packing"] and not args.packing:
                args.packing = True
            if prod_features["zero1"] and args.zero1 == "auto":
                args.zero1 = "true"
            if prod_features["zero1_overlap"] and args.zero1 != "false":
                args.zero1_overlap = True
            if prod_features["fsdp_overlap"]:
                args.fsdp_overlap = True
            logger.info(
                "mesh_config=production: "
                + " ".join(f"{k}={'on' if v else 'off'}"
                           for k, v in sorted(prod_features.items())))

        use_zero1 = (args.zero1 == "true"
                     or (args.zero1 == "auto" and mesh.shape["data"] > 1))
        zero1_overlap = bool(args.zero1_overlap) and use_zero1
        if args.zero1_overlap and not use_zero1:
            logger.info("WARNING: --zero1_overlap ignored (--zero1 is off "
                        "or the data axis is trivial)")
        fsdp_overlap = bool(args.fsdp_overlap) and mesh.shape["fsdp"] > 1
        if args.fsdp_overlap and not fsdp_overlap:
            logger.info("WARNING: --fsdp_overlap ignored (the mesh's fsdp "
                        "axis is trivial)")
        if fsdp_overlap and use_zero1 and not zero1_overlap:
            # the combined plan's post-update pin leaves params in the
            # data-appended shard layout — the resting layout must match,
            # which is exactly what --zero1_overlap constructs
            zero1_overlap = True
            logger.info("--fsdp_overlap with --zero1 forces "
                        "--zero1_overlap (resting layout must match the "
                        "update's output pin)")
        zero1_rs = bool(args.zero1_rs) and use_zero1
        if args.zero1_rs and not use_zero1:
            logger.info("WARNING: --zero1_rs ignored (--zero1 is off or "
                        "the data axis is trivial)")
        if zero1_rs:
            from bert_pytorch_tpu.parallel.zero import rs_supported

            if not rs_supported(mesh):
                # an explicit perf flag on a mesh it cannot serve is a
                # config error, not something to silently fall back from
                raise SystemExit(
                    "--zero1_rs needs a data-only mesh (every non-data "
                    f"axis trivial); got {dict(mesh.shape)} — drop the "
                    "flag or reshape the mesh")
            if not zero1_overlap:
                # the shard_map region consumes replicated params and
                # emits SHARDED grads: the params must rest sharded and
                # gather at point of use, which is the overlap layout
                zero1_overlap = True
                logger.info("--zero1_rs forces --zero1_overlap (the "
                            "scattered grad lands in the shard the "
                            "update owns; params must rest sharded)")
        coalesce = args.coalesce_reductions == "on"
        if zero1_rs and args.kfac and not coalesce:
            # the rs shard_map region emits PARTIAL factor statistics
            # only the bucketed reducer knows how to consume
            coalesce = True
            logger.info("--zero1_rs with --kfac forces "
                        "--coalesce_reductions on (factor statistics "
                        "leave the shard_map region as per-device "
                        "partials; the bucketed psum completes them)")
        if overlap_added:
            logger.info("overlap flag pack applied to LIBTPU_INIT_ARGS: "
                        + " ".join(overlap_added))
        health_cfg = (HealthConfig(action=args.nonfinite_action)
                      if args.health_pack == "on" else None)
        if health_cfg is None and args.nonfinite_action != "log":
            raise SystemExit(
                f"--nonfinite_action={args.nonfinite_action} requires "
                "--health_pack=on")

        # -- model config --------------------------------------------------
        if not args.model_config_file:
            raise SystemExit("--model_config_file (or run config) required")
        # the family is the config's `model_type`; an unknown one, or a key
        # the family does not know, is refused (never trimmed to a BERT)
        try:
            config = load_model_config(args.model_config_file)
        except ValueError as e:
            raise SystemExit(f"--model_config_file: {e}")
        # everything else this function chooses by family (BERT: MLM +
        # NSP; a decoder family: causal LM over packed rows) is in this one
        # record (models/families.py)
        family = family_of(config)
        refusal = family.refusal(args)
        if refusal:
            raise SystemExit(refusal)
        config = config.replace(
            vocab_size=pad_vocab_size(config.vocab_size,
                                      args.vocab_pad_multiple),
            dtype=args.dtype,
            checkpoint_activations=args.checkpoint_activations)
        if args.stacked_params != "auto":
            config = config.replace(
                stacked_params=(args.stacked_params == "true"))
        compute_dtype = (jnp.bfloat16 if args.dtype == "bfloat16"
                         else jnp.float32)
        grad_dtype_name = (args.dtype if args.grad_dtype == "auto"
                           else args.grad_dtype)
        grad_dtype = (jnp.bfloat16 if grad_dtype_name == "bfloat16"
                      else None)

        def make_model(config):
            return family.make_model(config, compute_dtype)

        model = make_model(config)

        # -- optimizer + schedule ------------------------------------------
        schedule = schedulers.make_schedule(
            args.lr_decay, args.learning_rate, args.max_steps,
            warmup=args.warmup_proportion,
            offset=args.previous_phase_end_step)
        tx = make_optimizer(args.optimizer, schedule)

        kfac = None
        if args.kfac:
            from bert_pytorch_tpu.optim.kfac import KFAC, KFACConfig

            # K-FAC + activation checkpointing compose: sow/perturb taps
            # under nn.remat re-fire during the recomputed forward, producing
            # factors identical to the un-rematted run (verified bit-exact in
            # tests/test_kfac.py::test_kfac_taps_under_remat); the reference
            # likewise ran both together (run_pretraining.py:257-258,311-345)
            config = config.replace(kfac_taps=True)
            model = make_model(config)
            # mesh=... -> distributed factor/inverse ownership: each device
            # stores and inverts only its slice of the layer-stacked factors
            # (the reference's HYBRID_OPT work partitioning,
            # run_pretraining.py:325-327); single-device meshes keep the
            # replicated layout (nothing to distribute)
            kfac = KFAC(KFACConfig(
                inv_interval=args.kfac_inv_interval,
                factor_interval=args.kfac_factor_interval,
                stat_decay=args.kfac_stat_decay,
                damping=args.kfac_damping,
                kl_clip=args.kfac_kl_clip,
                skip_layers=tuple(args.kfac_skip_layers),
                learning_rate=schedule,
                # --kfac_stats_dtype bf16: per-microbatch statistics thin
                # on the wire; the EMA/resting factors stay factor_dtype
                stats_dtype=(jnp.bfloat16
                             if args.kfac_stats_dtype == "bf16" else None)),
                mesh=mesh if data_shards > 1 else None,
                # --coalesce_reductions: factor statistics reduce in
                # size-capped buckets (one psum per bucket) instead of
                # one all-reduce per factor; assignment logged below
                factor_bucket_bytes=(int(args.kfac_bucket_mb * 2 ** 20)
                                     if coalesce else None),
                factor_sync_freq=args.kfac_factor_sync_freq)

        # -- dataset --------------------------------------------------------
        setup.end("backend")
        setup.begin("data")
        mask_id = find_mask_token_index(args, config)
        if args.stream_dir:
            # streaming plane (data/streaming.py, docs/DATA.md): raw text
            # tokenized on the fly; the rest of the loop — prefetch
            # executor, DevicePrefetcher/--h2d_prefetch staging, packing,
            # flight-recorder tap, checkpointed cursor — is byte-for-byte
            # the offline path's, by the shared loader interface
            from bert_pytorch_tpu.data.streaming import (
                StreamingPretrainingLoader, discover_sources,
                resolve_mask_id)
            from bert_pytorch_tpu.data.tokenization import TOKENIZERS

            sources = discover_sources(args.stream_dir)
            if not sources:
                raise SystemExit(f"no .txt corpus under {args.stream_dir}")
            vocab_path = (args.stream_vocab
                          or getattr(config, "vocab_file", None))
            if not vocab_path or not os.path.exists(vocab_path):
                raise SystemExit(
                    "stream mode needs a tokenizer vocab: pass "
                    "--stream_vocab or set vocab_file in the model config")
            tokenizer = TOKENIZERS[args.stream_tokenizer](vocab_path)
            if args.mask_token_index is None:
                # the tokenizer is the authority in stream mode: a BPE
                # .json vocab's <mask> is invisible to the line-based
                # find_mask_token_index lookup
                tokenizer_mask = resolve_mask_id(tokenizer)
                if tokenizer_mask is not None:
                    mask_id = tokenizer_mask
            loader = StreamingPretrainingLoader(
                sources, tokenizer, batch_size=host_step_batch,
                seq_len=args.stream_seq_len,
                mask_token_index=mask_id,
                max_pred_per_seq=args.max_predictions_per_seq,
                masked_lm_prob=args.masked_token_fraction,
                vocab_size=config.vocab_size, seed=args.seed,
                world_size=n_hosts, rank=dist.get_rank(),
                num_workers=args.stream_workers,
                queue_batches=args.stream_queue_batches,
                prefetch_batches=max(0, args.prefetch_batches),
                packing=args.packing,
                packing_max_segments=args.packing_max_segments,
                packing_lookahead=args.packing_lookahead,
                registry=tel.registry, inject=args.stream_inject)
            # /healthz names the plane's live cursor (telemetry/run.py)
            tel.attach_stream(loader)
            logger.info(
                f"dataset: STREAMING {len(sources)} raw-text sources "
                f"(hash {loader.sources_hash}), {args.stream_workers} "
                f"tokenize workers, seq {args.stream_seq_len}, host step "
                f"batch {host_step_batch}; [MASK]={mask_id}"
                + (f"; packing on (<= {args.packing_max_segments} "
                   "segments/row)" if args.packing else "")
                + (f"; FAULT INJECTION: {args.stream_inject}"
                   if args.stream_inject else ""))
        else:
            files = sorted(str(p)
                           for p in Path(args.input_dir).rglob("*.hdf5"))
            if not files:
                raise SystemExit(f"no .hdf5 shards under {args.input_dir}")
            index = ShardIndex(files)
            sampler = HostShardSampler(len(index), world_size=n_hosts,
                                       rank=dist.get_rank(), seed=args.seed)
            loader = PretrainingDataLoader(
                index, sampler, batch_size=host_step_batch,
                mask_token_index=mask_id,
                max_pred_per_seq=args.max_predictions_per_seq,
                masked_lm_prob=args.masked_token_fraction,
                vocab_size=config.vocab_size,
                seed=args.seed + dist.get_rank(),
                prefetch_batches=max(0, args.prefetch_batches),
                packing=args.packing,
                packing_max_segments=args.packing_max_segments,
                packing_lookahead=args.packing_lookahead,
                objective=family.objective)
            logger.info(f"dataset: {len(index)} samples in "
                        f"{len(index.files)} shards; host step batch "
                        f"{host_step_batch}; [MASK]={mask_id}"
                        + (f"; packing on (<= {args.packing_max_segments} "
                           "segments/row)" if args.packing else ""))

        # -- state: fresh or auto-resume (reference :236-255) ---------------
        sample = next(iter(loader))
        # peeked one batch for shapes; rewind through the LOADER so any
        # batches the prefetch executor assembled ahead are drained, not
        # replayed stale (pending=() also clears the packer's carry buffer)
        if args.stream_dir:
            loader.load_state_dict(loader.initial_state())
        else:
            loader.load_state_dict(dict(loader.state_dict(), index=0,
                                        pending=()))
        stacked = stack_microbatches(sample, accum_steps)
        seq_len = int(np.asarray(sample["input_ids"]).shape[-1])
        setup.end("data")
        setup.begin("state")

        # gathered-MLM-head budget: a packed row pools several examples'
        # masked positions, so the per-ROW cap grows beyond the per-example
        # --max_predictions_per_seq. Each example contributes at most
        # min(max_pred, floor(len * fraction)) + 1 (the masker's >=1 floor),
        # so the row total is bounded by floor(S * fraction) + segments and
        # by segments * max_pred; mlm_dropped warns loudly if reality ever
        # exceeds this.
        max_pred_row = args.max_predictions_per_seq
        if args.packing and family.mlm_head:
            max_pred_row = min(
                seq_len,
                args.packing_max_segments * args.max_predictions_per_seq,
                int(seq_len * args.masked_token_fraction)
                + args.packing_max_segments)
            logger.info(f"packing: gathered MLM head scores up to "
                        f"{max_pred_row} positions/row "
                        f"(per-example cap {args.max_predictions_per_seq})")

        def init_fn(rng):
            return model.init(rng, *family.init_inputs(
                {k: v[0] for k, v in stacked.items()}))

        ckpt_dir = os.path.join(args.output_dir, "pretrain_ckpts")
        manager = CheckpointManager(ckpt_dir,
                                    max_to_keep=args.keep_checkpoints,
                                    registry=tel.registry, log=logger.info)
        # every integrity sidecar carries the provenance stamp (and the
        # program fingerprint once the first dispatch's HLO parse lands)
        manager.manifest_context["provenance"] = prov
        # /healthz gains last_checkpoint_step + seconds_since_checkpoint
        tel.attach_checkpoints(manager)

        # the production config resolves its rule rows through the table's
        # named entry (identical to base today — the name is what carries
        # the feature pack); construction and the sharding_rules gate read
        # the same resolution
        resolved_rules = (rules_lib.resolve(
            mesh, config=rules_lib.PRODUCTION_CONFIG) if production
            else None)
        with mesh_lib.logical_rules():
            state, shardings = make_sharded_state(
                jax.random.PRNGKey(args.seed), init_fn, tx, mesh=mesh,
                rules=resolved_rules,
                zero1=use_zero1, zero1_params=zero1_overlap)

        zero1_plan = None
        if use_zero1:
            from bert_pytorch_tpu.parallel.zero import make_zero1_plan

            zero1_plan = make_zero1_plan(state.params, shardings.params,
                                         mesh, gather_on_use=zero1_overlap,
                                         reduce_scatter=zero1_rs)
            if zero1_plan is None:
                logger.info("zero1: nothing shardable over the data axis; "
                            "running the replicated update")
            else:
                logger.info(f"zero1: LAMB state sharded "
                            f"{mesh.shape['data']}-way over the data axis "
                            + ("(psum_scatter grads -> shard-local update "
                               "-> per-leaf gather-on-use next step "
                               "(--zero1_rs))" if zero1_rs else
                               "(reduce-scatter -> shard-local update -> "
                               + ("per-leaf gather-on-use next step "
                                  "(--zero1_overlap)" if zero1_overlap
                                  else "all-gather)")))
                # the silent-skip bugfix: leaves the derivation left
                # replicated are warned about by make_zero1_plan and
                # counted on the live registry so a layout regression
                # shows on /metrics, not just in a log scrollback
                tel.registry.gauge(
                    "bert_zero1_replicated_leaves",
                    "param leaves the ZeRO-1 spec derivation left on "
                    "their base layout (divisibility fallback)").set(
                        len(zero1_plan.replicated_leaves))

        from bert_pytorch_tpu.parallel.zero import placement_bytes

        # what sits where, read back from the arrays themselves (bytes per
        # device id): the proof that a sharded state is not "everything on
        # device 0" (chip_smoke.py --chips 4 reads this line)
        logger.info("state placement: " + json.dumps({
            "params": placement_bytes(state.params),
            "opt_state": placement_bytes(state.opt_state)}))

        plan = zero1_plan
        if fsdp_overlap:
            from bert_pytorch_tpu.parallel.zero import make_fsdp_plan

            fplan = make_fsdp_plan(state.params, shardings.params, mesh,
                                   zero1=zero1_plan is not None,
                                   warn_skipped=False)
            if fplan is None:
                logger.info("fsdp_overlap: nothing fsdp-sharded; keeping "
                            "the implicit layout")
            else:
                plan = fplan
                logger.info(
                    f"fsdp_overlap: per-leaf gather-on-use over the "
                    f"{mesh.shape['fsdp']}-way fsdp axis"
                    + (" composed with the zero1 overlap"
                       if zero1_plan is not None else ""))

        norm_reducer = None
        if coalesce and plan is not None:
            from bert_pytorch_tpu.parallel.coalesce import NormReducer

            norm_reducer = NormReducer(plan.grad_shardings, mesh)
            # rebuild the optimizer with the reducer: init semantics are
            # identical (the state above restores/donates unchanged),
            # only the update's norm reductions re-route
            tx = make_optimizer(args.optimizer, schedule,
                                norm_reducer=norm_reducer)
            logger.info("coalesce_reductions: trust-norm/global-norm "
                        "all-reduces bucketed (parallel/coalesce.py)")
        elif coalesce and kfac is not None and kfac.bucketed:
            # no sharded param layout to bucket norms over, but the K-FAC
            # factor psums (constructed above with factor_bucket_bytes)
            # ARE bucketed — say exactly that, never "ignored"
            logger.info("coalesce_reductions: K-FAC factor reductions "
                        "bucketed; trust norms stay per-tensor (no "
                        "sharded param layout to bucket)")
        elif coalesce:
            logger.info("WARNING: --coalesce_reductions has nothing to "
                        "bucket (no sharded layout, no bucketed K-FAC "
                        "— single-axis mesh?)")

        if kfac is not None:
            from bert_pytorch_tpu.training import init_kfac_state
            from bert_pytorch_tpu.training.pretrain import \
                build_kfac_pretrain_step

            state, pert_template = init_kfac_state(
                model, kfac, state,
                (stacked["input_ids"][0], stacked["token_type_ids"][0],
                 stacked["attention_mask"][0]))

        def build_step(model):
            # gathered MLM head: score only the <=max_predictions_per_seq
            # masked positions (the loader caps masking there, so the loss
            # is exact)
            common = dict(
                schedule=schedule, accum_steps=accum_steps,
                max_predictions=max_pred_row if family.mlm_head else None,
                grad_dtype=grad_dtype, zero1=plan, health=health_cfg,
                nan_inject_step=args.inject_nonfinite_step,
                norm_reducer=norm_reducer)
            if kfac is not None:
                return build_kfac_pretrain_step(model, tx, kfac,
                                                pert_template, **common)
            return build_pretrain_step(model, tx, **common,
                                       **family.step_kwargs)

        epoch = 0
        if manager.latest_step() is not None:
            abstract = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding),
                state)
            # tolerant of checkpoints written under the other encoder layout
            # (--stacked_params flipped mid-run): converted bit-exact on
            # restore. A torn/corrupt/digest-mismatched newest checkpoint
            # is quarantined (step_N.corrupt, loud warning naming the
            # failed item) and the walk falls back newest->oldest
            # (resilience/manifest.py) instead of crashing auto-resume
            state, extra, resumed = manager.restore_with_fallback(abstract)
            epoch = extra.get("epoch", 0)
            if "sampler" in extra:
                loader.load_state_dict(extra["sampler"])
            logger.info(f"auto-resumed from step {resumed}")
        elif args.init_checkpoint:
            # seed weights from an external checkpoint (reference ckpt_*.pt /
            # TF release / orbax dir) — optimizer state and step stay fresh;
            # missing/mismatched subtrees keep their fresh init and are
            # reported
            from run_squad import load_pretrained_params

            state = state.replace(params=load_pretrained_params(
                args.init_checkpoint, state.params, log=logger.info))

        if health_cfg is not None:
            # the EMA carry is attached AFTER restore and stripped before
            # every save: checkpoints never contain it, so their structure
            # is identical with the pack on or off (state.py contract)
            state = state.replace(telemetry=init_telemetry_state())

        setup.end("state")
        # StepProgram = jit + explicit first-dispatch lower/compile: same
        # one XLA compile, but the executable's HLO stays reachable for
        # the program fingerprint below (and tools/graphcheck.py gates the
        # same builders' compiled structure in CI)
        if config.checkpoint_activations and config.remat_policy == "auto":
            # what the rematted layer saves is decided here, once, from the
            # compiled step's memory against the device's: the program
            # that fits is the one the loop runs, compiled already
            def program_for(policy):
                return StepProgram(build_step(make_model(
                    config.replace(remat_policy=policy))))

            with mesh, mesh_lib.logical_rules(), setup.span("lower"):
                policy, jit_step = resolve_remat_policy(
                    program_for,
                    # a batch and a key placed as the loop places them
                    (state, mesh_lib.host_to_device_batch(mesh, stacked),
                     jax.random.fold_in(jax.random.PRNGKey(args.seed), 1)),
                    hbm_snapshot().get("hbm_bytes_limit"), log=logger.info)
            config = config.replace(remat_policy=policy)
            model = make_model(config)
        else:
            jit_step = StepProgram(build_step(model))
        remat_name = (config.remat_policy if config.checkpoint_activations
                      else "off")
        logger.info(f"activation checkpointing: remat_policy={remat_name}")
        # set once: whether the rematted layer keeps projection outputs
        # (models/bert.DENSE_SAVED), and (after the first dispatch) the
        # compiler's peak of the step
        step_fields = {"remat_saves_dense": int(remat_name == "dense"),
                       "step_peak_bytes": 0}
        steps_per_loop = max(1, args.steps_per_loop)
        jit_chunk = (StepProgram(chain_steps(build_step(model),
                                             steps_per_loop,
                                             per_step_batch=True))
                     if steps_per_loop > 1 else None)
        if kfac is not None and kfac.bucket_assignment is not None:
            logger.info("kfac: bucketed factor reductions — "
                        f"{len(kfac.bucket_assignment)} bucket(s): "
                        + json.dumps(kfac.bucket_assignment))

        # -- double-buffered h2d (round 11) ---------------------------------
        # DevicePrefetcher keeps the next batch's device_put in flight while
        # the current step computes; with --steps_per_loop>1 the whole-chunk
        # put already amortizes across n steps, so prefetch stays off there.
        h2d_depth = max(0, args.h2d_prefetch)
        use_h2d_prefetch = h2d_depth > 0 and steps_per_loop == 1
        if h2d_depth > 0 and not use_h2d_prefetch:
            logger.info("h2d prefetch: off (--steps_per_loop>1 stages whole "
                        "chunks; the per-chunk put already amortizes)")
        elif use_h2d_prefetch:
            logger.info(f"h2d prefetch: depth {h2d_depth} (next batch put to "
                        "device before the current step dispatches)")
        pf_holder = [None]  # the live DevicePrefetcher, per epoch

        def sampler_state():
            """Loader state as of the last batch the STEP LOOP consumed —
            under prefetch the loader itself runs ahead, so checkpoints
            must read the prefetcher's lagged snapshot, not the loader."""
            pf = pf_holder[0]
            return (pf.state_dict() if pf is not None
                    else loader.state_dict())

        target_step = args.previous_phase_end_step + args.max_steps
        session_limit = (int(state.step) + args.steps
                         if args.steps is not None else target_step)
        profile_range = None
        if args.profile_steps:
            lo, hi = args.profile_steps.split(",")
            profile_range = (int(lo), int(hi))

        # -- telemetry: StepWatch / MFU ------------------------------------
        # analytic FLOPs for one optimization step: per-seq fwd+bwd FLOPs
        # (gathered MLM head — only max_predictions positions hit the vocab
        # matmul) times the effective global batch; steps_per_loop is
        # handled by counting n steps per dispatch
        # micro_global spans the mesh-wide data axis, so seqs_per_step (and
        # therefore step_flops) is already GLOBAL across hosts — it pairs
        # with the global peak (peak_per_device * device_count) for MFU
        seqs_per_step = accum_steps * micro_global
        step_flops = (family.train_flops_per_row(config, seq_len,
                                                 max_pred_row)
                      * seqs_per_step)
        # None on the CPU backend (no MFU there); an accelerator the peak
        # table does not know is an error
        peak = device_peak_flops(jax.devices()[0], dtype=config.dtype)
        sw = tel.make_stepwatch(
            flops_per_step=step_flops, seqs_per_step=seqs_per_step,
            seq_len=seq_len,
            peak_flops=peak and peak * jax.device_count(),
            log_freq=args.log_freq, n_devices=jax.device_count())
        peak_txt = (f"peak {peak / 1e12:.0f} TFLOP/s/device" if peak
                    else "no MFU on this backend")
        logger.info(
            f"telemetry: {step_flops / 1e9:.2f} GFLOP/step global, "
            f"{peak_txt}, health_pack="
            f"{args.health_pack} nonfinite_action={args.nonfinite_action} "
            f"log_freq={args.log_freq}")

        # -- flight recorder: the black box ---------------------------------
        # captures loader output at the yield boundary (batch_tap), binds
        # batches to step ids + dispatch RNG below, and dumps a repro
        # bundle next to the checkpoints on a flagged step or crash. All
        # host-side references — no copies, no added device sync.
        recorder = None
        if args.flight_recorder == "on":
            from bert_pytorch_tpu.telemetry import FlightRecorder
            from bert_pytorch_tpu.telemetry.flight_recorder import \
                per_host_dir

            kfac_info = None
            if args.kfac:
                kfac_info = {
                    "inv_interval": args.kfac_inv_interval,
                    "factor_interval": args.kfac_factor_interval,
                    "stat_decay": args.kfac_stat_decay,
                    "damping": args.kfac_damping,
                    "kl_clip": args.kfac_kl_clip,
                    "skip_layers": list(args.kfac_skip_layers),
                    "factor_bucket_bytes": kfac.factor_bucket_bytes
                    if coalesce else None,
                    "factor_sync_freq": args.kfac_factor_sync_freq,
                    "bucket_assignment": kfac.bucket_assignment,
                    "stats_dtype": args.kfac_stats_dtype,
                }
            # the metric readback lags one dispatch: by the time a flagged
            # step is seen, the NEXT dispatch's record_dispatch has already
            # run its eviction. The flagged chunk survives it only if the
            # ring holds two full dispatches — clamp, or the flagship
            # nonfinite bundle could not replay its own trigger step.
            window = max(args.recorder_window, 2 * steps_per_loop)
            if window > args.recorder_window:
                logger.info(
                    f"flight recorder: window raised {args.recorder_window}"
                    f" -> {window} (2x --steps_per_loop: the one-dispatch "
                    "metric lag must not evict the flagged chunk)")
            recorder = FlightRecorder(
                per_host_dir(os.path.join(args.output_dir, "repro_bundles")),
                window=window,
                run_info={
                    "accum_steps": accum_steps,
                    "steps_per_loop": steps_per_loop,
                    "seed": args.seed,
                    "max_pred_row": max_pred_row,
                    "grad_dtype": grad_dtype_name,
                    "optimizer": args.optimizer,
                    "learning_rate": args.learning_rate,
                    "lr_decay": args.lr_decay,
                    "warmup_proportion": args.warmup_proportion,
                    "max_steps": args.max_steps,
                    "previous_phase_end_step": args.previous_phase_end_step,
                    "rng_impl": args.rng_impl,
                    "health_pack": args.health_pack,
                    "nonfinite_action": args.nonfinite_action,
                    "zero1": zero1_plan is not None,
                    "zero1_overlap": (zero1_plan is not None
                                      and zero1_plan.gather_on_use),
                    "zero1_rs": (zero1_plan is not None
                                 and zero1_plan.reduce_scatter),
                    "fsdp_overlap": (plan is not None
                                     and plan.axis == "fsdp"),
                    "mesh_config": mesh_config_name,
                    # the FLAG, not the reducer: replay re-derives the
                    # reducer under the same `and plan is not None`
                    # condition, and K-FAC-only bucketing (kfac_info's
                    # factor_bucket_bytes) must not be recorded as off
                    "coalesce_reductions": coalesce,
                    "kfac": kfac_info,
                    "mesh": {k: int(v) for k, v in dict(mesh.shape).items()},
                    "seq_len": seq_len,
                    "local_batch_size": args.local_batch_size,
                    "global_batch_size": args.global_batch_size,
                    "packing": args.packing,
                    "packing_max_segments": args.packing_max_segments,
                    "inject_nonfinite_step": args.inject_nonfinite_step,
                    "stream": bool(args.stream_dir),
                },
                model_config=config.to_dict(),
                checkpoint_dir=ckpt_dir,
                provenance=collect_provenance(mesh=mesh),
                checkpoint_step_fn=manager.latest_step)
            # bundle manifests carry the registry snapshot at dump time
            # and the jsonl path the metrics tail mirrors
            tel.attach_recorder(recorder)
            if args.stream_dir:
                # streaming bundles additionally carry the source list +
                # cursor + recent batch->record windows (manifest schema-v2
                # optional key), so replay names the exact records involved
                recorder.stream_info_fn = loader.stream_info
            if not use_h2d_prefetch:
                # under prefetch the loader yields AHEAD of dispatch; the
                # tap moves to the prefetcher (set at construction below)
                # so the ring still sees batches in dispatch order
                loader.batch_tap = recorder.capture_batch
            recorder.install_crash_handlers()
            recorder.arm()
            logger.info(f"flight recorder: on, window={window} steps, "
                        f"bundles under {recorder.out_dir}")

        # -- survival kit (bert_pytorch_tpu/resilience/, docs/RESILIENCE.md)
        # Preemption guard: layered AFTER the recorder's handlers, so one
        # SIGTERM walks guard -> recorder -> SystemExit(143) and the
        # except-path below lands BOTH the crash bundle and the emergency
        # checkpoint of the last completed step.
        guard = PreemptionGuard(registry=tel.registry, log=logger.info)
        guard.install()
        watchdog = arm_watchdog(
            args.watchdog_timeout, args.watchdog_action, sw,
            registry=tel.registry, log=logger.info,
            out_dir=args.output_dir, recorder=recorder)
        chaos = None
        if args.chaos:
            chaos = ChaosMonkey(args.chaos, args.chaos_step,
                                stall_secs=args.chaos_stall_secs,
                                log=logger.info)
            if chaos.mode:
                logger.info(f"CHAOS armed: {chaos.mode} at step "
                            f"{chaos.at_step}")

        # SLO plane (telemetry/slo.py, docs/OBSERVABILITY.md): the SAME
        # burn-rate engine the server runs, here over the train-phase
        # specs — step-time ceiling, checkpoint freshness, non-finite
        # rate — reading the registry this loop already feeds
        slo_engine = None
        if getattr(args, "slo_config", None):
            from bert_pytorch_tpu.telemetry.slo import (SLOEngine,
                                                        SLOEvaluator,
                                                        load_slo_config)

            slo_cfg = load_slo_config(args.slo_config)
            slo_engine = SLOEngine(slo_cfg.specs_for("train"),
                                   slo_cfg.windows, tel.registry,
                                   phase="train", log=logger.info)

            def _checkpoint_age_s():
                _, landed = manager.freshness()
                if landed is None:
                    return None  # nothing saved or restored yet: no sample
                return max(0.0, time.time() - float(landed))

            slo_engine.set_source("checkpoint_age_s", _checkpoint_age_s)
            tel.attach_slo(slo_engine)
            slo_eval = SLOEvaluator(
                slo_engine,
                interval_s=args.slo_eval_interval_s).start()
            logger.info(
                f"slo: {len(slo_cfg.specs_for('train'))} train spec(s) "
                f"from {args.slo_config}, action={args.slo_action}"
                + (f" (halt after {args.slo_halt_after_s:g}s of "
                   "page-severity firing)" if args.slo_action == "halt"
                   else ""))

        # -- train loop (reference :482-549) --------------------------------
        # The host never blocks on the step it just dispatched: metrics for
        # step N are pulled to floats only after step N+1 is in flight, so
        # input prep (dynamic masking, H2D) overlaps device compute.
        train_start = time.time()
        global_step = start_step = int(state.step)
        loss_sum, loss_n = 0.0, 0
        # per-dispatch PRNG: fold_in(base, first_step) rather than a
        # sequential split chain, so dropout keys are a pure function of
        # the global step — a preempted run resumed from ANY checkpoint
        # derives the identical keys an uninterrupted run would, which is
        # what makes the survival drill's bit-identity hold with dropout
        # on (the sequential chain restarted from split #1 on resume)
        rng_base = jax.random.PRNGKey(args.seed + 1000 + dist.get_rank())
        done = False
        pending = None  # (step, epoch, metrics) awaiting logging
        warned_dropped = False
        # the family's cumulative [perf] counters (a routed family's expert
        # loads), or None
        family_counters = family.make_counters()
        halt_pending = None  # message; raised after cleanup-safe point
        dispatches = 0  # jit calls made; gates compile-warmup closure
        fp_holder = [None]  # program fingerprint, filled by a worker thread
        fp_logged = [False]

        def fingerprint_worker():
            """Program fingerprint (collective counts + donation hash) of
            whichever program the first dispatch AOT-compiled: stamped into
            every flight-recorder bundle and re-logged as a header extension
            so tools/replay.py can warn when a replay's program structure
            diverges from the recorded run's. The HLO text render + parse
            runs on this worker thread — at BERT-Large scale the optimized
            HLO is tens of MB and must not stall dispatch 2; the header is
            logged from the MAIN thread once the result lands (MetricLogger
            is not thread-safe)."""
            scopes = step_subscopes(family_name(config))
            for prog, n in ((jit_chunk, steps_per_loop), (jit_step, 1)):
                f = prog.fingerprint(scopes) if prog is not None else None
                if f is not None:
                    fp = dict(f, steps_per_loop=n)
                    if recorder is not None:
                        recorder.program_fingerprint = fp
                    # later checkpoints' integrity sidecars carry it too
                    manager.manifest_context["program_fingerprint"] = fp
                    fp_holder[0] = fp
                    return

        fp_thread = threading.Thread(target=fingerprint_worker,
                                     name="program-fingerprint", daemon=True)

        def maybe_log_fingerprint():
            """Main-thread consumer of the fingerprint worker: append the
            header extension once the parse has landed. Idempotent."""
            fp = fp_holder[0]
            if fp is None or fp_logged[0]:
                return
            fp_logged[0] = True
            tel.log_header(
                **prov,
                program_fingerprint=fp["hash"],
                remat_policy=remat_name,
                program_collectives=" ".join(
                    f"{k}={v}" for k, v in sorted(
                        fp["collective_counts"].items())),
                program_kernels=" ".join(
                    f"{k}={v}" for k, v in sorted(
                        fp.get("kernel_counts", {}).items())),
                # instructions under each sub-scope the family's step opens
                # (training/pretrain.STEP_SUBSCOPES), as the executable
                # carries them
                program_scopes=" ".join(
                    f"{k}={v}"
                    for k, v in fp.get("scope_counts", {}).items()))
            # only an executable the persistent cache served can be older
            # than the program; a fresh compile that counts zero is a config
            # with no layer of that kind
            stale = stale_scopes_warning(fp)
            if stale and compile_watch.cache_hits:
                logger.info(f"WARNING: {stale}")

        def flush_pending():
            nonlocal pending
            if pending is None:
                return
            step_i, epoch_i, m = pending
            pending = None
            # the ONE place the loop waits for the device: step N's metrics
            # are read after step N+1 is in flight
            with sw.phase("metric_flush"):
                # one transfer for all of the step's scalars: a decoder
                # step returns some fifty (the routed layers' counters),
                # and read one by one each is a transfer of its own
                vals = {k: float(v) for k, v in jax.device_get(m).items()}
            setup.end("first_step")     # the first loss is on the host
            with sw.phase("log"):
                log_flushed(step_i, epoch_i, vals)

        def log_flushed(step_i, epoch_i, vals):
            nonlocal loss_sum, loss_n, warned_dropped, halt_pending
            if family_counters is not None:
                family_counters.update(vals)
            if recorder is not None:
                # metrics tail rides in the bundle: the black box records
                # what tripped, not just the inputs
                recorder.note_metrics(step_i, vals)
            loss = vals.pop("loss")
            bad = (vals.get("loss_nonfinite", 0) > 0
                   or vals.get("grad_nonfinite", 0) > 0)
            if math.isfinite(loss) and not bad:
                loss_sum += loss
                loss_n += 1
            if vals.get("mlm_dropped", 0) > 0 and not warned_dropped:
                warned_dropped = True
                logger.info(
                    f"WARNING: step {step_i}: "
                    f"{int(vals['mlm_dropped'])} masked positions beyond "
                    "--max_predictions_per_seq lost supervision — the data "
                    "pipeline and step config disagree (raise "
                    "--max_predictions_per_seq or lower "
                    "--masked_token_fraction)")
            if bad:
                groups = ", ".join(
                    f"{k.removeprefix('grad_nonfinite_')}="
                    f"{int(v)}" for k, v in sorted(vals.items())
                    if k.startswith("grad_nonfinite_") and v > 0)
                handled = {"log": "training on (--nonfinite_action=log)",
                           "skip": "update was skipped in-graph",
                           "halt": "halting"}[args.nonfinite_action]
                logger.info(
                    f"WARNING: step {step_i}: NON-FINITE "
                    f"loss/gradients (step_loss={loss}, "
                    f"nonfinite grads: {groups or 'none'}) — {handled}")
            elif vals.get("grad_spike", 0) > 0:
                logger.info(
                    f"WARNING: step {step_i}: gradient-norm spike "
                    f"(z={vals.get('grad_norm_z', 0):.1f}, "
                    f"norm={vals.get('grad_norm', 0):.3g} vs EMA "
                    f"{vals.get('grad_norm_ema', 0):.3g})")
            tel.log_train(step_i, epoch=epoch_i,
                          average_loss=loss_sum / max(loss_n, 1),
                          step_loss=loss, **vals)
            bundle = None
            if bad and recorder is not None:
                # dump for EVERY action: even log/skip runs want the
                # offline repro of what the health pack just flagged
                bundle = recorder.dump("nonfinite", trigger_step=step_i)
                logger.info(
                    f"flight recorder: repro bundle for step {step_i} "
                    f"dumped to {bundle} (replay: python tools/replay.py "
                    f"--bundle {bundle} --bisect)")
            if bad and args.nonfinite_action == "halt":
                halt_pending = (
                    f"non-finite loss/gradients at step {step_i} and "
                    "--nonfinite_action=halt; last checkpoint is the "
                    "restart point"
                    + (f"; repro bundle: {bundle}" if bundle else ""))

        def crash_flush_impl(exc):
            """Crash-safe exit (satellite): whatever kills the run —
            SIGTERM/SIGINT (mapped to SystemExit by the recorder's
            handler), an exception, a NonFiniteHalt — the buffered
            metrics (pending readback + StepWatch partial interval) land
            in the sinks and the flight recorder dumps its bundle BEFORE
            the stack unwinds."""
            try:
                flush_pending()
            except Exception:
                pass
            try:
                rec = sw.flush()
                if rec is not None:
                    tel.log_perf(global_step, rec)
            except Exception:
                pass
            if recorder is not None and recorder.last_dump is None:
                try:
                    path = recorder.dump(type(exc).__name__.lower(),
                                         trigger_step=global_step)
                    logger.info(f"flight recorder: crash bundle dumped "
                                f"to {path}")
                except Exception:
                    pass

        crash_flush = crash_flush_impl
        emergency_done = [False]
        # (step, sampler snapshot, epoch) captured right after each
        # dispatch — the SAME program point the periodic save reads, so
        # an emergency save is label-coherent: a preemption signal can
        # land between the loader yielding step N+1's batch and its
        # dispatch, where the LIVE sampler state already covers a batch
        # the params never consumed (resume from such a pair would skip
        # one batch and silently fork the run)
        sampler_coherent = [None]

        def emergency_ckpt_impl(exc):
            """Preemption-safe checkpointing (resilience/preemption.py):
            when the unwind was caused by a preemption notice, one final
            SYNCHRONOUS save + wait of the last completed step — a
            preempted run loses zero completed steps. One-shot (the
            atexit backstop and double signals cannot double-save), and
            never past a halt-flagged step (the last checkpoint must
            stay the restart point, not the post-blowup params)."""
            if emergency_done[0] or args.skip_checkpoint or halt_pending:
                return
            preempted = (guard is not None
                         and guard.preempted_signal is not None) \
                or is_preemption_exit(exc)
            if not preempted:
                return
            emergency_done[0] = True
            try:
                step = int(state.step)  # the device's truth, not the
                # host counter — a signal between dispatch and the
                # host-side increment must not mislabel the save
                snap = sampler_coherent[0]
                if snap is None:
                    logger.info(
                        "preemption: no step completed this session — "
                        "nothing to emergency-checkpoint")
                    return
                if snap[0] == step:
                    sampler_snap, epoch_snap = snap[1], snap[2]
                else:
                    # signal landed in the dispatch->snapshot gap: no
                    # new yield has happened yet, so the LIVE state is
                    # coherent with the just-advanced params
                    sampler_snap, epoch_snap = sampler_state(), epoch
                emergency_save(manager, step,
                               state.replace(telemetry=None),
                               extra={"sampler": sampler_snap,
                                      "epoch": epoch_snap},
                               log=logger.info)
            except Exception as e:
                logger.info(f"WARNING: emergency checkpoint failed: {e} "
                            "(the last periodic checkpoint is the "
                            "restart point)")

        emergency_ckpt = emergency_ckpt_impl

        def timed_batches():
            """Yields (numpy_batch, device_batch_or_None) pairs. With h2d
            prefetch the pair's device half was put while the PREVIOUS step
            computed (DevicePrefetcher); without it the loop does the
            stack+put itself and the device half is None."""
            def waited():
                it = iter(loader)
                while True:
                    with sw.phase("data_wait"):
                        try:
                            b = next(it)
                        except StopIteration:
                            return
                    yield b

            if not use_h2d_prefetch:
                yield from ((b, None) for b in waited())
                return
            from bert_pytorch_tpu.data.sharded import DevicePrefetcher

            def put_fn(b):
                with sw.phase("data_prep"):
                    st = stack_microbatches(b, accum_steps)
                with sw.phase("h2d"):
                    return mesh_lib.host_to_device_batch(mesh, st)

            pf = DevicePrefetcher(
                waited(), put_fn, depth=h2d_depth,
                state_fn=loader.state_dict,
                batch_tap=(recorder.capture_batch
                           if recorder is not None else None))
            pf_holder[0] = pf
            yield from pf

        def check_halts():
            """Raise for a flagged non-finite step, or for a page-severity
            train SLO that has fired past --slo_halt_after_s."""
            if halt_pending:
                raise NonFiniteHalt(halt_pending)
            if slo_engine is None or args.slo_action != "halt":
                return
            since = slo_engine.page_firing_since()
            if (since is not None
                    and time.time() - since >= args.slo_halt_after_s):
                firing = sorted({a["slo"] for a in
                                 slo_engine.alerts_view()["firing"]})
                raise SLOBreachHalt(
                    f"train SLO breach: page alert(s) {firing} firing for "
                    f"{time.time() - since:.0f}s (>= --slo_halt_after_s "
                    f"{args.slo_halt_after_s:g}) at step {global_step} — "
                    "exiting EXIT_SLO_BREACH(76) for the supervisor to "
                    "restart")

        # logical_rules must be active while the step traces (first jit_step
        # call), or every nn.with_logical_constraint inside the model
        # becomes a silent no-op and SPMD layout falls back to pure
        # propagation
        chunk_buf = []  # steps_per_loop>1: host-side batch staging
        limit = min(target_step, session_limit)

        # Every statement of the loop that can take time runs under ONE
        # StepWatch phase (each also a host/<phase> trace annotation), so
        # the phases tile the main thread from one dispatch to the next and
        # a [perf] record's loop_unaccounted_ms reads near zero.
        with mesh, mesh_lib.logical_rules():
            setup.begin("data")     # the first batch, up to the first dispatch
            while not done:
                for batch_np, dev_batch in timed_batches():
                    if global_step >= limit:
                        done = True
                        break
                    with sw.phase("log"):
                        check_halts()
                        if chaos is not None:
                            chaos.before_dispatch(global_step + 1)
                    if (profile_range and not trace_active
                            and profile_range[0] <= global_step
                            < profile_range[1]):
                        with sw.phase("profile"):
                            jax.profiler.start_trace(
                                os.path.join(args.output_dir, "traces"))
                        trace_active = True
                    with sw.phase("data_prep"):
                        if dev_batch is None:
                            stacked = stack_microbatches(batch_np,
                                                         accum_steps)
                        # real (non-pad) tokens this host feeds the step;
                        # every host feeds the same count in expectation, so
                        # x n_hosts matches the global seqs_per_step basis
                        sw.note_tokens(
                            float(np.asarray(batch_np["attention_mask"])
                                  .sum()) * n_hosts)
                    remaining = limit - global_step
                    chunked = (steps_per_loop > 1
                               and remaining >= steps_per_loop)
                    if chunked:
                        # stage until a full device-side loop's worth is ready
                        chunk_buf.append(stacked)
                        if len(chunk_buf) < steps_per_loop:
                            continue
                        with sw.phase("data_prep"):
                            stacked = {k: np.stack([b[k] for b in chunk_buf])
                                       for k in chunk_buf[0]}
                            chunk_buf = []
                    if chunked or dev_batch is None:
                        with sw.phase("h2d"):
                            batch = mesh_lib.host_to_device_batch(
                                mesh, stacked, n_leading=2 if chunked else 1)
                    else:
                        batch = dev_batch  # put while the last step ran
                    program, stepped = ((jit_chunk, steps_per_loop)
                                        if chunked else (jit_step, 1))
                    with sw.phase("dispatch"):
                        step_rng = jax.random.fold_in(rng_base,
                                                      global_step + 1)
                        if dispatches == 0:
                            setup.end("data")
                            if program.lowered is None:
                                with setup.span("lower"):
                                    program.lower(state, batch, step_rng)
                            setup.begin("first_step")
                        if chaos is not None:
                            chaos.stall(global_step + 1)
                        state, metrics = program(state, batch, step_rng)
                    with sw.phase("log"):
                        if recorder is not None:
                            # bind the staged loader batches to the steps
                            # this dispatch performs + the dispatch PRNG key
                            # (kept as the device array it is: reading it
                            # here would wait for the step in flight)
                            recorder.record_dispatch(global_step + 1,
                                                     stepped, step_rng)
                        global_step += stepped
                        sampler_coherent[0] = (global_step, sampler_state(),
                                               epoch)
                        dispatches += 1
                        if dispatches == 1:
                            fp_thread.start()
                            step_fields["step_peak_bytes"] = \
                                program.peak_bytes()
                        maybe_log_fingerprint()
                    flush_pending()
                    pending = (global_step, epoch, metrics)
                    perf = sw.step_done(stepped)
                    if perf is not None:
                        with sw.phase("log"):
                            # warmup closes at the first interval with >=3
                            # dispatches behind it: jit legitimately compiles
                            # twice (first call sees uncommitted input
                            # shardings, the donated output commits them),
                            # so only a compile past dispatch 3 is a true
                            # mid-run recompile worth a loud warning
                            if dispatches >= 3:
                                compile_watch.mark_steady()
                            perf.update(compile_watch.snapshot())
                            perf.update(hbm_snapshot())
                            perf.update(step_fields)
                            if family_counters is not None:
                                perf.update(family_counters.fields())
                            tel.log_perf(global_step, perf)
                    if trace_active and global_step >= profile_range[1]:
                        with sw.phase("profile"):
                            jax.profiler.stop_trace()
                        trace_active = False
                    if (not args.skip_checkpoint
                            and global_step % args.num_steps_per_checkpoint
                            < (steps_per_loop if chunked else 1)):
                        flush_pending()
                        if halt_pending:
                            # never checkpoint past a halt-flagged step: the
                            # LAST saved state must stay the restart point,
                            # not the post-blowup params
                            raise NonFiniteHalt(halt_pending)
                        with sw.phase("checkpoint"):
                            # loader.state_dict lags to the last YIELDED
                            # batch, so a resume replays nothing even with
                            # prefetch running ahead; telemetry EMAs are
                            # ephemeral — stripped so checkpoint structure
                            # never depends on the health pack
                            manager.save(
                                global_step, state.replace(telemetry=None),
                                extra={"sampler": sampler_state(),
                                       "epoch": epoch})
                        if chaos is not None:
                            chaos.after_checkpoint(manager, global_step)
                else:
                    with sw.phase("data_wait"):
                        # drains what the prefetch executor has in flight
                        loader.reset_epoch()
                    pf_holder[0] = None  # next epoch builds a fresh one
                    epoch += 1

        flush_pending()
        if dispatches:
            # short runs can finish before the fingerprint parse does;
            # give it a moment so the header extension still lands (the
            # thread is daemonic — a stuck parse never blocks shutdown)
            fp_thread.join(timeout=10.0)
            maybe_log_fingerprint()
        if halt_pending:
            raise NonFiniteHalt(halt_pending)
        if trace_active:
            jax.profiler.stop_trace()
            trace_active = False
        train_time = time.time() - train_start
        steps_done = global_step - start_step
        if not args.skip_checkpoint and steps_done:
            manager.save(global_step, state.replace(telemetry=None),
                         extra={"sampler": sampler_state(),
                                "epoch": epoch})
        manager.wait()
        if steps_done:
            # end-of-run throughput line (reference :574-580) — uses the
            # *effective* global batch actually trained per step
            seq_per_sec = accum_steps * micro_global * steps_done / train_time
            logger.info(f"training_seq_per_sec = {seq_per_sec:.2f} "
                        f"({steps_done} steps in {train_time:.1f}s)")
            logger.info(f"compiles: {compile_watch.snapshot()}")
        if recorder is not None:
            recorder.disarm()  # clean exit: the atexit backstop stands down
        return int(state.step), train_time
    except BaseException as exc:
        # crash-safe flush (satellite): buffered metrics + black box land
        # before the unwind; crash_flush is None only if the failure
        # happened before the loop-scope pieces existed (nothing buffered)
        if crash_flush is not None:
            crash_flush(exc)
        # preemption-safe checkpointing: the emergency save runs AFTER
        # the bundle dump (the black box must land even if the save
        # fails) and only on the preemption-signal unwind path
        if emergency_ckpt is not None:
            emergency_ckpt(exc)
        raise
    finally:
        # error-path resource cleanup (satellite: logger/trace leak fix) —
        # each close guarded so one failing teardown can't mask the others
        # or the original exception
        if trace_active:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
        # tel.close() releases the /metrics server, compile-watch listener,
        # multi-host aggregator, and every logger sink. Order matters for
        # the signal chain: guard.close() restores the recorder's handler,
        # recorder.close() then restores the original — closing the
        # recorder first would let guard re-install a dead layer
        for closeable in (slo_eval, watchdog, guard, recorder, tel, loader,
                          manager):
            if closeable is not None:
                try:
                    closeable.close()
                except Exception:
                    pass


def _cli(argv=None) -> int:
    """Script entry: a NonFiniteHalt exits with the DISTINCT code
    EXIT_NONFINITE_HALT (71) and a one-line FATAL (carrying the
    repro-bundle path) instead of a raw traceback — the operator AND
    supervisor contract for --nonfinite_action=halt (tools/supervise.py
    refuses to retry 71: restarting replays the same deterministic
    blowup). An SLOBreachHalt (--slo_action=halt) exits EXIT_SLO_BREACH
    (76) — restart-worthy, the supervisor retries it. Everything else
    propagates (tracebacks for real bugs, 128+sig for signals).
    Exit-code contract: docs/RESILIENCE.md."""
    from bert_pytorch_tpu.resilience import (EXIT_NONFINITE_HALT,
                                             EXIT_SLO_BREACH)

    try:
        main(argv)
    except NonFiniteHalt as e:
        print(f"FATAL: {e}", file=sys.stderr)
        return EXIT_NONFINITE_HALT
    except SLOBreachHalt as e:
        print(f"FATAL: {e}", file=sys.stderr)
        return EXIT_SLO_BREACH
    return 0


if __name__ == "__main__":
    sys.exit(_cli())
