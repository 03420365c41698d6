#!/usr/bin/env python
"""Inference server entry point: checkpoints -> HTTP traffic.

Assembles the serving stack (bert_pytorch_tpu/serving) from the task
registry (bert_pytorch_tpu/tasks/registry.py): every task served gets a
`POST /v1/<task>` route, an AOT-compiled bucketed forward per sequence
bucket, continuous packed batching, and the Prometheus /metrics +
/healthz on one port via telemetry.init_run(phase="serve").
docs/SERVING.md is the operator guide; tools/loadtest.py drives it.

    python run_server.py --model_config_file cfg.json --vocab_file vocab.txt \
        --task_checkpoint squad=out/ckpt --task_checkpoint ner=ner/ckpt \
        --task_checkpoint classify=cls/ckpt --task_checkpoint embed=emb/ckpt \
        --labels B-PER I-PER B-LOC I-LOC O --port 8000

`--squad_checkpoint` / `--ner_checkpoint` remain as aliases of the
generic `--task_checkpoint task=dir` form. `--port 0` binds an
ephemeral port; `--port_file` writes the bound port once the server is
WARM (every bucket compiled) — scripts poll that file instead of racing
the compile.
"""

from __future__ import annotations

import argparse
import os
import signal
import threading


def parse_arguments(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model_config_file", required=True, type=str)
    p.add_argument("--vocab_file", default=None, type=str)
    p.add_argument("--task_checkpoint", action="append", default=None,
                   metavar="TASK=DIR",
                   help="serve a registered task from an orbax checkpoint "
                        "dir (optionally dir@step); repeatable — every "
                        "TASK must exist in tasks/registry.py")
    p.add_argument("--squad_checkpoint", default=None, type=str,
                   help="alias of --task_checkpoint squad=DIR")
    p.add_argument("--ner_checkpoint", default=None, type=str,
                   help="alias of --task_checkpoint ner=DIR "
                        "(requires --labels)")
    p.add_argument("--labels", type=str, nargs="+", default=None,
                   help="NER label names (run_ner.py convention: ids "
                        "start at 1, 0 is the padding class)")
    p.add_argument("--class_names", type=str, nargs="+",
                   default=["negative", "positive"],
                   help="classify task's class names in label-id order "
                        "(sets the served head width)")
    p.add_argument("--num_choices", type=int, default=4,
                   help="choice task's training-time choice count (the "
                        "served per-segment scorer accepts any request "
                        "with 2..16 choices)")
    p.add_argument("--embed_labels", type=int, default=2,
                   help="embed task's probe-head width (must match the "
                        "checkpoint; serving returns embeddings, not "
                        "probe logits)")
    p.add_argument("--port", type=int, default=8000,
                   help="HTTP port (0 = ephemeral)")
    p.add_argument("--host", type=str, default="0.0.0.0")
    p.add_argument("--port_file", type=str, default=None,
                   help="write the bound port here once warm")
    p.add_argument("--buckets", type=str, default="64,128,256,512",
                   help="comma-separated AOT sequence-length buckets")
    p.add_argument("--batch_rows", type=int, default=8,
                   help="rows per forward batch (fixed — part of the "
                        "compiled shape)")
    p.add_argument("--max_segments", type=int, default=8,
                   help="max packed requests per row")
    p.add_argument("--packing", type=str, default="on",
                   choices=["on", "off"],
                   help="pack multiple requests per row (segment-aware "
                        "attention); off = one request per row, same "
                        "compiled program")
    p.add_argument("--serve_dtype", type=str, default="bfloat16",
                   choices=["bfloat16", "float32", "int8"],
                   help="compute dtype of the served forwards. bfloat16/"
                        "float32: params stay fp32. int8: symmetric "
                        "per-channel WEIGHT quantization at restore time "
                        "(serving/quantize.py) — weights live int8 in "
                        "device memory, dequantize in-graph, activations "
                        "compute in bf16; refuses to serve past "
                        "--int8_max_delta vs the f32 decode")
    p.add_argument("--int8_max_delta", type=float, default=0.1,
                   help="int8 accuracy gate: max relative decode delta vs "
                        "the f32 reference forward, per task "
                        "(tools/quantcheck.py is the offline check)")
    p.add_argument("--serve_replicas", type=int, default=1,
                   help="data-parallel replica engines over disjoint "
                        "device slices, fed by a work-stealing dispatcher "
                        "(saturation req/s scales ~linearly)")
    p.add_argument("--serve_mesh", type=str, default=None,
                   metavar="AXIS=K[,AXIS=K]",
                   help="shard each replica's engine over a device mesh, "
                        "e.g. model=2 — param shardings derive from the "
                        "logical-axis-rules table (parallel/rules.py); "
                        "each replica then occupies K devices")
    p.add_argument("--queue_size", type=int, default=128,
                   help="admission queue bound; a full queue sheds with "
                        "HTTP 503")
    p.add_argument("--admission_timeout", type=float, default=10.0,
                   help="seconds a request may wait before 504")
    p.add_argument("--drain_timeout", type=float, default=30.0,
                   help="graceful-drain deadline on SIGTERM/SIGINT: "
                        "admission stops immediately (503 + Retry-After),"
                        " in-flight requests get this many seconds to "
                        "finish, metrics flush, exit 0 "
                        "(docs/RESILIENCE.md)")
    p.add_argument("--batch_wait_ms", type=float, default=2.0,
                   help="coalescing window before dispatching a batch")
    p.add_argument("--request_tracing", type=str, default="on",
                   choices=["on", "off"],
                   help="per-request span timelines (X-Trace-Id header + "
                        "GET /v1/traces; docs/OBSERVABILITY.md). Host-side "
                        "only — cannot affect responses; off exists for "
                        "the A/B overhead measurement")
    p.add_argument("--trace_ring_slowest", type=int, default=32,
                   help="trace ring: keep the N slowest request traces "
                        "per rotating window")
    p.add_argument("--trace_ring_sample_every", type=int, default=16,
                   help="trace ring: also keep every K-th trace as a "
                        "healthy-baseline cross-section")
    p.add_argument("--trace_ring_window_s", type=float, default=60.0,
                   help="trace ring: slowest-window rotation period "
                        "(seconds); current + previous window are served")
    p.add_argument("--cost_per_device_hour", type=float, default=None,
                   help="price per device-hour for the cost-per-1k-tokens "
                        "gauges (default: BERT_COST_PER_DEVICE_HOUR env or "
                        "1.0 = normalized device-hours)")
    p.add_argument("--slo_config", type=str, default=None,
                   help="SLO spec file (configs/slo.json): turns on the "
                        "burn-rate engine — GET /v1/alerts + /v1/slo, and "
                        "/healthz's top-level status becomes the engine's "
                        "ok|degraded|failing verdict "
                        "(docs/OBSERVABILITY.md)")
    p.add_argument("--slo_eval_interval_s", type=float, default=1.0,
                   help="burn-rate engine evaluation period")
    p.add_argument("--prober", type=str, default="off",
                   choices=["on", "off"],
                   help="synthetic canary prober: a background thread "
                        "sends a known-answer request per served task "
                        "through the real HTTP frontend and verifies the "
                        "DECODED answer against the first response (the "
                        "engine is deterministic), flipping per-task "
                        "health + a page alert on drift")
    p.add_argument("--probe_interval_s", type=float, default=5.0,
                   help="seconds between canary probe rounds")
    p.add_argument("--probe_timeout_s", type=float, default=30.0,
                   help="per-probe HTTP timeout")
    p.add_argument("--slo_inject", type=str, default=None,
                   choices=["error_burst", "latency_burst",
                            "corrupt_answers"],
                   help="chaos drill for scripts/check_slo.sh: wrap the "
                        "engines' forward host-side AFTER warmup so the "
                        "named fault starts at --slo_inject_after_s and "
                        "the matching alert must fire within one fast "
                        "window (compiled programs stay untouched)")
    p.add_argument("--slo_inject_after_s", type=float, default=2.0,
                   help="seconds of clean serving before the injected "
                        "fault activates (lets the prober pin baselines)")
    p.add_argument("--slo_inject_task", type=str, default=None,
                   help="restrict corrupt_answers to one task (proves the "
                        "prober localizes: only that task flips unhealthy)")
    p.add_argument("--slo_inject_latency_ms", type=float, default=400.0,
                   help="latency_burst: added host-side delay per forward")
    p.add_argument("--doc_stride", type=int, default=128)
    p.add_argument("--max_query_length", type=int, default=64)
    p.add_argument("--n_best_size", type=int, default=20)
    p.add_argument("--max_answer_length", type=int, default=30)
    p.add_argument("--vocab_pad_multiple", type=int, default=8,
                   help="pad the vocab like the training entry points — "
                        "checkpoints carry the padded table")
    p.add_argument("--output_dir", type=str, default=None,
                   help="optional: write serve_log jsonl/txt here")
    p.add_argument("--force_cpu", action="store_true",
                   help="run on the CPU backend (CI/bench harness): sets "
                        "JAX_PLATFORMS=cpu, and the host device count a "
                        "replica fleet needs, before jax is imported")
    from bert_pytorch_tpu.config import merge_args_with_config

    return merge_args_with_config(p, argv)


def parse_serve_mesh(spec) -> dict:
    """'model=2' / 'model=2,seq=1' -> {"model": 2, ...}; None/'' -> {}.
    Axis names must come from the rules table's MESH_AXES (validated
    lazily in serve() against parallel.rules to stay jax-free here)."""
    out = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        axis, sep, k = part.partition("=")
        if not sep or not axis or not k.lstrip("-").isdigit():
            raise SystemExit(f"--serve_mesh wants AXIS=K[,AXIS=K], got "
                             f"{spec!r}")
        out[axis] = int(k)
        if out[axis] < 1:
            raise SystemExit(f"--serve_mesh {axis}={k}: K must be >= 1")
    return out


def _mesh_slice_size(mesh_axes: dict) -> int:
    n = 1
    for v in mesh_axes.values():
        n *= int(v)
    return n


def task_checkpoints(args) -> dict:
    """{task: checkpoint_dir} from --task_checkpoint entries plus the
    legacy --squad_checkpoint/--ner_checkpoint aliases, validated
    against the registry."""
    from bert_pytorch_tpu.tasks import registry

    out = {}
    for entry in args.task_checkpoint or []:
        task, sep, ckpt = entry.partition("=")
        if not sep or not task or not ckpt:
            raise SystemExit(f"--task_checkpoint wants TASK=DIR, got "
                             f"{entry!r}")
        out[task] = ckpt
    if args.squad_checkpoint:
        out.setdefault("squad", args.squad_checkpoint)
    if args.ner_checkpoint:
        out.setdefault("ner", args.ner_checkpoint)
    unknown = sorted(set(out) - set(registry.all_tasks()))
    if unknown:
        raise SystemExit(
            f"unknown task(s) {unknown}; registered: "
            + ", ".join(registry.all_tasks()))
    return out


class ServerHandle:
    """Everything `serve()` started, closable in one call (frontend first
    so no new requests land on a draining scheduler)."""

    def __init__(self, frontend, scheduler, engine, tel, slo=None,
                 prober=None, evaluator=None, injector=None):
        self.frontend = frontend
        self.scheduler = scheduler
        self.engine = engine
        self.engines = getattr(scheduler, "engines", [engine])
        self.tel = tel
        self.slo = slo
        self.prober = prober
        self.evaluator = evaluator
        self.injector = injector
        self.url = frontend.url
        self.port = frontend.port

    def close(self) -> None:
        # prober first (or it logs connection errors against the port the
        # frontend is about to release), then frontend so no new requests
        # land on a draining scheduler
        closers = []
        if self.prober is not None:
            closers.append(self.prober.close)
        closers.append(self.frontend.close)
        if self.evaluator is not None:
            closers.append(self.evaluator.close)
        closers += [self.scheduler.close, self.tel.close]
        for fn in closers:
            try:
                fn()
            except Exception:
                pass


def serve(args) -> ServerHandle:
    """Build the full stack and return a live ServerHandle (the port is
    open and every bucket is compiled when this returns)."""
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.config import BertConfig, pad_vocab_size
    from bert_pytorch_tpu.data.tokenization import get_wordpiece_tokenizer
    from bert_pytorch_tpu.parallel import rules as rules_lib
    from bert_pytorch_tpu.parallel.mesh import make_mesh
    from bert_pytorch_tpu.serving import quantize as quant_lib
    from bert_pytorch_tpu.serving.batcher import Scheduler
    from bert_pytorch_tpu.serving.engine import (ServingEngine,
                                                 restore_serving_params,
                                                 serving_param_shardings)
    from bert_pytorch_tpu.serving.frontend import ServingFrontend
    from bert_pytorch_tpu.tasks import registry, squad
    from bert_pytorch_tpu.telemetry import collect_provenance, init_run

    checkpoints = task_checkpoints(args)
    mesh_axes = parse_serve_mesh(getattr(args, "serve_mesh", None))
    bad_axes = sorted(set(mesh_axes) - set(rules_lib.MESH_AXES))
    if bad_axes:
        raise SystemExit(f"--serve_mesh axes {bad_axes} not in the rules "
                         f"table's {list(rules_lib.MESH_AXES)}")
    mesh_size = _mesh_slice_size(mesh_axes)
    replicas = max(1, int(getattr(args, "serve_replicas", 1) or 1))
    if args.serve_dtype == "int8" and mesh_size > 1:
        raise SystemExit(
            "--serve_dtype int8 with --serve_mesh is not supported: the "
            "quantized param tree carries {q8, scale} dict leaves the "
            "rules table has no logical annotations for (docs/SERVING.md)"
            " — pick one lever, or scale out with --serve_replicas")
    devices = jax.devices()
    need = replicas * mesh_size
    if len(devices) < need:
        raise SystemExit(
            f"--serve_replicas {replicas} x mesh slice {mesh_size} needs "
            f"{need} device(s), have {len(devices)} (with --force_cpu the "
            "launcher forces a matching host device count automatically)")
    if not checkpoints:
        raise SystemExit(
            "nothing to serve: pass --task_checkpoint TASK=DIR (tasks: "
            + ", ".join(registry.all_tasks())
            + ") or the --squad_checkpoint/--ner_checkpoint aliases")
    if "ner" in checkpoints and not args.labels:
        raise SystemExit("serving ner requires --labels")

    log_prefix = (os.path.join(args.output_dir, "serve_log")
                  if args.output_dir else None)
    if args.output_dir:
        os.makedirs(args.output_dir, exist_ok=True)
    tel = init_run(phase="serve", log_prefix=log_prefix, jsonl=True)
    log = tel.logger.info
    tel.log_header(**collect_provenance())

    config = BertConfig.from_json_file(args.model_config_file)
    config = config.replace(
        vocab_size=pad_vocab_size(config.vocab_size,
                                  args.vocab_pad_multiple))
    vocab_file = args.vocab_file or config.vocab_file
    if not vocab_file:
        raise SystemExit("vocab_file required (CLI or model config)")
    tokenizer = get_wordpiece_tokenizer(vocab_file,
                                        uppercase=not config.lowercase)
    # int8 is WEIGHT-only quantization — activations compute in bf16
    compute_dtype = (jnp.float32 if args.serve_dtype == "float32"
                     else jnp.bfloat16)

    buckets = sorted({int(b) for b in args.buckets.split(",") if b.strip()})
    usable = [b for b in buckets if b <= config.max_position_embeddings]
    if usable != buckets:
        log(f"WARNING: dropping buckets beyond max_position_embeddings="
            f"{config.max_position_embeddings}: "
            f"{sorted(set(buckets) - set(usable))}")
    if not usable:
        raise SystemExit("no usable bucket <= max_position_embeddings")
    sample_len = min(usable[-1], config.max_position_embeddings)

    # the per-task serving options the registry specs consume
    serve_opts = {
        # ONE tokenizer instance serves every task, so every service must
        # serialize on ONE lock (frontend.py service classes)
        "tok_lock": threading.Lock(),
        "labels": args.labels,
        "class_names": args.class_names,
        "num_choices": args.num_choices,
        "embed_labels": args.embed_labels,
        "max_segments": args.max_segments,
        "doc_stride": args.doc_stride,
        "max_query_length": args.max_query_length,
        "answer_cfg": squad.AnswerConfig(
            n_best_size=args.n_best_size,
            max_answer_length=args.max_answer_length,
            do_lower_case=config.lowercase),
    }

    forwards, params, output_kinds, services_spec = {}, {}, {}, {}
    task_models, model_params_count = {}, {}
    # fleet dashboards correlate cost_per_1k_tokens with model size
    # (teacher vs distilled student checkpoints serve through the same
    # stack) — export the served parameter count per task
    params_gauge = tel.registry.gauge(
        "bert_serve_model_params",
        "parameters served per task (model size)", labels=("task",))
    for task in sorted(checkpoints):
        spec = registry.get(task)
        model = spec.build_serving_model(config, compute_dtype, serve_opts)
        params[task], step = restore_serving_params(
            checkpoints[task], model, sample_len, log=log)
        forwards[task] = spec.forward_builder(model)
        output_kinds[task] = spec.output_kind
        services_spec[task] = step
        task_models[task] = model
        model_params_count[task] = sum(
            int(leaf.size)
            for leaf in jax.tree_util.tree_leaves(params[task]))
        params_gauge.set(model_params_count[task], task=task)

    int8_deltas = {}
    if args.serve_dtype == "int8":
        # quantize ONCE host-side; gate each task's decode against the
        # f32 reference before a single request is admitted — serving a
        # silently broken quantization is an outage, not a warning
        probe = quant_lib.probe_batch(
            min(2, args.batch_rows), usable[0], config.vocab_size,
            max_segments=min(2, args.max_segments))
        for task in sorted(checkpoints):
            qparams, stats = quant_lib.quantize_tree(
                jax.device_get(params[task]))
            spec = registry.get(task)
            ref_model = spec.build_serving_model(config, jnp.float32,
                                                 serve_opts)
            ref_forward = spec.forward_builder(ref_model)
            q_forward = quant_lib.wrap_forward(forwards[task],
                                               compute_dtype)
            delta = quant_lib.decode_delta(ref_forward, params[task],
                                           q_forward, qparams, probe)
            int8_deltas[task] = delta
            log(f"int8[{task}]: {stats['quantized_leaves']} leaves "
                f"quantized ({stats['bytes_before'] / 1e6:.1f} -> "
                f"{stats['bytes_after'] / 1e6:.1f} MB), rel_delta "
                f"{delta['rel_delta']:.4f}, argmax_agreement "
                f"{delta['argmax_agreement']:.4f}")
            if delta["rel_delta"] > args.int8_max_delta:
                raise SystemExit(
                    f"int8 accuracy gate: task {task!r} rel decode delta "
                    f"{delta['rel_delta']:.4f} exceeds --int8_max_delta "
                    f"{args.int8_max_delta:g}; refusing to serve "
                    "(tools/quantcheck.py to inspect offline)")
            params[task] = qparams
            forwards[task] = q_forward

    engines = []
    n = 0
    for i in range(replicas):
        dev_slice = devices[i * mesh_size:(i + 1) * mesh_size]
        mesh_i = make_mesh(dict(mesh_axes) or None, devices=dev_slice)
        shardings_i = None
        if mesh_size > 1:
            shardings_i = {
                t: serving_param_shardings(task_models[t], sample_len,
                                           mesh_i)[0]
                for t in sorted(checkpoints)}
        eng = ServingEngine(forwards, params, buckets=usable,
                            batch_rows=args.batch_rows,
                            max_segments=args.max_segments,
                            compile_watch=tel.compile_watch,
                            output_kinds=output_kinds,
                            mesh=mesh_i, param_shardings=shardings_i,
                            name=f"r{i}")
        # steady-state arms ONCE after every replica warmed up: arming
        # per-engine would flag replica K>0's warmup compiles as loud
        # RECOMPILEs (the bug this replaced)
        n += eng.warmup(log=log, mark_steady=False)
        engines.append(eng)
    if tel.compile_watch is not None:
        tel.compile_watch.mark_steady()
    engine = engines[0]
    log(f"serving: {n} bucketed program(s) compiled across "
        f"{replicas} replica(s) "
        f"(tasks {engine.tasks}, buckets {engine.buckets}, "
        f"batch_rows {engine.batch_rows}, packing {args.packing}, "
        f"dtype {args.serve_dtype}"
        + (f", mesh {mesh_axes}" if mesh_size > 1 else "") + ")")

    injector = None
    if getattr(args, "slo_inject", None):
        # chaos drill: wrap forward HOST-side after warmup — wrapping the
        # python callables before engine construction would be traced
        # into the AOT programs and compiled out
        from bert_pytorch_tpu.telemetry.slo import FaultInjector

        injector = FaultInjector(
            args.slo_inject,
            after_s=getattr(args, "slo_inject_after_s", 2.0),
            task=getattr(args, "slo_inject_task", None),
            latency_ms=getattr(args, "slo_inject_latency_ms", 400.0))
        for eng in engines:
            injector.install(eng)
        log(f"slo_inject: {args.slo_inject} arms "
            f"{args.slo_inject_after_s:g}s after warmup"
            + (f" (task {args.slo_inject_task})"
               if args.slo_inject_task else ""))

    # scale the batching window with the fleet size: N replicas consume
    # waves N× faster, so an unscaled window would freeze each wave with
    # 1/N the coalesced requests — every wave still costs the full padded
    # batch_rows x bucket compute, and the shallower packs would burn the
    # whole scale-out win (measured on the CPU harness: 2 replicas at the
    # single-replica window saturate ~25% EARLIER than one replica)
    tracing = getattr(args, "request_tracing", "on") == "on"
    trace_ring = None
    if tracing:
        from bert_pytorch_tpu.serving.request_trace import TraceRing

        trace_ring = TraceRing(
            keep_slowest=getattr(args, "trace_ring_slowest", 32),
            sample_every=getattr(args, "trace_ring_sample_every", 16),
            window_s=getattr(args, "trace_ring_window_s", 60.0))
    scheduler = Scheduler(engines, queue_size=args.queue_size,
                          admission_timeout_s=args.admission_timeout,
                          batch_wait_ms=args.batch_wait_ms * len(engines),
                          packing=(args.packing == "on"),
                          registry=tel.registry,
                          trace_ring=trace_ring, tracing=tracing,
                          cost_per_device_hour=getattr(
                              args, "cost_per_device_hour", None)).start()

    services = {task: registry.get(task).make_service(
        scheduler, tokenizer, serve_opts) for task in sorted(checkpoints)}

    slo_engine = None
    if getattr(args, "slo_config", None):
        from bert_pytorch_tpu.telemetry.slo import SLOEngine, load_slo_config

        slo_cfg = load_slo_config(args.slo_config)
        slo_engine = SLOEngine(slo_cfg.specs_for("serve"), slo_cfg.windows,
                               tel.registry, phase="serve",
                               trace_ring=scheduler.trace_ring, log=log)
        tel.attach_slo(slo_engine)
        log(f"slo: {len(slo_cfg.specs_for('serve'))} serve spec(s) from "
            f"{args.slo_config} — GET /v1/alerts + /v1/slo; /healthz "
            "status is now the burn-rate engine's verdict")

    # the prober needs the bound port, which only exists once the
    # frontend is up — healthz reads it through this holder instead
    prober_holder = {}

    def healthz():
        h = tel.healthz()
        if prober_holder.get("prober") is not None:
            h["prober"] = prober_holder["prober"].status()
        h.update({
            "tasks": {t: {"checkpoint_step": services_spec[t],
                          "head": registry.get(t).head,
                          "model_params": model_params_count.get(t),
                          "request_schema": dict(
                              registry.get(t).request_schema)}
                      for t in sorted(services_spec)},
            "buckets": list(engine.buckets),
            "packing": args.packing == "on",
            "queue_depth": int(
                scheduler.registry.gauge("bert_serve_queue_depth").value()),
            "serve_dtype": args.serve_dtype,
            "serve_replicas": replicas,
            "serve_mesh": {k: int(v) for k, v in mesh_axes.items()},
            "int8_deltas": {t: {k: round(float(v), 6)
                                for k, v in d.items()}
                            for t, d in sorted(int8_deltas.items())},
            "replicas": scheduler.replica_stats(),
            "request_tracing": (
                dict(scheduler.trace_ring.stats(),
                     cost_per_device_hour=scheduler.cost_per_device_hour)
                if scheduler.trace_ring is not None else None),
        })
        return h

    frontend = ServingFrontend(services, tel.registry, healthz_fn=healthz,
                               port=args.port, host=args.host,
                               trace_ring=scheduler.trace_ring,
                               slo_engine=slo_engine)

    prober = None
    if getattr(args, "prober", "off") == "on":
        from bert_pytorch_tpu.serving.prober import (CanaryProber,
                                                     KNOWN_ANSWER_PAYLOADS)

        probe_tasks = sorted(set(services) & set(KNOWN_ANSWER_PAYLOADS))
        skipped = sorted(set(services) - set(probe_tasks))
        if skipped:
            log(f"prober: no known-answer payload for {skipped}; probing "
                f"{probe_tasks}")
        if probe_tasks:
            prober = CanaryProber(
                frontend.url, probe_tasks,
                interval_s=getattr(args, "probe_interval_s", 5.0),
                timeout_s=getattr(args, "probe_timeout_s", 30.0),
                registry=tel.registry, log=log).start()
            prober_holder["prober"] = prober
            if slo_engine is not None:
                slo_engine.add_alert_source(prober.alerts)
            log(f"prober: canary thread probing "
                f"{{{','.join(probe_tasks)}}} every "
                f"{args.probe_interval_s:g}s through {frontend.url}")

    evaluator = None
    if slo_engine is not None:
        from bert_pytorch_tpu.telemetry.slo import SLOEvaluator

        evaluator = SLOEvaluator(
            slo_engine,
            interval_s=getattr(args, "slo_eval_interval_s", 1.0)).start()

    log(f"serving: listening on {frontend.url} "
        f"(POST /v1/{{{','.join(sorted(services))}}}, GET /metrics, "
        f"GET /healthz"
        + (", GET /v1/traces" if trace_ring is not None else "")
        + (", GET /v1/alerts, GET /v1/slo" if slo_engine is not None
           else "") + ")")
    return ServerHandle(frontend, scheduler, engine, tel, slo=slo_engine,
                        prober=prober, evaluator=evaluator,
                        injector=injector)


def main(argv=None):
    args = parse_arguments(argv)
    if args.force_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        # a replica fleet (or mesh slice) needs that many host devices;
        # force them BEFORE jax initializes, same recipe as
        # tests/conftest.py — scripts then just pass --serve_replicas
        need = (max(1, args.serve_replicas)
                * _mesh_slice_size(parse_serve_mesh(args.serve_mesh)))
        flags = os.environ.get("XLA_FLAGS", "")
        if need > 1 and "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={need}"
            ).strip()
    from bert_pytorch_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    handle = serve(args)
    if args.port_file:
        tmp = args.port_file + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            f.write(str(handle.port))
        os.replace(tmp, args.port_file)

    stop = threading.Event()

    def _on_signal(signum, frame):
        stop.set()

    old = {}
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            old[sig] = signal.signal(sig, _on_signal)
        except ValueError:
            pass  # non-main thread (tests drive serve() directly instead)
    log = handle.tel.logger.info
    try:
        stop.wait()
        # graceful drain (docs/RESILIENCE.md): stop admission first —
        # new requests shed 503 + Retry-After while /metrics + /healthz
        # (now reporting draining:true) keep answering — then let the
        # in-flight requests finish, then tear down and exit 0 so the
        # orchestrator records a clean stop, not a crash
        handle.frontend.begin_drain()
        inflight = handle.frontend.inflight
        log(f"drain: admission stopped (503 + Retry-After); waiting up "
            f"to {args.drain_timeout:g}s for {inflight} in-flight "
            "request(s)")
        drained = handle.frontend.wait_idle(timeout=args.drain_timeout)
        # every replica must come to rest too — a wave sitting on a
        # replica queue when we exit would strand its requests
        drained = (handle.scheduler.wait_idle(timeout=args.drain_timeout)
                   and drained)
        stats = handle.scheduler.replica_stats()
        log(("drain: complete — all in-flight requests finished, "
             if drained else
             f"WARNING: drain deadline ({args.drain_timeout:g}s) hit with "
             f"{handle.frontend.inflight} request(s) still in flight — "
             "closing anyway; ")
            + "replicas "
            + ", ".join(f"r{s['replica']}: {s['dispatched']} waves "
                        f"({s['steals']} stolen)" for s in stats))
    finally:
        for sig, handler in old.items():
            signal.signal(sig, handler)
        # handle.close() flushes metrics sinks via tel.close()
        handle.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
