"""One wiring path for run telemetry: `init_run(phase=...)`.

Before this module, every entry point hand-assembled the same block —
MetricLogger with the right sinks, CompileWatch with a warn hook into the
logger, StepWatch from the shared FLOPs formula, provenance header — four
slightly-different copies (run_pretraining / run_squad / run_ner and a
benchmark script), and a fifth consumer (the `serving/` process) would
have made five. `init_run` is the single construction site:

    tel = telemetry.init_run(phase="pretrain",
                             log_prefix=os.path.join(out, "logfile"),
                             verbose=dist.is_main_process(),
                             tensorboard=True, jsonl=True,
                             metrics_port=args.metrics_port)
    tel.log_header(**collect_provenance(mesh=mesh))
    sw = tel.make_stepwatch(flops_per_step=..., seqs_per_step=..., ...)
    ...
    tel.log_train(step, step_loss=..., loss_nonfinite=..., ...)
    rec = sw.step_done();  tel.log_perf(step, rec) if rec else None
    ...
    tel.close()

What the handle owns:

- `.logger` — the MetricLogger (all sinks, rank-0 gated by `verbose`).
- `.compile_watch` — installed, warn-wired into the logger.
- `.registry` — the phase-labeled MetricsRegistry every piece publishes
  through (StepWatch steps/step-time, CompileWatch compiles, MetricLogger
  record gauges, the nonfinite counters below).
- `.server` — opt-in `/metrics` + `/healthz` exporter (`metrics_port`).
- `.aggregator` — opt-in multi-host fold (`multihost_dir`): every
  process publishes its interval records; process 0's `log_perf` folds
  cross-host min/mean/max and straggler warnings into its record.
- `.stepwatch` / `.recorder` — attached later (`make_stepwatch`,
  `attach_recorder`) because their parameters only exist mid-setup.
- `.setup` — the entry point's SetupWatch (`attach_setup`; it starts at
  the entry of `main()`, before this handle can exist): its `setup_*_s`
  counters ride in every perf record `log_perf` writes.

`log_train` / `log_perf` are the phase-agnostic record paths: they update
the registry + `/healthz` state, run the multi-host fold, then fan out
through the logger — so a record logged by any phase carries the same
schema and reaches the same places. PERF_RECORD_CORE_KEYS is the
contract every phase's perf record satisfies (asserted per-phase by the
e2e tests).
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, Optional

from bert_pytorch_tpu.telemetry.registry import MetricsRegistry

# every phase's StepWatch interval record carries at least these keys —
# the "identical perf schema" contract the e2e tests pin per entry point
# (`mfu` / `peak_flops` join them on an accelerator only: the CPU backend
# has no peak to quote against, stepwatch.device_peak_flops)
PERF_RECORD_CORE_KEYS = (
    "steps", "step_time_ms", "seq_per_sec", "tokens_per_sec",
    "model_flops_per_sec",
)

# health-pack keys every phase's train record may carry; the subset that
# is present drives the nonfinite counters and /healthz flags
HEALTH_FLAG_KEYS = ("loss_nonfinite", "grad_nonfinite",
                    "skipped_nonfinite", "grad_spike")

# perf-record fields promoted to dedicated gauge families (everything
# else numeric lands in the generic bert_perf{field=...} gauge)
_PERF_GAUGES = {
    "step_time_ms": ("bert_step_time_ms",
                     "wall time per optimization step (ms)"),
    "seq_per_sec": ("bert_seq_per_sec", "sequences per second"),
    "tokens_per_sec": ("bert_tokens_per_sec",
                       "slot tokens per second (pad included)"),
    "mfu": ("bert_mfu", "model FLOPs utilization vs device peak"),
}


class TelemetryRun:
    """The per-run telemetry handle. Construct via `init_run`."""

    def __init__(self, phase: str, logger, compile_watch,
                 registry: MetricsRegistry, server=None, aggregator=None):
        self.phase = phase
        self.logger = logger
        self.compile_watch = compile_watch
        self.registry = registry
        self.server = server
        self.aggregator = aggregator
        self.stepwatch = None
        self.setup = None
        self.recorder = None
        self.stream_loader = None
        self.ckpt_manager = None
        self.slo = None
        self._closed = False
        # restart lineage: tools/supervise.py stamps the attempt index
        # into the child env so the run (and /healthz, and Prometheus)
        # can report how many lives it has already spent
        try:
            self.supervisor_restarts = int(
                os.environ.get("BERT_SUPERVISOR_RESTARTS", "0"))
        except ValueError:
            self.supervisor_restarts = 0
        self._health: Dict[str, Any] = {
            "phase": phase,
            "started_unix": round(time.time(), 3),
            "last_step": None,
            "last_perf_step": None,
            "last_perf": {},
            "last_health": {},
            "last_nonfinite_step": None,
            "nonfinite_flags": {},
            "compiles": 0,
        }
        # declared up front so /metrics shows the zeros from the first
        # scrape, not only after the first flagged step
        self._nonfinite_steps = registry.counter(
            "bert_nonfinite_steps_total",
            "steps flagged non-finite by the in-graph health pack")
        self._loss_nonfinite = registry.counter(
            "bert_loss_nonfinite_steps_total",
            "steps with a non-finite loss")
        self._grad_nonfinite = registry.counter(
            "bert_grad_nonfinite_steps_total",
            "steps with non-finite gradient elements")
        self._steps_total = registry.counter(
            "bert_train_steps_total", "optimization steps completed")
        self._perf_g = {
            k: registry.gauge(name, help)
            for k, (name, help) in _PERF_GAUGES.items()}
        self._perf_other = registry.gauge(
            "bert_perf", "other StepWatch interval fields", labels=("field",))
        if self.supervisor_restarts or "BERT_SUPERVISOR_RESTARTS" in \
                os.environ:
            registry.gauge(
                "bert_supervisor_restarts",
                "restart count of this process under tools/supervise.py"
            ).set(float(self.supervisor_restarts))
            self._health["supervisor_restarts"] = self.supervisor_restarts

    # -- construction-time helpers -------------------------------------------

    def log_header(self, **fields: Any) -> None:
        self.logger.log_header(**fields)

    def make_stepwatch(self, **kwargs):
        """Build the run's StepWatch wired into the registry; kwargs are
        StepWatch's (flops_per_step, seqs_per_step, seq_len, peak_flops,
        log_freq, ...)."""
        from bert_pytorch_tpu.telemetry.stepwatch import StepWatch

        kwargs.setdefault("registry", self.registry)
        self.stepwatch = StepWatch(**kwargs)
        return self.stepwatch

    def attach_setup(self, setup) -> None:
        """The run's set-up account (telemetry/stepwatch.SetupWatch): it
        discounts what this handle's CompileWatch counts as compiling, and
        every perf record carries its cumulative `setup_*_s` counters."""
        self.setup = setup
        setup.compile_watch = self.compile_watch

    def attach_recorder(self, recorder) -> None:
        """Cross-wire the flight recorder: its bundle manifests gain the
        registry snapshot at dump time and a `metrics_tail_source`
        pointing at the jsonl whose records the tail mirrors."""
        self.recorder = recorder
        recorder.registry = self.registry
        if getattr(self.logger, "jsonl_path", None):
            recorder.metrics_tail_source = self.logger.jsonl_path

    def attach_checkpoints(self, manager) -> None:
        """Checkpoint-freshness on /healthz: `last_checkpoint_step` and
        `seconds_since_checkpoint` (training/checkpoint.py freshness()),
        so an external orchestrator can gate restarts/alerts on how much
        work a death right now would cost."""
        self.ckpt_manager = manager

    def attach_slo(self, engine) -> None:
        """SLO plane on /healthz: the engine's ok|degraded|failing
        verdict becomes the payload's top-level `status` and a compact
        `slo` block (telemetry/slo.py; /v1/alerts and /v1/slo carry the
        full views on the serving frontend)."""
        self.slo = engine

    def attach_stream(self, loader) -> None:
        """Streaming-plane runs (data/streaming.py): /healthz names the
        plane's live cursor — epoch / source / record / batches — so an
        operator probing a streaming job sees WHERE in the corpus it is,
        not just that it is stepping."""
        self.stream_loader = loader

    # -- record paths ---------------------------------------------------------

    def log_train(self, step: int, tag: str = "train",
                  **vals: Any) -> None:
        """One per-step record: registry counters + /healthz flags, then
        the logger fan-out. The phase-agnostic replacement for
        `logger.log("train", ...)`."""
        step = int(step)
        self._health["last_step"] = step
        flags = {k: vals[k] for k in HEALTH_FLAG_KEYS
                 if isinstance(vals.get(k), (int, float))}
        if flags:
            self._health["last_health"] = flags
        loss_bad = flags.get("loss_nonfinite", 0) > 0
        grad_bad = flags.get("grad_nonfinite", 0) > 0
        if loss_bad or grad_bad:
            self._health["last_nonfinite_step"] = step
            self._health["nonfinite_flags"] = flags
            self._nonfinite_steps.inc()
            if loss_bad:
                self._loss_nonfinite.inc()
            if grad_bad:
                self._grad_nonfinite.inc()
        self.logger.log(tag, step, **vals)

    def log_perf(self, step: int, record: Dict[str, Any],
                 tag: str = "perf") -> Dict[str, Any]:
        """One StepWatch interval record: multi-host fold (publish this
        host's numbers; on process 0 fold the fleet's into the record),
        registry gauges, /healthz state, then the logger fan-out. Returns
        the (possibly fold-augmented) record actually logged."""
        step = int(step)
        record = dict(record)
        if self.setup is not None:
            record.update(self.setup.snapshot())
        if self.aggregator is not None:
            self.aggregator.publish(step, record)
            if self.aggregator.process_index == 0:
                agg, warning = self.aggregator.fold()
                record.update(agg)
                if warning:
                    self.logger.info("WARNING: " + warning)
        for k, g in self._perf_g.items():
            if isinstance(record.get(k), (int, float)):
                g.set(float(record[k]))
        for k, v in record.items():
            if k in self._perf_g or isinstance(v, bool) \
                    or not isinstance(v, (int, float)):
                continue
            self._perf_other.set(float(v), field=k)
        self._health["last_perf_step"] = step
        self._health["last_perf"] = {
            k: record[k] for k in ("step_time_ms", "seq_per_sec", "mfu",
                                   "data_wait_ms")
            if isinstance(record.get(k), (int, float))}
        if isinstance(record.get("compiles"), (int, float)):
            self._health["compiles"] = int(record["compiles"])
        self.logger.log(tag, step, **record)
        return record

    def healthz(self) -> Dict[str, Any]:
        """The /healthz payload: a consistent snapshot of run liveness."""
        h = dict(self._health)
        h["compiles"] = max(h["compiles"], self.compile_watch.compiles)
        h["uptime_secs"] = round(time.time() - h["started_unix"], 1)
        # machine-readable verdict, ALWAYS present: orchestrators gate
        # on h["status"] without caring whether the SLO plane is on
        if self.slo is not None:
            try:
                h["slo"] = self.slo.health_summary()
                h["status"] = h["slo"]["status"]
            except Exception:
                h["status"] = "ok"  # a probe must never take the run down
        else:
            h["status"] = "ok"
        if self.stream_loader is not None:
            try:
                cursor = dict(self.stream_loader.state_dict())
                cursor.pop("pending", None)  # bulky and not liveness
                h["stream"] = cursor
            except Exception:
                pass  # a probe must never take the run down
        if self.ckpt_manager is not None:
            try:
                step, t = self.ckpt_manager.freshness()
                h["last_checkpoint_step"] = step
                h["seconds_since_checkpoint"] = (
                    round(time.time() - t, 1) if t is not None else None)
            except Exception:
                pass  # a probe must never take the run down
        return h

    # -- teardown -------------------------------------------------------------

    def close(self) -> None:
        """Release everything the handle owns (server first — a scrape
        must not race the logger teardown). Idempotent; each piece is
        guarded so one failing close cannot mask the others."""
        if self._closed:
            return
        self._closed = True
        for fn in ((self.server.close if self.server is not None
                    else None),
                   self.compile_watch.uninstall,
                   (self.aggregator.close if self.aggregator is not None
                    else None),
                   self.logger.close):
            if fn is None:
                continue
            try:
                fn()
            except Exception:
                pass

    def __enter__(self) -> "TelemetryRun":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()


def init_run(phase: str,
             log_prefix: Optional[str] = None,
             verbose: bool = True,
             stream=None,
             tensorboard: bool = False,
             jsonl: bool = True,
             metrics_port: Optional[int] = None,
             metrics_host: str = "0.0.0.0",
             registry: Optional[MetricsRegistry] = None,
             multihost_dir: Optional[str] = None,
             process_index: int = 0,
             process_count: int = 1,
             straggler_z: float = 3.0) -> TelemetryRun:
    """Build the run's telemetry in one call — THE wiring path every
    entry point uses; see the module docstring for the
    handle's surface.

    `metrics_port=None` disables the exporter; `0` binds an ephemeral
    port (read `tel.server.port`). `multihost_dir` enables the per-host
    publish + process-0 fold (pass `process_index`/`process_count` from
    dist — this module never imports jax)."""
    from bert_pytorch_tpu.training.metrics import MetricLogger
    from bert_pytorch_tpu.telemetry.compile_watch import CompileWatch

    registry = registry if registry is not None \
        else MetricsRegistry(constant_labels={"phase": phase})
    logger = MetricLogger(log_prefix=log_prefix, verbose=verbose,
                          stream=stream, tensorboard=tensorboard,
                          jsonl=jsonl, registry=registry)
    compile_watch = CompileWatch(
        warn=lambda msg: logger.info("WARNING: " + msg),
        registry=registry).install()

    aggregator = None
    if multihost_dir:
        from bert_pytorch_tpu.telemetry.multihost import \
            HostMetricsAggregator

        aggregator = HostMetricsAggregator(
            multihost_dir, process_index=process_index,
            process_count=process_count, z_threshold=straggler_z)

    tel = TelemetryRun(phase, logger, compile_watch, registry,
                       aggregator=aggregator)
    if metrics_port is not None:
        from bert_pytorch_tpu.telemetry.exporter import MetricsServer

        tel.server = MetricsServer(registry, healthz_fn=tel.healthz,
                                   port=metrics_port, host=metrics_host)
        logger.info(f"metrics: serving /metrics and /healthz on "
                    f"{tel.server.url} (phase={phase})")
    return tel
