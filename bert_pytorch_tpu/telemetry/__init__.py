"""Training telemetry: eyes on a running job.

Four pieces, one per failure mode the ROADMAP's "fast as the hardware
allows" goal keeps hitting blind:

- `health`  — in-graph (device-side) numerical health pack: non-finite
  counts for loss and per-param-group gradients, grad-norm EMA + z-score
  spike flag, param-norm drift, and the ZeRO-safe `skip` update guard.
  Signals ride in the train step's existing metrics dict, so the host's
  one-step-lag readback stays non-blocking.
- `stepwatch` — host-side per-interval accounting: step wall time, data-wait
  vs dispatch vs metric-flush time, seq/s, tokens/s, and MFU from the
  analytic BERT FLOPs formula (`flops_per_seq`).
- `compile_watch` — jax.monitoring listener counting XLA compiles and their
  durations, loud on recompiles after warmup (the ZeRO-1 gate saga: a
  silent recompile is a silent 2x step time), plus device memory_stats
  snapshots (peak HBM).
- `provenance` — run stamps (git SHA, jax/jaxlib versions, mesh shape,
  xla_flags pack) so every log header is self-describing.
- `flight_recorder` — the black box: a bounded host-side ring of the last
  K batches + RNGs + metric records, dumped as a self-contained repro
  bundle when the health pack flags a step or the process dies;
  tools/replay.py re-executes the offending step from the bundle plus the
  matching checkpoint, bit-identically, and bisects the first non-finite
  model scope.
- `trace` — profiler-trace summarizer: buckets a jax.profiler trace's
  events into collective vs compute vs host time (reusing the host-loop
  TraceAnnotations), the attribution layer under the multichip scaling
  numbers; tools/trace_summary.py is the CLI.
- `registry` / `exporter` / `multihost` / `run` — the phase-agnostic
  metrics plane: one registry (counters/gauges/histograms with labels)
  every producer above publishes through, a stdlib `/metrics` +
  `/healthz` HTTP exporter (`--metrics_port`), per-host metrics jsonl
  with a process-0 cross-host fold + straggler detection, and
  `init_run(phase=...)` — the single wiring path all entry points
  construct their telemetry through.

Re-exports resolve LAZILY (PEP 562): `health` pulls in jax+flax at import
time, and a parent process that starts children on the chip imports only
the pure-host pieces (stepwatch/provenance) and stays jax-free so its
children can own the backend.

docs/OBSERVABILITY.md is the operator-facing guide.
"""

_EXPORTS = {
    "HealthConfig": ("bert_pytorch_tpu.telemetry.health", "HealthConfig"),
    "TelemetryState": ("bert_pytorch_tpu.telemetry.health",
                       "TelemetryState"),
    "init_telemetry_state": ("bert_pytorch_tpu.telemetry.health",
                             "init_telemetry_state"),
    "StepWatch": ("bert_pytorch_tpu.telemetry.stepwatch", "StepWatch"),
    "SetupWatch": ("bert_pytorch_tpu.telemetry.stepwatch", "SetupWatch"),
    "flops_per_seq": ("bert_pytorch_tpu.telemetry.stepwatch",
                      "flops_per_seq"),
    "lookup_peak_flops": ("bert_pytorch_tpu.telemetry.stepwatch",
                          "lookup_peak_flops"),
    "device_peak_flops": ("bert_pytorch_tpu.telemetry.stepwatch",
                          "device_peak_flops"),
    "CompileWatch": ("bert_pytorch_tpu.telemetry.compile_watch",
                     "CompileWatch"),
    "hbm_snapshot": ("bert_pytorch_tpu.telemetry.compile_watch",
                     "hbm_snapshot"),
    "collect_provenance": ("bert_pytorch_tpu.telemetry.provenance",
                           "collect"),
    "FlightRecorder": ("bert_pytorch_tpu.telemetry.flight_recorder",
                       "FlightRecorder"),
    "validate_bundle": ("bert_pytorch_tpu.telemetry.flight_recorder",
                        "validate_bundle"),
    "summarize_trace": ("bert_pytorch_tpu.telemetry.trace",
                        "summarize_trace"),
    "MetricsRegistry": ("bert_pytorch_tpu.telemetry.registry",
                        "MetricsRegistry"),
    "MetricsServer": ("bert_pytorch_tpu.telemetry.exporter",
                      "MetricsServer"),
    "HostMetricsAggregator": ("bert_pytorch_tpu.telemetry.multihost",
                              "HostMetricsAggregator"),
    "init_run": ("bert_pytorch_tpu.telemetry.run", "init_run"),
    "TelemetryRun": ("bert_pytorch_tpu.telemetry.run", "TelemetryRun"),
    "PERF_RECORD_CORE_KEYS": ("bert_pytorch_tpu.telemetry.run",
                              "PERF_RECORD_CORE_KEYS"),
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    try:
        module_name, attr = _EXPORTS[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}") from None
    import importlib

    return getattr(importlib.import_module(module_name), attr)


def __dir__():
    return __all__
