"""Host-side step/throughput/MFU accounting.

"Scalable Training of Language Models using JAX pjit and TPUv4" (PAPERS.md)
treats MFU and step-time breakdown as the primary health number of a
pretraining job; the reference framework printed one seq/s line at the END
of the run (run_pretraining.py:574-580), which is exactly when it is no
longer useful. StepWatch keeps per-interval accounting while the job runs:

- wall time per optimization step,
- named host phases that tile the main thread's time from one dispatch
  to the next: `data_wait`, `data_prep`, `h2d`, `dispatch`, `metric_flush`,
  `log`, `checkpoint`, `profile`. `metric_flush` is the ONE place the
  pretraining loop waits for the device (the one-step-lag readback of the
  previous step's metrics; on the v5e it reads 662-665 ms of a 669 ms
  step, PERF.md section 5) — until PR 24 it read 6-7 ms, because the loop
  waited, under no span, in the flight recorder's read of the dispatch
  key. `log` is everything the loop does with a record once it has it
  (log_train / log_perf and their sinks, recorder notes, HBM and compile
  snapshots, SLO and halt checks, the fingerprint hand-over); `profile` is
  jax.profiler's start and stop. Each phase is also a `host/<name>`
  TraceAnnotation, opened and closed with the phase's own clock readings,
  so a --profile_steps trace shows the same spans the record sums. What
  the phases leave over is `loop_unaccounted_ms`,
- seq/s and tokens/s,
- real tokens/s, pad fraction and packing efficiency when the caller feeds
  per-batch real-token counts (`note_tokens`, from the attention mask):
  `tokens_per_sec` counts every slot the device computes — pad included —
  so it measures hardware occupancy, while `real_tokens_per_sec` counts
  only non-pad tokens, i.e. training progress. The gap between them is
  exactly what --packing recovers,
- MFU from the analytic BERT FLOPs-per-step formula below, against the
  device's known peak.

The FLOPs formula is the program's single source of truth for the live
MFU. The benchmark keeps its own copy (benchmark/harness/flops.py) so that
the yardstick does not move when the program does.

Everything here is plain host Python — no device work, no added
host-device sync. Timing uses time.perf_counter (injectable for tests).
"""

from __future__ import annotations

import os
import sys
import time
from contextlib import contextmanager, nullcontext
from typing import Callable, ContextManager, Dict, List, Optional

# Peak dense bf16 FLOP/s per chip by device kind. Source: Google Cloud TPU
# documentation, the per-generation system-architecture pages ("TPU v4",
# "TPU v5e": 197 TFLOP/s bf16 per chip, "TPU v5p", "TPU v6e"). Longest
# matching key wins ('TPU v5 lite' must not hit a 'TPU v5' prefix). A kind
# that is not here is an error (lookup_peak_flops), never a default: MFU
# against a guessed peak is a wrong number under a trusted name.
# The MXU runs f32 matmuls at half the bf16 rate on every listed
# generation, so the f32 peak is derived rather than tabled —
# lookup_peak_flops(kind, dtype="f32") halves these numbers. MFU must be
# quoted against the peak of the dtype the dots actually run in: dividing
# f32-compute FLOP/s by the bf16 peak under-reports utilization 2x (looks
# like headroom that is not there), and quoting a bf16 run against an f32
# peak inflates it 2x.
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e reports device_kind "TPU v5 lite"
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # v6e / Trillium
    "TPU v6e": 918e12,
}
_F32_PEAK_RATIO = 0.5

# Cost accounting price knob, shared by training (StepWatch) and serving
# (serving/batcher.py): device-seconds are priced at this rate per
# device-HOUR. The default of 1.0 makes the cost fields normalized
# device-hours-per-1k-tokens — a hardware-relative efficiency number
# that survives price changes; pass the real $/chip-hour to quote money.
DEFAULT_COST_PER_DEVICE_HOUR = 1.0


def resolve_cost_per_device_hour(value: Optional[float] = None) -> float:
    """Explicit value > BERT_COST_PER_DEVICE_HOUR env > 1.0 default."""
    if value is not None:
        return float(value)
    env = os.environ.get("BERT_COST_PER_DEVICE_HOUR", "").strip()
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    return DEFAULT_COST_PER_DEVICE_HOUR


def lookup_peak_flops(device_kind: str, dtype: str = "bf16") -> float:
    """Peak FLOP/s of one chip of `device_kind` at the given compute dtype
    ("bf16" or "f32"/"float32"). Raises ValueError for a kind the table
    does not know — add it to PEAK_FLOPS with its source."""
    kind = device_kind.lower()
    hits = [v for k, v in sorted(PEAK_FLOPS.items(), key=lambda kv: -len(kv[0]))
            if k.lower() in kind]
    if not hits:
        raise ValueError(
            f"no peak FLOP/s known for device kind {device_kind!r}; add it "
            "to telemetry.stepwatch.PEAK_FLOPS (with its source) before "
            "quoting MFU on it")
    d = dtype.lower()
    if d in ("f32", "float32", "fp32"):
        return hits[0] * _F32_PEAK_RATIO
    if d in ("bf16", "bfloat16"):
        return hits[0]
    raise ValueError(f"unknown compute dtype for peak lookup: {dtype!r}")


def device_peak_flops(device, dtype: str = "bf16") -> Optional[float]:
    """Peak FLOP/s of a jax device for MFU: None on the CPU backend (no MFU
    is reported there), the table's figure on an accelerator — an unknown
    accelerator kind raises (lookup_peak_flops)."""
    if device.platform == "cpu":
        return None
    return lookup_peak_flops(device.device_kind, dtype)


def flops_per_seq(cfg, seq_len: int, vocab: int, n_pred: int) -> float:
    """Analytic fwd+bwd FLOPs for one sequence: 6*params*positions for the
    dense matmuls + 12*L*E*S^2 for attention score/value products. The MLM
    transform + tied decoder run only on the n_pred gathered masked
    positions (models/bert.py BertForPreTraining), so their FLOPs scale
    with n_pred, not S — MFU counts FLOPs actually computed."""
    E, F, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    per_layer = 4 * E * E + 2 * E * F          # qkv+proj, mlp in+out
    trunk = L * per_layer * seq_len
    head = (vocab * E + E * E) * n_pred        # tied decoder + mlm transform
    return 6.0 * (trunk + head) + 12.0 * L * E * seq_len * seq_len


def host_annotation(name: str) -> ContextManager:
    """A `host/...` span in jax.profiler's trace. A process that never
    imported jax (a parent that leaves the chip to its children) has no
    profiler to write to and gets a no-op; this module stays importable
    without jax."""
    jax = sys.modules.get("jax")
    if jax is None:
        return nullcontext()
    return jax.profiler.TraceAnnotation(name)


class SetupWatch:
    """The account of a run's set-up: the wall time from the entry of
    `main()` to the first step's loss on the host, under named spans.

        setup = SetupWatch(start=<clock at main()'s entry>)  # opens 'backend'
        setup.end("backend")
        with setup.span("data"): ...
        setup.begin("first_step") ... setup.end("first_step")  # closes it

    Each span is a `host/setup/<name>` TraceAnnotation when a trace is
    open, and its seconds, LESS what `compile_watch` counted as compiling
    meanwhile, ride in every [perf] record as the cumulative counter
    `setup_<name>_s` beside `compile_secs` (`snapshot()`); a span still
    open counts nothing yet, so the counters only grow. What is left of the
    wall time after the spans and `compile_secs` is `setup_unaccounted_s`,
    taken at the last span's end: time between spans, less compiling that
    no span was open for. `end("first_step")` closes the account; later
    calls change nothing.
    """

    SPANS = ("backend", "data", "state", "lower", "first_step")

    def __init__(self, start: float,
                 time_fn: Callable[[], float] = time.perf_counter,
                 annotate: Callable[[str], ContextManager] = host_annotation):
        self._start = start
        self._time = time_fn
        self._annotate = annotate
        self.compile_watch = None   # set once init_run has installed it
        self._secs = {name: 0.0 for name in self.SPANS}
        self._open: Dict[str, tuple] = {}
        self._unaccounted = 0.0
        self._closed = False
        self.begin("backend", at=start)

    def _compile_secs(self) -> float:
        return self.compile_watch.compile_secs if self.compile_watch else 0.0

    def begin(self, name: str, at: Optional[float] = None) -> None:
        if self._closed or name in self._open:
            return
        note = self._annotate("host/setup/" + name)
        note.__enter__()
        self._open[name] = (self._time() if at is None else at,
                            self._compile_secs(), note)

    def end(self, name: str) -> None:
        if name not in self._open:
            return
        t0, compiled0, note = self._open.pop(name)
        now, compiled = self._time(), self._compile_secs()
        note.__exit__(None, None, None)
        self._secs[name] += (now - t0) - (compiled - compiled0)
        self._unaccounted = (now - self._start - sum(self._secs.values())
                             - compiled)
        if name == "first_step":
            self._closed = True

    @contextmanager
    def span(self, name: str):
        self.begin(name)
        try:
            yield
        finally:
            self.end(name)

    def snapshot(self) -> Dict[str, float]:
        out = {f"setup_{name}_s": round(secs, 3)
               for name, secs in self._secs.items()}
        out["setup_unaccounted_s"] = round(self._unaccounted, 3)
        return out


class StepWatch:
    """Interval accounting for the host train loop.

    Usage:
        sw = StepWatch(flops_per_step=..., seqs_per_step=..., seq_len=...,
                       peak_flops=..., log_freq=10)
        with sw.phase("data_wait"): batch = next(it)
        with sw.phase("dispatch"):  state, m = jit_step(...)
        rec = sw.step_done()        # dict every log_freq steps, else None

    `flops_per_step` must account for the full optimization step — i.e.
    flops_per_seq(...) * (accum_steps * micro_global). With
    --steps_per_loop > 1 pass n=steps_per_loop to step_done; the interval
    math divides by optimization steps, so MFU/seq_per_sec stay exact.

    `peak_flops=None` (the CPU backend, see device_peak_flops) leaves
    `mfu` and `peak_flops` out of the record: there is no peak to quote
    against.
    """

    def __init__(self, flops_per_step: float, seqs_per_step: float,
                 seq_len: int, peak_flops: Optional[float],
                 log_freq: int = 10,
                 time_fn: Callable[[], float] = time.perf_counter,
                 registry=None,
                 n_devices: int = 1,
                 cost_per_device_hour: Optional[float] = None,
                 annotate: Callable[[str], ContextManager] = host_annotation):
        self.flops_per_step = float(flops_per_step)
        self.seqs_per_step = float(seqs_per_step)
        self.seq_len = int(seq_len)
        self.peak_flops = peak_flops
        # cost accounting: interval wall time x n_devices = the
        # device-seconds this job consumed, priced per device-hour —
        # the serving fleet's cost gauges use the identical formula so
        # train and serve cost-per-token are directly comparable
        self.n_devices = max(1, int(n_devices))
        self.cost_per_device_hour = resolve_cost_per_device_hour(
            cost_per_device_hour)
        self.log_freq = max(1, int(log_freq))
        self._time = time_fn
        self._annotate = annotate
        self._phases: Dict[str, float] = {}
        # seconds spent in phases entered from inside each open phase
        self._open: List[List[float]] = []
        # optional fn(name, entering: bool) fired on every phase
        # enter/exit — the hung-step watchdog's feed
        # (resilience/watchdog.py); None costs one attribute load per
        # phase
        self.phase_listener: Optional[Callable[[str, bool], None]] = None
        self._steps = 0
        self._interval_start = self._time()
        self._real_tokens = 0.0
        self._noted_tokens = False
        # registry publication (telemetry/registry.py): the live step
        # counter ticks per step_done call — not per log_freq interval —
        # so a /metrics scrape between intervals still sees progress; the
        # histogram accumulates the per-interval mean step time
        self._steps_total = self._step_hist = None
        if registry is not None:
            self._steps_total = registry.counter(
                "bert_train_steps_total", "optimization steps completed")
            self._step_hist = registry.histogram(
                "bert_step_time_ms_hist",
                "distribution of per-step wall time (ms), sampled per "
                "StepWatch interval")

    @contextmanager
    def phase(self, name: str):
        """Time a leaf phase of the loop and show it as `host/<name>` in a
        profiler trace: the annotation opens and closes at the phase's own
        clock readings. A phase entered from inside another counts once:
        its seconds go to it and come off the one around it, so the phases
        of an interval never sum past its wall time."""
        listener = self.phase_listener
        if listener is not None:
            listener(name, True)
        inside = [0.0]
        self._open.append(inside)
        with self._annotate("host/" + name):
            t0 = self._time()
            try:
                yield
            finally:
                spent = self._time() - t0
                self._open.pop()
                if self._open:
                    self._open[-1][0] += spent
                self._phases[name] = (self._phases.get(name, 0.0)
                                      + spent - inside[0])
                if listener is not None:
                    listener(name, False)

    @contextmanager
    def pause(self):
        """Exclude a non-training span (mid-epoch eval, restore) from the
        interval wall clock by advancing the interval start past it —
        without this, an epoch-boundary eval silently inflates the NEXT
        interval's step_time_ms and deflates its seq/s and MFU."""
        t0 = self._time()
        try:
            yield
        finally:
            self._interval_start += self._time() - t0

    def note_tokens(self, real_tokens: float) -> None:
        """Count a dispatched batch's REAL (non-pad) tokens — typically
        `attention_mask.sum()` on the host-side numpy batch, a cost of
        microseconds. Unlocks `real_tokens_per_sec` / `pad_fraction` /
        `packing_efficiency` in the interval record; without any call the
        record carries only the slot-token throughput, as before."""
        self._real_tokens += float(real_tokens)
        self._noted_tokens = True

    def step_done(self, n: int = 1) -> Optional[Dict[str, float]]:
        """Count n optimization steps; at a log_freq boundary, return the
        interval record and reset."""
        self._steps += n
        if self._steps_total is not None:
            self._steps_total.inc(n)
        if self._steps < self.log_freq:
            return None
        return self._emit()

    def flush(self) -> Optional[Dict[str, float]]:
        """Force out the partial interval (None if no steps since the last
        boundary). The crash-safe exit path: a SIGTERM or exception must
        not lose the buffered accounting of up to log_freq-1 steps."""
        if self._steps == 0:
            return None
        return self._emit()

    def _emit(self) -> Dict[str, float]:
        now = self._time()
        wall = max(now - self._interval_start, 1e-9)
        steps = self._steps
        seqs_per_sec = self.seqs_per_step * steps / wall
        achieved = self.flops_per_step * steps / wall
        rec = {
            "steps": steps,
            "step_time_ms": round(wall / steps * 1e3, 3),
            "seq_per_sec": round(seqs_per_sec, 2),
            "tokens_per_sec": round(seqs_per_sec * self.seq_len, 1),
            "model_flops_per_sec": round(achieved, 1),
        }
        if self.peak_flops:
            rec["mfu"] = round(achieved / self.peak_flops, 6)
            rec["peak_flops"] = self.peak_flops
        if self._noted_tokens:
            # slot tokens = everything the device computed (pad included);
            # real tokens = training progress. packing_efficiency is their
            # ratio — with packing off it is simply 1 - pad_fraction of the
            # natural corpus, the number that says what packing would buy
            slot_tokens = self.seqs_per_step * steps * self.seq_len
            eff = self._real_tokens / max(slot_tokens, 1.0)
            rec["real_tokens_per_sec"] = round(self._real_tokens / wall, 1)
            rec["pad_fraction"] = round(max(0.0, 1.0 - eff), 6)
            rec["packing_efficiency"] = round(eff, 6)
        # device-seconds -> cost-per-token, in EVERY record: interval
        # wall x n_devices priced per device-hour, over real tokens when
        # note_tokens fed them (training progress) else slot tokens
        device_seconds = wall * self.n_devices
        cost_tokens = (self._real_tokens if self._noted_tokens
                       else self.seqs_per_step * steps * self.seq_len)
        rec["device_seconds_per_step"] = round(device_seconds / steps, 6)
        cost = device_seconds / 3600.0 * self.cost_per_device_hour
        rec["cost_per_1k_tokens"] = (round(cost / (cost_tokens / 1000.0), 9)
                                     if cost_tokens > 0 else 0.0)
        if self._step_hist is not None:
            self._step_hist.observe(rec["step_time_ms"])
        for name, secs in sorted(self._phases.items()):
            rec[f"{name}_ms"] = round(secs / steps * 1e3, 3)
        # the residual of the host account: wall time no leaf phase covers
        rec["loop_unaccounted_ms"] = round(
            (wall - sum(self._phases.values())) / steps * 1e3, 3)
        self._phases = {}
        self._steps = 0
        self._interval_start = now
        self._real_tokens = 0.0
        return rec
