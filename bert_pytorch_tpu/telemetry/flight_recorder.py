"""Flight recorder: the training job's black box.

PR 8 gave a running job eyes (health pack / StepWatch / CompileWatch), but
when a step goes bad the operator still only learns "non-finite grad in
group X", one step late — the batch, RNG, and state that produced it are
gone, and a crash loses the buffered tail of the metrics stream entirely.
Large-scale pjit training reports NaN/divergence triage as a dominant
operational cost ("Scalable Training of Language Models using JAX pjit and
TPUv4", PAPERS.md); the fix production systems use is a black box: record
the last K inputs continuously, dump them when something dies.

`FlightRecorder` is that box, host-side and bounded:

- a ring of the last `window` per-step batch records — the loader-output
  numpy batch (packed fields included), the dispatch PRNG key, and the
  step id. References, not copies: the loader materializes fresh arrays
  per batch, so holding them costs zero extra memcpy and the bound is
  `window * batch_nbytes`. The key is kept AS HANDED OVER (a device array
  in the train loop) and turned into host memory only when a bundle is
  dumped: the key of step N is the result of a device program queued
  behind step N-1, so reading it at dispatch time made the loop wait a
  whole step where none of its phases showed it (PERF.md, PR 24);
- a bounded tail of the most recent flushed metric records (the health
  pack's readback), so the bundle says WHAT tripped, not just WITH WHAT;
- `dump()` writes a self-contained repro bundle — `batches.npz` plus a
  `manifest.json` carrying the provenance stamp, the resolved model
  config, and everything `tools/replay.py` needs to rebuild the exact
  train step (accum math, optimizer, schedule, health action, packing,
  mesh) — next to the checkpoints;
- crash handlers: SIGTERM/SIGINT are mapped to `SystemExit(128+sig)` so
  the entry point's except-path can flush metrics and dump before the
  process unwinds, with an atexit backstop for exits that bypass it.

Everything here is plain host Python (numpy + stdlib, no jax import), so
the recorder can never be the thing that kills a run, and the schema
check (`validate_bundle`) runs anywhere.

`tools/replay.py` is the consumer; docs/OBSERVABILITY.md the operator
guide.
"""

from __future__ import annotations

import atexit
import json
import math
import os
import re
import signal
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import numpy as np

# v2 (round 12): + `metrics_tail_source` (the jsonl sink the metrics tail
# mirrors — the cross-ref from a bundle back to the run's full record
# stream) and `registry` (the metrics-registry snapshot at dump time, so
# the bundle carries the run's cumulative counters — steps, compiles,
# nonfinite totals — not just the last few records).
# v2 extension (round 13, same version — the key is OPTIONAL so round-12
# bundles stay valid): + `program_fingerprint`, the compiled train step's
# structural identity (collective counts + donation-summary hash,
# analysis/hlo.program_fingerprint) recorded at the first dispatch;
# tools/replay.py compares it against the program IT compiles and warns
# on divergence — a replay that silently runs a different program is the
# failure mode this kills.
# v2 extension (round 16, same version, OPTIONAL key): + `stream`, the
# streaming data plane's cursor state at dump time (source list + hash +
# per-source offsets + the cursor of the last yielded batch + recent
# batch->record windows, data/streaming.py stream_info()) — so a bundle
# from a streaming-mode run names the exact corpus records in its window
# and an operator can re-point the plane at the same position.
MANIFEST_SCHEMA_VERSION = 2

# run-manifest keys tools/replay.py needs to rebuild the train step; the
# schema check fails loudly on any absence so a stale bundle errors with
# "missing run key", never with a deep jax shape mismatch
REQUIRED_RUN_KEYS = (
    "accum_steps", "steps_per_loop", "seed", "max_pred_row", "grad_dtype",
    "optimizer", "learning_rate", "lr_decay", "warmup_proportion",
    "max_steps", "previous_phase_end_step", "rng_impl", "health_pack",
    "nonfinite_action", "zero1", "mesh", "seq_len", "packing",
)

REQUIRED_MANIFEST_KEYS = (
    "schema_version", "reason", "trigger_step", "created_unix",
    "provenance", "model_config", "run", "checkpoint", "records",
    "metrics_tail", "metrics_tail_source", "registry",
)


# how long one dump() waits, in all, for dispatch keys to reach host
# memory. A live device hands them over within the step in flight; a hung
# one (the watchdog's dump) never does, and the dump must still end.
KEY_READ_PATIENCE_S = 10.0


def _npz_key(step: int, field: str) -> str:
    return f"s{step:08d}__{field}"


def _host_key(rng, patience_s: float) -> Optional[np.ndarray]:
    """The dispatch key in host memory, or None if it cannot be had within
    `patience_s` (read on a helper thread: a device-to-host read has no
    time limit of its own, and a hung device would hold dump() forever)."""
    if isinstance(rng, np.ndarray):
        return rng
    box: List[np.ndarray] = []

    def read():
        try:
            box.append(np.asarray(rng))
        except Exception:
            pass    # reported by the caller as a key it could not read

    reader = threading.Thread(target=read, name="recorder-key-read",
                              daemon=True)
    try:
        reader.start()
    except RuntimeError:    # no new threads this late in interpreter exit
        read()
    else:
        reader.join(patience_s)
    return box[0] if box else None


def per_host_dir(out_dir: str) -> str:
    """Multi-host bundle root: suffix `out_dir` with this process's index.

    Every host's ring holds only ITS loader shard and ITS dispatch keys, so
    on a multi-host run each process must dump its own bundles — two hosts
    dumping the same trigger step into one shared directory race
    `os.makedirs` on the same `stepNNN_reason` path and the loser's
    "_2"-suffixed bundle is indistinguishable from a retry. Single-process
    runs get `out_dir` unchanged (bundle layout identical to round 10), and
    jax is imported lazily so this module stays importable without it
    (the validate_bundle contract)."""
    try:
        import jax

        if jax.process_count() > 1:
            return os.path.join(out_dir, f"host{jax.process_index():05d}")
    except Exception:
        pass
    return out_dir


def _json_strict(obj):
    """Strict-JSON sanitizer: non-finite floats become their repr strings
    ('nan', 'inf', '-inf'). A nonfinite bundle's metrics tail contains
    loss=NaN by construction; bare NaN/Infinity tokens are Python-json-only
    and would make manifest.json unreadable to jq / JS dashboards / strict
    parsers. float('nan') round-trips the strings, which is exactly what
    tools/replay.py does when comparing recorded against replayed."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return repr(obj)
    if isinstance(obj, dict):
        return {k: _json_strict(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_strict(v) for v in obj]
    return obj


class FlightRecorder:
    """Bounded black box for the host train loop.

    Usage (run_pretraining.py):
        recorder = FlightRecorder(out_dir, window=8, run_info=...,
                                  model_config=..., checkpoint_dir=...,
                                  provenance=...)
        loader.batch_tap = recorder.capture_batch   # loader boundary
        recorder.install_crash_handlers()
        recorder.arm()
        ...
        recorder.record_dispatch(step, n_steps, rng)  # per jit dispatch
        recorder.note_metrics(step, vals)             # per readback
        path = recorder.dump("nonfinite", trigger_step=step)  # on alarm
        ...
        recorder.disarm(); recorder.close()

    `window` bounds the ring in BATCHES (optimization steps), not
    dispatches: with --steps_per_loop n, one dispatch consumes n slots.
    A dispatch wider than the ring keeps only its trailing steps —
    replay then reports the coverage gap loudly instead of lying.
    """

    def __init__(self, out_dir: str, window: int = 8,
                 metrics_tail: int = 64,
                 run_info: Optional[Dict[str, Any]] = None,
                 model_config: Optional[Dict[str, Any]] = None,
                 checkpoint_dir: Optional[str] = None,
                 provenance: Optional[Dict[str, Any]] = None,
                 checkpoint_step_fn: Optional[Callable[[], Any]] = None,
                 metrics_tail_source: Optional[str] = None,
                 registry=None):
        self.out_dir = out_dir
        self.window = max(1, int(window))
        self.run_info = dict(run_info or {})
        self.model_config = dict(model_config or {})
        self.checkpoint_dir = checkpoint_dir
        self.provenance = dict(provenance or {})
        # cross-refs into the metrics plane (set here or later by
        # TelemetryRun.attach_recorder): the jsonl whose records the tail
        # mirrors, and a MetricsRegistry whose snapshot() rides in every
        # manifest dumped
        self.metrics_tail_source = metrics_tail_source
        self.registry = registry
        # set by the entry point once the first dispatch has compiled
        # (analysis/hlo.program_fingerprint via StepProgram.fingerprint)
        self.program_fingerprint: Optional[Dict[str, Any]] = None
        # streaming-plane runs set this to the loader's stream_info so the
        # manifest's optional `stream` key carries the cursor at dump time
        self.stream_info_fn: Optional[Callable[[], Dict[str, Any]]] = None
        self._checkpoint_step_fn = checkpoint_step_fn
        self._staged: List[Dict[str, np.ndarray]] = []
        self._records: deque = deque()
        self._tail: deque = deque(maxlen=max(1, int(metrics_tail)))
        self.last_dump: Optional[str] = None
        self._armed = False
        self._old_handlers: Dict[int, Any] = {}
        self._atexit_registered = False

    # -- capture --------------------------------------------------------------

    def capture_batch(self, batch: Dict[str, np.ndarray]) -> None:
        """Loader-boundary tap (PretrainingDataLoader.batch_tap): stage one
        yielded batch. The next record_dispatch binds staged batches to
        step ids; stale stages (peeked / never-dispatched batches) are
        dropped there. Called on the consumer thread, so staging order is
        yield order even with prefetch assembly running ahead."""
        self._staged.append({k: np.asarray(v) for k, v in batch.items()})
        if len(self._staged) > max(self.window, 1):
            del self._staged[0]

    def record_dispatch(self, first_step: int, n_steps: int, rng) -> None:
        """Bind the trailing `n_steps` staged batches to the dispatch that
        just consumed them: steps first_step .. first_step+n_steps-1, all
        sharing the dispatch PRNG key (a --steps_per_loop chunk derives
        inner-step keys by fold_in(rng, pos) — replay reproduces that).
        `rng` is kept as it is (no device-to-host read here: that read
        cannot return before the step in flight ends); dump() converts."""
        take = self._staged[-n_steps:]
        offset = n_steps - len(take)
        for i, batch in enumerate(take):
            pos = offset + i
            self._records.append({
                "step": int(first_step + pos),
                "pos": int(pos),
                "n_steps": int(n_steps),
                "rng": rng,
                "batch": batch,
            })
        self._staged.clear()
        while len(self._records) > self.window:
            self._records.popleft()

    def note_metrics(self, step: int, metrics: Dict[str, Any]) -> None:
        """Append one flushed metric record (already host floats) to the
        bounded tail that rides in the bundle manifest."""
        self._tail.append({"step": int(step),
                           **{k: v for k, v in metrics.items()}})

    def nbytes(self) -> int:
        """Bytes held by the ring + staging — the recorder's whole batch
        footprint (the metrics tail is a few KB of floats)."""
        total = 0
        for rec in self._records:
            total += sum(v.nbytes for v in rec["batch"].values())
        for batch in self._staged:
            total += sum(v.nbytes for v in batch.values())
        return total

    # -- dump -----------------------------------------------------------------

    def dump(self, reason: str, trigger_step: Optional[int] = None) -> str:
        """Write the repro bundle; returns its directory. Never raises into
        the caller's alarm path for cosmetic reasons — but a genuinely
        failed write (disk full) does propagate: a silently-empty black
        box is worse than a second error."""
        reason = re.sub(r"[^A-Za-z0-9_.-]+", "_", str(reason)) or "unknown"
        if trigger_step is None:
            trigger_step = (self._records[-1]["step"] if self._records
                            else 0)
        os.makedirs(self.out_dir, exist_ok=True)
        base = os.path.join(self.out_dir,
                            f"step{int(trigger_step):08d}_{reason}")
        path, n = base, 1
        while os.path.exists(path):
            n += 1
            path = f"{base}_{n}"
        os.makedirs(path)

        arrays: Dict[str, np.ndarray] = {}
        records_meta = []
        unkeyed = []
        deadline = time.monotonic() + KEY_READ_PATIENCE_S
        for rec in self._records:
            sid = rec["step"]
            rng = _host_key(rec["rng"],
                            max(0.2, deadline - time.monotonic()))
            if rng is None:
                # no key, no replay of this step: leave the record out and
                # say so, rather than write a bundle that fails validation
                unkeyed.append(sid)
                continue
            rec["rng"] = rng
            for k, v in rec["batch"].items():
                arrays[_npz_key(sid, k)] = v
            arrays[_npz_key(sid, "rng")] = rng
            records_meta.append({"step": sid, "pos": rec["pos"],
                                 "n_steps": rec["n_steps"],
                                 "fields": sorted(rec["batch"])})
        np.savez(os.path.join(path, "batches.npz"), **arrays)

        latest_ckpt = None
        if self._checkpoint_step_fn is not None:
            try:
                latest_ckpt = self._checkpoint_step_fn()
            except Exception:
                latest_ckpt = None
        manifest = {
            "schema_version": MANIFEST_SCHEMA_VERSION,
            "reason": reason,
            "trigger_step": int(trigger_step),
            "created_unix": round(time.time(), 3),
            "provenance": self.provenance,
            "model_config": self.model_config,
            "run": self.run_info,
            "checkpoint": {"dir": self.checkpoint_dir,
                           "latest_step": latest_ckpt},
            "records": records_meta,
            "metrics_tail": list(self._tail),
            "metrics_tail_source": self.metrics_tail_source,
            "registry": {},
            "program_fingerprint": self.program_fingerprint,
            "stream": None,
        }
        if unkeyed:
            manifest["unkeyed_steps"] = unkeyed
        if self.stream_info_fn is not None:
            try:
                manifest["stream"] = self.stream_info_fn()
            except Exception:
                pass  # cursor snapshot must not kill the alarm path
        if self.registry is not None:
            try:
                manifest["registry"] = self.registry.snapshot()
            except Exception:
                pass  # a broken snapshot must not kill the alarm path
        with open(os.path.join(path, "manifest.json"), "w",
                  encoding="utf-8") as f:
            json.dump(_json_strict(manifest), f, indent=2, allow_nan=False)
        self.last_dump = path
        return path

    # -- crash safety ---------------------------------------------------------

    def arm(self) -> None:
        """Training is in flight: an exit without disarm() is abnormal and
        the atexit backstop will dump."""
        self._armed = True

    def disarm(self) -> None:
        self._armed = False

    def install_crash_handlers(self,
                               signals=(signal.SIGTERM, signal.SIGINT)
                               ) -> None:
        """Map SIGTERM/SIGINT to SystemExit(128+sig) so the train loop's
        except-path flushes metrics and dumps the bundle before the
        process unwinds.
        Also registers an atexit backstop that dumps if the process exits
        while armed with nothing dumped yet. No-op for handlers that
        cannot be installed (non-main thread)."""
        for sig in signals:
            try:
                self._old_handlers[sig] = signal.signal(sig,
                                                        self._on_signal)
            except (ValueError, OSError):
                pass
        if not self._atexit_registered:
            atexit.register(self._atexit_dump)
            self._atexit_registered = True

    def _on_signal(self, signum, frame):
        # minimal work here — the except-path in the entry point does the
        # flushing/dumping with normal (non-async-signal) code
        raise SystemExit(128 + signum)

    def _atexit_dump(self) -> None:
        if self._armed and self.last_dump is None:
            try:
                self.dump("atexit")
            except Exception:
                pass

    def close(self) -> None:
        """Restore signal handlers, unregister atexit, release the ring.
        Idempotent."""
        for sig, old in self._old_handlers.items():
            try:
                signal.signal(sig, old)
            except (ValueError, OSError):
                pass
        self._old_handlers.clear()
        if self._atexit_registered:
            atexit.unregister(self._atexit_dump)
            self._atexit_registered = False
        self._armed = False
        self._records.clear()
        self._staged.clear()


# -- bundle schema validation -------------------------------------------------


def validate_manifest(manifest: Any,
                      npz_keys: Optional[set] = None) -> List[str]:
    """Schema-check a bundle manifest; returns a list of human-readable
    errors (empty = valid). With `npz_keys` (the names inside batches.npz)
    also cross-checks that every record's arrays are actually present —
    the failure mode this kills is a stale/truncated bundle failing
    mysteriously deep inside replay instead of loudly at the door."""
    errors: List[str] = []
    if not isinstance(manifest, dict):
        return ["manifest is not a JSON object"]
    for key in REQUIRED_MANIFEST_KEYS:
        if key not in manifest:
            errors.append(f"missing manifest key '{key}'")
    if errors:
        return errors
    if manifest["schema_version"] != MANIFEST_SCHEMA_VERSION:
        errors.append(
            f"schema_version {manifest['schema_version']!r} != "
            f"{MANIFEST_SCHEMA_VERSION} (this replay tool)")
    run = manifest["run"]
    if not isinstance(run, dict):
        errors.append("'run' is not an object")
    else:
        for key in REQUIRED_RUN_KEYS:
            if key not in run:
                errors.append(f"missing run key '{key}'")
    mc = manifest["model_config"]
    if not isinstance(mc, dict) or "hidden_size" not in mc \
            or "num_hidden_layers" not in mc:
        errors.append("'model_config' is not a BertConfig dict")
    records = manifest["records"]
    if not isinstance(records, list) or not records:
        errors.append("'records' is empty — nothing to replay")
        records = []
    for rec in records:
        if not isinstance(rec, dict) or not {"step", "pos", "n_steps",
                                             "fields"} <= set(rec):
            errors.append(f"malformed record {rec!r}")
            continue
        if not (0 <= rec["pos"] < rec["n_steps"]):
            errors.append(f"record step {rec['step']}: pos {rec['pos']} "
                          f"outside n_steps {rec['n_steps']}")
        if npz_keys is not None:
            for field in list(rec["fields"]) + ["rng"]:
                key = _npz_key(rec["step"], field)
                if key not in npz_keys:
                    errors.append(
                        f"batches.npz missing array '{key}'")
    if not isinstance(manifest["metrics_tail"], list):
        errors.append("'metrics_tail' is not a list")
    if not isinstance(manifest["registry"], dict):
        errors.append("'registry' is not an object (the metrics-registry "
                      "snapshot at dump time)")
    src = manifest["metrics_tail_source"]
    if src is not None and not isinstance(src, str):
        errors.append("'metrics_tail_source' is neither null nor a path")
    fp = manifest.get("program_fingerprint")
    if fp is not None and (not isinstance(fp, dict)
                           or "collective_counts" not in fp
                           or "donation_hash" not in fp):
        errors.append(
            "'program_fingerprint' present but malformed (want the "
            "analysis/hlo.program_fingerprint shape: collective_counts + "
            "donation_hash)")
    stream = manifest.get("stream")
    if stream is not None:
        recent = stream.get("recent_batches") if isinstance(stream, dict) \
            else None
        if not isinstance(stream, dict) \
                or not isinstance(stream.get("sources_hash"), str) \
                or not isinstance(stream.get("sources"), list) \
                or not isinstance(stream.get("cursor"), dict) \
                or not isinstance(recent, (list, type(None))):
            errors.append(
                "'stream' present but malformed (want the "
                "data/streaming.py stream_info shape: sources_hash + "
                "sources + cursor [+ recent_batches list])")
        else:
            for w in recent or []:
                if not isinstance(w, dict) or "record_lo" not in w \
                        or "record_hi" not in w:
                    errors.append(
                        f"'stream.recent_batches' entry malformed: {w!r}")
                    break
    return errors


def validate_bundle(bundle_dir: str) -> List[str]:
    """Validate a bundle directory on disk (manifest + npz cross-check)."""
    manifest_path = os.path.join(bundle_dir, "manifest.json")
    npz_path = os.path.join(bundle_dir, "batches.npz")
    if not os.path.isfile(manifest_path):
        return [f"no manifest.json under {bundle_dir}"]
    try:
        with open(manifest_path, encoding="utf-8") as f:
            manifest = json.load(f)
    except Exception as e:
        return [f"manifest.json unreadable: {e}"]
    if not os.path.isfile(npz_path):
        return [f"no batches.npz under {bundle_dir}"]
    try:
        with np.load(npz_path) as npz:
            keys = set(npz.files)
    except Exception as e:
        return [f"batches.npz unreadable: {e}"]
    return validate_manifest(manifest, npz_keys=keys)
