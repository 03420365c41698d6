"""Checkpoint / auto-resume on top of orbax.

Parity targets (SURVEY §5.4):
- checkpoint dict {model, optimizer, sampler, epoch} — here {state, extra}
  where state is the TrainState pytree and extra is JSON (sampler cursor,
  epoch, config echo) (reference run_pretraining.py:501-511);
- rank-0-coordinated multi-host write, every `num_steps_per_checkpoint`
  optimization steps (reference :484-492) — orbax handles the multi-host
  coordination natively;
- rolling window of the most recent 3 (reference :513-516);
- auto-resume: newest step found in the directory wins (reference scans for
  ckpt_*.pt and takes max, run_pretraining.py:236-255);
- two-phase handoff: checkpoints are named by *global* step
  (ckpt_{global+previous_phase_end}, reference :497-500). Phase 2 restores
  phase-1 state and keeps the optimizer moments; the new phase's schedule
  takes `offset=previous_phase_end_step` (optim/schedulers.py) instead of the
  reference's in-place rewrite of optimizer hyperparameters (:288-299).

Resilience layer (round 17, bert_pytorch_tpu/resilience/manifest.py,
docs/RESILIENCE.md): every committed checkpoint gains a jax-free
`integrity.json` sidecar (per-item content digests + provenance +
sampler/stream-cursor echo + program fingerprint), written AFTER the
async commit lands; `restore` verifies digests BEFORE deserializing and
raises CorruptCheckpointError on mismatch; `restore_with_fallback`
quarantines a corrupt newest checkpoint (renamed `<step>.corrupt`, loud
warning naming the failed item) and walks `all_steps()` newest→oldest
instead of crashing. Save/restore health is published through the
optional registry (`bert_ckpt_saves_total` / `bert_ckpt_failures_total`)
and `freshness()` feeds /healthz `last_checkpoint_step` /
`seconds_since_checkpoint`.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import orbax.checkpoint as ocp

from bert_pytorch_tpu.resilience.manifest import (CorruptCheckpointError,
                                                  quarantine_step,
                                                  step_dir_path,
                                                  verify_step_dir,
                                                  write_step_manifest)


def _placed_here(abstract_state: Any) -> Any:
    """The restore template with every leaf that names no sharding placed
    on this process's first device. A checkpoint records the devices that
    wrote it; restored without a target, orbax re-creates that placement
    and fails wherever those devices do not exist — a CPU-built serving
    fixture on the TPU, a 4-chip training checkpoint on one chip. Leaves
    that carry a sharding (a resuming trainer's state) keep it."""
    here = jax.sharding.SingleDeviceSharding(jax.local_devices()[0])

    def place(leaf):
        if (isinstance(leaf, jax.ShapeDtypeStruct)
                and getattr(leaf, "sharding", None) is None):
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=here)
        return leaf

    return jax.tree.map(place, abstract_state)


class CheckpointManager:
    """Thin wrapper over ocp.CheckpointManager with the reference's policy."""

    def __init__(self, directory: str, max_to_keep: int = 3,
                 save_interval_steps: int = 1,
                 registry=None, log: Callable[[str], None] = print):
        directory = os.path.abspath(directory)
        self.directory = directory
        self._max_to_keep = max_to_keep
        self._save_interval_steps = save_interval_steps
        self._log = log
        self._mgr = self._open()
        # context stamped into every integrity sidecar; the entry point
        # fills provenance at setup and the program fingerprint when the
        # first dispatch's HLO parse lands (run_pretraining.py)
        self.manifest_context: Dict[str, Any] = {}
        # steps saved but (possibly) not yet committed: their sidecars are
        # written at the next wait()/save() once the async commit is final
        # (save() hands the digesting to a daemon worker; wait() drains
        # synchronously)
        self._pending_manifests: Dict[int, Any] = {}
        self._manifest_worker = None
        # freshness for /healthz (telemetry/run.py attach_checkpoints)
        self.last_saved_step: Optional[int] = None
        self.last_saved_time: Optional[float] = None
        self._saves_total = self._failures_total = None
        if registry is not None:
            self._saves_total = registry.counter(
                "bert_ckpt_saves_total", "checkpoint saves issued")
            self._failures_total = registry.counter(
                "bert_ckpt_failures_total",
                "checkpoint save/commit/sidecar failures")

    def _open(self):
        options = ocp.CheckpointManagerOptions(
            max_to_keep=self._max_to_keep,
            save_interval_steps=self._save_interval_steps,
            create=True,
            enable_async_checkpointing=True,
        )
        return ocp.CheckpointManager(self.directory, options=options)

    def _reopen(self) -> None:
        """Rebuild the underlying manager after an external directory
        mutation (quarantine rename): orbax caches its step scan, and a
        stale cache would make the rolling-window GC or latest_step()
        chase a renamed directory."""
        try:
            self._mgr.close()
        except Exception:
            pass
        self._mgr = self._open()

    def save(self, step: int, state: Any,
             extra: Optional[Dict[str, Any]] = None) -> bool:
        """Async save; returns False if skipped by save_interval policy.
        Sidecar manifests for previously-issued saves are flushed here
        (their commits are final once the previous async save drains) —
        on a BACKGROUND thread: digesting a multi-GB checkpoint must not
        stall the train loop inside the watchdog-watched 'checkpoint'
        phase (a slow filesystem would read as a device hang)."""
        if self._pending_manifests:
            try:
                self._mgr.wait_until_finished()
            except Exception:
                if self._failures_total is not None:
                    self._failures_total.inc()
                raise
            self._spawn_manifest_flush()
        args = {"state": ocp.args.StandardSave(state)}
        if extra is not None:
            args["extra"] = ocp.args.JsonSave(extra)
        try:
            saved = self._mgr.save(step, args=ocp.args.Composite(**args))
        except Exception:
            if self._failures_total is not None:
                self._failures_total.inc()
            raise
        if saved:
            self._pending_manifests[int(step)] = extra
            self.last_saved_step = int(step)
            self.last_saved_time = time.time()
            if self._saves_total is not None:
                self._saves_total.inc()
        return saved

    def _spawn_manifest_flush(self) -> None:
        """Hand the pending sidecars to a daemon worker. Caller must have
        waited out the async commit first — digesting an in-flight write
        would freeze a lie into the sidecar."""
        import threading

        self._join_manifest_worker()
        pending, self._pending_manifests = self._pending_manifests, {}
        self._manifest_worker = threading.Thread(
            target=self._write_manifests, args=(pending,),
            name="ckpt-integrity-sidecars", daemon=True)
        self._manifest_worker.start()

    def _join_manifest_worker(self, timeout: Optional[float] = None
                              ) -> None:
        worker = self._manifest_worker
        if worker is not None:
            worker.join(timeout=timeout)
            self._manifest_worker = None

    def _flush_manifests(self) -> None:
        """Synchronous drain: join any in-flight worker, then write the
        remaining sidecars on THIS thread — wait()/close() and the
        emergency-save path need them on disk before the process exits."""
        self._join_manifest_worker()
        pending, self._pending_manifests = self._pending_manifests, {}
        self._write_manifests(pending)

    def _write_manifests(self, pending: Dict[int, Any]) -> None:
        for step, extra in sorted(pending.items()):
            sd = step_dir_path(self.directory, step)
            if not os.path.isdir(sd):
                continue  # evicted by the rolling window before commit
            try:
                write_step_manifest(
                    sd, step, extra_echo=extra,
                    provenance=self.manifest_context.get("provenance"),
                    program_fingerprint=self.manifest_context.get(
                        "program_fingerprint"))
            except Exception as e:
                if self._failures_total is not None:
                    self._failures_total.inc()
                self._log(f"WARNING: integrity sidecar for checkpoint "
                          f"step {step} failed: {e} (checkpoint itself "
                          "is committed; it will restore unverified)")

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def all_steps(self, read: bool = False) -> list:
        """Every completed checkpoint step, ascending. tools/replay.py uses
        this to pick the newest checkpoint whose gap to the target step
        the flight-recorder bundle's records actually cover. read=True
        forces a directory re-scan (the fallback walk needs fresh truth
        after a quarantine rename)."""
        if read:
            self._mgr.reload()
        return sorted(int(s) for s in self._mgr.all_steps())

    def verify(self, step: int) -> Optional[list]:
        """Integrity-check one committed step against its sidecar:
        None = no sidecar (legacy checkpoint, unverifiable), [] = clean,
        list of errors = corrupt. Never raises for a missing sidecar;
        a torn sidecar IS corruption (manifest.read_step_manifest)."""
        return verify_step_dir(step_dir_path(self.directory, step))

    def restore(self, abstract_state: Any, step: Optional[int] = None
                ) -> Tuple[Any, Dict[str, Any], int]:
        """Restore (state, extra, step). abstract_state (e.g. from
        jax.eval_shape, with shardings attached) drives sharded restore —
        arrays land directly on their devices, no host bounce.

        Digests are verified BEFORE deserialization: a corrupt
        checkpoint raises CorruptCheckpointError naming the failed item,
        never a tensorstore stack trace."""
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(
                f"no checkpoint found under {self.directory}")
        errors = self.verify(step)
        if errors:
            raise CorruptCheckpointError(step, errors)
        restored = self._mgr.restore(
            step,
            args=ocp.args.Composite(
                state=ocp.args.StandardRestore(_placed_here(abstract_state))),
        )
        extra = self._read_extra(step)
        return restored["state"], extra, step

    def restore_either_layout(self, abstract_state: Any,
                              step: Optional[int] = None
                              ) -> Tuple[Any, Dict[str, Any], int]:
        """Restore like `restore`, but tolerate a checkpoint written under
        the OTHER encoder parameter layout (config.stacked_params flipped
        between save and resume): on a structure mismatch, retry with the
        template converted to the alternate layout and convert the restored
        state back. The conversion is bit-exact (models/pretrained.py
        stack_layer_tree/unstack_layer_tree), so a stacked-era checkpoint
        resumes into an unstacked run — and vice versa — with identical
        values."""
        from bert_pytorch_tpu.models.pretrained import (convert_tree_layout,
                                                        tree_layout)

        try:
            return self.restore(abstract_state, step)
        except FileNotFoundError:
            raise
        except CorruptCheckpointError:
            # bugfix (round 17): a digest-mismatched checkpoint is NOT a
            # layout mismatch — short-circuit before the layout retry, or
            # the retry's confusing structure complaint masks the real,
            # actionable corruption error until first_err surfaces
            raise
        except Exception as first_err:
            want = tree_layout(getattr(abstract_state, "params",
                                       abstract_state))
            if want is None:
                raise
            alt = convert_tree_layout(abstract_state,
                                      stacked=(want == "unstacked"))
            try:
                state, extra, step = self.restore(alt, step)
            except Exception:
                # the alternate layout fails too: this was never a layout
                # mismatch (shape/dtype drift, ...) — surface the
                # ORIGINAL, actionable error, not the second attempt's
                # confusing structure complaint
                raise first_err
            return (convert_tree_layout(state, stacked=(want == "stacked")),
                    extra, step)

    def restore_with_fallback(self, abstract_state: Any
                              ) -> Tuple[Any, Dict[str, Any], int]:
        """Auto-resume that survives a torn/corrupt newest checkpoint:
        walk `all_steps()` newest→oldest; a step that fails integrity
        verification (or fails to deserialize while unverifiable) is
        QUARANTINED (renamed `<step>.corrupt`) with a loud warning naming
        the failed item, and the walk continues. A checkpoint whose
        digests VERIFY but whose restore still raises is surfaced as-is:
        intact data + failing restore means config/shape drift, i.e. an
        operator error quarantining would silently destroy evidence of.

        Raises CorruptCheckpointError when every checkpoint was
        quarantined, FileNotFoundError when there were none to begin
        with."""
        steps = self.all_steps(read=True)
        if not steps:
            raise FileNotFoundError(
                f"no checkpoint found under {self.directory}")
        quarantined = []
        deferred = []   # sidecar-less restore failures, quarantine pending
        first_err: Optional[BaseException] = None
        for step in reversed(steps):
            errors = None
            try:
                # verify INSIDE the try: a torn/unreadable sidecar raises
                # CorruptCheckpointError itself and must quarantine + walk
                # like any other corruption, not crash the resume
                errors = self.verify(step)
                if errors:
                    raise CorruptCheckpointError(step, errors)
                result = self.restore_either_layout(abstract_state, step)
            except CorruptCheckpointError as e:
                dst = quarantine_step(self.directory, step)
                quarantined.append(step)
                self._log(
                    f"WARNING: checkpoint step {step} is CORRUPT — "
                    f"{'; '.join(e.errors)}. Quarantined to {dst}; "
                    "auto-resume falling back to the next-newest "
                    "checkpoint")
                self._reopen()
                continue
            except Exception as e:
                if errors is None:
                    # unverifiable (no sidecar) AND undeserializable:
                    # PROBABLY torn — but an environmental failure
                    # (config/mesh drift, transient FS error) looks the
                    # same and would hit every legacy checkpoint in the
                    # walk. Defer the quarantine until a deeper
                    # checkpoint proves the environment can restore at
                    # all; if nothing restores, surface the error and
                    # rename NOTHING.
                    first_err = first_err or e
                    deferred.append(step)
                    self._log(
                        f"WARNING: checkpoint step {step} failed to "
                        f"restore ({type(e).__name__}: {e}) and has no "
                        "integrity sidecar to verify against — falling "
                        "back (quarantine deferred until an older "
                        "checkpoint restores)")
                    continue
                # digests verified clean: the data is intact and the
                # failure is structural (config drift) — surface it
                raise
            # success: the environment restores fine, so the deferred
            # failures really were torn checkpoints — quarantine them now
            for dstep in deferred:
                dst = quarantine_step(self.directory, dstep)
                quarantined.append(dstep)
                self._log(
                    f"WARNING: checkpoint step {dstep} (unverifiable, "
                    f"failed to restore) quarantined to {dst} — step "
                    f"{step} restored cleanly, so the failure was the "
                    "checkpoint, not the environment")
            if deferred:
                self._reopen()
            return result
        if first_err is not None:
            # nothing restored and at least one failure was unverifiable:
            # this smells like config drift or an environmental fault —
            # surface the newest error, destroy no evidence
            raise first_err
        raise CorruptCheckpointError(
            None, [f"every checkpoint under {self.directory} failed "
                   f"verification; quarantined steps: {quarantined}"])

    def restore_raw(self, step: Optional[int] = None) -> Tuple[Any, int]:
        """Restore the state tree exactly as saved (no abstract template, no
        shape enforcement). For transfer-style loads — e.g. finetuning pulls
        encoder weights out of a pretraining checkpoint whose head shapes
        differ (reference loads ckpt['model'] with strict=False,
        run_squad.py:961)."""
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(
                f"no checkpoint found under {self.directory}")
        # "as saved" means the saved TREE, not the saved placement: with no
        # target orbax re-creates the sharding the writer used, and fails
        # wherever those devices are not this process's (a pretraining
        # checkpoint from a 4-chip mesh seeding a 1-chip finetune). Build
        # the target from the checkpoint's own array metadata instead.
        saved = ocp.StandardCheckpointer().metadata(
            os.path.join(step_dir_path(self.directory, step), "state"))
        abstract = jax.tree.map(
            lambda m: jax.ShapeDtypeStruct(m.shape, m.dtype),
            saved.item_metadata.tree)
        restored = self._mgr.restore(
            step, args=ocp.args.Composite(
                state=ocp.args.StandardRestore(_placed_here(abstract))))
        return restored["state"], step

    def _read_extra(self, step: int) -> Dict[str, Any]:
        # Distinguish "saved without extra" (fine, return {}) from "extra is
        # present but unreadable" (corrupt ckpt — surface it rather than
        # silently resetting the sampler and re-reading consumed data).
        try:
            items = self._mgr.item_metadata(step)
            has_extra = "extra" in items
        except Exception:
            has_extra = True  # metadata unreadable: attempt restore, let it raise
        if not has_extra:
            return {}
        restored = self._mgr.restore(
            step, args=ocp.args.Composite(extra=ocp.args.JsonRestore()))
        return restored.get("extra") or {}

    def freshness(self) -> Tuple[Optional[int], Optional[float]]:
        """(last checkpoint step, unix time it landed) for /healthz
        checkpoint-freshness gating. Falls back to the on-disk newest
        step + its directory mtime when this process has not saved yet
        (a freshly-resumed run reports the checkpoint it restored)."""
        if self.last_saved_step is not None:
            return self.last_saved_step, self.last_saved_time
        step = self.latest_step()
        if step is None:
            return None, None
        try:
            t = os.path.getmtime(step_dir_path(self.directory, step))
        except OSError:
            t = None
        return step, t

    def wait(self) -> None:
        try:
            self._mgr.wait_until_finished()
        except Exception:
            if self._failures_total is not None:
                self._failures_total.inc()
            raise
        self._flush_manifests()

    def close(self) -> None:
        self.wait()
        self._mgr.close()
