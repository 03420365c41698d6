"""The one process of a training run that reaches the chip, whatever the
model family.

A thin wrapper of the benchmark's around the user's entry point: it calls
`run_pretraining.main(argv)` in this process, so the program's own host
loop, data plane and compiled step are what is timed. Around that call it

- hands the program the benchmark's weights (made from --seed by the
  family's reference) where the program would have drawn its own, so that
  the reference never takes anything the program made;
- watches the ONE compiled step object the program builds: its first FOLLOW
  calls (set-up) are observed (inputs copied to the host, the optimizer's
  first moment after one step, the parameters' change after FOLLOW), then
  WARM more steps run, then the SAME object is timed for --seconds (and for
  at least the traffic file's `min_window_steps` whole steps);
- takes the window's clock itself: the window opens and closes at the
  program's host read of a step's loss (`TelemetryRun.log_train` is called
  right after it), on this process's clock;
- after the window has closed and the program's state is freed, has the
  family's reference follow the first FOLLOW steps' inputs and writes every
  number compared, beside what the parent needs for the metrics, to --out.

Nothing of the program is edited; the three hooks replace names the entry
point looks up when it runs (`make_sharded_state`, `StepProgram`,
`TelemetryRun.log_train` / `log_perf`). What names a model (the reference,
the adapter, the batch's fields, the family's counters) is the family
module's, benchmark/families/<family>.py, found by the name in the spec.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

FOLLOW = 2      # optimisation steps the reference follows (see PERF.md: three took longer than the window)
WARM = 2        # further warm-up steps before the window opens
LAMB_B1 = 0.9


class WindowClosed(BaseException):
    """Raised from the loss-read hook to end the program's loop."""


class Obs:
    """What the hooks saw."""

    def __init__(self):
        self.calls = 0
        self.batches = []           # host copies of the first FOLLOW inputs
        self.keys = []              # ... and of the steps' rng keys (uint32[2])
        self.grad_sample = None     # a few matrices of mu_1 / (1 - b1), whole
        self.masks = {}             # call number -> device attention mask
        self.segs = {}              # call number -> device segment ids
        self.grad_norms = None      # per-leaf norms of mu_1 / (1 - b1)
        self.delta_norms = None     # per-leaf norms of p_FOLLOW - p_0
        self.opt_share = None       # optimizer bytes on device 0 / total
        self.loss_reads = []        # (clock, step, loss)
        self.scalars = {}           # step -> every value logged with its loss
        self.perf = []              # the program's [perf] records
        self.step_program = None
        self.t_open = self.t_close = None
        self.step_open = self.step_close = None
        self.window_error = None


def _program_argv(spec: dict, family, out_dir: str) -> list:
    t = spec["traffic"]
    mesh_shards = int(t.get("data_shards", 1))
    argv = [
        "--model_config_file", spec["config_path"],
        "--input_dir", spec["data_dir"], "--output_dir", out_dir,
        "--learning_rate", str(t["learning_rate"]),
        "--warmup_proportion", str(t["warmup_proportion"]),
        "--max_steps", str(t["max_steps"]), "--steps", "1000000",
        "--global_batch_size",
        str(t["local_batch"] * t["accum"] * mesh_shards),
        "--local_batch_size", str(t["local_batch"]),
        "--skip_checkpoint", "--log_freq", "1", "--tensorboard", "off",
        "--seed", str(spec["seed"] % 2147483647), "--log_prefix", "bench",
    ] + family.program_args(t) + list(t.get("extra_args", []))
    if spec["rehearse"]:
        argv += list(t.get("rehearse", {}).get("extra_args", []))
    if spec["trace"]:
        lo = FOLLOW + WARM + 2
        argv += ["--profile_steps", f"{lo},{lo + int(t['trace_steps'])}"]
    return argv


def _install_hooks(spec: dict, obs: Obs, family, sizes: dict):
    import jax

    import bert_pytorch_tpu.training as training
    import bert_pytorch_tpu.training.pretrain as pretrain
    from bert_pytorch_tpu.telemetry.run import TelemetryRun

    from benchmark.harness import adapter

    leaf_norms, leaf_diff_norms, sample_matrices = family.adapter_functions(
        sizes)
    # the window holds --seconds AND at least this many whole steps (the
    # traffic file says why, with its readings)
    min_steps = 1 if spec["rehearse"] else int(
        spec["traffic"].get("min_window_steps", 1))

    orig_make = training.make_sharded_state

    def make_sharded_state(*args, **kwargs):
        state, shardings = orig_make(*args, **kwargs)
        state = state.replace(params=adapter.place_like(
            family.weights(spec, sizes), state.params))
        return state, shardings

    training.make_sharded_state = make_sharded_state

    program_step = pretrain.StepProgram

    class ObservedStep(program_step):
        def __call__(self, state, batch, rng):
            obs.calls += 1
            n = obs.calls
            obs.step_program = self
            if n <= FOLLOW:
                obs.batches.append(jax.device_get(batch))
                obs.keys.append(adapter.key_data(rng))
            state, metrics = self.run(state, batch, rng)
            if n == 1:
                mu = state.opt_state.mu
                obs.grad_norms = {
                    k: v / (1.0 - LAMB_B1)
                    for k, v in leaf_norms(mu).items()}
                obs.grad_sample = {
                    k: v / (1.0 - LAMB_B1)
                    for k, v in sample_matrices(mu).items()}
                total = local = 0
                for leaf in jax.tree.leaves(mu):
                    total += leaf.nbytes
                    local += leaf.addressable_shards[0].data.nbytes
                obs.opt_share = local / max(total, 1)
            if n == FOLLOW:
                obs.delta_norms = leaf_diff_norms(
                    state.params, adapter.place_like(
                        family.weights(spec, sizes), state.params))
            if n > FOLLOW:
                # read after the window: real tokens of each timed step
                obs.masks[n] = batch["attention_mask"]
                if "segment_ids" in batch:
                    obs.segs[n] = batch["segment_ids"]
            return state, metrics

        def run(self, state, batch, rng):
            return program_step.__call__(self, state, batch, rng)

    class NoopStep(ObservedStep):
        """--fault noop_step (tests only): the step hands its state back
        unchanged but for the counter."""

        def run(self, state, batch, rng):
            kept = jax.tree.map(jax.numpy.copy, state)
            state, metrics = super().run(state, batch, rng)
            return kept.replace(step=state.step), metrics

    pretrain.StepProgram = (NoopStep if spec.get("fault") == "noop_step"
                            else ObservedStep)

    orig_log_train = TelemetryRun.log_train
    orig_log_perf = TelemetryRun.log_perf

    def log_train(self, step, tag="train", **vals):
        now = time.perf_counter()
        if tag == "train" and "step_loss" in vals:
            step = int(step)
            obs.loss_reads.append((now, step, float(vals["step_loss"])))
            obs.scalars[step] = vals
            if step == FOLLOW + WARM:
                obs.t_open, obs.step_open = now, step
                obs.wall_open = time.time()
            elif (obs.t_open is not None
                  and now - obs.t_open >= spec["seconds"]
                  and step - obs.step_open >= min_steps):
                obs.t_close, obs.step_close = now, step
                orig_log_train(self, step, tag, **vals)
                raise WindowClosed()
        return orig_log_train(self, step, tag, **vals)

    def log_perf(self, step, record, tag="perf"):
        out = orig_log_perf(self, step, record, tag)
        obs.perf.append(dict(out, step=int(step),
                             clock=time.perf_counter()))
        return out

    TelemetryRun.log_train = log_train
    TelemetryRun.log_perf = log_perf


def _kernel_counts(text, names: list) -> dict:
    """How often each named Pallas kernel and each kind of collective
    stands in the compiled step's HLO text."""
    if text is None:
        return {}
    import re

    lines = text.splitlines()
    kernel_lines = [ln for ln in lines
                    if 'custom_call_target="tpu_custom_call"' in ln]
    counts = {n: sum(1 for ln in kernel_lines
                     if re.search(rf"[/(]{re.escape(n)}\)*/pallas_call", ln))
              for n in names}
    for kind in ("all-gather", "all-reduce", "reduce-scatter",
                 "collective-permute"):
        counts[kind] = sum(
            1 for ln in lines
            if f" {kind}(" in ln or f" {kind}-start(" in ln)
    return counts


def _memory_peak(obs: Obs) -> dict:
    import jax

    runtime = limit = 0
    for d in jax.local_devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        runtime = max(runtime, int(stats.get("peak_bytes_in_use", 0)))
        limit = max(limit, int(stats.get("bytes_limit", 0)))
    # the compiler's own account of the timed step, every part of it
    m = obs.step_program.compiled.memory_analysis()
    parts = {k: int(getattr(m, f"{k}_size_in_bytes", 0))
             for k in ("argument", "output", "alias", "temp",
                       "generated_code")}
    parts["peak_memory"] = int(getattr(m, "peak_memory_in_bytes", 0))
    return dict(parts, runtime_peak_bytes=runtime, bytes_limit=limit)


def _compared(got: dict, ref: dict, family, keep_norms: bool) -> dict:
    """The numbers `correct` rests on: `got` (the program's followed steps,
    or the control's in the program's place) against the reference's; with
    --keep, every leaf's norms beside them."""
    import numpy as np

    from benchmark.harness import adapter

    by_matrix = adapter.diff_by_matrix(got["grad_sample"],
                                       ref["grad_sample"])
    norms = {}
    if keep_norms:
        norms["norms"] = {
            f"{who}_{what}": {k: v.tolist() for k, v in
                              side[f"{what}_norms"].items()}
            for who, side in (("got", got), ("reference", ref))
            for what in ("grad", "delta")}
    return dict(
        program_losses=got["losses"], reference_losses=ref["losses"],
        loss_rel=[abs(a - b) / abs(b)
                  for a, b in zip(got["losses"], ref["losses"])],
        grad=adapter.worst_gap(got["grad_norms"], ref["grad_norms"]),
        delta=adapter.worst_gap(got["delta_norms"], ref["delta_norms"]),
        grad_diff=float(np.mean(list(by_matrix.values()))),   # = diff_gap
        grad_diff_by_matrix={k: float(v) for k, v in by_matrix.items()},
        **norms,
        **family.compare_extras(got, ref))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(args.spec, encoding="utf-8") as f:
        spec = json.load(f)
    sys.path.insert(0, spec["root"])
    os.chdir(spec["root"])

    import jax
    import numpy as np

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    want = "cpu" if spec["rehearse"] else "tpu"
    if device["platform"] != want or device["count"] < spec["chips"]:
        print(f"[bench-child] need {spec['chips']} {want} device(s), JAX "
              f"sees {device}", flush=True)
        return 3

    from benchmark.harness import spec as spec_lib

    family = spec_lib.load_family(spec["family"], spec["root"])
    sizes = family.sizes(spec["config"], spec["traffic"])
    obs = Obs()
    _install_hooks(spec, obs, family, sizes)
    out_dir = spec["out_dir"]

    import run_pretraining

    closed = False
    try:
        run_pretraining.main(_program_argv(spec, family, out_dir))
    except WindowClosed:
        closed = True
    except SystemExit as e:
        print(f"[bench-child] the program exited: {e}", flush=True)
    # the program's frames (and its state) must be gone before the
    # reference takes the device
    sys.last_traceback = None
    gc.collect()

    result = {"device": device, "closed": closed,
              "window": None, "compare": None}
    if not closed:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(result, f)
        print("[bench-child] the window never closed", flush=True)
        return 4

    steps_in = list(range(obs.step_open + 1, obs.step_close + 1))
    in_window = set(steps_in)
    real = {n: int(np.asarray(jax.device_get(m)).sum())
            for n, m in obs.masks.items()}
    slots = {n: int(np.prod(m.shape)) for n, m in obs.masks.items()}
    obs.masks.clear()
    segs = {n: np.asarray(jax.device_get(seg))
            for n, seg in obs.segs.items()}
    obs.segs.clear()
    memory = _memory_peak(obs)
    t = spec["traffic"]
    hlo = obs.step_program.as_text()    # tens of MB: rendered once
    counts = _kernel_counts(hlo, list(t.get("expect_kernels", [])))
    scopes = {}
    if spec["trace"] and hlo:
        from benchmark.harness import trace_reduce

        scopes = trace_reduce.scopes_from_hlo(hlo)
    del hlo
    result["window"] = dict({
        "seconds": obs.t_close - obs.t_open,
        "steps": len(steps_in), "first_step": steps_in[0],
        "last_step": steps_in[-1],
        "real_tokens": sum(real.get(n, 0) for n in steps_in),
        "slot_tokens": sum(slots.get(n, 0) for n in steps_in),
        "setup_s": obs.wall_open - spec["start_time"],
        "losses": [l for _, s, l in obs.loss_reads if s in in_window],
        "loss_reads": [(c - obs.t_open, s) for c, s, _ in obs.loss_reads],
        "perf": [p for p in obs.perf if p["step"] in in_window],
        "perf_open": next((p for p in obs.perf
                           if p["step"] == obs.step_open), None),
        "kernel_counts": counts, "opt_share": obs.opt_share,
        "scopes": scopes, "real_by_step": real,
        "traced_first_step": FOLLOW + WARM + 3,
        "memory": memory,
    }, **family.window_extras(segs, obs.scalars, spec))
    del segs
    got = dict({"losses": [l for _, s, l in obs.loss_reads if s <= FOLLOW],
                "grad_norms": obs.grad_norms, "grad_sample": obs.grad_sample,
                "delta_norms": obs.delta_norms},
               **family.followed_by_program(obs.scalars, FOLLOW))
    obs.step_program = None
    gc.collect()
    # the reference may need the whole device (at lfm2's widths a row's
    # float32 gradient pass beside 469 M float32 weights, their gradient
    # and LAMB's moments): whatever of the program's is still on it goes.
    # Everything the comparison needs of the program is on the host.
    for array in jax.live_arrays():
        array.delete()

    t0 = time.perf_counter()
    ref = family.follow(spec, sizes, obs.batches, obs.keys)
    ref_seconds = time.perf_counter() - t0

    keep = bool(spec.get("keep_norms"))
    compare = dict(_compared(got, ref, family, keep),
                   reference_seconds=ref_seconds)
    if spec.get("control"):
        ctl = family.follow(spec, sizes, obs.batches, obs.keys,
                            spec["control"])
        compare["control"] = dict(_compared(ctl, ref, family, keep),
                                  precision=spec["control"])
    result["compare"] = compare
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
