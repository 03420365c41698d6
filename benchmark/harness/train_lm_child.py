"""The one process of a causal-LM training run that reaches the chip (the
cells of the `lfm2_moe` family; drivers/train_lm.py starts it).

The same wrapper around `run_pretraining.main(argv)` as harness/
train_child.py, whose window, counters and memory account it imports: the
program gets the benchmark's weights (made from --seed by the reference's
initialiser, `lfm2_moe_ref.init_params`), the ONE compiled step object is
observed for FOLLOW calls (inputs to the host; after call 1 LAMB's first
moment, i.e. the clipped gradient; after call FOLLOW the parameters' change;
each step's held-expert token counts from the program's counters), warms up
WARM more and is then timed; the window opens and closes at the program's
host read of a step's loss. After the window the reference follows the same
steps, a row at a time.

What differs from train_child.py is what names the model: the reference, the
adapter, the batch's fields (no masking: ids, segments, positions), and the
expert counts. PERF.md section 7 says which of train_child's functions
would have to take the reference and the adapter as arguments for this file
to shrink to those.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness.train_child import (  # noqa: E402
    FOLLOW, LAMB_B1, WARM, Obs, WindowClosed, _kernel_counts, _memory_peak,
    _place_for_reference)

_EXPERT_LOAD = re.compile(r"^moe_l(\d+)_e(\d+)$")


def _program_argv(spec: dict, out_dir: str) -> list:
    t = spec["traffic"]
    argv = [
        "--model_config_file", spec["config_path"],
        "--input_dir", spec["data_dir"], "--output_dir", out_dir,
        "--learning_rate", str(t["learning_rate"]),
        "--warmup_proportion", str(t["warmup_proportion"]),
        "--max_steps", str(t["max_steps"]), "--steps", "1000000",
        "--global_batch_size",
        str(t["local_batch"] * t["accum"] * int(t.get("data_shards", 1))),
        "--local_batch_size", str(t["local_batch"]),
        "--skip_checkpoint", "--log_freq", "1", "--tensorboard", "off",
        "--seed", str(spec["seed"] % 2147483647), "--log_prefix", "bench",
    ] + list(t.get("extra_args", []))
    if spec["rehearse"]:
        argv += list(t.get("rehearse", {}).get("extra_args", []))
    if spec["trace"]:
        lo = FOLLOW + WARM + 2
        argv += ["--profile_steps", f"{lo},{lo + int(t['trace_steps'])}"]
    return argv


def _expert_counts(vals: dict) -> list:
    """[[tokens of each held expert] per routed layer] from a step's
    scalars."""
    loads = {}
    for key, value in vals.items():
        m = _EXPERT_LOAD.match(key)
        if m:
            loads.setdefault(int(m.group(1)), {})[int(m.group(2))] = int(value)
    return [[loads[layer][e] for e in sorted(loads[layer])]
            for layer in sorted(loads)]


def _install_hooks(spec: dict, obs: Obs, sizes: dict):
    import jax

    import bert_pytorch_tpu.training as training
    import bert_pytorch_tpu.training.pretrain as pretrain
    from bert_pytorch_tpu.telemetry.run import TelemetryRun

    from benchmark.harness import adapter, lm_adapter
    from benchmark.reference import lfm2_moe_ref

    # the window holds --seconds AND at least this many whole steps (the
    # traffic file says why, with its readings)
    min_steps = 1 if spec["rehearse"] else int(
        spec["traffic"].get("min_window_steps", 1))
    obs.expert_counts = {}      # step -> [[tokens per held expert] per layer]
    obs.dropped = {}            # step -> held pairs not computed, all layers

    def our_weights():
        tree = lm_adapter.to_program_tree(
            lfm2_moe_ref.init_params(spec["seed"], sizes))
        if spec.get("fault") == "zero_bias":
            # tests only: a program that selects its experts by score alone
            tree = jax.tree_util.tree_map_with_path(
                lambda path, x: x * 0 if getattr(
                    path[-1], "key", None) == "expert_bias" else x, tree)
        return tree

    orig_make = training.make_sharded_state

    def make_sharded_state(*args, **kwargs):
        state, shardings = orig_make(*args, **kwargs)
        state = state.replace(
            params=adapter.place_like(our_weights(), state.params))
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
                    for k, v in lm_adapter.leaf_norms(mu).items()}
                obs.grad_sample = {
                    k: v / (1.0 - LAMB_B1) for k, v in
                    lm_adapter.sample_matrices(mu, sizes["kinds"]).items()}
                total = local = 0
                for leaf in jax.tree.leaves(mu):
                    total += leaf.nbytes
                    local += leaf.addressable_shards[0].data.nbytes
                obs.opt_share = local / max(total, 1)
            if n == FOLLOW:
                obs.delta_norms = lm_adapter.leaf_diff_norms(
                    state.params,
                    adapter.place_like(our_weights(), state.params))
            if n > FOLLOW:
                # read after the window: real tokens of each timed step
                obs.masks[n] = batch["attention_mask"]
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
            obs.expert_counts[step] = _expert_counts(vals)
            obs.dropped[step] = sum(
                int(v) for k, v in vals.items() if k.endswith("_dropped")
                and k.startswith("moe_l"))
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


def _follow_with_reference(spec: dict, obs: Obs, sizes: dict,
                           quant=None) -> dict:
    """The reference's losses, first clipped gradient, parameter change and
    expert counts over the observed steps' own inputs."""
    import jax
    import numpy as np

    from benchmark.harness import lm_adapter
    from benchmark.reference import lfm2_moe_ref as ref
    from benchmark.reference.bert_ref import clipped_gradient

    t = spec["traffic"]
    lim = dict(t["limits"])
    if spec["rehearse"]:
        lim.update(t.get("rehearse", {}).get("limits", {}))
    params = _place_for_reference(ref.init_params(spec["seed"], sizes),
                                  False)
    opt = ref.lamb_init(params)
    losses, counts, ties = [], [], []
    grad_norms = grad_sample = None
    for batch in obs.batches:
        accum = batch["input_ids"].shape[0]
        micros = [_place_for_reference(
            {k: batch[k][i] for k in ("input_ids", "segment_ids")}, False)
            for i in range(accum)]
        loss, grads, count, tie = ref.step_loss_and_grad(
            params, micros, sizes, quant, float(lim["tie_tol"]))
        losses.append(float(loss))
        counts.append(np.asarray(jax.device_get(count)).tolist())
        ties.append(np.asarray(jax.device_get(tie)).tolist())
        if grad_norms is None:
            clipped, _ = jax.jit(clipped_gradient)(grads)
            clipped = lm_adapter.to_program_tree(clipped)
            grad_norms = lm_adapter.leaf_norms(clipped)
            grad_sample = lm_adapter.sample_matrices(clipped,
                                                     sizes["kinds"])
            del clipped
        params, opt = ref.lamb_step(
            params, grads, opt, float(t["learning_rate"]),
            int(t["max_steps"]), float(t["warmup_proportion"]))
        del grads
    del opt
    start = _place_for_reference(ref.init_params(spec["seed"], sizes), False)
    delta_norms = lm_adapter.leaf_diff_norms(
        lm_adapter.to_program_tree(params),
        lm_adapter.to_program_tree(start))
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_sample": grad_sample, "delta_norms": delta_norms,
            "expert_counts": counts, "near_ties": ties}


def _causal_pairs(seg) -> int:
    """Sum over the documents of a step's rows of len * (len + 1) / 2: the
    (query, key) pairs causal attention inside documents needs."""
    import numpy as np

    seg = seg.reshape(-1, seg.shape[-1])
    total = 0
    for row in seg:
        lens = np.bincount(row, minlength=2)[1:].astype(np.int64)
        total += int((lens * (lens + 1) // 2).sum())
    return total


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(args.spec, encoding="utf-8") as f:
        spec = json.load(f)
    sys.path.insert(0, spec["root"])
    os.chdir(spec["root"])

    # the program's family, before anything reaches the chip: a checkout
    # without it fails here, at once
    import bert_pytorch_tpu.models.lfm2_moe  # noqa: F401

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

    from benchmark.harness import adapter
    from benchmark.reference import lfm2_moe_ref

    sizes = lfm2_moe_ref.sizes_from_config(spec["config"])
    obs = Obs()
    _install_hooks(spec, obs, sizes)
    out_dir = spec["out_dir"]

    import run_pretraining

    closed = False
    try:
        run_pretraining.main(_program_argv(spec, out_dir))
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
    real = {n: int(np.asarray(jax.device_get(m)).sum())
            for n, m in obs.masks.items()}
    slots = {n: int(np.prod(m.shape)) for n, m in obs.masks.items()}
    obs.masks.clear()
    causal_pairs = {n: _causal_pairs(np.asarray(jax.device_get(seg)))
                    for n, seg in obs.segs.items()}
    obs.segs.clear()
    memory = _memory_peak(obs)
    t = spec["traffic"]
    hlo = obs.step_program.as_text()
    counts = _kernel_counts(hlo, list(t.get("expect_kernels", [])))
    scopes = {}
    if spec["trace"] and hlo:
        from benchmark.harness import trace_reduce

        scopes = trace_reduce.scopes_from_hlo(hlo)
    del hlo
    in_window = set(steps_in)
    result["window"] = {
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
        "scopes": scopes, "causal_pairs": causal_pairs,
        "real_by_step": real,
        "traced_first_step": FOLLOW + WARM + 3,
        "expert_counts": {s: c for s, c in obs.expert_counts.items()
                          if s in in_window},
        "dropped_pairs": sum(obs.dropped.values()),
        "memory": memory,
    }
    obs.step_program = None
    gc.collect()
    # the reference needs the whole device at these widths (a row's
    # float32 gradient pass beside 469 M float32 weights, their gradient
    # and LAMB's moments): whatever of the program's is still on it goes.
    # Everything the comparison needs of the program is on the host.
    for array in jax.live_arrays():
        array.delete()

    t0 = time.perf_counter()
    ref = _follow_with_reference(spec, obs, sizes)
    ref_seconds = time.perf_counter() - t0

    prog_losses = [l for _, s, l in obs.loss_reads if s <= FOLLOW]
    prog_counts = [obs.expert_counts[s] for s in range(1, FOLLOW + 1)]
    compare = {
        "reference_seconds": ref_seconds,
        "program_losses": prog_losses, "reference_losses": ref["losses"],
        "loss_rel": [abs(a - b) / abs(b)
                     for a, b in zip(prog_losses, ref["losses"])],
        "grad": adapter.worst_gap(obs.grad_norms, ref["grad_norms"]),
        "delta": adapter.worst_gap(obs.delta_norms, ref["delta_norms"]),
        "grad_diff": adapter.diff_gap(obs.grad_sample, ref["grad_sample"]),
        "grad_diff_by_matrix": {
            k: float(np.linalg.norm((obs.grad_sample[k]
                                     - ref["grad_sample"][k]).ravel())
                     / max(np.linalg.norm(ref["grad_sample"][k].ravel()),
                           1e-30)) for k in ref["grad_sample"]},
        "experts": {"program": prog_counts,
                    "reference": ref["expert_counts"],
                    "near_ties": ref["near_ties"]},
    }
    if spec.get("control"):
        ctl = _follow_with_reference(spec, obs, sizes, spec["control"])
        compare["control"] = {
            "precision": spec["control"],
            "program_losses": ctl["losses"],
            "reference_losses": ref["losses"],
            "loss_rel": [abs(a - b) / abs(b)
                         for a, b in zip(ctl["losses"], ref["losses"])],
            "grad": adapter.worst_gap(ctl["grad_norms"], ref["grad_norms"]),
            "delta": adapter.worst_gap(ctl["delta_norms"],
                                       ref["delta_norms"]),
            "grad_diff": adapter.diff_gap(ctl["grad_sample"],
                                          ref["grad_sample"]),
            "experts": {"program": ctl["expert_counts"],
                        "reference": ref["expert_counts"],
                        "near_ties": ref["near_ties"]},
        }
    result["compare"] = compare
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
