#!/usr/bin/env python
"""Deterministic step replay from a flight-recorder bundle.

A training run that hits a non-finite step (or dies) dumps a repro bundle
(telemetry/flight_recorder.py): the last K loader batches, the per-dispatch
PRNG keys, the recent metric tail, and a manifest carrying everything the
train step was built from. This tool closes the loop:

  python tools/replay.py --bundle <dir>              # reproduce
  python tools/replay.py --bundle <dir> --bisect     # name the guilty scope
  python tools/replay.py --bundle <dir> --validate   # schema check only

Replay restores the newest checkpoint whose gap to the offending step the
bundle's records cover, re-executes those steps with the EXACT step program
the run used — same builders, same optimizer/schedule construction
(run_pretraining.make_optimizer), same accum math, same packed-field
threading, same mesh when the local device count allows — and asserts the
recorded loss/health flags reproduce bit-identically. Works on CPU
(JAX_PLATFORMS=cpu) against bundles recorded on TPU: the program is the
same, only the backend differs (bitwise equality is asserted when recording
and replay platforms match; across backends expect agreement to float
tolerance and identical flags).

--bisect re-runs the offending step's forward microbatch-by-microbatch on a
model with config.debug_taps=True and reports the first tensor to go
non-finite in execution order (embeddings -> layer_i/attention ->
layer_i/mlp -> pooler -> mlm_head -> nsp_head), across stacked and
unstacked layouts. If every forward scope is finite but gradients were
flagged, the blowup is in the backward pass and the per-group
grad_nonfinite_* counts localize it.

Exit codes (script mode): 0 reproduced / valid, 1 mismatch, 2 bundle or
schema error.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bert_pytorch_tpu.telemetry.flight_recorder import (  # noqa: E402
    validate_bundle)

# metric keys that are pure functions of (restored state, recorded batches,
# recorded rng) and therefore must reproduce BIT-identically. The EMA-carried
# signals (grad_norm_ema/z, grad_spike, param_norm_drift) are excluded by
# design: TelemetryState is ephemeral (stripped from checkpoints), so replay
# re-warms it from zero exactly like a live resume does.
DETERMINISTIC_KEYS = (
    "loss", "grad_norm", "param_norm", "mlm_accuracy", "learning_rate",
    "loss_nonfinite", "grad_nonfinite", "skipped_nonfinite", "mlm_dropped",
)


class ReplayError(RuntimeError):
    """Bundle unusable: schema, coverage, or checkpoint problems."""


def parse_arguments(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--bundle", required=True, type=str,
                   help="repro bundle directory (manifest.json + "
                        "batches.npz)")
    p.add_argument("--step", type=int, default=None,
                   help="step to reproduce (default: the manifest's "
                        "trigger_step)")
    p.add_argument("--checkpoint", type=str, default=None,
                   help="checkpoint dir override (default: the manifest's "
                        "recorded checkpoint dir — override when the "
                        "bundle moved machines)")
    p.add_argument("--bisect", action="store_true",
                   help="after reproducing, re-run the offending step's "
                        "forward with per-named_scope taps and report the "
                        "first non-finite tensor")
    p.add_argument("--validate", action="store_true",
                   help="schema-check the bundle manifest + npz and exit "
                        "(no jax, no checkpoint needed)")
    p.add_argument("--stacked_params", type=str, default="auto",
                   choices=["auto", "true", "false"],
                   help="encoder layout override; 'auto' replays the "
                        "layout the bundle recorded. The checkpoint "
                        "restores across layouts either way "
                        "(restore_either_layout)")
    return p.parse_args(argv)


def _load_manifest(bundle: str) -> dict:
    path = os.path.join(bundle, "manifest.json")
    if not os.path.isfile(path):
        raise ReplayError(f"no manifest.json under {bundle}")
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except Exception as e:
        raise ReplayError(f"manifest.json unreadable: {e}")


def _batch_for(npz, rec) -> dict:
    return {k: npz[f"s{rec['step']:08d}__{k}"] for k in rec["fields"]}


def _rng_for(npz, rec):
    return npz[f"s{rec['step']:08d}__rng"]


def _values_equal(a: float, b: float) -> bool:
    if math.isnan(a) and math.isnan(b):
        return True  # both NaN: the non-finiteness reproduced
    return a == b


def _order_taps(taps) -> list:
    """Flatten a 'debug_taps' collection into [(scope_name, array), ...] in
    forward-execution order, across both encoder layouts. Stacked taps
    (bert/encoder/layers/layer/*) carry a leading L axis and are split
    into per-layer entries; unstacked taps live under layer_{i}."""
    entries = []

    def walk(tree, path):
        if isinstance(tree, dict):
            for k, v in tree.items():
                walk(v, path + (str(k),))
        else:
            vals = tree if isinstance(tree, (tuple, list)) else (tree,)
            for v in vals:
                entries.append((path, np.asarray(v)))

    walk(taps, ())

    ordered = []
    for path, arr in entries:
        if "embeddings_out" in path:
            ordered.append(((0, 0, 0), "embeddings", arr))
            continue
        sub = (0 if "attention_out" in path
               else 1 if "mlp_out" in path else None)
        if sub is not None:
            layer = next((int(p.split("_", 1)[1]) for p in path
                          if p.startswith("layer_")
                          and p.split("_", 1)[1].isdigit()), None)
            name = "attention" if sub == 0 else "mlp"
            if layer is not None:  # unstacked: per-layer modules
                ordered.append(((1, layer, sub), f"layer_{layer}/{name}",
                                arr))
            else:  # stacked: leading (L, ...) axis from nn.scan
                for i in range(arr.shape[0]):
                    ordered.append(((1, i, sub), f"layer_{i}/{name}",
                                    arr[i]))
            continue
        if "pooled" in path:
            ordered.append(((2, 0, 0), "pooler", arr))
        elif "mlm_logits" in path:
            ordered.append(((3, 0, 0), "mlm_head", arr))
        elif "nsp_logits" in path:
            ordered.append(((4, 0, 0), "nsp_head", arr))
    ordered.sort(key=lambda t: t[0])
    return [(name, arr) for _, name, arr in ordered]


def main(argv=None) -> dict:
    args = parse_arguments(argv)
    bundle = args.bundle

    errors = validate_bundle(bundle)
    if args.validate:
        for e in errors:
            print(f"INVALID: {e}")
        if not errors:
            print(f"bundle {bundle}: manifest schema v-ok, arrays "
                  "cross-checked")
        return {"valid": not errors, "errors": errors}
    if errors:
        raise ReplayError("bundle failed schema validation: "
                          + "; ".join(errors))

    manifest = _load_manifest(bundle)
    run = manifest["run"]
    # a run-block key of older bundles: the multi-tensor LAMB update that
    # --fused_optim selected went with its flag
    if run.get("fused_optim", "off") != "off":
        raise ReplayError(
            f"this bundle was recorded with --fused_optim "
            f"{run['fused_optim']}, a path that differed from the one "
            "LAMB update this program has by ulps, so a bit-exact replay "
            "is not possible")
    npz = np.load(os.path.join(bundle, "batches.npz"))

    stream = manifest.get("stream")
    if isinstance(stream, dict):
        # streaming-plane bundle (manifest schema-v2 optional key): the
        # recorded batches came from tokenize-on-the-fly ingestion. Replay
        # itself needs no source access — the batches are in the npz — but
        # the operator re-pointing the plane does, so name the exact
        # corpus records the window covers.
        windows = [w for w in stream.get("recent_batches") or []
                   if isinstance(w, dict)]
        span = ""
        if windows:
            lo = min(w["record_lo"] for w in windows)
            hi = max(w["record_hi"] for w in windows)
            span = (f"; recorded batches cover global records {lo}..{hi} "
                    "(global_seq numbering across all sources)")
        cursor = stream.get("cursor") or {}
        print(f"streaming-mode bundle: {len(stream.get('sources') or [])} "
              f"sources (hash {stream.get('sources_hash')}), cursor at "
              f"epoch {cursor.get('epoch')} source {cursor.get('source')} "
              f"record {cursor.get('record')} "
              f"(global_seq {cursor.get('global_seq')}){span}",
              file=sys.stderr)

    import jax

    jax.config.update("jax_default_prng_impl",
                      run.get("rng_impl", "threefry2x32"))
    import jax.numpy as jnp

    from bert_pytorch_tpu.config import BertConfig
    from bert_pytorch_tpu.models import BertForPreTraining
    from bert_pytorch_tpu.optim import schedulers
    from bert_pytorch_tpu.parallel import mesh as mesh_lib
    from bert_pytorch_tpu.telemetry import HealthConfig, init_telemetry_state
    from bert_pytorch_tpu.training import (CheckpointManager,
                                           build_pretrain_step,
                                           make_sharded_state)
    from bert_pytorch_tpu.training.pretrain import (StepProgram,
                                                    build_debug_forward,
                                                    chain_steps,
                                                    inject_nonfinite,
                                                    stack_microbatches)
    from run_pretraining import make_optimizer

    cfg = BertConfig.from_dict(manifest["model_config"])
    if args.stacked_params != "auto":
        cfg = cfg.replace(stacked_params=(args.stacked_params == "true"))
    compute_dtype = (jnp.bfloat16 if cfg.dtype == "bfloat16"
                     else jnp.float32)
    model = BertForPreTraining(cfg, dtype=compute_dtype)

    schedule = schedulers.make_schedule(
        run["lr_decay"], run["learning_rate"], run["max_steps"],
        warmup=run["warmup_proportion"],
        offset=run["previous_phase_end_step"])
    tx = make_optimizer(run["optimizer"], schedule)

    # same mesh as the run when this machine can host it; otherwise pure-DP
    # over whatever devices exist (cross-shape replay stays deterministic,
    # but reduction orders may differ from the recorded run — warn)
    want_mesh = {k: int(v) for k, v in run["mesh"].items()}
    mesh_size = int(np.prod(list(want_mesh.values()) or [1]))
    same_mesh = mesh_size == jax.device_count()
    mesh = mesh_lib.make_mesh(want_mesh if same_mesh else None)
    if not same_mesh:
        print(f"WARNING: recorded mesh {want_mesh} needs {mesh_size} "
              f"devices, have {jax.device_count()}; replaying on "
              f"{dict(mesh.shape)} — flags will reproduce, bitwise "
              "equality may not", file=sys.stderr)

    records = {r["step"]: r for r in manifest["records"]}
    target = args.step if args.step is not None else manifest["trigger_step"]
    if target not in records:
        raise ReplayError(
            f"step {target} not in the bundle (recorded steps: "
            f"{sorted(records)})")
    recorded = next((m for m in manifest["metrics_tail"]
                     if m.get("step") == target), None)

    ckpt_dir = args.checkpoint or manifest["checkpoint"]["dir"]
    if not ckpt_dir or not os.path.isdir(ckpt_dir):
        raise ReplayError(
            f"checkpoint dir {ckpt_dir!r} not found — pass --checkpoint")
    manager = CheckpointManager(ckpt_dir)
    try:
        steps_avail = manager.all_steps()
        base = next((c for c in sorted(steps_avail, reverse=True)
                     if c < target
                     and all(s in records
                             for s in range(c + 1, target + 1))), None)
        if base is None:
            raise ReplayError(
                f"no checkpoint covers step {target}: checkpoints "
                f"{steps_avail}, recorded steps {sorted(records)} — the "
                "recorder window did not reach back to a checkpoint "
                "(raise --recorder_window or checkpoint more often)")
        if records[base + 1]["pos"] != 0:
            raise ReplayError(
                f"replay would start mid-dispatch at step {base + 1} "
                "(--steps_per_loop chunk partially evicted from the ring)")

        health = (HealthConfig(action=run["nonfinite_action"])
                  if run["health_pack"] == "on" else None)
        grad_dtype = (jnp.bfloat16 if run["grad_dtype"] == "bfloat16"
                      else None)
        accum = int(run["accum_steps"])
        inject_step = run.get("inject_nonfinite_step")

        first_batch = _batch_for(npz, records[base + 1])
        stacked0 = stack_microbatches(first_batch, accum)

        def init_fn(rng):
            return model.init(rng,
                              jnp.asarray(stacked0["input_ids"][0]),
                              jnp.asarray(stacked0["token_type_ids"][0]),
                              jnp.asarray(stacked0["attention_mask"][0]))

        with mesh_lib.logical_rules():
            state, shardings = make_sharded_state(
                jax.random.PRNGKey(run["seed"]), init_fn, tx, mesh=mesh,
                zero1=bool(run.get("zero1")),
                zero1_params=bool(run.get("zero1_overlap")))

        zero1_plan = None
        if run.get("zero1"):
            from bert_pytorch_tpu.parallel.zero import make_zero1_plan

            # zero1_rs is recorded from the plan (not the flag), so a
            # same-mesh replay rebuilds the psum_scatter exit exactly; on
            # a cross-shape fallback mesh rs may be unsupportable — drop
            # it rather than refuse the replay (values are identical by
            # the rs parity tests; only the collective schedule differs)
            from bert_pytorch_tpu.parallel.zero import rs_supported

            want_rs = bool(run.get("zero1_rs"))
            if want_rs and not rs_supported(mesh):
                print("WARNING: recorded run used zero1_rs but the "
                      f"replay mesh {dict(mesh.shape)} cannot host it; "
                      "replaying on the all-reduce path", file=sys.stderr)
                want_rs = False
            zero1_plan = make_zero1_plan(
                state.params, shardings.params, mesh,
                gather_on_use=bool(run.get("zero1_overlap")) or want_rs,
                reduce_scatter=want_rs,
                warn_skipped=False)

        # round-15 run-block keys (absent in older bundles -> falsy):
        # rebuild the fsdp gather-on-use plan and the coalesced-reduction
        # machinery exactly as run_pretraining wired them, or the replayed
        # program's collective structure (and the fingerprint compare)
        # would diverge from the recorded run
        plan = zero1_plan
        if run.get("fsdp_overlap"):
            from bert_pytorch_tpu.parallel.zero import make_fsdp_plan

            fplan = make_fsdp_plan(state.params, shardings.params, mesh,
                                   zero1=zero1_plan is not None,
                                   warn_skipped=False)
            if fplan is not None:
                plan = fplan
        norm_reducer = None
        if run.get("coalesce_reductions") and plan is not None:
            from bert_pytorch_tpu.parallel.coalesce import NormReducer

            norm_reducer = NormReducer(plan.grad_shardings, mesh)
            tx = make_optimizer(run["optimizer"], schedule,
                                norm_reducer=norm_reducer)

        if run.get("kfac"):
            from bert_pytorch_tpu.optim.kfac import KFAC, KFACConfig
            from bert_pytorch_tpu.training import init_kfac_state
            from bert_pytorch_tpu.training.pretrain import \
                build_kfac_pretrain_step

            kcfg = run["kfac"]
            cfg = cfg.replace(kfac_taps=True)
            model = BertForPreTraining(cfg, dtype=compute_dtype)
            kfac = KFAC(KFACConfig(
                inv_interval=kcfg["inv_interval"],
                factor_interval=kcfg["factor_interval"],
                stat_decay=kcfg["stat_decay"],
                damping=kcfg["damping"],
                kl_clip=kcfg["kl_clip"],
                skip_layers=tuple(kcfg["skip_layers"]),
                learning_rate=schedule,
                stats_dtype=(jnp.bfloat16
                             if kcfg.get("stats_dtype") == "bf16"
                             else None)),
                mesh=mesh if mesh_lib.data_shard_count(mesh) > 1 else None,
                factor_bucket_bytes=kcfg.get("factor_bucket_bytes"),
                factor_sync_freq=kcfg.get("factor_sync_freq", 1))
            state, pert_template = init_kfac_state(
                model, kfac, state,
                (stacked0["input_ids"][0], stacked0["token_type_ids"][0],
                 stacked0["attention_mask"][0]))
            step_fn = build_kfac_pretrain_step(
                model, tx, kfac, pert_template, schedule=schedule,
                accum_steps=accum, max_predictions=run["max_pred_row"],
                grad_dtype=grad_dtype, zero1=plan, health=health,
                nan_inject_step=inject_step, norm_reducer=norm_reducer)
        else:
            step_fn = build_pretrain_step(
                model, tx, schedule=schedule, accum_steps=accum,
                max_predictions=run["max_pred_row"],
                grad_dtype=grad_dtype, zero1=plan, health=health,
                nan_inject_step=inject_step, norm_reducer=norm_reducer)

        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), state)

        def restore():
            s, _extra, _step = manager.restore_either_layout(abstract,
                                                             step=base)
            if health is not None:
                s = s.replace(telemetry=init_telemetry_state())
            return s

        # StepProgram keeps the compiled HLO reachable, so the replayed
        # program's fingerprint can be compared against the recorded one
        jit_step = StepProgram(step_fn)
        jit_chunks = {}

        def replay_steps(state, stop_before_target: bool):
            """Re-execute base+1..target, dispatch-faithfully: single
            steps through jit_step, --steps_per_loop chunks through the
            same chain_steps program the run used. Returns (state,
            final-step metrics) — with stop_before_target, returns the
            state ENTERING the target step instead (for bisect)."""
            metrics = None
            s = base + 1
            while s <= target:
                rec = records[s]
                n = rec["n_steps"]
                if stop_before_target and s == target and n == 1:
                    return state, None
                rng = jnp.asarray(_rng_for(npz, rec))
                if n == 1:
                    stacked = stack_microbatches(_batch_for(npz, rec),
                                                 accum)
                    batch = mesh_lib.host_to_device_batch(mesh, stacked)
                    state, metrics = jit_step(state, batch, rng)
                    s += 1
                    continue
                d0 = s - rec["pos"]
                last = d0 + n - 1
                if rec["pos"] != 0 or any(
                        i not in records
                        for i in range(d0, min(last, target) + 1)):
                    raise ReplayError(
                        f"steps {d0}..{last} form one --steps_per_loop "
                        "dispatch; the ring evicted part of it — cannot "
                        "replay dispatch-faithfully")
                if last > target or (stop_before_target
                                     and last == target):
                    # the target lands INSIDE this dispatch (--step on an
                    # inner chunk step — the sticky chunk metrics live on
                    # the final step, but the bad batch may not), or
                    # bisect needs the state entering it: walk the inner
                    # steps with the single-step program (numerically the
                    # same body the fori_loop ran), keys by fold_in(rng, i)
                    end = target - 1 if stop_before_target else target
                    for i in range(end - d0 + 1):
                        inner = records[d0 + i]
                        stacked = stack_microbatches(
                            _batch_for(npz, inner), accum)
                        batch = mesh_lib.host_to_device_batch(mesh,
                                                              stacked)
                        state, metrics = jit_step(
                            state, batch, jax.random.fold_in(rng, i))
                    if stop_before_target:
                        return state, None
                    return state, metrics
                chunk = {
                    k: np.stack([
                        stack_microbatches(_batch_for(npz,
                                                      records[d0 + i]),
                                           accum)[k]
                        for i in range(n)])
                    for k in records[d0]["fields"]}
                if n not in jit_chunks:
                    jit_chunks[n] = StepProgram(
                        chain_steps(step_fn, n, per_step_batch=True))
                batch = mesh_lib.host_to_device_batch(mesh, chunk,
                                                      n_leading=2)
                state, metrics = jit_chunks[n](state, batch, rng)
                s = last + 1
            return state, metrics

        with mesh, mesh_lib.logical_rules():
            _, metrics = replay_steps(restore(),
                                      stop_before_target=False)
        replayed = {k: float(v) for k, v in metrics.items()}

        result = {
            "step": target,
            "base_checkpoint": base,
            "replayed": replayed,
            "recorded": recorded,
            "match": None,
            "mismatches": [],
        }

        # program-structure check (manifest schema-v2 extension): the run
        # recorded its compiled step's fingerprint; compare it against the
        # program THIS replay compiled. A divergence means the replay is
        # faithfully re-running a structurally different program — values
        # may still match, but any conclusion about collectives/donation
        # drawn here would not transfer back to the recorded run.
        recorded_fp = manifest.get("program_fingerprint")
        replayed_fp = None
        if isinstance(recorded_fp, dict):
            from bert_pytorch_tpu.analysis.hlo import compare_fingerprints

            want = int(recorded_fp.get("steps_per_loop", 1))
            prog = jit_chunks.get(want) if want > 1 else jit_step
            f = prog.fingerprint() if prog is not None else None
            if f is not None:
                replayed_fp = dict(f, steps_per_loop=want)
            comparable, fp_diffs = compare_fingerprints(recorded_fp,
                                                        replayed_fp)
            result["program_fingerprint"] = {
                "recorded": recorded_fp, "replayed": replayed_fp,
                "match": (comparable and not fp_diffs) if replayed_fp
                else None,
                "diffs": fp_diffs,
            }
            if replayed_fp is None:
                print("program fingerprint: recorded but the replay's "
                      f"{want}-step program was not AOT-compiled — "
                      "structure not compared", file=sys.stderr)
            elif not comparable:
                print("program fingerprint: not comparable ("
                      + "; ".join(fp_diffs) + ") — cross-backend/mesh "
                      "replay, structure differences are expected",
                      file=sys.stderr)
            elif fp_diffs:
                print("WARNING: replayed program structure DIVERGES from "
                      "the recorded run:", file=sys.stderr)
                for d in fp_diffs:
                    print(f"  {d}", file=sys.stderr)
            else:
                print(f"program fingerprint: replayed program matches the "
                      f"recorded one ({recorded_fp.get('hash')})",
                      file=sys.stderr)
        if recorded is None:
            print(f"step {target}: no recorded metrics in the bundle tail "
                  "(crash before readback, or an inner --steps_per_loop "
                  "step — the chunk's sticky metrics live on its final "
                  "step) — replayed values reported, nothing to compare "
                  "against", file=sys.stderr)
        else:
            keys = [k for k in DETERMINISTIC_KEYS if k in recorded] + \
                [k for k in sorted(recorded)
                 if k.startswith("grad_nonfinite_")]
            for k in keys:
                if k not in replayed:
                    result["mismatches"].append(
                        {"key": k, "recorded": recorded[k],
                         "replayed": None})
                    continue
                if not _values_equal(float(recorded[k]),
                                     float(replayed[k])):
                    result["mismatches"].append(
                        {"key": k, "recorded": float(recorded[k]),
                         "replayed": float(replayed[k])})
            result["match"] = not result["mismatches"]
            verdict = ("REPRODUCED bit-identically" if result["match"]
                       else "MISMATCH")
            print(f"step {target} (from checkpoint {base}): {verdict} "
                  f"(loss={replayed.get('loss')}, loss_nonfinite="
                  f"{replayed.get('loss_nonfinite')}, grad_nonfinite="
                  f"{replayed.get('grad_nonfinite')})")
            for m in result["mismatches"]:
                print(f"  {m['key']}: recorded {m['recorded']} != "
                      f"replayed {m['replayed']}")

        if args.bisect:
            with mesh, mesh_lib.logical_rules():
                state2, _ = replay_steps(restore(),
                                         stop_before_target=True)
                params_in = jax.tree.map(np.asarray, state2.params)
            rec = records[target]
            rng = jnp.asarray(_rng_for(npz, rec))
            inner = (jax.random.fold_in(rng, rec["pos"])
                     if rec["n_steps"] > 1 else rng)
            rngs = jax.random.split(inner, accum)
            stacked = stack_microbatches(_batch_for(npz, rec), accum)
            params_probe = params_in
            if inject_step == target:
                params_probe = inject_nonfinite(params_in,
                                                jnp.asarray(True))
            dbg_model = BertForPreTraining(cfg.replace(debug_taps=True),
                                           dtype=compute_dtype)
            fwd = jax.jit(build_debug_forward(
                dbg_model, max_predictions=run["max_pred_row"]))
            first_bad = None
            scopes = []
            for i in range(accum):
                micro = {k: jnp.asarray(v[i]) for k, v in stacked.items()}
                loss_i, taps = fwd(params_probe, micro, rngs[i])
                for name, arr in _order_taps(taps):
                    finite = bool(np.isfinite(np.asarray(arr)).all())
                    if i == 0:
                        scopes.append({"scope": name, "finite": finite})
                    if not finite and first_bad is None:
                        first_bad = {"scope": name, "microbatch": i}
                if first_bad is not None:
                    break
                if not math.isfinite(float(loss_i)) and first_bad is None:
                    first_bad = {"scope": "loss", "microbatch": i}
                    break
            if first_bad is None and float(
                    replayed.get("grad_nonfinite", 0)) > 0:
                groups = {k: v for k, v in replayed.items()
                          if k.startswith("grad_nonfinite_") and v > 0}
                first_bad = {"scope": "backward", "microbatch": None,
                             "grad_groups": groups}
            result["bisect"] = {"first_nonfinite": first_bad,
                                "scopes": scopes}
            if first_bad is None:
                print("bisect: every forward scope finite, no non-finite "
                      "gradients — nothing to blame at this step")
            else:
                where = first_bad["scope"]
                mb = first_bad.get("microbatch")
                print(f"bisect: first non-finite tensor in scope "
                      f"'{where}'"
                      + (f" (microbatch {mb})" if mb is not None else "")
                      + (f" — grad groups {first_bad['grad_groups']}"
                         if "grad_groups" in first_bad else ""))
        return result
    finally:
        manager.close()


def _cli(argv=None) -> int:
    try:
        result = main(argv)
    except ReplayError as e:
        print(f"ERROR: {e}", file=sys.stderr)
        return 2
    if result.get("valid") is False:
        return 2
    if result.get("match") is False:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(_cli())
