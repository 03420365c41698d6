#!/usr/bin/env python
"""Distill a teacher checkpoint into a student through the finetune driver.

    python run_distill.py --task classify --student student_6l_768 \
        --teacher_checkpoint teacher_out/ckpt \
        --train_file pairs.tsv --test_file test.tsv \
        --model_config_file teacher_config.json --output_dir student_out \
        --packing --alpha_hidden 1.0

`--task` names any registered task (run_finetune.py's registry);
`--student` a `student_<L>l_<H>` preset (config.student_config) or a
BertConfig JSON path; the rest of the CLI is the task's own parser. The
run rides training/finetune.run_task end to end — packing, telemetry,
preemption guard, watchdog, checkpointing — with the task's loss swapped
for training/distill.py's KD + hard + layer-matched tap mix; the teacher
is restored read-only (serving/engine.restore_serving_params, tolerant
of either encoder layout) and runs under stop_gradient inside the same
jitted step.

Outputs in --output_dir: the student checkpoint (`ckpt/`, serving-
restorable), the student's `model_config.json` (what run_server needs),
and `distill_summary.json` — student/teacher eval accuracy, the
accuracy delta, and the logged train-loss trajectory (first/last KD mix
loss: scripts/check_distill.sh asserts it decreases).

`--inject broken_student` (negative control, CI only): evaluate a
fresh-random student instead of the trained one, so the distillation
accuracy-floor gate (tools/loadtest.py --check_distill) must trip.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys


def _distill_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(add_help=False)
    p.add_argument("--task", default=None,
                   help="registered task to distill (see --list_tasks)")
    p.add_argument("--student", required="--list_tasks" not in sys.argv,
                   help="student preset (student_<L>l_<H>) or a BertConfig "
                        "JSON path")
    p.add_argument("--teacher_checkpoint",
                   required="--list_tasks" not in sys.argv,
                   help="teacher checkpoint dir (or dir@step)")
    p.add_argument("--distill_temperature", type=float, default=2.0)
    p.add_argument("--alpha_kd", type=float, default=1.0,
                   help="soft-target KL weight")
    p.add_argument("--alpha_ce", type=float, default=0.5,
                   help="hard-label task-loss weight")
    p.add_argument("--alpha_hidden", type=float, default=0.0,
                   help="layer-matched mlp_out MSE weight")
    p.add_argument("--alpha_attn", type=float, default=0.0,
                   help="layer-matched attention_out MSE weight")
    p.add_argument("--distill_layer_map", default=None,
                   help="'s:t,s:t,...' student<-teacher layer pairs "
                        "(default: evenly spaced)")
    p.add_argument("--inject", choices=["broken_student"], default=None,
                   help="fault injection for CI negative controls")
    return p


def main(argv=None) -> dict:
    argv = list(sys.argv[1:] if argv is None else argv)

    from bert_pytorch_tpu.tasks import registry

    if "--list_tasks" in argv:
        for name in registry.all_tasks():
            spec = registry.get(name)
            print(f"{name}: {spec.title} [{spec.head}, "
                  f"metric {spec.metric}]")
        return {}

    dargs, rest = _distill_parser().parse_known_args(argv)
    if not dargs.task:
        raise SystemExit(
            "--task <name> is required; registered tasks: "
            + ", ".join(registry.all_tasks()))
    try:
        base_spec = registry.get(dargs.task)
    except KeyError as e:
        raise SystemExit(str(e))
    args = base_spec.parse_arguments(rest)

    # facts produced inside setup (which run_task owns) that the summary
    # written after run_task returns needs
    shared: dict = {}

    def distill_setup(args, config, tel):
        import jax

        from bert_pytorch_tpu.config import BertConfig, student_config
        from bert_pytorch_tpu.serving.engine import restore_serving_params
        from bert_pytorch_tpu.training import distill
        from bert_pytorch_tpu.training.state import unbox

        need_taps = dargs.alpha_hidden > 0 or dargs.alpha_attn > 0
        teacher_cfg = config.replace(
            debug_taps=config.debug_taps or need_taps)
        if dargs.student.endswith(".json"):
            student_cfg = BertConfig.from_json_file(dargs.student).replace(
                vocab_size=teacher_cfg.vocab_size)
        else:
            student_cfg = student_config(dargs.student, teacher_cfg)
        student_cfg = student_cfg.replace(
            debug_taps=student_cfg.debug_taps or need_taps)

        t_run = base_spec.setup(args, teacher_cfg, tel)
        s_run = base_spec.setup(args, student_cfg, tel)

        teacher_params, teacher_step = restore_serving_params(
            dargs.teacher_checkpoint, t_run.model, args.max_seq_len,
            log=tel.logger.info)

        dcfg = distill.DistillConfig(
            temperature=dargs.distill_temperature,
            alpha_kd=dargs.alpha_kd, alpha_ce=dargs.alpha_ce,
            alpha_hidden=dargs.alpha_hidden, alpha_attn=dargs.alpha_attn,
            layer_map=distill.parse_layer_map(
                dargs.distill_layer_map, student_cfg.num_hidden_layers,
                teacher_cfg.num_hidden_layers),
            max_segments=getattr(args, "packing_max_segments", 8))
        tel.logger.info(
            f"distill[{base_spec.name}]: teacher "
            f"{teacher_cfg.num_hidden_layers}L/{teacher_cfg.hidden_size}H "
            f"@{dargs.teacher_checkpoint} step {teacher_step} -> student "
            f"{student_cfg.num_hidden_layers}L/{student_cfg.hidden_size}H "
            f"({dargs.student}), T={dcfg.temperature}, layer map "
            f"{list(dcfg.layer_map)}")

        common = dict(teacher_model=t_run.model,
                      teacher_params=teacher_params, dcfg=dcfg,
                      output_kind=base_spec.output_kind,
                      label_ignore=s_run.label_ignore)
        loss_builder = distill.make_distill_loss_builder(
            packed=False, **common)
        packed_loss_builder = distill.make_distill_loss_builder(
            packed=True, **common)

        base_init = s_run.init_fn
        proj_template = distill.init_projections(
            jax.random.PRNGKey(0), dcfg, student_cfg, teacher_cfg)

        def init_fn(rng):
            variables = base_init(rng)
            if not proj_template:
                return variables
            r_proj = jax.random.fold_in(rng, 0x5D15)
            params = dict(variables["params"])
            params["distill_proj"] = distill.init_projections(
                r_proj, dcfg, student_cfg, teacher_cfg)
            return {**dict(variables), "params": params}

        base_finalize = s_run.finalize

        def finalize(params, results):
            eval_params = params
            if dargs.inject == "broken_student":
                tel.logger.info("distill: INJECTED broken_student — "
                                "evaluating a fresh random student")
                fresh = base_init(jax.random.PRNGKey(args.seed + 1317))
                eval_params = unbox(fresh["params"])
            out = {}
            if base_finalize is not None:
                out.update(base_finalize(eval_params, results) or {})
            if t_run.finalize is not None:
                t_out = t_run.finalize(teacher_params, {}) or {}
                out.update({f"teacher_{k}": v for k, v in t_out.items()})
            if ("test_accuracy" in out
                    and "teacher_test_accuracy" in out):
                out["accuracy_delta"] = (out["teacher_test_accuracy"]
                                         - out["test_accuracy"])
            out["teacher_checkpoint_step"] = teacher_step
            return out

        # the student's serving config — run_server needs the STUDENT
        # depth/width, not the teacher's model_config_file
        cfg_path = os.path.join(args.output_dir, "model_config.json")
        with open(cfg_path, "w", encoding="utf-8") as f:
            f.write(student_cfg.replace(debug_taps=False).to_json_string())
        shared.update(student_config=cfg_path,
                      student_layers=student_cfg.num_hidden_layers,
                      student_hidden=student_cfg.hidden_size,
                      teacher_layers=teacher_cfg.num_hidden_layers,
                      teacher_hidden=teacher_cfg.hidden_size,
                      layer_map=[list(p) for p in dcfg.layer_map],
                      projections=sorted(proj_template))

        return dataclasses.replace(
            s_run, loss_builder=loss_builder,
            packed_loss_builder=packed_loss_builder, init_fn=init_fn,
            finalize=finalize)

    spec = dataclasses.replace(base_spec, setup=distill_setup)

    from bert_pytorch_tpu.training.finetune import run_task

    results = run_task(spec, args)

    # train-loss trajectory from the run's jsonl telemetry sink: the
    # check_distill.sh KD-loss-decrease assertion reads first vs last
    log_prefix = getattr(args, "log_prefix", None) or f"{spec.name}_log"
    jsonl = os.path.join(args.output_dir, f"{log_prefix}.jsonl")
    train_losses = []
    try:
        with open(jsonl, encoding="utf-8") as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("tag") == "train" and "loss" in rec:
                    train_losses.append(float(rec["loss"]))
    except OSError:
        pass

    summary = {
        "kind": "distill_run",
        "task": dargs.task,
        "student": dargs.student,
        "teacher_checkpoint": dargs.teacher_checkpoint,
        "temperature": dargs.distill_temperature,
        "alpha_kd": dargs.alpha_kd, "alpha_ce": dargs.alpha_ce,
        "alpha_hidden": dargs.alpha_hidden,
        "alpha_attn": dargs.alpha_attn,
        "inject": dargs.inject,
        "train_losses": train_losses,
        "loss_first": train_losses[0] if train_losses else None,
        "loss_last": train_losses[-1] if train_losses else None,
        **shared,
        **{k: v for k, v in results.items()
           if isinstance(v, (int, float, str))},
    }
    out_path = os.path.join(args.output_dir, "distill_summary.json")
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(summary, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"distill: summary -> {out_path}")
    return summary


if __name__ == "__main__":
    main()
