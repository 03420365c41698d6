"""Driver of a training cell (traffic files with `"driver": "train"`),
whatever its model family.

The parent: writes the seeded corpus, starts the one child that reaches the
chip (harness/train_child.py), waits, decides `correct` from the numbers the
child compared, and turns the child's record into the cell's metrics. It
never imports JAX's backends. What names a model is the cell's family
module (benchmark/families/<family>.py): its FLOPs for the MFU line and the
readers, and the checks it adds to `decide_correct`.
"""

from __future__ import annotations

import glob
import json
import math
import os
import re
import shutil
import subprocess
import sys
import tempfile

from benchmark.harness import corpus as corpus_lib
from benchmark.harness import spec as spec_lib

CHILD = os.path.join(spec_lib.BENCH_DIR, "harness", "train_child.py")
CHILD_TIME_LIMIT_S = 1150.0


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def _child_env(chips: int, rehearse: bool) -> dict:
    env = dict(os.environ, PYTHONUNBUFFERED="1")
    if rehearse:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       env.get("XLA_FLAGS", "")).strip()
        env.update(JAX_PLATFORMS="cpu", BPT_PALLAS_INTERPRET="1",
                   XLA_FLAGS=(f"{flags} --xla_force_host_platform_device_"
                              f"count={chips}").strip())
    return env


def _effective(cell: dict, rehearse: bool, work: str) -> dict:
    """The cell as run: under --rehearse the traffic file's `rehearse` block
    overrides sizes and limits, and a toy model configuration is written."""
    cell = dict(cell)
    if not rehearse:
        return cell
    r = cell["traffic"].get("rehearse", {})
    traffic = dict(cell["traffic"])
    traffic.update({k: v for k, v in r.items()
                    if k not in ("config", "extra_args", "limits")})
    traffic["limits"] = dict(traffic["limits"], **r.get("limits", {}))
    config = dict(cell["config"], **r.get("config", {}))
    path = os.path.join(work, "rehearse_config.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(config, f)
    cell.update(traffic=traffic, config=config, config_path=path)
    return cell


def _within(value: float, limit) -> bool:
    """A limit that is not set yet (null in the traffic file) fails."""
    return limit is not None and value <= limit


class Checks:
    """The numbers compared: each is printed beside its limit as it is
    checked (`what` says it in full), and kept under a short name for the
    result's line."""

    def __init__(self, tag: str = "correct?"):
        self.tag, self.rows = tag, []

    def __call__(self, name: str, what: str, value, limit, ok: bool) -> None:
        shown = f"{value:.3e}" if isinstance(value, float) else value
        say(f"{self.tag} {what}: {shown} (limit {limit}) -> "
            f"{'ok' if ok else 'NOT OK'}")
        if isinstance(value, float) and not math.isfinite(value):
            value = str(value)      # the result's line stays strict JSON
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": bool(ok)})

    def all_ok(self) -> bool:
        return all(row["ok"] for row in self.rows)

    def finish(self, line: dict):
        """Each number compared beside its limit, as the run's last lines
        on standard error and as the last key of its result's line."""
        for row in self.rows:
            print(f"[bench] compared {row['name']}: {row['value']} (limit "
                  f"{row['limit']}){'' if row['ok'] else ' NOT OK'}",
                  file=sys.stderr, flush=True)
        line["compared"] = {row["name"]: {k: row[k] for k in (
            "value", "limit", "ok")} for row in self.rows}
        return 0, line


def decide_correct(cell: dict, record: dict, rehearse: bool, family,
                   check: Checks) -> bool:
    """Every number compared, printed beside its limit. Limits live in the
    cell's traffic file (`limits`), each with the readings it was set from
    in PERF.md. `record["compare"]` holds the program's numbers, or the
    control's in the program's place."""
    t, lim = cell["traffic"], cell["traffic"]["limits"]
    w, c = record["window"], record["compare"]
    for i, rel in enumerate(c["loss_rel"]):
        check(f"loss_rel_step{i + 1}",
              f"step {i + 1} loss vs reference, relative "
              f"({c['program_losses'][i]:.6f} vs "
              f"{c['reference_losses'][i]:.6f})", rel,
              lim["loss_rel"], _within(rel, lim["loss_rel"]))
    for key, what in (("grad", "first gradient, worst leaf's norm gap"),
                      ("delta", "parameters' change after the followed "
                                "steps, worst leaf's norm gap")):
        g = c[key]
        check(f"{key}_gap", f"{what} (at {g['leaf']})", g["gap"],
              lim[f"{key}_gap"], _within(g["gap"], lim[f"{key}_gap"]))
    check("grad_diff", "first gradient, mean relative norm of the "
          "difference over sampled matrices", c["grad_diff"],
          lim["grad_diff"], _within(c["grad_diff"], lim["grad_diff"]))
    lo, hi = lim["loss_band"]
    losses = w["losses"]
    bad = [x for x in losses if not (math.isfinite(x) and lo <= x <= hi)]
    check("losses_outside_band", f"window losses in band (min "
          f"{min(losses):.4f} max {max(losses):.4f}, {len(bad)} outside)",
          len(bad), f"[{lo}, {hi}]", not bad)
    first, last = w["perf_open"], w["perf"][-1]
    compiled = last["compiles"] - first["compiles"]
    check("compiles_in_window", "compiles inside the window", compiled, 0,
          compiled == 0)
    if not rehearse:
        counts = w["kernel_counts"]
        for name in t.get("expect_kernels", []):
            check(f"kernel_{name}", f"kernel {name} in the compiled step",
                  counts.get(name, 0), ">= 1", counts.get(name, 0) >= 1)
    if cell["chips"] > 1:
        counts = w["kernel_counts"]
        if not rehearse:
            gathers = counts.get("all-gather", 0)
            reduces = (counts.get("all-reduce", 0)
                       + counts.get("reduce-scatter", 0))
            check("collectives", "collectives in the compiled step "
                  "(all-gather, all-reduce + reduce-scatter)",
                  (gathers, reduces), ">= 1 each",
                  gathers >= 1 and reduces >= 1)
        share = w["opt_share"]
        check("opt_share", "optimizer state on one chip / whole state",
              share, lim["opt_share_max"],
              share <= lim["opt_share_max"])
    family.decide(cell, record, check)
    return check.all_ok()


def program_peak_bytes(mem: dict, rehearse: bool):
    """`memory_peak_bytes`: the compiler's own statement of the timed step's
    peak (`memory_analysis().peak_memory_in_bytes`: arguments, outputs and
    temporaries alive together at the worst point of its schedule). The
    runtime's `peak_bytes_in_use` leaves the program's temporaries out on
    this runtime (1.9 GB read where the step needs 11.6), and the sum of the
    parts counts buffers that are never alive together (17.7 GB on a 16.9 GB
    chip); both are printed on an earlier line. None, and the run fails,
    where the compiler states no peak or one the device cannot hold."""
    peak, limit = mem["peak_memory"], mem["bytes_limit"]
    if rehearse:
        return peak
    if peak <= 0 or (limit and peak > limit):
        say(f"the compiler's peak of the step, {peak} bytes, is not a peak "
            f"this device (bytes_limit {limit}) can have held")
        return None
    return peak


def _tail(path: str, lines: int = 60) -> str:
    try:
        with open(path, encoding="utf-8", errors="replace") as f:
            return "".join(f.readlines()[-lines:])
    except OSError:
        return "(no log)"


def run(cell: dict, args, manifest: dict):
    rehearse = bool(args.rehearse)
    work = tempfile.mkdtemp(prefix="bench_train_")
    proc = None
    try:
        family = spec_lib.load_family(cell["family"])
        cell = _effective(cell, rehearse, work)
        t = cell["traffic"]
        vocab = int(cell["config"]["vocab_size"])
        data_dir = os.path.join(work, "data")
        totals = corpus_lib.write_shards(data_dir, t["corpus"],
                                         int(t["seq_len"]), vocab, args.seed)
        say(f"cell {cell['name']} seed {args.seed} window {args.seconds}s "
            f"trace {args.trace}; corpus {totals}; work {work}")
        spec = {
            "root": spec_lib.ROOT, "seed": int(args.seed),
            "seconds": float(args.seconds), "trace": int(args.trace),
            "rehearse": rehearse, "control": args.control,
            "fault": args.fault, "chips": cell["chips"],
            "keep_norms": bool(args.keep), "family": cell["family"],
            "config": cell["config"], "config_path": cell["config_path"],
            "traffic": t, "data_dir": data_dir,
            "out_dir": os.path.join(work, "out"),
            "start_time": args.start_time,
        }
        spec_path = os.path.join(work, "spec.json")
        with open(spec_path, "w", encoding="utf-8") as f:
            json.dump(spec, f)
        out_path = os.path.join(work, "record.json")
        log_path = os.path.join(work, "child.log")
        with open(log_path, "w", encoding="utf-8") as log:
            proc = subprocess.Popen(
                [sys.executable, CHILD, "--spec", spec_path,
                 "--out", out_path],
                cwd=spec_lib.ROOT, env=_child_env(cell["chips"], rehearse),
                stdout=log, stderr=subprocess.STDOUT)
            try:
                rc = proc.wait(timeout=CHILD_TIME_LIMIT_S)
            except subprocess.TimeoutExpired:
                say("the child ran into its time limit")
                rc = -1
        if rc != 0 or not os.path.isfile(out_path):
            say(f"the child failed (exit {rc}); the end of its log:\n"
                + _tail(log_path))
            return 1, None
        with open(out_path, encoding="utf-8") as f:
            record = json.load(f)
        device, w, c = record["device"], record["window"], record["compare"]
        chips = cell["chips"]
        if device["count"] != chips:
            say(f"the cell asks for {chips} chip(s), the run had {device}")
            return 1, None
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            shutil.copy(out_path, os.path.join(
                args.keep, f"record_{cell['name']}_{args.seed}.json"))
            shutil.copy(log_path, os.path.join(
                args.keep, f"child_{cell['name']}_{args.seed}.log"))

        tokens_per_s_chip = w["real_tokens"] / w["seconds"] / chips
        say(f"window: {w['steps']} steps (steps {w['first_step']}-"
            f"{w['last_step']}) in {w['seconds']:.4f}s; real tokens "
            f"{w['real_tokens']} of {w['slot_tokens']} slots (real share "
            f"{w['real_tokens'] / w['slot_tokens']:.4f}); step "
            f"{w['seconds'] / w['steps'] * 1e3:.2f} ms")
        say(f"set-up {w['setup_s']:.2f}s; the reference took "
            f"{c['reference_seconds']:.1f}s after the window; memory "
            f"{w['memory']}; kernels/collectives in the step "
            f"{w['kernel_counts']}; compile record at the window's end "
            f"{ {k: w['perf'][-1].get(k) for k in ('compiles', 'compile_secs', 'compile_cache_hits', 'remat_saves_dense')} }")
        say("first gradient, norm of the difference over the reference's, "
            f"by sampled matrix: {c['grad_diff_by_matrix']}")
        peak_mem = program_peak_bytes(w["memory"], rehearse)
        if peak_mem is None:
            return 1, None
        dev_out = {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"], "memory_peak_bytes": peak_mem}
        if not rehearse:
            peak = family.flops.peaks(device["kind"])
            flops, what = family.window_flops(cell, w)
            mfu = flops / w["seconds"] / (chips * peak["flops_per_s_bf16"])
            say(f"MFU {mfu:.4f} (analytic fwd+bwd FLOPs {what}, no "
                f"recompute, over {chips} x {peak['flops_per_s_bf16']:.3g})")
        checks = Checks()
        correct = decide_correct(cell, record, rehearse, family, checks)
        if "control" in c:
            # the control's numbers through the same decision, in the
            # program's place: it has to come out as not correct
            say(f"control ({c['control']['precision']}), held to the "
                "program's limits:")
            control = decide_correct(
                cell, {"compare": c["control"], "window": w}, rehearse,
                family, Checks("control?"))
            say(f"control comes out as correct: {str(control).lower()}"
                + ("" if not control else
                   " (no limit tells it from a sound run)"))
        failed = sum(1 for x in w["losses"] if not math.isfinite(x))
        line = {"correct": correct, "attempted": w["steps"],
                "failed": failed, "metrics": {}, "device": dev_out}
        if rehearse:
            return checks.finish(line)
        if not args.trace:
            line["metrics"] = {
                "train_tokens_per_s_chip": {"value": tokens_per_s_chip,
                                            "unit": "tokens/s"},
                "setup_s": {"value": w["setup_s"], "unit": "s"},
            }
            return checks.finish(line)
        from benchmark.harness import trace_reduce

        traces = glob.glob(os.path.join(spec["out_dir"], "traces", "**",
                                        "*.xplane.pb"), recursive=True)
        if not traces:
            say("no trace was written")
            return 1, None
        events = trace_reduce.load_xplane(traces[0])
        events["scopes"] = w.get("scopes", {})
        if args.keep:
            with open(os.path.join(
                    args.keep, f"trace_{cell['name']}_{args.seed}.json"),
                    "w", encoding="utf-8") as f:
                json.dump(trace_reduce.cut(events), f)
        reduced = trace_reduce.reduce(events)
        ctx = {"cell": cell, "record": record, "trace": reduced,
               "peaks": family.flops.peaks(device["kind"]),
               "flops": family.flops, "chips": chips}
        line["metrics"] = spec_lib.read_layer_metrics(
            manifest, cell["name"], ctx)
        dev_out["busy_s"] = reduced["busy_s"]
        dev_out["window_s"] = reduced["window_s"]
        line["breakdown"] = reduced["breakdown"]
        say(f"trace: {reduced['steps']} whole steps, window "
            f"{reduced['window_s']:.4f}s, busy {reduced['busy_s']:.4f}s")
        return checks.finish(line)
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait()
        shutil.rmtree(work, ignore_errors=True)
