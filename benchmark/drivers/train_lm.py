"""Driver of a causal-LM training cell (traffic files with `"driver":
"train_lm"`): the cells of the `lfm2_moe` family.

drivers/train.py with the parts that name BERT exchanged: the child
(harness/train_lm_child.py: the family's reference and adapter), the MFU
line's arithmetic (harness/lm_flops.py) and, on top of train.py's
`decide_correct`, the routed layers' checks: held-expert token counts of
the followed steps against the reference's, and no held pair left out. The
window, the clock, `setup_s`, `train_tokens_per_s_chip`, the memory peak and
the reading of the per-layer metrics are train.py's own functions, so the
metrics mean here what they mean in the other training cells.
"""

from __future__ import annotations

import glob
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile

from benchmark.drivers import train
from benchmark.harness import corpus as corpus_lib
from benchmark.harness import lm_flops
from benchmark.harness import spec as spec_lib

CHILD = os.path.join(spec_lib.BENCH_DIR, "harness", "train_lm_child.py")
say = train.say


def decide_experts(cell: dict, record: dict, who: str = "program") -> bool:
    """Top-k selection is discrete: a token whose k-th and (k+1)-th
    selection scores lie within rounding of each other may pick another
    expert than the reference. Per followed step and routed layer: the
    held experts' token counts beside the reference's, their L1 gap, and
    the reference's count of tokens within `tie_tol` of a tie; the gap has
    to stay under the latter (of all 64 experts' flips only those that
    touch a held expert move a count here). And no held pair may have been
    left out."""
    e = record["compare"]["experts"]
    oks: list = []
    for step, (got, want, ties) in enumerate(zip(e[who], e["reference"],
                                                 e["near_ties"])):
        for layer, (g, w, tie) in enumerate(zip(got, want, ties)):
            gap = sum(abs(a - b) for a, b in zip(g, w))
            train._check(oks, f"step {step + 1} routed layer {layer} held-"
                         f"expert tokens {g} vs reference {w}: L1 gap", gap,
                         f"{tie} near-tie tokens", gap <= tie)
    dropped = record["window"]["dropped_pairs"]
    train._check(oks, "held (token, expert) pairs not computed, whole run",
                 dropped, 0, dropped == 0)
    return all(oks)


def run(cell: dict, args, manifest: dict):
    rehearse = bool(args.rehearse)
    work = tempfile.mkdtemp(prefix="bench_train_lm_")
    proc = None
    try:
        cell = train._effective(cell, rehearse, work)
        t = cell["traffic"]
        cfg = cell["config"]
        data_dir = os.path.join(work, "data")
        totals = corpus_lib.write_shards(
            data_dir, t["corpus"], int(t["seq_len"]),
            int(cfg["vocab_size"]), args.seed)
        say(f"cell {cell['name']} seed {args.seed} window {args.seconds}s "
            f"trace {args.trace}; corpus {totals}; work {work}")
        spec = {
            "root": spec_lib.ROOT, "seed": int(args.seed),
            "seconds": float(args.seconds), "trace": int(args.trace),
            "rehearse": rehearse, "control": args.control,
            "fault": args.fault, "chips": cell["chips"],
            "config": cfg, "config_path": cell["config_path"],
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
                cwd=spec_lib.ROOT,
                env=train._child_env(cell["chips"], rehearse),
                stdout=log, stderr=subprocess.STDOUT)
            try:
                rc = proc.wait(timeout=train.CHILD_TIME_LIMIT_S)
            except subprocess.TimeoutExpired:
                say("the child ran into its time limit")
                rc = -1
        if rc != 0 or not os.path.isfile(out_path):
            say(f"the child failed (exit {rc}); the end of its log:\n"
                + train._tail(log_path))
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
        peak_mem = train.program_peak_bytes(w["memory"], rehearse)
        if peak_mem is None:
            return 1, None
        dev_out = {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"], "memory_peak_bytes": peak_mem}
        if not rehearse:
            peak = lm_flops.peaks(device["kind"])
            in_window = [str(s) for s in range(w["first_step"],
                                               w["last_step"] + 1)]
            pairs = sum(w["causal_pairs"][s] for s in in_window)
            mfu = (lm_flops.train_flops(cfg, w["slot_tokens"], pairs)
                   / w["seconds"] / (chips * peak["flops_per_s_bf16"]))
            say(f"MFU {mfu:.4f} (analytic fwd+bwd FLOPs of the slots and "
                f"of the documents' causal attention, no recompute, over "
                f"{chips} x {peak['flops_per_s_bf16']:.3g})")
        correct = train.decide_correct(cell, record, rehearse)
        correct = decide_experts(cell, record) and correct
        if "control" in c:
            # the control's numbers through the same decision, in the
            # program's place: it has to come out as not correct
            say(f"control ({c['control']['precision']}), held to the "
                "program's limits:")
            stand_in = {"compare": c["control"], "window": w}
            control = train.decide_correct(cell, stand_in, rehearse)
            control = decide_experts(cell, stand_in) and control
            say(f"control comes out as correct: {str(control).lower()}"
                + ("" if not control else
                   " (no limit tells it from a sound run)"))
        failed = sum(1 for x in w["losses"] if not math.isfinite(x))
        line = {"correct": correct, "attempted": w["steps"],
                "failed": failed, "metrics": {}, "device": dev_out}
        if rehearse:
            return 0, line
        if not args.trace:
            line["metrics"] = {
                "train_tokens_per_s_chip": {"value": tokens_per_s_chip,
                                            "unit": "tokens/s"},
                "setup_s": {"value": w["setup_s"], "unit": "s"},
            }
            return 0, line
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
               "peaks": lm_flops.peaks(device["kind"]), "flops": lm_flops,
               "chips": chips}
        line["metrics"] = spec_lib.read_layer_metrics(
            manifest, cell["name"], ctx)
        dev_out["busy_s"] = reduced["busy_s"]
        dev_out["window_s"] = reduced["window_s"]
        line["breakdown"] = reduced["breakdown"]
        say(f"trace: {reduced['steps']} whole steps, window "
            f"{reduced['window_s']:.4f}s, busy {reduced['busy_s']:.4f}s")
        return 0, line
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait()
        shutil.rmtree(work, ignore_errors=True)
