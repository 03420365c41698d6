#!/usr/bin/env python3
"""The builder's set-runner: the runs a bound and the limits are set from.

    chiprun -- python3 benchmark/tools/sets.py --cells A,B --seeds 1,2,3 \
        [--sets 2] [--control fp8] [--trace-seed N] [--seconds S]

For each cell, `--sets` sets of runs over the same seeds, one process a run
(`benchmark/run.py`, as the driver calls it), one after another. The first
set runs with `--control` if given, so the control's readings come from the
same processes as the sound ones. `--trace-seed` adds one `--trace 1` run a
cell. Everything a run printed is kept under chiprun_out/sets/, and the
summary (per metric: each set's median and its spread, the distance between
the quartiles of statistics.quantiles(n=4) over the median; per compared
number: every reading) is printed and written beside them. Not part of a
run of the benchmark; it never touches JAX itself.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RUN = os.path.join(ROOT, "benchmark", "run.py")
COMPARED = re.compile(r"\[bench\] correct\? (.*?)(?: \(.*?\))?: (\S+) \(limit")
CONTROL = re.compile(r"\[bench\] control.*")


def spread(values: list) -> float:
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def one_run(cell, seed, seconds, extra, out_dir, tag):
    cmd = [sys.executable, RUN, "--workload", cell, "--seed", str(seed),
           "--seconds", str(seconds)] + extra
    t0 = time.time()
    proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    text = proc.stdout + "\n--- stderr ---\n" + proc.stderr[-4000:]
    with open(os.path.join(out_dir, f"{cell}_{tag}_{seed}.txt"), "w",
              encoding="utf-8") as f:
        f.write(text)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    last = (json.loads(lines[-1])
            if lines and lines[-1].startswith("{") else None)
    compared = {m.group(1): m.group(2) for m in map(COMPARED.match, lines)
                if m}
    control = [ln for ln in lines if CONTROL.match(ln)]
    info = [ln for ln in lines if ln.startswith(("[bench] window",
                                                 "[bench] set-up",
                                                 "[bench] MFU",
                                                 "[bench] memory"))]
    print(f"--- {cell} {tag} seed {seed}: exit {proc.returncode}, "
          f"{time.time() - t0:.0f}s, correct "
          f"{None if last is None else last['correct']}", flush=True)
    for ln in info + control:
        print("    " + ln[:600], flush=True)
    if last is None or not last["correct"]:
        print(text[-3000:], flush=True)
    return {"seed": seed, "rc": proc.returncode, "line": last,
            "compared": compared, "control": control}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--control", default=None)
    ap.add_argument("--trace-seed", type=int, default=None)
    ap.add_argument("--stop-on-failure", action="store_true",
                    help="stop everything after a run with no result line")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "sets"))
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    summary = {}
    for cell in args.cells.split(","):
        sets = []
        for k in range(args.sets):
            tag = "ABCDEFGH"[k]
            extra = ["--trace", "0"]
            if k == 0 and args.control:
                extra += ["--control", args.control]
            runs = []
            for s in seeds:
                runs.append(one_run(cell, s, seconds, extra, args.out, tag))
                if args.stop_on_failure and runs[-1]["line"] is None:
                    print("stopping: a run gave no result", flush=True)
                    return 1
            sets.append(runs)
        traced = None
        if args.trace_seed is not None:
            traced = one_run(cell, args.trace_seed, seconds,
                             ["--trace", "1", "--keep", args.out], args.out,
                             "T")
            if traced["line"]:
                print("    traced: " + json.dumps(traced["line"])[:3000],
                      flush=True)
        cell_sum = {"metrics": {}, "compared": {}, "runs": sets,
                    "traced": traced}
        names = sorted({n for st in sets for r in st if r["line"]
                        for n in r["line"]["metrics"]})
        for n in names:
            per_set = [[r["line"]["metrics"][n]["value"] for r in st
                        if r["line"]] for st in sets]
            cell_sum["metrics"][n] = [
                {"median": statistics.median(v), "spread": spread(v),
                 "values": v} for v in per_set if v]
        for st in sets:
            for r in st:
                for k, v in r["compared"].items():
                    cell_sum["compared"].setdefault(k, []).append(v)
        summary[cell] = cell_sum
        print(f"=== {cell}", flush=True)
        for n, per_set in cell_sum["metrics"].items():
            for i, s in enumerate(per_set):
                print(f"    {n} set {'ABCDEFGH'[i]}: median {s['median']} "
                      f"spread {s['spread']:.5f} values {s['values']}",
                      flush=True)
        for k, v in cell_sum["compared"].items():
            print(f"    {k}: {v}", flush=True)
        with open(os.path.join(args.out, "summary.json"), "w",
                  encoding="utf-8") as f:
            json.dump(summary, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
