#!/usr/bin/env python3
"""The builder's account of one traced run: does the program's own
book-keeping close?

    chiprun -- python3 benchmark/tools/books.py --cell C --seed N \
        [--seconds S] [--out chiprun_out/books]

Runs `benchmark/run.py --workload C --seed N --trace 1` in this process
(which stays off JAX's backends, as run.py does; the child reaches the
chip) and, from the very events and record the run's metrics were read
from, writes <out>/<cell>_<seed>.json and prints:

- the device's busy time by the program's list of step scopes (the
  `scopes` argument of layer_metrics/unscoped_share.train.json), each
  operation under the FIRST entry its `op_name` matches; what matches
  none, split into operations that carry an `op_name` and operations
  that carry none; the sum, which is by_scope over busy time; beside it
  the recomputed share, and the largest operations of `encoder` (the
  scan's carry) and of the unmatched;
- idle time by the host span over each gap (`breakdown.idle_gaps`), and
  the self time of operations that hold others, which `reduce` leaves out
  of busy time;
- the clock check: per host phase, the summed durations of its `host/*`
  annotations over the [perf] intervals that lie wholly inside the trace
  against the same records' `*_ms` fields; and, for every
  `host/metric_flush` span, how long after the nearest end of a step's
  execution on the device it ended;
- the set-up counters, the host phases and tokens/s of the window.

Not part of a run of the benchmark.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import spec as spec_lib  # noqa: E402
from benchmark.harness import trace_reduce  # noqa: E402
from benchmark.readers.scope_under_share import under as component  # noqa: E402,E501


def by_first_match(by_scope: dict, scopes: list) -> dict:
    """Seconds under each scope (first match), under "(named, unmatched)"
    and under "(no op_name)"."""
    pats = [(s, component(s)) for s in scopes]
    out: dict = {}
    for path, t in by_scope.items():
        if not path:
            key = "(no op_name)"
        else:
            key = next((s for s, p in pats if p.search(path)),
                       "(named, unmatched)")
        out[key] = out.get(key, 0.0) + t
    return out


def largest(events: dict, reduced: dict, keep, n: int = 8) -> list:
    """The n operations with the most self time among those whose scope
    path `keep` accepts: [instruction, scope path, seconds]."""
    scopes = events.get("scopes", {})
    rows = [(name, scopes.get(name, ""), t)
            for name, t in reduced["by_op"].items()
            if keep(scopes.get(name, ""))]
    return [[a, b[-160:], c] for a, b, c in
            sorted(rows, key=lambda r: -r[2])[:n]]


def holders(events: dict, reduced: dict) -> dict:
    """Self time, as a share of busy time, of operations that hold others,
    by kind. `reduce` counts the device busy only while a LEAF runs, so a
    holder's self time is in by_scope and not in busy_s (why the scope
    shares sum past 100 %), and reads as idle: the gaps inside a `while`.
    A fusion with nothing but zero-length markers inside is a leaf (since
    PR 40; `trace_reduce.self_times`), so "other" stays empty unless an
    operation of some length runs inside another that is no loop."""
    plane = sorted(events["devices"])[0]
    dev = events["devices"][plane]
    lo, hi = trace_reduce._step_window(dev)[:2]
    ops = [o for o in dev["ops"] if o[1] >= lo and o[1] + o[2] <= hi]
    own, holds = trace_reduce.self_times(ops)
    out: dict = {}
    for (name, _, _), t, h in zip(ops, own, holds):
        if h:
            kind = ("while" if name.startswith(("while", "conditional",
                                                "call")) else "other")
            out[kind] = out.get(kind, 0.0) + t / 1e9
    return {k: 100.0 * v / reduced["busy_s"] for k, v in out.items()}


def timeline(events: dict) -> dict:
    """Every execution of a module on the first device and every host span,
    in milliseconds from the first of them: the trace's own story of who
    waited for whom."""
    plane = sorted(events["devices"])[0]
    modules = sorted(events["devices"][plane]["modules"], key=lambda m: m[1])
    host = sorted(events.get("host", []), key=lambda h: h[1])
    t0 = min([m[1] for m in modules] + [h[1] for h in host])
    return {"modules_ms": [[n[:40], (s - t0) / 1e6, d / 1e6]
                           for n, s, d in modules],
            "host_ms": [[n, (s - t0) / 1e6, d / 1e6] for n, s, d in host]}


def host_intervals(host: list) -> list:
    """[perf] intervals inside the trace, as lists of host spans: an
    interval ends with the `host/log` span that follows a
    `host/metric_flush` (the loop emits its record right after it)."""
    spans = sorted(host, key=lambda h: h[1])
    bounds = []
    for i, (name, start, dur) in enumerate(spans):
        if name != "host/metric_flush":
            continue
        after = next((h for h in spans[i + 1:] if h[0] == "host/log"
                      and h[1] >= start + dur), None)
        if after is not None:
            bounds.append(after[1] + after[2])
    return [[h for h in spans if lo < h[1] + h[2] <= hi]
            for lo, hi in zip(bounds, bounds[1:])]


def clock_check(events: dict, record: dict) -> dict:
    perf = {p["step"]: p for p in record["window"]["perf"]}
    first = record["window"]["traced_first_step"]   # first step dispatched
    intervals = host_intervals(events.get("host", []))
    # the first whole interval is the record of the step after it
    rows, worst = [], 0.0
    for k, spans in enumerate(intervals):
        rec = perf.get(first + 1 + k)
        if rec is None:
            continue
        names = sorted({s[0] for s in spans}
                       | {"host/" + f[:-3] for f in rec if f.endswith("_ms")
                          and f not in ("step_time_ms",
                                        "loop_unaccounted_ms")})
        for name in names:
            traced = sum(s[2] for s in spans if s[0] == name) / 1e6
            field = rec.get(name[len("host/"):] + "_ms", 0.0)
            rows.append([rec["step"], name, traced, field])
            worst = max(worst, abs(traced - field))
    # whole executions only: the trace's end cuts the last one short
    runs = [(s, d) for dev in events["devices"].values()
            for _, s, d in dev["modules"]]
    median = sorted(d for _, d in runs)[len(runs) // 2] if runs else 0
    ends = sorted(s + d for s, d in runs if d >= 0.8 * median)
    waits = []
    for name, start, dur in events.get("host", []):
        if name == "host/metric_flush" and ends:
            end = start + dur
            near = min(ends, key=lambda e: abs(e - end))
            waits.append([dur / 1e6, (end - near) / 1e6])
    return {"phase_rows": rows, "phase_worst_abs_ms": worst,
            "metric_flush_ms_and_end_after_device_end_ms": waits}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                   "books"))
    args = ap.parse_args()
    manifest = spec_lib.load_manifest(ROOT)
    seconds = args.seconds or manifest["run_seconds"]
    os.makedirs(args.out, exist_ok=True)

    seen = {}
    reduce = trace_reduce.reduce

    def watching(events):
        seen["events"], seen["reduced"] = events, reduce(events)
        return seen["reduced"]

    trace_reduce.reduce = watching
    try:
        code = bench_run.main(["--workload", args.cell, "--seed",
                               str(args.seed), "--seconds", str(seconds),
                               "--trace", "1", "--keep", args.out])
    finally:
        trace_reduce.reduce = reduce
    if code != 0 or not seen:
        print(f"[books] the run failed (exit {code})", flush=True)
        return code or 1
    events, reduced = seen["events"], seen["reduced"]
    with open(glob.glob(os.path.join(
            args.out, f"record_{args.cell}_{args.seed}.json"))[0],
            encoding="utf-8") as f:
        record = json.load(f)
    scopes = spec_lib.load_layer_metric(
        "unscoped_share.train", ROOT)["args"]["scopes"]
    busy = reduced["busy_s"]
    shares = {k: 100.0 * v / busy for k, v in
              by_first_match(reduced["by_scope"], scopes).items()}
    remat = component("rematted_computation")
    unmatched = re.compile("|".join(component(s).pattern for s in scopes))
    w = record["window"]
    last = w["perf"][-1]
    gaps = reduced["breakdown"]["idle_gaps"]
    listed = sum(t for _, t in gaps) or 1.0
    out = {
        "cell": args.cell, "seed": args.seed, "steps_traced": reduced["steps"],
        "window_s": reduced["window_s"], "busy_s": busy,
        "scope_share_pct": dict(sorted(shares.items(), key=lambda kv: -kv[1])),
        "scope_share_sum_pct": sum(shares.values()),
        "recompute_share_pct": 100.0 * sum(
            t for p, t in reduced["by_scope"].items() if remat.search(p))
        / busy,
        "largest_scan_carry": largest(
            events, reduced, lambda p: bool(component("encoder").search(p))
            and not component("attention").search(p)
            and not component("mlp").search(p)),
        "largest_named_unmatched": largest(
            events, reduced, lambda p: bool(p) and not unmatched.search(p)),
        "largest_no_op_name": largest(events, reduced, lambda p: not p),
        "largest_grad_accum": largest(
            events, reduced,
            lambda p: next((s for s in scopes if component(s).search(p)),
                           None) == "grad_accum"),
        "holders_self_pct_of_busy": holders(events, reduced),
        "idle_gaps_s": gaps,
        "idle_unattributed_share_pct": 100.0 * sum(
            t for n, t in gaps if n == "host/unattributed") / listed,
        "clock_check": clock_check(events, record),
        "timeline": timeline(events),
        "setup": {k: v for k, v in last.items() if k.startswith("setup_")
                  or k == "compile_secs"},
        "window_tokens_per_s": w["real_tokens"] / w["seconds"],
        "window_step_ms": 1e3 * w["seconds"] / w["steps"],
        "host_phases_ms_mean": {
            f: sum(p.get(f, 0.0) for p in w["perf"]) / len(w["perf"])
            for f in sorted({f for p in w["perf"] for f in p
                             if f.endswith("_ms")})},
    }
    path = os.path.join(args.out, f"books_{args.cell}_{args.seed}.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(out, f, indent=1)
    print("[books] " + json.dumps(out)[:20000], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
