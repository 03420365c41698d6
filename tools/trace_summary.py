#!/usr/bin/env python
"""Collective/compute/host time attribution from a jax.profiler trace.

A step time says WHAT a program costs; this tool says WHERE the time
goes. Point it at a profiler log dir (the `--profile_steps` output of
run_pretraining) and it buckets every op event into

  collective  — all-gather / all-reduce / reduce-scatter / collective-permute
                / all-to-all (async -start/-done and fusions included),
  compute     — every other HLO op,
  host        — the train loop's TraceAnnotations (host/data_wait, host/h2d,
                host/dispatch, host/metric_flush, ...), per phase,

with same-bucket overlaps interval-merged per thread so nothing is counted
twice (telemetry/trace.py is the engine; stdlib-only, runs anywhere).

  python tools/trace_summary.py --trace <output_dir>/traces
  python tools/trace_summary.py --trace traces/ --steps 10 --devices 8
  python tools/trace_summary.py --trace traces/ --json out.json

--steps / --devices add per-step / per-device normalizations (a
single-process n-device mesh logs every device's ops into one trace, so raw
bucket totals are device-seconds). Exit 0 with a table on stdout; --json
additionally writes the machine-readable summary.

--requests switches to SERVING request-trace mode: point --trace at a
/v1/traces export (what `tools/loadtest.py --save_traces` writes) and the
table becomes per-phase p50/p99 latency attribution across request
timelines — admit/queue_wait/pack/dispatch/compute/demux/respond — ending
with the tail headline: which phase dominates the p99 cohort and on which
replica ("p99 is 78% queue_wait on r0").

  python tools/trace_summary.py --requests --trace traces_r1_f32.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bert_pytorch_tpu.telemetry.trace import (  # noqa: E402
    find_trace_file, load_trace_events, summarize_request_events,
    summarize_trace)


def format_summary(s: dict) -> str:
    lines = [f"trace: {s.get('trace_file', '?')}",
             f"events classified: {s['events_classified']}"]
    if s.get("truncated"):
        lines.append(
            f"WARNING: {s['truncated_intervals']} interval(s) never "
            "completed (trace cut short mid-op — crashed run?); closed at "
            "the trace end and included in the totals")
    dev = f" ({s['n_devices']} devices)" if "n_devices" in s else ""
    lines.append(
        f"collective: {s['collective_ms']:.1f} ms"
        f"  compute: {s['compute_ms']:.1f} ms"
        f"  collective_fraction: {s['collective_fraction']:.1%}{dev}")
    if "collective_ms_per_step_device" in s:
        basis = ("per step per device" if "n_devices" in s
                 else "per step (device-seconds; pass --devices to "
                      "normalize)")
        lines.append(
            f"{basis}: collective "
            f"{s['collective_ms_per_step_device']:.2f} ms, compute "
            f"{s['compute_ms_per_step_device']:.2f} ms "
            f"({s['steps']} steps)")
    if s.get("collective_kind_ms"):
        total = max(s["collective_ms"], 1e-9)
        lines.append("collectives by kind (device-ms; class-merged, "
                     "overlap means kinds need not sum to the total):")
        for kind, ms in sorted(s["collective_kind_ms"].items(),
                               key=lambda kv: -kv[1]):
            lines.append(f"  {kind:<24} {ms:>10.1f} ms "
                         f"({ms / total:6.1%} of collective)")
    if s["collective_by_op_ms"]:
        lines.append("collectives by op:")
        for op, ms in sorted(s["collective_by_op_ms"].items(),
                             key=lambda kv: -kv[1]):
            lines.append(f"  {op:<24} {ms:>10.1f} ms")
    if s["host_ms"]:
        lines.append("host phases:")
        for phase, ms in sorted(s["host_ms"].items(),
                                key=lambda kv: -kv[1]):
            lines.append(f"  {phase:<24} {ms:>10.1f} ms")
    return "\n".join(lines)


def format_request_summary(s: dict) -> str:
    lines = [f"request traces: {s['n_traces']}"]
    if not s["n_traces"]:
        lines.append("(no req/ spans in this trace — is it a /v1/traces "
                     "export?)")
        return "\n".join(lines)
    lines.append("  by outcome: " + ", ".join(
        f"{k}={v}" for k, v in sorted(s["by_outcome"].items())))
    lines.append("  by task:    " + ", ".join(
        f"{k}={v}" for k, v in sorted(s["by_task"].items())))
    lines.append(f"{'phase':<12} {'count':>6} {'p50 ms':>10} "
                 f"{'p99 ms':>10} {'mean ms':>10}")
    for phase, st in s["phases"].items():
        lines.append(f"{phase:<12} {st['count']:>6} {st['p50_ms']:>10.2f} "
                     f"{st['p99_ms']:>10.2f} {st['mean_ms']:>10.2f}")
    tot = s["total_ms"]
    lines.append(f"{'total':<12} {s['n_traces']:>6} {tot['p50']:>10.2f} "
                 f"{tot['p99']:>10.2f} {tot['mean']:>10.2f}")
    p99 = s.get("p99") or {}
    if p99.get("dominant_phase"):
        where = f" on {p99['replica']}" if p99.get("replica") else ""
        lines.append(
            f"p99 is {p99['dominant_share']:.0%} "
            f"{p99['dominant_phase']}{where} "
            f"({p99['n_traces']} trace(s) at/above "
            f"{p99['total_ms']:.1f} ms)")
    return "\n".join(lines)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace", required=True,
                    help="profiler log dir (or a *.trace.json.gz directly)")
    ap.add_argument("--requests", action="store_true",
                    help="summarize serving request spans (a /v1/traces "
                         "export) instead of device op time")
    ap.add_argument("--ids", default=None,
                    help="--requests: only summarize these comma-separated "
                         "trace ids — paste the trace_ids a firing "
                         "latency alert carries (GET /v1/alerts, "
                         "docs/OBSERVABILITY.md) to attribute exactly the "
                         "requests that burned the budget")
    ap.add_argument("--steps", type=int, default=None,
                    help="optimization steps the traced window covered")
    ap.add_argument("--devices", type=int, default=None,
                    help="devices sharing this trace (single-process mesh)")
    ap.add_argument("--json", default=None,
                    help="also write the summary dict to this path")
    args = ap.parse_args(argv)

    if args.requests:
        trace_file = find_trace_file(args.trace)
        events = load_trace_events(trace_file)
        if args.ids:
            want = {i.strip() for i in args.ids.split(",") if i.strip()}
            events = [e for e in events
                      if (e.get("args") or {}).get("trace_id") in want]
            if not events:
                print(f"trace_summary: none of the {len(want)} requested "
                      f"id(s) appear in {trace_file} (the ring only "
                      "retains the slowest + sampled traces; export soon "
                      "after the alert fires)", file=sys.stderr)
        summary = summarize_request_events(events)
        summary["trace_file"] = trace_file
        if args.ids:
            summary["filtered_ids"] = sorted(
                i.strip() for i in args.ids.split(",") if i.strip())
        print(format_request_summary(summary))
    else:
        summary = summarize_trace(args.trace, steps=args.steps,
                                  n_devices=args.devices)
        print(format_summary(summary))
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(summary, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    return summary


if __name__ == "__main__":
    main()
