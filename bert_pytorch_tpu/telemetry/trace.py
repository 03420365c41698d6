"""Trace-event summarizer: collective vs compute vs host attribution.

A multi-chip run that scales badly cannot say WHERE the time went from
wall clocks alone. jax.profiler
already writes a Chrome-trace-event JSON (`*.trace.json.gz` under
`<log_dir>/plugins/profile/<run>/`) whose per-op events carry HLO names on
both TPU and the forced-CPU mesh, and the PR-8 host-loop TraceAnnotations
(`host/data_wait`, `host/h2d`, `host/dispatch`, ...) land in the same
stream. This module turns that file into the three numbers a scaling
investigation actually needs, per step:

- **collective**: time in cross-device communication ops (all-gather,
  all-reduce, reduce-scatter, collective-permute, all-to-all — async
  `-start`/`-done` variants and fusions with a collective root included),
- **compute**: every other HLO op (dots, fusions, copies, elementwise),
- **host**: the annotated host-loop phases, reported per annotation.

Durations are bucket-wise interval-merged per thread before summing, so a
collective nested inside another collective (or an op re-reported by a
wrapper event) is never double-counted; framework wrapper events
(`ThunkExecutor::...`, `TfrtCpuExecutable::...`, Python frames) match
neither class and are excluded. On an n-device single-process mesh every
device's ops land in one trace, so bucket totals are device-seconds; the
summary divides by `n_devices` when given to report per-device time.

stdlib-only (gzip + json), no jax import — the summarizer must run on a
login host against a trace scp'd out of a pod job. `tools/trace_summary.py`
is the CLI.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

# HLO collective roots. Fusion/async variants keep the root as a prefix of
# the op name ("all-gather-start.3", "all-reduce-scatter" does not exist —
# reduce-scatter is its own root). Order is irrelevant; matching is by
# prefix after stripping nothing.
COLLECTIVE_PREFIXES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "collective-permute",
    "collective-broadcast",
    "all-to-all",
    "ragged-all-to-all",
    "partition-id",
    "replica-id",
    "send",
    "recv",
)

# an HLO instruction name: lowercase root, optional .N suffix, dashes/
# underscores/digits inside (e.g. "transpose_copy_fusion", "dot.1",
# "all-gather-start.12"). Framework wrappers ("Transpose::Execute",
# "PjitFunction(f)", "$profiler.py:91 ...") all fail this.
_HLO_NAME_RE = re.compile(r"^[a-z][a-z0-9_\-.]*$")

HOST_PREFIX = "host/"

# serving request spans (serving/request_trace.py) ride the same Chrome
# event format under this prefix; classify() excludes them from device
# summaries, summarize_request_events() below aggregates them
REQUEST_PREFIX = "req/"

# request lifecycle phases in request order, + the terminal error spans
REQUEST_PHASE_ORDER = ("admit", "queue_wait", "pack", "dispatch",
                       "compute", "demux", "respond",
                       "shed", "timeout", "too_long", "error")

# the per-kind split (round 15): every collective root maps to one of
# these classes so the summary can say WHICH collective class a variant
# pays for — all-gathers (param gathers), all-reduces (grad/factor/norm
# reductions), reduce-scatters, permutes (ring attention), all-to-alls
# (reshard transitions) — instead of one undifferentiated 'collective'
# bucket. Roots outside the named classes (send/recv, partition/replica
# ids, broadcasts) land in 'other'.
COLLECTIVE_KIND_CLASSES = ("all-gather", "all-reduce", "reduce-scatter",
                           "collective-permute", "all-to-all")


def collective_kind(root: str) -> str:
    """Canonical kind class for one collective root name (the root is the
    op name with any `.N` instance suffix and `-start`/`-done` already
    stripped)."""
    return root if root in COLLECTIVE_KIND_CLASSES else "other"


def classify(name: str) -> Optional[str]:
    """Bucket for one trace-event name: 'collective' | 'compute' | a
    'host/...' phase name | None (framework noise, excluded)."""
    if name.startswith(HOST_PREFIX):
        return name
    if name.startswith(REQUEST_PREFIX):
        return None  # serving request spans: not device time
    if not _HLO_NAME_RE.match(name):
        return None
    for p in COLLECTIVE_PREFIXES:
        if name.startswith(p):
            # "-done" events measure scheduler wait for an async collective
            # already counted from its "-start"; keeping both is correct
            # under interval merge only if they overlap — they do not, so
            # count both: start = issue+transfer, done = the un-hidden tail.
            return "collective"
    return "compute"


def _merged_total_us(intervals: List[Tuple[float, float]]) -> float:
    """Sum of a set of [start, end) intervals with overlaps merged."""
    total = 0.0
    end = -1.0
    for s, e in sorted(intervals):
        if s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def find_trace_file(path: str) -> str:
    """Resolve a profiler log dir (or a direct file) to the newest
    *.trace.json.gz jax wrote under it."""
    if os.path.isfile(path):
        return path
    hits = (glob.glob(os.path.join(path, "plugins", "profile", "*",
                                   "*.trace.json.gz"))
            + glob.glob(os.path.join(path, "*.trace.json.gz")))
    if not hits:
        raise FileNotFoundError(
            f"no *.trace.json.gz under {path} (expected "
            "<log_dir>/plugins/profile/<run>/ from jax.profiler.start_trace)")
    return max(hits, key=os.path.getmtime)


def load_trace_events(trace_file: str) -> List[Dict[str, Any]]:
    opener = gzip.open if trace_file.endswith(".gz") else open
    with opener(trace_file, "rt", encoding="utf-8") as f:
        trace = json.load(f)
    return trace.get("traceEvents", [])


def _per_op_totals(op_iv: Dict[Tuple[Any, Any, str],
                               List[Tuple[float, float]]]) -> Dict[str, float]:
    """Per-root device-time: merge each thread's intervals, then SUM across
    threads — the same aggregation as the bucket totals, so the per-op map
    decomposes collective_ms instead of contradicting it."""
    totals: Dict[str, float] = {}
    for (pid, tid, root), iv in op_iv.items():
        totals[root] = totals.get(root, 0.0) + _merged_total_us(iv)
    return {op: round(us / 1e3, 3) for op, us in sorted(totals.items())}


def summarize_events(events: Iterable[Dict[str, Any]],
                     steps: Optional[int] = None,
                     n_devices: Optional[int] = None) -> Dict[str, Any]:
    """Bucket trace events into collective/compute/host totals.

    Complete ('X') events are the common case; duration pairs are also
    understood — synchronous 'B'/'E' per (pid, tid) stack and async
    'b'/'e' ('S'/'F' legacy) matched by (pid, id, cat, name). A trace cut
    short mid-interval (the run crashed while an op was open — exactly
    when a postmortem reads the trace) leaves unmatched begins: those are
    closed at the trace's end and reported via `truncated: true` +
    `truncated_intervals`, instead of being dropped or raising. An 'E'
    with no matching 'B' began before the capture window — there is no
    start to attribute, so it is skipped.

    `steps`: optimization steps the traced window covered — adds *_ms_per_step.
    `n_devices`: devices whose ops share this trace (single-process mesh) —
    device buckets are additionally reported per device."""
    # per (pid, tid, bucket) interval lists; host annotations keyed by name.
    # op_iv is ALSO keyed per thread — merging a root's intervals across
    # device threads would collapse concurrent same-op collectives into one
    # interval and undercount device-time ~n_devices-fold, making the
    # per-op map inconsistent with collective_ms.
    device_iv: Dict[Tuple[Any, Any, str], List[Tuple[float, float]]] = {}
    host_iv: Dict[str, List[Tuple[float, float]]] = {}
    op_iv: Dict[Tuple[Any, Any, str], List[Tuple[float, float]]] = {}
    n_classified = 0

    def record(pid, tid, name: str, ts: float, end: float) -> bool:
        nonlocal n_classified
        bucket = classify(name)
        if bucket is None:
            return False
        n_classified += 1
        if bucket.startswith(HOST_PREFIX):
            host_iv.setdefault(bucket, []).append((ts, end))
            return True
        device_iv.setdefault((pid, tid, bucket), []).append((ts, end))
        if bucket == "collective":
            # per-root collective map: strip the .N instance suffix and any
            # -start/-done so "all-gather-start.3" aggregates as all-gather
            root = re.sub(r"\.\d+$", "", name)
            root = re.sub(r"-(start|done)$", "", root)
            op_iv.setdefault((pid, tid, root), []).append((ts, end))
        return True

    open_sync: Dict[Tuple[Any, Any], List[Tuple[str, float]]] = {}
    # async opens keep (ts, tid) — the tid must survive to the close (or
    # the truncation pass), or the interval lands under a synthetic thread
    # and can't interval-merge with the same thread's completed ops
    open_async: Dict[Tuple[Any, Any, Any, str],
                     List[Tuple[float, Any]]] = {}
    max_ts = 0.0
    truncated = 0
    for e in events:
        ph = e.get("ph")
        name = e.get("name", "")
        ts = float(e.get("ts", 0.0))
        pid, tid = e.get("pid"), e.get("tid")
        if ph == "X":
            dur = float(e.get("dur", 0.0))
            max_ts = max(max_ts, ts + dur)
            record(pid, tid, name, ts, ts + dur)
        elif ph == "B":
            max_ts = max(max_ts, ts)
            open_sync.setdefault((pid, tid), []).append((name, ts))
        elif ph == "E":
            max_ts = max(max_ts, ts)
            stack = open_sync.get((pid, tid))
            if stack:
                bname, bts = stack.pop()
                record(pid, tid, bname, bts, ts)
        elif ph in ("b", "S"):
            max_ts = max(max_ts, ts)
            key = (pid, e.get("id"), e.get("cat"), name)
            open_async.setdefault(key, []).append((ts, tid))
        elif ph in ("e", "F"):
            max_ts = max(max_ts, ts)
            starts = open_async.get((pid, e.get("id"), e.get("cat"), name))
            if starts:
                bts, btid = starts.pop(0)
                record(pid, btid if btid is not None else tid, name,
                       bts, ts)
    # crashed-run tail: close every still-open interval at the trace end
    # (flagged below) rather than losing it — the op that never completed
    # is usually the one the postmortem is looking for
    for (pid, tid), stack in open_sync.items():
        for name, ts in stack:
            if record(pid, tid, name, ts, max(max_ts, ts)):
                truncated += 1
    for (pid, _id, _cat, name), starts in open_async.items():
        for ts, btid in starts:
            if record(pid, btid, name, ts, max(max_ts, ts)):
                truncated += 1

    def bucket_total(which: str) -> float:
        return sum(_merged_total_us(iv)
                   for (pid, tid, b), iv in device_iv.items() if b == which)

    collective_us = bucket_total("collective")
    compute_us = bucket_total("compute")
    host = {name[len(HOST_PREFIX):]: round(_merged_total_us(iv) / 1e3, 3)
            for name, iv in sorted(host_iv.items())}
    # the per-KIND split: class intervals re-merged per thread (two roots
    # of the same class can overlap under async scheduling, so summing
    # the per-root map would double-count; re-merging keeps each class
    # total consistent with how collective_ms itself is computed). The
    # classes need not sum exactly to collective_ms — cross-class overlap
    # on one thread is attributed to both classes but merged away in the
    # total, by design.
    kind_iv: Dict[Tuple[Any, Any, str], List[Tuple[float, float]]] = {}
    for (pid, tid, root), iv in op_iv.items():
        kind_iv.setdefault((pid, tid, collective_kind(root)),
                           []).extend(iv)
    kind_ms: Dict[str, float] = {}
    for (pid, tid, kind), iv in kind_iv.items():
        kind_ms[kind] = kind_ms.get(kind, 0.0) + _merged_total_us(iv)
    kind_ms = {k: round(us / 1e3, 3) for k, us in sorted(kind_ms.items())}
    out: Dict[str, Any] = {
        "collective_ms": round(collective_us / 1e3, 3),
        "compute_ms": round(compute_us / 1e3, 3),
        "host_ms": host,
        "collective_fraction": round(
            collective_us / max(collective_us + compute_us, 1e-9), 4),
        "collective_by_op_ms": _per_op_totals(op_iv),
        "collective_kind_ms": kind_ms,
        "events_classified": n_classified,
    }
    if truncated:
        out["truncated"] = True
        out["truncated_intervals"] = truncated
    if n_devices:
        out["n_devices"] = int(n_devices)
        out["collective_ms_per_device"] = round(
            collective_us / 1e3 / n_devices, 3)
        out["compute_ms_per_device"] = round(compute_us / 1e3 / n_devices, 3)
    if steps:
        out["steps"] = int(steps)
        div = steps * (n_devices or 1)
        out["collective_ms_per_step_device"] = round(
            collective_us / 1e3 / div, 3)
        out["compute_ms_per_step_device"] = round(compute_us / 1e3 / div, 3)
        out["collective_kind_ms_per_step_device"] = {
            k: round(v / div, 3) for k, v in kind_ms.items()}
    return out


def _pct(sorted_vals: List[float], q: float) -> float:
    """Linear-interpolated percentile of an already-sorted list."""
    if not sorted_vals:
        return 0.0
    if len(sorted_vals) == 1:
        return sorted_vals[0]
    pos = (len(sorted_vals) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    return sorted_vals[lo] + (sorted_vals[hi] - sorted_vals[lo]) * (pos - lo)


def _phase_key(name: str) -> Tuple[int, str]:
    try:
        return (REQUEST_PHASE_ORDER.index(name), name)
    except ValueError:
        return (len(REQUEST_PHASE_ORDER), name)


def summarize_request_events(
        events: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Aggregate serving request spans (`req/` complete events from
    /v1/traces) into per-phase latency attribution.

    Groups events by `args.trace_id`, sums each trace's span durations
    per phase, and reports per-phase p50/p99/mean across traces plus a
    tail-cohort attribution: over the traces whose total latency is at
    or above the p99 of totals, the mean time per phase, the DOMINANT
    phase (largest mean), its share of the cohort's mean total, and the
    modal replica the cohort computed on — i.e. the "p99 is 78%
    queue_wait on r0" answer. Non-request events are ignored, so the
    summarizer runs unchanged on a merged device+request trace file."""
    traces: Dict[str, Dict[str, Any]] = {}
    for e in events:
        name = e.get("name", "")
        if e.get("ph") != "X" or not name.startswith(REQUEST_PREFIX):
            continue
        args = e.get("args") or {}
        trace_id = args.get("trace_id")
        if trace_id is None:
            continue
        t = traces.setdefault(trace_id, {
            "phases": {}, "total_ms": 0.0, "task": args.get("task"),
            "outcome": None, "replica": None, "t0": None, "t1": 0.0})
        phase = name[len(REQUEST_PREFIX):]
        ts = float(e.get("ts", 0.0))
        dur = float(e.get("dur", 0.0))
        t["phases"][phase] = t["phases"].get(phase, 0.0) + dur / 1e3
        t["t0"] = ts if t["t0"] is None else min(t["t0"], ts)
        t["t1"] = max(t["t1"], ts + dur)
        if args.get("total_ms"):
            t["total_ms"] = max(t["total_ms"], float(args["total_ms"]))
        if args.get("outcome") not in (None, "open"):
            t["outcome"] = args["outcome"]
        if phase == "compute" and "replica" in args:
            t["replica"] = args["replica"]
        elif t["replica"] is None and "replica" in args:
            t["replica"] = args["replica"]
    out: Dict[str, Any] = {"n_traces": len(traces), "by_outcome": {},
                           "by_task": {}, "phases": {}, "total_ms": {}}
    if not traces:
        return out
    totals: List[float] = []
    phase_samples: Dict[str, List[float]] = {}
    for t in traces.values():
        if not t["total_ms"] and t["t0"] is not None:
            t["total_ms"] = (t["t1"] - t["t0"]) / 1e3
        totals.append(t["total_ms"])
        key = t["outcome"] or "open"
        out["by_outcome"][key] = out["by_outcome"].get(key, 0) + 1
        task = t["task"] or "?"
        out["by_task"][task] = out["by_task"].get(task, 0) + 1
        for phase, ms in t["phases"].items():
            phase_samples.setdefault(phase, []).append(ms)
    totals.sort()
    for phase in sorted(phase_samples, key=_phase_key):
        vals = sorted(phase_samples[phase])
        out["phases"][phase] = {
            "count": len(vals),
            "mean_ms": round(sum(vals) / len(vals), 3),
            "p50_ms": round(_pct(vals, 50.0), 3),
            "p99_ms": round(_pct(vals, 99.0), 3),
        }
    out["total_ms"] = {
        "p50": round(_pct(totals, 50.0), 3),
        "p99": round(_pct(totals, 99.0), 3),
        "mean": round(sum(totals) / len(totals), 3),
        "max": round(totals[-1], 3),
    }
    # tail cohort: everything at/above the p99 total
    p99_total = _pct(totals, 99.0)
    tail = [t for t in traces.values() if t["total_ms"] >= p99_total]
    n_tail = max(len(tail), 1)
    tail_phase: Dict[str, float] = {}
    for t in tail:
        for phase, ms in t["phases"].items():
            tail_phase[phase] = tail_phase.get(phase, 0.0) + ms
    tail_phase = {p: ms / n_tail for p, ms in tail_phase.items()}
    tail_total = sum(t["total_ms"] for t in tail) / n_tail
    dominant_phase, dominant_ms = (
        max(tail_phase.items(), key=lambda kv: kv[1])
        if tail_phase else (None, 0.0))
    replica_votes: Dict[Any, int] = {}
    for t in tail:
        if t["replica"] is not None:
            replica_votes[t["replica"]] = \
                replica_votes.get(t["replica"], 0) + 1
    replica = (f"r{max(replica_votes.items(), key=lambda kv: kv[1])[0]}"
               if replica_votes else None)
    out["p99"] = {
        "total_ms": round(p99_total, 3),
        "n_traces": len(tail),
        "phase_ms": {p: round(ms, 3) for p, ms
                     in sorted(tail_phase.items(),
                               key=lambda kv: _phase_key(kv[0]))},
        "dominant_phase": dominant_phase,
        "dominant_share": round(dominant_ms / tail_total, 4)
        if tail_total > 0 else 0.0,
        "replica": replica,
    }
    return out


def summarize_trace(path: str, steps: Optional[int] = None,
                    n_devices: Optional[int] = None) -> Dict[str, Any]:
    """find_trace_file + load + summarize, with the resolved file recorded
    so the artifact says what it measured."""
    trace_file = find_trace_file(path)
    out = summarize_events(load_trace_events(trace_file), steps=steps,
                           n_devices=n_devices)
    out["trace_file"] = trace_file
    return out
