#!/usr/bin/env python
"""Open-loop load generator + SERVE artifact assembly for the inference server.

Open-loop means arrival times are scheduled from the target rate alone
(request j fires at t0 + j/rate) regardless of how fast responses come
back — the discipline that actually measures tail latency under load; a
closed loop self-throttles exactly when the server saturates and reports
flattering percentiles. Jax-free (a load generator that imports the
serving stack is measuring itself).

Four modes:

  python tools/loadtest.py --url http://127.0.0.1:8000 --label packed \
      --rates 20,50 --duration 3 --out /tmp/packed.json
      # fire a mixed squad/ner burst at each swept rate; per rate record
      # p50/p95/p99 latency, achieved req/s, real_tokens/s, and the batch
      # occupancy over the window (delta of the server's cumulative
      # real/slot token counters, scraped from /metrics).

  python tools/loadtest.py --assemble serve.json packed.json padded.json
      # merge mode files into the cross-mode SERVE artifact.

  python tools/loadtest.py --validate serve.json
      # jax-free schema check (scripts/check_serve.sh gates on it); exit
      # 2 on violations.

  python tools/loadtest.py --check_distill distill.json 0.05
      # accuracy floor over a distill artifact (--assemble --kind
      # distill): exit 1 if any student leg lost more than 0.05 accuracy
      # to its teacher (scripts/check_distill.sh gates on it).

Exit codes (run mode): 0 with >=1 2xx response, 1 when every request
failed (the server is down or shedding everything), 2 unusable input.
"""

from __future__ import annotations

import argparse
import http.client
import json
import math
import os
import sys
import threading
import time
import urllib.parse
import urllib.request
from typing import Any, Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bert_pytorch_tpu.telemetry.registry import parse_prometheus  # noqa: E402

SERVE_SCHEMA_VERSION = 1
RATE_REQUIRED_KEYS = ("n", "n_2xx", "n_err", "duration_s", "p50_ms",
                      "p95_ms", "p99_ms", "req_per_sec",
                      "real_tokens_per_sec", "batch_occupancy")

# tiny deterministic word pool for synthetic payloads — the server's
# tokenizer maps unknown words to [UNK]; token COUNTS (what batching and
# throughput accounting see) are what matters here, not semantics
_WORDS = ("the cat sat on the mat a dog did run in the park who what "
          "where when how why fast slow red blue green bert serves "
          "packed rows").split()


def _payload(task: str, i: int, squad_long_every: int = 0,
             long_index: Optional[int] = None) -> Dict[str, Any]:
    """Deterministic request #i for any registered task, lengths varied
    so packing has something to pack (contexts 8-56 words, sentences
    4-36). Every task in tasks/registry.py must have a generator here —
    tests/test_task_registry.py pins the coverage.

    squad_long_every=N injects one LONG squad context (~440 words, the
    largest serving bucket) every Nth request — the heavy-tailed service
    mix the replica scale-out sweep needs: a realistic fleet serves rare
    long documents alongside dominant short traffic, and the tail of the
    SHORT requests stuck behind a long wave is exactly what work stealing
    exists to fix. 0 (default) keeps the legacy all-short mix.

    `long_index` decouples long placement from content: run_rate passes
    the LEG-LOCAL request index so every rate leg carries the same long
    fraction at the same phase (longs land at leg index N/2, 3N/2, ...).
    A global index here would scatter 0..5 longs per leg depending on
    where the cumulative offset fell — measured to make the per-rate p99
    curve non-monotone and the saturation rate meaningless."""
    pick = lambda k, n: " ".join(_WORDS[(k * 7 + j) % len(_WORDS)]
                                 for j in range(n))
    if task == "squad":
        if squad_long_every:
            li = i if long_index is None else long_index
            if li % squad_long_every == squad_long_every // 2:
                return {"question": f"who did thing {i % 13} ?",
                        "context": pick(i, 440) + " ."}
            # heavy-tailed mode needs the tail CONTROLLED: clamp short
            # contexts under the 64-token bucket, or every ~49th
            # "short" (56 words ~ 65+ tokens) silently rides the
            # largest bucket and the injected long fraction is a lie
            return {"question": f"who did thing {i % 13} ?",
                    "context": pick(i, 8 + (i * 11) % 28) + " ."}
        return {"question": f"who did thing {i % 13} ?",
                "context": pick(i, 8 + (i * 11) % 49) + " ."}
    if task == "classify":
        out = {"text": pick(i, 4 + (i * 5) % 29)}
        if i % 3 == 0:
            out["text_pair"] = pick(i + 1, 3 + (i * 7) % 17)
        return out
    if task == "choice":
        return {"question": pick(i, 3 + i % 7),
                "choices": [pick(i + c, 2 + (i + c) % 9)
                            for c in range(2 + i % 3)]}
    if task == "embed":
        if i % 4 == 0:  # batch-embed request
            return {"texts": [pick(i + t, 3 + (i + t) % 13)
                              for t in range(2 + i % 3)]}
        return {"text": pick(i, 4 + (i * 5) % 29)}
    return {"tokens": pick(i, 4 + (i * 5) % 33).split()}


def parse_task_mix(spec: str) -> List[str]:
    """'squad:2,ner:1' -> ['squad', 'squad', 'ner'] — the weighted
    round-robin task cycle a mixed-traffic sweep alternates through.
    Bare names get weight 1; 'all' expands to every registered task
    (the only path that imports the registry — plain --tasks stays
    jax-free)."""
    tasks: List[str] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, weight = part.partition(":")
        name = name.strip()
        w = int(weight) if weight.strip() else 1
        if w < 1:
            raise SystemExit(f"loadtest: --task_mix weight {w} < 1 "
                             f"({part!r})")
        if name == "all":
            from bert_pytorch_tpu.tasks.registry import all_tasks

            names = list(all_tasks())
        else:
            names = [name]
        for n in names:
            tasks.extend([n] * w)
    if not tasks:
        raise SystemExit(f"loadtest: empty --task_mix {spec!r}")
    return tasks


def _get(url: str, timeout: float = 5.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode("utf-8")


class _Client:
    """One persistent HTTP/1.1 connection (keep-alive). A per-request
    TCP connect + server-side thread spawn costs more than a tiny-model
    forward — without reuse the load test measures connection churn, not
    the serving stack."""

    def __init__(self, base_url: str, timeout: float):
        u = urllib.parse.urlsplit(base_url)
        self._host, self._port = u.hostname, u.port or 80
        self._timeout = timeout
        self._conn: Optional[http.client.HTTPConnection] = None

    def post(self, path: str, body: Dict[str, Any]
             ) -> Tuple[int, Dict[str, Any], Optional[str]]:
        """(status, body, X-Trace-Id header) — the trace id is what turns
        a slow response in this load test into a /v1/traces lookup."""
        data = json.dumps(body).encode("utf-8")
        for attempt in (0, 1):  # one silent reconnect on a dropped conn
            if self._conn is None:
                self._conn = http.client.HTTPConnection(
                    self._host, self._port, timeout=self._timeout)
            try:
                self._conn.request(
                    "POST", path, body=data,
                    headers={"Content-Type": "application/json"})
                r = self._conn.getresponse()
                payload = r.read()
                trace_id = r.getheader("X-Trace-Id")
                try:
                    return (r.status,
                            json.loads(payload.decode("utf-8")), trace_id)
                except ValueError:
                    return r.status, {}, trace_id
            except Exception as e:
                try:
                    self._conn.close()
                except Exception:
                    pass
                self._conn = None
                if attempt:
                    return 0, {"error": f"{type(e).__name__}: {e}"}, None
        return 0, {}, None

    def close(self) -> None:
        if self._conn is not None:
            try:
                self._conn.close()
            except Exception:
                pass


def _percentile(xs: List[float], q: float) -> float:
    if not xs:
        return float("nan")
    xs = sorted(xs)
    return xs[min(len(xs) - 1, max(0, math.ceil(q * len(xs)) - 1))]


def _scrape_serve(url: str) -> Optional[Dict[str, float]]:
    """Cumulative serving counters from /metrics, labels summed: real/slot
    tokens (occupancy) plus device-seconds and the device-hour price
    (cost-per-token). Missing series sum to 0.0 — an older server without
    the cost counters still yields occupancy."""
    try:
        parsed = parse_prometheus(_get(url + "/metrics"))
    except Exception:
        return None
    price = parsed.get("bert_serve_cost_per_device_hour", {})
    return {
        "real": sum(parsed.get("bert_serve_real_tokens_total", {}).values()),
        "slot": sum(parsed.get("bert_serve_slot_tokens_total", {}).values()),
        "device_seconds": sum(
            parsed.get("bert_serve_device_seconds_total", {}).values()),
        "cost_per_device_hour": next(iter(price.values()), 0.0),
    }


def run_rate(url: str, rate: float, duration: float, tasks: List[str],
             timeout: float, offset: int = 0,
             squad_long_every: int = 0,
             trace_log: Optional[List[Tuple[float, str]]] = None
             ) -> Dict[str, Any]:
    """One open-loop sweep at `rate` req/s for `duration` seconds.
    `trace_log` (when given) accumulates (latency_ms, X-Trace-Id) pairs
    for every 2xx across legs — the slowest entries are what
    --save_traces fetches back from /v1/traces after the sweep."""
    n = max(1, int(round(rate * duration)))
    lat_ms: List[float] = []
    statuses: List[int] = []
    real_tokens = [0.0]
    lock = threading.Lock()
    before = _scrape_serve(url)
    t0 = time.perf_counter()

    def fire(client: _Client, j: int) -> None:
        target = t0 + j / rate
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        task = tasks[j % len(tasks)]
        t_send = time.perf_counter()
        code, body, trace_id = client.post(
            f"/v1/{task}",
            _payload(task, offset + j, squad_long_every=squad_long_every,
                     long_index=j))
        ms = (time.perf_counter() - t_send) * 1e3
        with lock:
            statuses.append(code)
            if 200 <= code < 300:
                lat_ms.append(ms)
                real_tokens[0] += float(body.get("real_tokens", 0))
                if trace_log is not None and trace_id:
                    trace_log.append((ms, trace_id))

    # capped worker pool, arrivals interleaved across workers: worker w
    # owns requests w, w+W, w+2W, ... at their open-loop times, all on
    # ONE keep-alive connection. A slow response delays only that
    # worker's next arrival (1/W of the stream) — close enough to
    # open-loop at W=128 without a thread+connection per request.
    n_workers = min(128, n)

    def worker(w: int) -> None:
        client = _Client(url, timeout)
        try:
            for j in range(w, n, n_workers):
                fire(client, j)
        finally:
            client.close()

    threads = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(n_workers)]
    for t in threads:
        t.start()
    # worst case per worker: its whole request chain times out one by one
    # — budget for that, or stats below would be computed from a moving
    # snapshot while stragglers still append
    per_worker = -(-n // n_workers)  # ceil
    join_deadline = time.monotonic() + duration + per_worker * timeout + 60
    for t in threads:
        t.join(max(0.0, join_deadline - time.monotonic()))
    straggling = sum(1 for t in threads if t.is_alive())
    elapsed = max(time.perf_counter() - t0, 1e-9)
    after = _scrape_serve(url)
    with lock:  # freeze the shared lists even if stragglers survive
        lat_ms = list(lat_ms)
        statuses = list(statuses)
        total_real_tokens = real_tokens[0]

    occupancy = 0.0
    cost_fields: Dict[str, float] = {}
    if before is not None and after is not None:
        d_real = after["real"] - before["real"]
        d_slot = after["slot"] - before["slot"]
        occupancy = round(d_real / d_slot, 6) if d_slot > 0 else 0.0
        d_dev = after["device_seconds"] - before["device_seconds"]
        price = after["cost_per_device_hour"]
        if d_dev > 0:
            cost_fields["device_seconds"] = round(d_dev, 6)
            if d_real > 0 and price > 0:
                cost_fields["cost_per_1k_tokens"] = round(
                    d_dev / 3600.0 * price / (d_real / 1000.0), 9)
    n_2xx = sum(1 for s in statuses if 200 <= s < 300)
    by_code: Dict[str, int] = {}
    for s in statuses:
        by_code[str(s)] = by_code.get(str(s), 0) + 1

    def pct(q: float) -> Optional[float]:
        # a sweep with zero 2xx has no latency distribution: null (not 0)
        # so the artifact FAILS validation instead of flattering the gate
        v = _percentile(lat_ms, q)
        return None if math.isnan(v) else round(v, 3)

    out = {
        "rate_target": rate,
        "n": n,
        "n_2xx": n_2xx,
        "n_err": len(statuses) - n_2xx,
        "by_code": dict(sorted(by_code.items())),
        "duration_s": round(elapsed, 3),
        "p50_ms": pct(0.50),
        "p95_ms": pct(0.95),
        "p99_ms": pct(0.99),
        "req_per_sec": round(n_2xx / elapsed, 3),
        "real_tokens_per_sec": round(total_real_tokens / elapsed, 1),
        "batch_occupancy": occupancy,
    }
    out.update(cost_fields)
    if straggling:
        out["straggling_workers"] = straggling
    return out


def parse_rate_sweep(spec: str) -> List[float]:
    """'START:FACTOR:MAX' -> geometric rate ramp [START, START*FACTOR,
    ...] up to and including the first rate >= MAX — the open-loop
    saturation curve grid (`--rate_sweep`)."""
    parts = spec.split(":")
    if len(parts) != 3:
        raise SystemExit(f"loadtest: --rate_sweep wants START:FACTOR:MAX, "
                         f"got {spec!r}")
    start, factor, stop = (float(p) for p in parts)
    if start <= 0 or factor <= 1 or stop < start:
        raise SystemExit(f"loadtest: bad --rate_sweep {spec!r} (need "
                         "START>0, FACTOR>1, MAX>=START)")
    rates, r = [], start
    while True:
        rates.append(round(r, 6))
        if r >= stop:
            return rates
        r *= factor


def saturation_from_rates(rates: Dict[str, Any],
                          p99_bound: Optional[float]) -> Dict[str, Any]:
    """Mode-level saturation: the best ACHIEVED req/s among swept rates
    whose p99 stayed under the bound (no bound: among all rates with any
    2xx). 'At equal p99 bound' is the whole point — raw peak req/s past
    the latency knee flatters a saturated server that is busy timing
    everyone out."""
    best = None
    for rec in rates.values():
        p99 = rec.get("p99_ms")
        if not rec.get("n_2xx") or not isinstance(p99, (int, float)):
            continue
        if p99_bound is not None and p99 > p99_bound:
            continue
        if best is None or rec["req_per_sec"] > best["req_per_sec"]:
            best = rec
    out = {
        "p99_bound_ms": p99_bound,
        "req_per_sec": best["req_per_sec"] if best else 0.0,
        "at_rate": best["rate_target"] if best else None,
        "p99_ms": best["p99_ms"] if best else None,
    }
    # cost at the saturation point — the "cost per 1k tokens at equal
    # p99" number (lower-better)
    if best is not None:
        for k in ("cost_per_1k_tokens", "device_seconds"):
            if k in best:
                out[k] = best[k]
    return out


def _collect_traces(url: str, label: str,
                    trace_log: List[Tuple[float, str]],
                    out_dir: str, top_n: int = 16) -> Dict[str, Any]:
    """Fetch the slowest client-observed request traces from /v1/traces
    and save them beside the SERVE artifact. Targeted fetch first (the
    X-Trace-Ids of our slowest 2xx responses); falls back to the server's
    full flight-recorder snapshot when those ids already rotated out of
    the ring. Returns the mode-record fields (file path + per-phase
    summary); empty dict when the server has no tracing."""
    fields: Dict[str, Any] = {}
    slowest = sorted(trace_log, reverse=True)[:top_n]
    # one response can carry several comma-joined ids (batch embed)
    ids = [tid for _, joined in slowest
           for tid in joined.split(",") if tid]
    doc = None
    if ids:
        try:
            doc = json.loads(_get(
                url + "/v1/traces?id=" + ",".join(ids[:64])))
        except Exception:
            doc = None
    if not (doc and doc.get("traceEvents")):
        try:
            doc = json.loads(_get(url + "/v1/traces"))
        except Exception:
            return fields
    if not doc.get("traceEvents"):
        return fields
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"traces_{label}.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True, allow_nan=False)
        f.write("\n")
    fields["trace_file"] = path
    if ids:
        fields["slowest_trace_ids"] = ids[:top_n]
    try:
        from bert_pytorch_tpu.telemetry.trace import \
            summarize_request_events

        summary = summarize_request_events(doc["traceEvents"])
        fields["request_trace_summary"] = summary
        p99 = summary.get("p99") or {}
        if p99.get("dominant_phase"):
            where = f" on {p99['replica']}" if p99.get("replica") else ""
            print(f"loadtest: [{label}] p99 is "
                  f"{p99['dominant_share']:.0%} "
                  f"{p99['dominant_phase']}{where} "
                  f"({summary['n_traces']} trace(s) saved -> {path})",
                  file=sys.stderr)
    except Exception as e:  # summary is best-effort; the file is saved
        print(f"loadtest: [{label}] trace summary failed: {e}",
              file=sys.stderr)
    return fields


def run_mode(url: str, label: str, rates: List[float], duration: float,
             tasks: List[str], timeout: float,
             meta: Optional[Dict[str, Any]] = None,
             p99_bound: Optional[float] = None,
             squad_long_every: int = 0,
             save_traces: Optional[str] = None) -> Dict[str, Any]:
    out: Dict[str, Any] = {"schema_version": SERVE_SCHEMA_VERSION,
                           "kind": "serve_mode", "label": label,
                           "url": url, "tasks": tasks,
                           "time_unix": round(time.time(), 3), "rates": {}}
    if meta:
        out["meta"] = dict(meta)
    trace_log: Optional[List[Tuple[float, str]]] = \
        [] if save_traces else None
    offset = 0
    for rate in rates:
        print(f"loadtest: [{label}] rate {rate:g} req/s x {duration:g}s ...",
              file=sys.stderr)
        rec = run_rate(url, rate, duration, tasks, timeout, offset=offset,
                       squad_long_every=squad_long_every,
                       trace_log=trace_log)
        offset += rec["n"]
        out["rates"][f"{rate:g}"] = rec
        print(f"loadtest: [{label}] rate {rate:g}: {rec['n_2xx']}/{rec['n']} "
              f"2xx, p50 {rec['p50_ms']}ms p99 {rec['p99_ms']}ms, "
              f"{rec['req_per_sec']} req/s, occupancy "
              f"{rec['batch_occupancy']}", file=sys.stderr)
    out["saturation"] = saturation_from_rates(out["rates"], p99_bound)
    sat = out["saturation"]
    print(f"loadtest: [{label}] saturation {sat['req_per_sec']:g} req/s "
          f"(p99 bound {p99_bound}, at target rate {sat['at_rate']})",
          file=sys.stderr)
    if save_traces and trace_log is not None:
        out.update(_collect_traces(url, label, trace_log, save_traces))
    try:
        out["healthz"] = json.loads(_get(url + "/healthz"))
    except Exception:
        pass
    return out


# -- artifact assembly + validation (jax-free) --------------------------------


def _sat_per_chip(mode: Dict[str, Any]) -> Optional[float]:
    """Saturation req/s per chip — the distillation headline unit."""
    sat = mode.get("saturation") or {}
    rps = sat.get("req_per_sec")
    if not isinstance(rps, (int, float)) or not rps:
        return None
    n_chips = (mode.get("meta") or {}).get("n_chips")
    return rps / (n_chips if isinstance(n_chips, (int, float))
                  and n_chips > 0 else 1)


def assemble(mode_paths: List[str], kind: str = "serve",
             accuracies: Optional[Dict[str, float]] = None
             ) -> Dict[str, Any]:
    modes: Dict[str, Any] = {}
    newest = 0.0
    for path in mode_paths:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        label = doc.get("label") or os.path.splitext(
            os.path.basename(path))[0]
        modes[label] = {"rates": doc.get("rates", {}),
                        "tasks": doc.get("tasks"),
                        "url": doc.get("url")}
        for extra in ("meta", "saturation", "request_trace_summary",
                      "trace_file", "slowest_trace_ids"):
            if doc.get(extra) is not None:
                modes[label][extra] = doc[extra]
        newest = max(newest, float(doc.get("time_unix") or 0))
    # replica scale-out ratio: each multi-replica mode vs the
    # single-replica mode of the SAME dtype (higher is better)
    singles = {str(m.get("meta", {}).get("dtype", "")): m
               for m in modes.values()
               if m.get("meta", {}).get("replicas") == 1
               and m.get("saturation", {}).get("req_per_sec")}
    for mode in modes.values():
        meta = mode.get("meta", {})
        base = singles.get(str(meta.get("dtype", "")))
        if (base is not None and base is not mode
                and isinstance(meta.get("replicas"), int)
                and meta["replicas"] > 1
                and mode.get("saturation", {}).get("req_per_sec")):
            mode["saturation"]["vs_single_replica"] = round(
                mode["saturation"]["req_per_sec"]
                / base["saturation"]["req_per_sec"], 3)
    out = {"schema_version": SERVE_SCHEMA_VERSION, "kind": kind,
           "time_unix": newest or round(time.time(), 3), "modes": modes}
    if kind != "distill":
        return out
    # distill artifact: modes are teacher/student serving legs keyed by
    # meta.model_tag (--model_tag — no filename conventions); each leg
    # gains its task accuracy, its delta vs the teacher (the accuracy-
    # floor gate input), and its per-chip saturation ratio vs the
    # teacher leg of the same dtype (f32 teacher as fallback)
    acc = dict(accuracies or {})
    out["accuracies"] = acc
    teacher_acc = acc.get("teacher")
    teachers = {str(m.get("meta", {}).get("dtype", "")): m
                for m in modes.values()
                if str(m.get("meta", {}).get("model_tag", "")) == "teacher"
                and m.get("saturation", {}).get("req_per_sec")}
    for mode in modes.values():
        meta = mode.get("meta", {})
        tag = meta.get("model_tag")
        if tag is None:
            continue
        tag = str(tag)
        if tag in acc:
            mode["accuracy"] = acc[tag]
            if teacher_acc is not None:
                mode["accuracy_delta"] = round(teacher_acc - acc[tag], 6)
        if tag == "teacher":
            continue
        base = (teachers.get(str(meta.get("dtype", "")))
                or next(iter(teachers.values()), None))
        mine = _sat_per_chip(mode)
        theirs = _sat_per_chip(base) if base is not None else None
        if mine and theirs:
            mode["saturation"]["vs_teacher_per_chip"] = round(
                mine / theirs, 3)
    return out


def validate_serve(doc: Any) -> List[str]:
    """Schema errors of a SERVE artifact (empty list = valid)."""
    errors: List[str] = []
    if not isinstance(doc, dict):
        return ["artifact is not a JSON object"]
    if doc.get("schema_version") != SERVE_SCHEMA_VERSION:
        errors.append(f"schema_version {doc.get('schema_version')!r} != "
                      f"{SERVE_SCHEMA_VERSION}")
    modes = doc.get("modes")
    if not isinstance(modes, dict) or not modes:
        return errors + ["'modes' missing or empty"]
    for label, mode in sorted(modes.items()):
        rates = mode.get("rates") if isinstance(mode, dict) else None
        if not isinstance(rates, dict) or not rates:
            errors.append(f"mode '{label}': no 'rates'")
            continue
        for rate, rec in sorted(rates.items()):
            if not isinstance(rec, dict):
                errors.append(f"mode '{label}' rate {rate}: not an object")
                continue
            for k in RATE_REQUIRED_KEYS:
                v = rec.get(k)
                if not isinstance(v, (int, float)) or isinstance(v, bool) \
                        or (isinstance(v, float) and math.isnan(v)):
                    errors.append(f"mode '{label}' rate {rate}: field "
                                  f"'{k}' missing or non-numeric ({v!r})")
        sat = mode.get("saturation") if isinstance(mode, dict) else None
        if sat is not None:
            v = sat.get("req_per_sec") if isinstance(sat, dict) else None
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                errors.append(f"mode '{label}': saturation.req_per_sec "
                              f"missing or non-numeric ({v!r})")
    return errors


def validate_distill(doc: Any, floor: float
                     ) -> Tuple[List[str], List[str]]:
    """Accuracy-floor gate over ONE distill artifact: every student leg
    (meta.model_tag set and != 'teacher') must carry an accuracy_delta
    (teacher accuracy minus its own) no larger than floor.
    Direction-aware: a student BEATING its teacher (delta <= 0) passes
    by any margin; only quality lost to compression trips. A student
    leg with no delta recorded fails loudly — an unmeasured student is
    not a passing student. Returns (failures, notes)."""
    if not isinstance(doc, dict) or doc.get("kind") != "distill":
        kind = doc.get("kind") if isinstance(doc, dict) else None
        return [f"GATE: artifact is kind {kind!r}, not a distill artifact "
                "(tools/loadtest.py --assemble --kind distill)"], []
    failures: List[str] = []
    notes: List[str] = []
    students = 0
    for label, mode in sorted((doc.get("modes") or {}).items()):
        if not isinstance(mode, dict):
            continue
        tag = str((mode.get("meta") or {}).get("model_tag") or "")
        if not tag or tag == "teacher":
            continue
        students += 1
        delta = mode.get("accuracy_delta")
        if not isinstance(delta, (int, float)) or isinstance(delta, bool):
            failures.append(
                f"GATE: student leg '{label}' ({tag}) carries no "
                "accuracy_delta — unmeasured students do not pass")
        elif delta > floor:
            failures.append(
                f"GATE: student leg '{label}' ({tag}) lost {delta:g} "
                f"accuracy vs its teacher (> floor {floor:g})")
        else:
            notes.append(
                f"ok: '{label}' ({tag}) accuracy_delta {delta:g} "
                f"<= {floor:g}"
                + (" (beats teacher)" if delta < 0 else ""))
    if students == 0:
        failures.append(
            "GATE: no student legs (modes with meta.model_tag != "
            "'teacher') in artifact — nothing to gate")
    return failures, notes


def _load_artifact(path: str) -> Any:
    """The JSON document at path, or None (with the reason printed)."""
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        print(f"loadtest: unreadable {path}: {e}")
        return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--url", default=None, help="server base URL")
    ap.add_argument("--label", default="packed",
                    help="mode label recorded in the output (packed/padded)")
    ap.add_argument("--rates", default="10,30",
                    help="comma-separated request rates (req/s) to sweep")
    ap.add_argument("--rate_sweep", default=None, metavar="START:FACTOR:MAX",
                    help="geometric saturation ramp (overrides --rates): "
                         "sweep START, START*FACTOR, ... through MAX and "
                         "record the mode's saturation req/s at the p99 "
                         "bound")
    ap.add_argument("--p99_bound", type=float, default=None,
                    help="latency SLO for the saturation number: only "
                         "rates with p99_ms <= this count (no bound: any "
                         "rate with >=1 2xx)")
    ap.add_argument("--meta", action="append", default=None,
                    metavar="KEY=VALUE",
                    help="mode metadata recorded in the artifact "
                         "(replicas=2, dtype=f32, n_chips=2, ...); "
                         "repeatable")
    ap.add_argument("--duration", type=float, default=3.0,
                    help="seconds per rate sweep")
    ap.add_argument("--tasks", default="squad,ner",
                    help="comma-separated tasks to alternate between")
    ap.add_argument("--task_mix", default=None,
                    help="weighted mixed-traffic spec, e.g. "
                         "'squad:2,ner:1,classify:1' or 'all' / 'all:1' "
                         "(every registered task, equal weight); "
                         "overrides --tasks")
    ap.add_argument("--squad_long_every", type=int, default=0,
                    help="inject one ~440-word squad context every Nth "
                         "request (0 = off): the heavy-tailed service "
                         "mix the replica scale-out sweep measures")
    ap.add_argument("--timeout", type=float, default=30.0,
                    help="per-request client timeout (s)")
    ap.add_argument("--save_traces", default=None, metavar="DIR",
                    help="after the sweep, fetch the slowest-request "
                         "span timelines from /v1/traces (ids captured "
                         "from X-Trace-Id response headers) and save "
                         "traces_{label}.json under DIR; the per-phase "
                         "summary is embedded in the mode record")
    ap.add_argument("--model_tag", default=None,
                    help="which model this leg serves (teacher, "
                         "student_6l_768, ...); recorded as "
                         "meta.model_tag: --assemble --kind distill "
                         "tells teacher and student legs apart by it")
    ap.add_argument("--out", default=None, help="mode JSON output path")
    ap.add_argument("--assemble", nargs="+", default=None,
                    metavar=("OUT", "MODE_JSON"),
                    help="merge mode files into a SERVE artifact: "
                         "OUT IN1 [IN2 ...]")
    ap.add_argument("--kind", choices=["serve", "distill"],
                    default="serve",
                    help="artifact kind for --assemble: 'distill' adds "
                         "per-leg accuracy, accuracy_delta vs the "
                         "teacher leg, and saturation."
                         "vs_teacher_per_chip")
    ap.add_argument("--accuracy", action="append", default=None,
                    metavar="TAG=VAL",
                    help="task accuracy for a model_tag (teacher=0.92 "
                         "student_6l_768=0.91); repeatable, used by "
                         "--assemble --kind distill")
    ap.add_argument("--require_healthy", action="store_true",
                    help="check /healthz before sending traffic and fail "
                         "fast (exit 3) when the target's SLO status is "
                         "'failing' — a bench leg against a failing "
                         "server measures the outage, not the server")
    ap.add_argument("--validate", default=None, metavar="SERVE_JSON",
                    help="schema-check a SERVE artifact and exit")
    ap.add_argument("--check_distill", nargs=2, default=None,
                    metavar=("DISTILL_JSON", "FLOOR"),
                    help="accuracy-floor gate over one distill artifact: "
                         "exit 1 if any student leg lost more than FLOOR "
                         "accuracy to the teacher (or carries no "
                         "measured delta); students that beat the "
                         "teacher always pass")
    args = ap.parse_args(argv)

    if args.check_distill:
        path = args.check_distill[0]
        try:
            floor = float(args.check_distill[1])
        except ValueError:
            print(f"loadtest: --check_distill FLOOR must be a number, "
                  f"got {args.check_distill[1]!r}")
            return 2
        doc = _load_artifact(path)
        if doc is None:
            return 2
        failures, notes = validate_distill(doc, floor)
        for line in notes + failures:
            print(line)
        if failures:
            print(f"loadtest: distill accuracy gate FAILED "
                  f"({len(failures)} problem(s), floor {floor:g}, {path})")
            return 1
        print(f"loadtest: distill accuracy gate ok (floor {floor:g}, "
              f"{path})")
        return 0

    if args.validate:
        doc = _load_artifact(args.validate)
        if doc is None:
            return 2
        errors = validate_serve(doc)
        for e in errors:
            print(f"loadtest: schema: {e}")
        if errors:
            return 2
        n_rates = sum(len(m.get("rates", {}))
                      for m in doc["modes"].values())
        print(f"loadtest: {args.validate} schema ok "
              f"({len(doc['modes'])} mode(s), {n_rates} rate sweep(s))")
        return 0

    if args.assemble:
        if len(args.assemble) < 2:
            print("loadtest: --assemble needs OUT and >=1 mode file")
            return 2
        out_path, mode_paths = args.assemble[0], args.assemble[1:]
        accuracies = {}
        for entry in args.accuracy or []:
            k, sep, v = entry.partition("=")
            try:
                accuracies[k] = float(v)
            except ValueError:
                sep = ""
            if not sep or not k:
                print(f"loadtest: --accuracy wants TAG=VAL, got {entry!r}")
                return 2
        doc = assemble(mode_paths, kind=args.kind, accuracies=accuracies)
        errors = validate_serve(doc)
        for e in errors:
            print(f"loadtest: schema: {e}")
        if errors:
            return 2
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1, sort_keys=True, allow_nan=False)
            f.write("\n")
        print(f"loadtest: wrote {out_path} ({', '.join(sorted(doc['modes']))})")
        return 0

    if not args.url:
        print("loadtest: --url required (or --assemble/--validate)")
        return 2
    if args.require_healthy:
        hz_url = args.url.rstrip("/") + "/healthz"
        try:
            hz = json.loads(_get(hz_url, timeout=args.timeout))
        except Exception as e:
            print(f"loadtest: --require_healthy: {hz_url} unreachable "
                  f"({e})", file=sys.stderr)
            return 3
        status = hz.get("status", "ok")
        if status == "failing":
            firing = (hz.get("slo") or {}).get("firing", [])
            print(f"loadtest: --require_healthy: target reports "
                  f"status=failing (firing: {', '.join(firing) or '?'}) "
                  "— refusing to send traffic", file=sys.stderr)
            return 3
        if status != "ok":
            print(f"loadtest: warning: target status={status} "
                  "(proceeding)", file=sys.stderr)
    if args.rate_sweep:
        rates = parse_rate_sweep(args.rate_sweep)
    else:
        rates = [float(r) for r in args.rates.split(",") if r.strip()]
    if args.task_mix:
        tasks = parse_task_mix(args.task_mix)
    else:
        tasks = [t.strip() for t in args.tasks.split(",") if t.strip()]
    meta = {}
    if args.model_tag:
        meta["model_tag"] = args.model_tag
    for entry in args.meta or []:
        k, sep, v = entry.partition("=")
        if not sep or not k:
            print(f"loadtest: --meta wants KEY=VALUE, got {entry!r}")
            return 2
        try:
            meta[k] = int(v)
        except ValueError:
            try:
                meta[k] = float(v)
            except ValueError:
                meta[k] = v
    doc = run_mode(args.url.rstrip("/"), args.label, rates, args.duration,
                   tasks, args.timeout, meta=meta or None,
                   p99_bound=args.p99_bound,
                   squad_long_every=args.squad_long_every,
                   save_traces=args.save_traces)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1, sort_keys=True, allow_nan=False)
            f.write("\n")
        print(f"loadtest: wrote {args.out}", file=sys.stderr)
    else:
        json.dump(doc, sys.stdout, indent=1, sort_keys=True,
                  allow_nan=False)
        print()
    total_2xx = sum(r["n_2xx"] for r in doc["rates"].values())
    if total_2xx == 0:
        print("loadtest: FAILED — zero 2xx responses", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
