"""Open-loop load for a serving cell: a schedule from the seed, latency from
the instant a request was DUE, failures kept in the tail.

No cell uses it yet (PERF.md section 7: the serving cell is open); it is here,
with its tests against a stub server, so that the cell's PR adds data files
and a driver only. The three faults of `tools/loadtest.py` it exists to avoid:
latency timed from the send (a starved generator reads as a fast server),
evenly spaced arrivals, and failed requests dropping out of the percentiles.
Standard library only; it never touches JAX.
"""

from __future__ import annotations

import math
import queue
import random
import threading
import time

CANON_SEED = 20260927   # every seed permutes the SAME gaps and lengths


def schedule(seed: int, mix: dict, seconds: float) -> list:
    """[(due_s, length)]: Poisson arrivals at mix["rate_per_s"] over
    `seconds`, request lengths lognormal (mix["length"]: median, sigma, min,
    max). The multiset of gaps and of lengths is the same for every seed (it
    is drawn once from CANON_SEED); the seed orders them, so two seeds offer
    the same work in another order."""
    n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    canon = random.Random(CANON_SEED)
    gaps = [canon.expovariate(1.0) for _ in range(n)]
    scale = seconds / sum(gaps) * n / (n + 1)   # the last arrival is inside
    ln = mix["length"]
    mu = math.log(float(ln["median"]))
    lengths = [int(min(max(round(canon.lognormvariate(mu, float(ln["sigma"]))),
                           int(ln["min"])), int(ln["max"])))
               for _ in range(n)]
    order = random.Random(int(seed))
    order.shuffle(gaps)
    order.shuffle(lengths)
    due, out = 0.0, []
    for gap, length in zip(gaps, lengths):
        due += gap * scale
        out.append((due, length))
    return out


def run_load(send, plan: list, workers: int, timeout_s: float) -> list:
    """Offers `plan` in real time: a dispatcher hands each request to a pool
    of `workers` threads at its due time; `send(length)` returns the number of
    real tokens served or raises. One record a request: due, sent (when a
    worker picked it up), done, tokens, ok. A request still unanswered
    `timeout_s` after the last due time is a failure."""
    jobs: queue.Queue = queue.Queue()
    records = [None] * len(plan)
    t0 = time.perf_counter()

    def worker():
        while True:
            item = jobs.get()
            if item is None:
                return
            i, due, length = item
            sent = time.perf_counter() - t0
            try:
                tokens, ok = int(send(length)), True
            except Exception:
                tokens, ok = 0, False
            records[i] = {"due": due, "sent": sent,
                          "done": time.perf_counter() - t0,
                          "tokens": tokens, "ok": ok}

    pool = [threading.Thread(target=worker, daemon=True)
            for _ in range(workers)]
    for th in pool:
        th.start()
    for i, (due, length) in enumerate(plan):
        wait = due - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        jobs.put((i, due, length))
    for _ in pool:
        jobs.put(None)
    deadline = t0 + plan[-1][0] + timeout_s
    for th in pool:
        th.join(max(0.0, deadline - time.perf_counter()))
    end = time.perf_counter() - t0
    records = list(records)     # a worker still waiting writes to the old one
    for i, (due, _) in enumerate(plan):
        if records[i] is None:      # never answered: as long as we waited
            records[i] = {"due": due, "sent": None, "done": end,
                          "tokens": 0, "ok": False}
    return records


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile of a non-empty list."""
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1,
                       max(0, math.ceil(q / 100.0 * len(ordered)) - 1))]


def summarize(records: list, window_s: float) -> dict:
    """Latency from the DUE time. A failed request counts as the longest
    latency seen (or its own wait, if longer), so it stays in the tail. The
    rate counts tokens of requests completed inside the window. `lateness`
    is the generator's own: pick-up time minus due time."""
    ok = [r for r in records if r["ok"]]
    longest = max([r["done"] - r["due"] for r in records], default=0.0)
    lat = [(r["done"] - r["due"]) if r["ok"] else longest for r in records]
    late = [r["sent"] - r["due"] for r in records if r["sent"] is not None]
    return {
        "attempted": len(records), "failed": len(records) - len(ok),
        "p50_ms": 1e3 * percentile(lat, 50), "p95_ms": 1e3 * percentile(lat, 95),
        "tokens_per_s": sum(r["tokens"] for r in ok
                            if r["done"] <= window_s) / window_s,
        "lateness_p95_ms": 1e3 * percentile(late, 95) if late else None,
    }
