"""Continuous batching: bounded queue -> packed rows -> per-request demux.

The training packer (data/packing.first_fit) is exactly the multi-tenant
batching primitive an inference server needs ("Boosting Distributed
Training Performance of the Unpadded BERT Model", PAPERS.md 2208.08124):
several short requests share one (S,) row, segment-aware attention keeps
them from seeing each other, and the per-request outputs are plain row
slices because every head this server runs (QA span logits, NER token
logits) is token-local. Packed-vs-one-per-batch responses decode to the
same answers, with logits equal up to the summation order of the attention
core's `probs @ V` (tests/test_serving.py pins it; bit-identical for a
request at the start of its row): cross-segment attention probabilities
are exactly zero on every kernel path, reductions keep the same length
(the row is the bucket either way), and nothing else mixes tokens.

Flow control, in order:

- `submit()` raises `TooLong` when the request exceeds the largest bucket
  (HTTP 413 — no amount of waiting will ever fit it) and `Overloaded`
  when the bounded queue is full (HTTP 503 + Retry-After: shedding at
  admission keeps tail latency bounded instead of letting the queue grow
  without limit).
- the DISPATCHER thread drains the queue, expires requests older than the
  admission timeout (`RequestTimeout`, HTTP 504 — the client has likely
  given up; computing its answer is pure waste), groups one task per
  batch, picks the bucket of the longest drained request, and first-fits
  requests into `batch_rows` rows. Packing off = the same first_fit with
  max_segments=1, so both modes run the identical compiled program and
  differ only in row occupancy.
- a packed wave is handed to a REPLICA queue (shallowest first) and a
  per-replica worker thread executes it on that replica's engine. An
  idle worker steals the OLDEST waiting wave from the DEEPEST other
  queue (work stealing, not static round-robin: mixed-bucket traffic
  makes static assignment lumpy — one replica drowning in 512-bucket
  squad waves while another idles on drained ner traffic). With one
  replica this degenerates to exactly the old single-loop behavior.
  The dispatcher keeps at most ~2 waves per replica outstanding
  (backpressure), so packing still sees a deep pending pool —
  continuous batching, not fixed waves.
- requests that do not fit the current batch stay pending IN ARRIVAL
  ORDER for the next one.

Every signal lands in the phase="serve" registry: request counters by
task/outcome, end-to-end latency histograms, live queue depth (global
plus per-replica `{replica=}` gauges, published on every enqueue/
dequeue/steal transition so scrapes between waves read live depths),
per-batch occupancy, a steal counter, and cumulative real/slot token
counters (the loadtest derives batch occupancy per rate sweep from
their deltas).

Request-path tracing (serving/request_trace.py) rides the same flow:
every admitted request gets a RequestTrace that accumulates host-side
spans (admit/queue_wait/pack/dispatch/compute/demux/respond, terminal
shed/timeout/too_long/error) and retires into the scheduler's TraceRing.
All span recording is host Python on host timestamps — nothing touches
the batch arrays or the compiled program, which is why tracing on/off
cannot perturb a response. The compute span also
drives the cost layer: wave wall-time x replica device count =
device-seconds, pro-rated to member requests by real tokens and
accumulated into `bert_serve_device_seconds_total` and the per-task
cost-per-1k-tokens gauge at the configured price per device-hour.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bert_pytorch_tpu.data.packing import first_fit
from bert_pytorch_tpu.serving.request_trace import TraceRing, note_trace_id
from bert_pytorch_tpu.telemetry.stepwatch import resolve_cost_per_device_hour


class Overloaded(Exception):
    """Queue full — shed at admission (HTTP 503)."""


class RequestTimeout(Exception):
    """Waited longer than the admission timeout (HTTP 504)."""


class TooLong(Exception):
    """Longer than the largest bucket (HTTP 413)."""


# histogram buckets for end-to-end request latency (ms): sub-ms cache-hit
# territory through multi-second overload tails
LATENCY_BUCKETS_MS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                      500.0, 1000.0, 2500.0, 5000.0, 10000.0, 30000.0)


@dataclass
class InferenceRequest:
    """One queued forward: already-featurized token ids (length L <= the
    largest bucket), resolved to a per-segment output slice."""

    task: str
    input_ids: np.ndarray            # (L,) int32
    token_type_ids: np.ndarray       # (L,) int32
    t_enqueue: float = field(default_factory=time.perf_counter)
    done: threading.Event = field(default_factory=threading.Event)
    result: Any = None               # task-shaped output slices
    error: Optional[Exception] = None
    trace: Any = None                # RequestTrace when tracing is on
    t_resolve: float = 0.0           # respond-span start (set by resolve)

    @property
    def length(self) -> int:
        return int(len(self.input_ids))

    def resolve(self, result: Any = None,
                error: Optional[Exception] = None) -> None:
        self.result = result
        self.error = error
        self.t_resolve = time.perf_counter()
        self.done.set()


@dataclass
class _Wave:
    """One packed batch, ready to execute: the dispatcher builds these,
    a replica worker runs them. placements is the (request, row, offset,
    segment) demux layout from `Scheduler._assemble`."""

    task: str
    bucket: int
    batch: Dict[str, np.ndarray]
    placements: List[Tuple[InferenceRequest, int, int, int]]
    t_queued: float = 0.0            # when the dispatcher queued it
    queued_on: int = 0               # replica whose queue received it


class Scheduler:
    """The continuous-batching loop around one or more ServingEngines.

    `engine` is a single engine (the common case, and the pre-replica
    signature every existing caller uses) or a sequence of data-parallel
    replica engines over disjoint device slices (`--serve_replicas`).
    All replicas must share buckets/batch_rows/max_segments — the
    dispatcher packs once and any replica can run the wave."""

    def __init__(self, engine,
                 queue_size: int = 128,
                 admission_timeout_s: float = 10.0,
                 batch_wait_ms: float = 2.0,
                 packing: bool = True,
                 registry=None,
                 trace_ring: Optional[TraceRing] = None,
                 tracing: bool = True,
                 cost_per_device_hour: Optional[float] = None):
        engines = (list(engine) if isinstance(engine, (list, tuple))
                   else [engine])
        if not engines:
            raise ValueError("need at least one engine")
        self.engines = engines
        self.engine = engines[0]
        self.packing = bool(packing)
        # tracing=False is the A/B switch the bit-identity/overhead tests
        # flip; on by default because the per-request cost is microseconds
        if not tracing:
            self.trace_ring: Optional[TraceRing] = None
        else:
            self.trace_ring = (trace_ring if trace_ring is not None
                               else TraceRing())
        self.cost_per_device_hour = resolve_cost_per_device_hour(
            cost_per_device_hour)
        self._cost_lock = threading.Lock()
        self._task_device_seconds: Dict[str, float] = {}
        self._task_real_tokens: Dict[str, float] = {}
        self.admission_timeout_s = float(admission_timeout_s)
        self.batch_wait_s = float(batch_wait_ms) / 1e3
        self._q: "queue.Queue[InferenceRequest]" = queue.Queue(
            maxsize=int(queue_size))
        self._pending: List[InferenceRequest] = []
        self._closed = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._workers: List[threading.Thread] = []
        # per-replica dispatch queues + everything their workers touch,
        # all under one condition: wave handoff, stealing, backpressure
        self._wv = threading.Condition()
        self._waves: List[deque] = [deque() for _ in engines]
        self._inflight = [0] * len(engines)
        self._rstats = [{"dispatched": 0, "steals": 0,
                         "last_dispatch_unix": None} for _ in engines]
        # dispatcher keeps at most this many waves queued fleet-wide so
        # late arrivals still coalesce into deep packs
        self._wave_cap = 2 * len(engines)
        self._init_metrics(registry)

    # -- metrics --------------------------------------------------------------

    def _init_metrics(self, registry) -> None:
        if registry is None:
            from bert_pytorch_tpu.telemetry.registry import MetricsRegistry

            registry = MetricsRegistry(constant_labels={"phase": "serve"})
        self.registry = registry
        self._m_requests = registry.counter(
            "bert_serve_requests_total",
            "requests by task and outcome (ok/too_long/overloaded/"
            "timeout/error)", labels=("task", "outcome"))
        self._m_latency = registry.histogram(
            "bert_serve_request_latency_ms",
            "end-to-end request latency (enqueue -> result), ms",
            labels=("task",), buckets=LATENCY_BUCKETS_MS)
        self._m_depth = registry.gauge(
            "bert_serve_queue_depth",
            "requests admitted but not yet dispatched")
        self._m_batches = registry.counter(
            "bert_serve_batches_total", "forward batches dispatched",
            labels=("task", "bucket"))
        self._m_real_tokens = registry.counter(
            "bert_serve_real_tokens_total",
            "non-pad tokens dispatched to the device")
        self._m_slot_tokens = registry.counter(
            "bert_serve_slot_tokens_total",
            "token slots the device computed (batch_rows x bucket per "
            "batch, pad included)")
        self._m_occupancy = registry.gauge(
            "bert_serve_batch_occupancy",
            "last batch's real tokens / computed slots")
        self._m_segments = registry.gauge(
            "bert_serve_batch_segments",
            "last batch's packed request count")
        self._m_replica_depth = registry.gauge(
            "bert_serve_replica_queue_depth",
            "waves queued on one replica's dispatch queue",
            labels=("replica",))
        self._m_replica_occupancy = registry.gauge(
            "bert_serve_replica_batch_occupancy",
            "one replica's last batch real tokens / computed slots",
            labels=("replica",))
        self._m_steals = registry.counter(
            "bert_serve_steals_total",
            "waves an idle replica stole from another replica's queue",
            labels=("replica",))
        self._m_device_seconds = registry.counter(
            "bert_serve_device_seconds_total",
            "device-seconds of engine compute (wave wall time x the "
            "replica's device count)", labels=("task",))
        self._m_cost = registry.gauge(
            "bert_serve_cost_per_1k_tokens",
            "cumulative device-seconds priced at cost_per_device_hour, "
            "per 1000 real (non-pad) tokens served", labels=("task",))
        self._m_cost_rate = registry.gauge(
            "bert_serve_cost_per_device_hour",
            "the price knob the cost gauges are quoted in "
            "(currency units per device-hour)")
        self._m_cost_rate.set(self.cost_per_device_hour)
        for i in range(len(self.engines)):
            self._m_replica_depth.set(0, replica=str(i))
            self._m_replica_occupancy.set(0.0, replica=str(i))
            self._m_steals.inc(0, replica=str(i))

    def _update_depth(self) -> None:
        with self._wv:
            queued = sum(len(w.placements) for q in self._waves for w in q)
        self._m_depth.set(self._q.qsize() + len(self._pending) + queued)

    def _publish_replica_depth(self, *indices: int) -> None:
        """Publish replica queue-depth gauges. Called (with _wv held) at
        EVERY enqueue/dequeue/steal transition — not only from batching-
        loop iterations — so a /metrics scrape between waves reads the
        live depth, never a stale one."""
        for k in indices:
            self._m_replica_depth.set(len(self._waves[k]), replica=str(k))

    # -- client side ----------------------------------------------------------

    def submit(self, task: str, input_ids: np.ndarray,
               token_type_ids: Optional[np.ndarray] = None
               ) -> InferenceRequest:
        """Admit one request (raises TooLong/Overloaded). The caller waits
        on `result(req)`."""
        input_ids = np.asarray(input_ids, np.int32).reshape(-1)
        if token_type_ids is None:
            token_type_ids = np.zeros_like(input_ids)
        token_type_ids = np.asarray(token_type_ids, np.int32).reshape(-1)
        tr = None
        if self.trace_ring is not None:
            tr = self.trace_ring.new_trace(task)
            note_trace_id(tr.trace_id)
        if self.engine.select_bucket(len(input_ids)) is None:
            self._m_requests.inc(task=task, outcome="too_long")
            if tr is not None:
                self._finish_trace(tr, "too_long",
                                   length=int(len(input_ids)))
            raise TooLong(
                f"request length {len(input_ids)} exceeds the largest "
                f"bucket {self.engine.max_bucket}")
        req = InferenceRequest(task=task, input_ids=input_ids,
                               token_type_ids=token_type_ids)
        try:
            self._q.put_nowait(req)
        except queue.Full:
            self._m_requests.inc(task=task, outcome="overloaded")
            if tr is not None:
                self._finish_trace(tr, "shed",
                                   queue_size=int(self._q.maxsize))
            raise Overloaded(
                f"request queue full ({self._q.maxsize}); shedding — "
                "retry with backoff")
        if tr is not None:
            # admit span: featurized arrays -> a slot in the bounded queue
            tr.span("admit", tr.t_admit, req.t_enqueue,
                    length=req.length)
            req.trace = tr
        self._update_depth()
        return req

    def result(self, req: InferenceRequest,
               timeout: Optional[float] = None) -> Any:
        """Block until the request resolves; re-raises its error. The
        latency histogram observes here — the full enqueue->result path
        the client experienced."""
        timeout = (self.admission_timeout_s + 30.0
                   if timeout is None else timeout)
        if not req.done.wait(timeout):
            req.error = RequestTimeout(f"no result within {timeout:.1f}s")
        ms = (time.perf_counter() - req.t_enqueue) * 1e3
        if req.error is not None:
            outcome = ("timeout" if isinstance(req.error, RequestTimeout)
                       else "error")
            self._m_requests.inc(task=req.task, outcome=outcome)
            if req.trace is not None:
                # no-op when the resolution site already finished it;
                # closes the client-side wait-timeout path otherwise
                self._finish_trace(req.trace, outcome, t0=req.t_enqueue)
            raise req.error
        self._m_requests.inc(task=req.task, outcome="ok")
        self._m_latency.observe(ms, task=req.task)
        if req.trace is not None:
            # respond span: resolved on the worker -> picked up here
            self._finish_trace(req.trace, "ok",
                               t0=req.t_resolve or req.t_enqueue)
        return req.result

    def _finish_trace(self, tr, outcome: str,
                      t0: Optional[float] = None, **attrs: Any) -> None:
        """Record the closing span ('respond' for ok, the terminal name
        otherwise) and retire the trace into the ring. Safe to call from
        racing terminators: finish() is first-wins and the loser's
        ring.add is skipped."""
        now = time.perf_counter()
        tr.span("respond" if outcome == "ok" else outcome,
                tr.t_admit if t0 is None else t0, now, **attrs)
        if tr.finish(outcome, now):
            self.trace_ring.add(tr)

    # -- scheduler side -------------------------------------------------------

    def start(self) -> "Scheduler":
        self._thread = threading.Thread(target=self._loop,
                                        name="serve-batcher", daemon=True)
        self._workers = [
            threading.Thread(target=self._worker, args=(i,),
                             name=f"serve-replica-{i}", daemon=True)
            for i in range(len(self.engines))]
        for w in self._workers:
            w.start()
        self._thread.start()
        return self

    def close(self) -> None:
        self._closed.set()
        with self._wv:
            self._wv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)
        for w in self._workers:
            w.join(timeout=10)
        leftovers = self._drain_all()
        with self._wv:
            for q in self._waves:
                while q:
                    leftovers.extend(
                        req for req, _, _, _ in q.popleft().placements)
            self._publish_replica_depth(*range(len(self.engines)))
        for req in leftovers:
            if not req.done.is_set():
                if req.trace is not None:
                    self._finish_trace(req.trace, "timeout",
                                       t0=req.t_enqueue,
                                       reason="shutdown")
                req.resolve(error=RequestTimeout("server shutting down"))

    def wait_idle(self, timeout: float = 30.0) -> bool:
        """Block until every admitted request has resolved — admission
        queue drained, nothing pending, every replica queue empty, no
        wave in flight on any replica. The graceful-drain path calls this
        so ALL replicas finish before the process exits 0."""
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            with self._wv:
                busy = (any(self._waves) or any(self._inflight))
            if not busy and self._q.qsize() == 0 and not self._pending:
                return True
            time.sleep(0.01)
        return False

    def replica_stats(self) -> List[Dict[str, Any]]:
        """Per-replica snapshot for /healthz: dispatch-queue depth,
        in-flight wave count, dispatched/stolen totals, last dispatch
        time, and the engine's compiled bucket set."""
        out = []
        with self._wv:
            for i, eng in enumerate(self.engines):
                st = self._rstats[i]
                out.append({
                    "replica": i,
                    "name": getattr(eng, "name", f"r{i}"),
                    "queue_depth": len(self._waves[i]),
                    "inflight": self._inflight[i],
                    "dispatched": st["dispatched"],
                    "steals": st["steals"],
                    "last_dispatch_unix": st["last_dispatch_unix"],
                    "compiled_buckets": [int(b) for b in
                                         getattr(eng, "buckets", ())],
                })
        return out

    def _drain_all(self) -> List[InferenceRequest]:
        out, self._pending = list(self._pending), []
        while True:
            try:
                out.append(self._q.get_nowait())
            except queue.Empty:
                return out

    def _expire(self, now: float) -> None:
        """Admission timeout: a request that waited longer than the budget
        resolves with RequestTimeout instead of consuming a batch slot."""
        keep = []
        for req in self._pending:
            if now - req.t_enqueue > self.admission_timeout_s:
                if req.trace is not None:
                    self._finish_trace(req.trace, "timeout",
                                       t0=req.t_enqueue,
                                       waited_s=round(
                                           now - req.t_enqueue, 3))
                req.resolve(error=RequestTimeout(
                    f"queued {now - req.t_enqueue:.1f}s > admission "
                    f"timeout {self.admission_timeout_s:.1f}s"))
            else:
                keep.append(req)
        self._pending = keep

    def _loop(self) -> None:
        while not self._closed.is_set():
            if not self._pending:
                try:
                    self._pending.append(self._q.get(timeout=0.05))
                except queue.Empty:
                    self._update_depth()
                    continue
            # drain whatever arrived, then give stragglers one batching
            # window to coalesce (continuous batching's only wait)
            self._drain_into_pending()
            if self.batch_wait_s > 0:
                time.sleep(self.batch_wait_s)
                self._drain_into_pending()
            self._expire(time.perf_counter())
            if not self._pending:
                continue
            # backpressure: with every replica already ~2 waves deep,
            # packing another now would just freeze its contents early —
            # wait a beat (expiry keeps running via the loop) and retry
            with self._wv:
                if sum(map(len, self._waves)) >= self._wave_cap:
                    self._wv.wait(0.02)
                    full = sum(map(len, self._waves)) >= self._wave_cap
                else:
                    full = False
            if full:
                continue
            task = self._pending[0].task
            wave = [r for r in self._pending if r.task == task]
            try:
                placed = self._dispatch(task, wave)
            except Exception as e:
                # replica failures resolve inside the worker; this guards
                # pack/assemble bugs. Fail the HEAD request only — it is
                # the one a broken layout implicates, and dropping it
                # guarantees progress instead of a poison-pill loop
                head = wave[0]
                if head.trace is not None:
                    self._finish_trace(head.trace, "error",
                                       t0=head.t_enqueue, site="pack")
                head.resolve(error=e)
                placed = {id(head)}
            self._pending = [r for r in self._pending
                             if id(r) not in placed]
            self._update_depth()

    def _drain_into_pending(self) -> None:
        cap = self.engine.batch_rows * self.engine.max_segments * 4
        while len(self._pending) < cap:
            try:
                self._pending.append(self._q.get_nowait())
            except queue.Empty:
                return

    def _dispatch(self, task: str, wave: List[InferenceRequest]) -> set:
        """Pack one batch and queue it on the shallowest replica; returns
        the ids of the requests actually placed (the rest stay pending,
        arrival order preserved).

        The bucket is the HEAD request's natural bucket, and only
        requests that fit it ride along — sizing by the wave's max would
        drag every short request into the largest bucket under load
        (measured: it inverts the packed-vs-padded win at saturation).
        A longer request waits one round; once it ages to the head, its
        bucket is chosen and shorter traffic packs around it."""
        t_pack0 = time.perf_counter()
        bucket = self.engine.select_bucket(wave[0].length)
        wave = [r for r in wave if r.length <= bucket]
        max_segments = self.engine.max_segments if self.packing else 1
        bins = first_fit([r.length for r in wave],
                         n_bins=self.engine.batch_rows,
                         capacity=bucket, max_segments=max_segments)
        batch, placements = self._assemble(wave, bins, bucket)
        if not placements:
            return set()
        t_pack1 = time.perf_counter()
        if self.trace_ring is not None:
            for req, _, _, _ in placements:
                if req.trace is not None:
                    req.trace.span("queue_wait", req.t_enqueue, t_pack0)
                    req.trace.span("pack", t_pack0, t_pack1,
                                   bucket=int(bucket),
                                   wave_segments=len(placements))
        placed = set(id(req) for req, _, _, _ in placements)
        with self._wv:
            depths = [len(q) for q in self._waves]
            k = depths.index(min(depths))
            self._waves[k].append(_Wave(task, bucket, batch, placements,
                                        t_queued=time.perf_counter(),
                                        queued_on=k))
            self._publish_replica_depth(k)
            self._wv.notify_all()
        return placed

    def _worker(self, i: int) -> None:
        """One replica's executor: run own queue FIFO; when idle, steal
        the OLDEST wave from the DEEPEST other queue."""
        while True:
            with self._wv:
                if self._closed.is_set():
                    return
                wave, src = None, i
                if self._waves[i]:
                    wave = self._waves[i].popleft()
                else:
                    others = [(len(self._waves[j]), -j) for j
                              in range(len(self._waves)) if j != i]
                    if others:
                        depth, negj = max(others)
                        if depth > 0:
                            src = -negj
                            wave = self._waves[src].popleft()
                            self._rstats[i]["steals"] += 1
                            self._m_steals.inc(replica=str(i))
                if wave is None:
                    self._wv.wait(0.05)
                    continue
                self._publish_replica_depth(src, i)
                self._inflight[i] += 1
                self._rstats[i]["last_dispatch_unix"] = time.time()
                self._wv.notify_all()     # backpressure slot freed
            try:
                self._execute(i, wave)
            finally:
                with self._wv:
                    self._inflight[i] -= 1
                    self._rstats[i]["dispatched"] += 1
                    self._wv.notify_all()
                self._update_depth()

    def _execute(self, i: int, wave: _Wave) -> None:
        """Forward one wave on replica i and demux. Replica choice cannot
        change results: every replica compiled the same program from the
        same params, so what holds packed-vs-single holds per replica.

        Tracing here is timestamps around existing calls — the batch
        arrays and the forward are untouched, so tracing on/off cannot
        perturb outputs. The dispatch span records the steal hop
        (queued_on vs the replica that ran it); the compute span carries
        the request's pro-rated share of the wave's device-seconds."""
        tracing = self.trace_ring is not None
        t0 = time.perf_counter()
        if tracing:
            stolen = wave.queued_on != i
            for req, _, _, _ in wave.placements:
                if req.trace is not None:
                    req.trace.span("dispatch", wave.t_queued or t0, t0,
                                   replica=i, queued_on=wave.queued_on,
                                   stolen=stolen)
        try:
            outputs = self.engines[i].forward(wave.task, wave.batch)
        except Exception as e:
            # fail loudly — but ONLY the requests that rode this batch;
            # queued requests that never dispatched stay pending for the
            # next round instead of inheriting a stranger's error
            for req, _, _, _ in wave.placements:
                if req.trace is not None:
                    self._finish_trace(req.trace, "error", t0=t0,
                                       replica=i, site="forward")
                req.resolve(error=e)
            return
        t1 = time.perf_counter()
        real = sum(req.length for req, _, _, _ in wave.placements)
        n_dev = int(getattr(self.engines[i], "n_devices", 1) or 1)
        device_seconds = (t1 - t0) * n_dev
        self._note_batch(i, wave.task, wave.bucket, wave.placements)
        self._note_cost(wave.task, device_seconds, real)
        kind = self._output_kind(wave.task)
        for req, row, offset, seg in wave.placements:
            if req.trace is not None:
                share = req.length / real if real else 0.0
                req.trace.span("compute", t0, t1, replica=i,
                               bucket=int(wave.bucket), n_devices=n_dev,
                               device_seconds=round(
                                   device_seconds * share, 9))
                td0 = time.perf_counter()
                out = self._demux(outputs, row, offset, req.length, seg,
                                  kind)
                req.trace.span("demux", td0, time.perf_counter())
                req.resolve(result=out)
            else:
                req.resolve(result=self._demux(outputs, row, offset,
                                               req.length, seg, kind))

    def _note_cost(self, task: str, device_seconds: float,
                   real_tokens: float) -> None:
        """Accumulate per-task device-seconds and set the cost gauge:
        cumulative device-hours x price, per 1000 real tokens served."""
        with self._cost_lock:
            ds = self._task_device_seconds.get(task, 0.0) + device_seconds
            tk = self._task_real_tokens.get(task, 0.0) + real_tokens
            self._task_device_seconds[task] = ds
            self._task_real_tokens[task] = tk
        self._m_device_seconds.inc(device_seconds, task=task)
        if tk > 0:
            cost = ds / 3600.0 * self.cost_per_device_hour
            self._m_cost.set(cost / (tk / 1000.0), task=task)

    def _output_kind(self, task: str) -> str:
        getter = getattr(self.engine, "output_kind", None)
        return getter(task) if callable(getter) else "token"

    def _assemble(self, wave: List[InferenceRequest],
                  bins: List[List[int]], bucket: int
                  ) -> Tuple[Dict[str, np.ndarray],
                             List[Tuple[InferenceRequest, int, int, int]]]:
        """Bin layout -> the packed (batch_rows, bucket) arrays
        (data/packing.py field contract minus the training-only labels)
        plus (request, row, offset, segment) placements for the demux."""
        from bert_pytorch_tpu.serving.engine import zero_batch

        batch = zero_batch(self.engine.batch_rows, bucket)
        placements: List[Tuple[InferenceRequest, int, int, int]] = []
        for row, members in enumerate(bins):
            cursor = 0
            for seg, ri in enumerate(members):
                req = wave[ri]
                ln = req.length
                sl = slice(cursor, cursor + ln)
                batch["input_ids"][row, sl] = req.input_ids
                batch["token_type_ids"][row, sl] = req.token_type_ids
                batch["attention_mask"][row, sl] = 1
                batch["segment_ids"][row, sl] = seg + 1
                batch["position_ids"][row, sl] = np.arange(ln,
                                                           dtype=np.int32)
                placements.append((req, row, cursor, seg))
                cursor += ln
        return batch, placements

    def _note_batch(self, replica: int, task: str, bucket: int,
                    placements: List[Tuple[InferenceRequest, int, int, int]]
                    ) -> None:
        real = sum(req.length for req, _, _, _ in placements)
        slots = self.engine.batch_rows * bucket
        self._m_batches.inc(task=task, bucket=str(bucket))
        self._m_real_tokens.inc(real)
        self._m_slot_tokens.inc(slots)
        self._m_occupancy.set(real / slots)
        self._m_replica_occupancy.set(real / slots, replica=str(replica))
        self._m_segments.set(len(placements))

    @staticmethod
    def _demux(outputs: Any, row: int, offset: int, length: int,
               seg: int, kind: str = "token") -> Any:
        """Per-request slice of the batch outputs.

        kind='token' (QA span logits, NER token logits): the request's
        tokens live at [row, offset:offset+length] because the head is
        token-local. kind='segment' (pooled heads — classification
        logits (B, G, C), choice scores (B, G), embeddings (B, G, E)):
        the request IS segment `seg` of its row, one pooled output per
        packed segment (registry TaskSpec.output_kind picks the mode)."""
        if kind == "segment":
            if isinstance(outputs, tuple):
                return tuple(np.asarray(o)[row, seg].copy()
                             for o in outputs)
            return np.asarray(outputs)[row, seg].copy()
        sl = slice(offset, offset + length)
        if isinstance(outputs, tuple):
            return tuple(np.asarray(o)[row, sl].copy() for o in outputs)
        return np.asarray(outputs)[row, sl].copy()
