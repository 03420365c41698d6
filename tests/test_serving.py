"""Serving stack tests: bucket selection, packed-vs-single equality,
queue overflow shedding, admission timeout, zero-recompile steady state,
checkpoint restore contracts, and the HTTP frontend end to end.

The acceptance pins (ISSUE round 14): responses from a packed
multi-request batch decode to the same answers as the same requests served
one-per-batch, with logits equal up to the summation order of one
contraction (conftest.assert_packing_invariant), and bit-identical with
one request a row; the compile count is flat after warmup across buckets;
len == bucket boundary rides that bucket and len > max bucket is shed
with 413."""

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from bert_pytorch_tpu.serving.batcher import (  # noqa: E402
    Overloaded, RequestTimeout, Scheduler, TooLong)
from bert_pytorch_tpu.serving.engine import (  # noqa: E402
    ServingEngine, restore_serving_params, select_bucket, zero_batch)
from bert_pytorch_tpu.tasks import predict  # noqa: E402


def _tiny_config(**kw):
    from bert_pytorch_tpu.config import BertConfig

    base = dict(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                num_attention_heads=4, intermediate_size=64,
                max_position_embeddings=64, hidden_dropout_prob=0.0,
                attention_probs_dropout_prob=0.0, fused_ops=False,
                attention_impl="xla")
    base.update(kw)
    return BertConfig(**base)


def _qa_model_params(config=None):
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.models import BertForQuestionAnswering
    from bert_pytorch_tpu.training.state import unbox

    config = config or _tiny_config()
    model = BertForQuestionAnswering(config, dtype=jnp.float32)
    s = jnp.zeros((1, 32), jnp.int32)
    params = unbox(model.init(jax.random.PRNGKey(0), s, s, s)["params"])
    return model, params


@pytest.fixture(scope="module")
def qa_engine():
    """One compiled two-bucket QA engine shared by the batching tests."""
    model, params = _qa_model_params()
    engine = ServingEngine({"squad": predict.build_qa_forward(model)},
                           {"squad": params}, buckets=(16, 32),
                           batch_rows=4, max_segments=4)
    engine.warmup()
    return engine


def _single_reference(engine, ids):
    """Serve one request alone in a batch — the bit-identity reference."""
    bucket = engine.select_bucket(len(ids))
    batch = zero_batch(engine.batch_rows, bucket)
    batch["input_ids"][0, :len(ids)] = ids
    batch["attention_mask"][0, :len(ids)] = 1
    batch["segment_ids"][0, :len(ids)] = 1
    batch["position_ids"][0, :len(ids)] = np.arange(len(ids))
    start, end = engine.forward("squad", batch)
    return start[0, :len(ids)].copy(), end[0, :len(ids)].copy()


# -- bucket selection ---------------------------------------------------------


def test_select_bucket_edges():
    buckets = (64, 128, 256, 512)
    assert select_bucket(1, buckets) == 64
    assert select_bucket(64, buckets) == 64      # boundary rides the bucket
    assert select_bucket(65, buckets) == 128
    assert select_bucket(512, buckets) == 512
    assert select_bucket(513, buckets) is None   # frontend turns into 413
    assert select_bucket(5, (128, 64)) == 64     # unsorted input tolerated


def test_submit_too_long_rejected(qa_engine):
    sch = Scheduler(qa_engine, packing=True)
    with pytest.raises(TooLong):
        sch.submit("squad", np.arange(33, dtype=np.int32) + 5)
    # counted as an outcome, not silently dropped
    assert sch.registry.counter(
        "bert_serve_requests_total",
        labels=("task", "outcome")).value(task="squad",
                                          outcome="too_long") == 1


# -- packed vs single ---------------------------------------------------------


def test_packed_bit_identical_to_single_requests(qa_engine,
                                                 assert_packing_invariant):
    """The acceptance pin: packed multi-request batches return what
    one-per-batch serving returns — segment masking is exact-zero,
    reductions keep the row length, every served head is token-local.
    Lengths cover a bucket boundary (16) and a full-capacity row (32).

    A request the packer puts at the start of a row (as the reference
    serves it) comes back bit-identical. One it puts behind another request
    differs in the last bits on this CPU: `probs @ V` in the attention core
    is the first operation whose output differs, because the dot sums the
    row's keys in vector lanes and a key's lane goes by its index in the
    row (conftest.assert_packing_invariant has the account and the
    bound). So: the same span from every request, and logits equal to what
    that contraction's order allows."""
    rng = np.random.RandomState(0)
    lengths = [7, 9, 16, 12, 3, 32, 5]
    reqs = [rng.randint(5, 64, (ln,)).astype(np.int32) for ln in lengths]
    singles = [_single_reference(qa_engine, ids) for ids in reqs]

    sch = Scheduler(qa_engine, packing=True, batch_wait_ms=1.0).start()
    try:
        handles = [sch.submit("squad", ids) for ids in reqs]
        packed = [sch.result(h, timeout=60) for h in handles]
    finally:
        sch.close()
    for i, (single, got) in enumerate(zip(singles, packed)):
        assert_packing_invariant(
            single, got, n_keys=qa_engine.select_bucket(lengths[i]),
            n_sums=2,
            ctx=f"request {i} (len {lengths[i]}) packed vs single")
    # alone in its row (the bucket-32 request fills one) nothing is
    # summed in another order: bit-identical
    assert np.array_equal(singles[5][0], packed[5][0])
    assert np.array_equal(singles[5][1], packed[5][1])


def test_padded_mode_bit_identical_too(qa_engine):
    """packing=off runs the SAME compiled program with one segment per
    row — responses must also be bit-identical to the packed ones."""
    rng = np.random.RandomState(1)
    reqs = [rng.randint(5, 64, (ln,)).astype(np.int32)
            for ln in (4, 11, 16, 8)]
    singles = [_single_reference(qa_engine, ids) for ids in reqs]
    sch = Scheduler(qa_engine, packing=False, batch_wait_ms=1.0).start()
    try:
        handles = [sch.submit("squad", ids) for ids in reqs]
        padded = [sch.result(h, timeout=60) for h in handles]
    finally:
        sch.close()
    for (s1, e1), (s2, e2) in zip(singles, padded):
        assert np.array_equal(s1, s2) and np.array_equal(e1, e2)


# -- flow control -------------------------------------------------------------


def test_queue_overflow_sheds(qa_engine):
    """No consumer thread: the bounded queue fills, then submit sheds
    with Overloaded (the frontend's 503)."""
    sch = Scheduler(qa_engine, queue_size=4, packing=True)  # not started
    ids = np.arange(8, dtype=np.int32) + 5
    for _ in range(4):
        sch.submit("squad", ids)
    with pytest.raises(Overloaded):
        sch.submit("squad", ids)
    assert sch.registry.counter(
        "bert_serve_requests_total",
        labels=("task", "outcome")).value(task="squad",
                                          outcome="overloaded") == 1


class _StallEngine:
    """Engine stub whose forward blocks — admission-timeout fuel."""

    buckets = (16,)
    batch_rows = 2
    max_segments = 2
    max_bucket = 16

    def __init__(self, stall_s: float):
        self.stall_s = stall_s

    def select_bucket(self, length):
        return 16 if length <= 16 else None

    def forward(self, task, batch):
        time.sleep(self.stall_s)
        b, s = np.shape(batch["input_ids"])
        return np.zeros((b, s)), np.zeros((b, s))


def test_admission_timeout_expires_queued_requests():
    """Requests older than the admission budget resolve with
    RequestTimeout (the frontend's 504) instead of consuming batch
    slots."""
    sch = Scheduler(_StallEngine(stall_s=0.25), admission_timeout_s=0.1,
                    batch_wait_ms=0.0, packing=True).start()
    try:
        ids = np.arange(10, dtype=np.int32)
        handles = [sch.submit("squad", ids) for _ in range(12)]
        outcomes = []
        for h in handles:
            try:
                sch.result(h, timeout=10)
                outcomes.append("ok")
            except RequestTimeout:
                outcomes.append("timeout")
        # the first wave(s) are served; requests stuck behind the stalled
        # forward age past 0.1s and expire
        assert "ok" in outcomes
        assert "timeout" in outcomes
    finally:
        sch.close()


def test_result_timeout_without_scheduler(qa_engine):
    sch = Scheduler(qa_engine, packing=True)  # never started
    req = sch.submit("squad", np.arange(6, dtype=np.int32) + 5)
    with pytest.raises(RequestTimeout):
        sch.result(req, timeout=0.1)


# -- zero-recompile steady state ----------------------------------------------


def test_zero_recompile_after_warmup_across_buckets():
    """The acceptance pin: CompileWatch's count is flat after warmup no
    matter how traffic mixes the buckets — steady-state serving never
    touches the compiler."""
    from bert_pytorch_tpu.telemetry.compile_watch import CompileWatch

    cw = CompileWatch().install()
    try:
        model, params = _qa_model_params()
        engine = ServingEngine({"squad": predict.build_qa_forward(model)},
                               {"squad": params}, buckets=(16, 32),
                               batch_rows=2, max_segments=2,
                               compile_watch=cw)
        engine.warmup()
        warm = cw.compiles
        assert warm >= 2  # both buckets actually compiled
        sch = Scheduler(engine, packing=True, batch_wait_ms=0.5).start()
        try:
            rng = np.random.RandomState(2)
            for round_ in range(3):
                handles = [
                    sch.submit("squad",
                               rng.randint(5, 64, (ln,)).astype(np.int32))
                    for ln in (3, 16, 9, 32, 12, 7)]  # hits BOTH buckets
                for h in handles:
                    sch.result(h, timeout=60)
        finally:
            sch.close()
        assert cw.compiles == warm, (
            f"steady-state traffic recompiled: {warm} compiles after "
            f"warmup, {cw.compiles} after serving")
    finally:
        cw.uninstall()


# -- checkpoint restore -------------------------------------------------------


def test_restore_params_only_and_finetune_layouts(tmp_path):
    """Both serving restore contracts: a params-only checkpoint (the
    restore_either_layout path) and a full finetune TrainState dict (the
    strict-merge path) round-trip bit-exactly; a checkpoint missing the
    task head fails LOUDLY instead of serving random weights."""
    import jax

    from bert_pytorch_tpu.training.checkpoint import CheckpointManager

    model, params = _qa_model_params()

    mgr = CheckpointManager(str(tmp_path / "params_only"))
    mgr.save(0, {"params": params})
    mgr.close()
    restored, step = restore_serving_params(
        str(tmp_path / "params_only"), model, 32, log=lambda m: None)
    assert step == 0
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        assert np.array_equal(np.asarray(a), np.asarray(b))

    # finetune-shaped save: a TrainState-like dict with extra subtrees
    mgr = CheckpointManager(str(tmp_path / "finetune"))
    mgr.save(7, {"step": 7, "params": params,
                 "opt_state": {"mu": {"x": np.zeros(3, np.float32)}}})
    mgr.close()
    restored, step = restore_serving_params(
        str(tmp_path / "finetune"), model, 32, log=lambda m: None)
    assert step == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        assert np.array_equal(np.asarray(a), np.asarray(b))

    # missing head: drop qa_outputs and expect a loud failure
    headless = {k: v for k, v in params.items() if k != "qa_outputs"}
    mgr = CheckpointManager(str(tmp_path / "headless"))
    mgr.save(0, {"step": 0, "params": headless, "opt_state": {}})
    mgr.close()
    with pytest.raises(ValueError, match="qa_outputs"):
        restore_serving_params(str(tmp_path / "headless"), model, 32,
                               log=lambda m: None)


# -- HTTP frontend e2e --------------------------------------------------------


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.loads(r.read().decode("utf-8"))


def _post(url, body, timeout=30):
    data = json.dumps(body).encode("utf-8")
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode("utf-8"))
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode("utf-8"))


@pytest.fixture(scope="module")
def live_server(serving_fixture):
    """The full run_server.serve() stack on a fixture checkpoint: both
    tasks, ephemeral port, packed batching."""
    import run_server

    msf, _root, paths = serving_fixture
    args = run_server.parse_arguments([
        "--model_config_file", paths["model_config"],
        "--vocab_file", paths["vocab"],
        "--squad_checkpoint", paths["squad_ckpt"],
        "--ner_checkpoint", paths["ner_ckpt"],
        "--labels", *msf.NER_LABELS,
        "--buckets", "16,32", "--batch_rows", "2", "--max_segments", "2",
        "--serve_dtype", "float32", "--packing", "on",
        "--port", "0", "--host", "127.0.0.1",
        "--queue_size", "64", "--admission_timeout", "30"])
    handle = run_server.serve(args)
    yield handle
    handle.close()


def test_http_squad_and_ner_roundtrip(live_server):
    url = live_server.url
    code, out = _post(url + "/v1/squad", {
        "question": "who sat on the mat ?",
        "context": "the cat sat on the mat"})
    assert code == 200
    assert isinstance(out["answer"], str)
    assert out["n_windows"] >= 1 and out["real_tokens"] > 0
    assert isinstance(out["nbest"], list) and out["nbest"]

    code, out = _post(url + "/v1/ner", {
        "tokens": ["the", "cat", "sat"]})
    assert code == 200
    assert out["labels"] and len(out["labels"]) == 3
    assert all(isinstance(l, str) for l in out["labels"])


def test_http_error_mapping(live_server):
    url = live_server.url
    # 413: tokenizes past the largest bucket (32 pieces incl CLS/SEP)
    code, out = _post(url + "/v1/ner", {"tokens": ["cat"] * 80})
    assert code == 413 and "error" in out
    # 400: malformed / missing fields
    code, _ = _post(url + "/v1/squad", {"question": "q"})
    assert code == 400
    # 404: unknown route
    code, _ = _post(url + "/v1/nope", {})
    assert code == 404


def test_http_metrics_and_healthz(live_server):
    from bert_pytorch_tpu.telemetry.registry import parse_prometheus

    url = live_server.url
    # drive at least one request so the counters are nonzero
    _post(url + "/v1/ner", {"tokens": ["cat", "sat"]})
    with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
        text = r.read().decode("utf-8")
    parsed = parse_prometheus(text)
    lab = '{phase="serve"'
    ok_series = [v for k, v in parsed.get(
        "bert_serve_requests_total", {}).items()
        if k.startswith(lab) and 'outcome="ok"' in k]
    assert ok_series and sum(ok_series) >= 1
    assert any(k.startswith("bert_serve_request_latency_ms")
               for k in parsed)
    assert "bert_serve_queue_depth" in parsed
    assert "bert_serve_batch_occupancy" in parsed

    code, hz = _get(url + "/healthz")
    assert code == 200
    assert hz["phase"] == "serve"
    assert hz["packing"] is True
    assert set(hz["tasks"]) == {"squad", "ner"}
    assert hz["buckets"] == [16, 32]


def test_http_concurrent_mixed_burst(live_server):
    """A threaded mixed squad/ner burst — every response 2xx, no
    cross-request contamination in shapes (labels match token counts)."""
    url = live_server.url
    results = []
    lock = threading.Lock()

    def one(i):
        if i % 2:
            code, out = _post(url + "/v1/ner",
                              {"tokens": ["the", "cat", "sat"][:1 + i % 3]})
            good = code == 200 and len(out["labels"]) == 1 + i % 3
        else:
            code, out = _post(url + "/v1/squad", {
                "question": "who ?",
                "context": "the cat sat on the mat " * (1 + i % 3)})
            good = code == 200 and isinstance(out["answer"], str)
        with lock:
            results.append(good)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert len(results) == 12 and all(results)


def test_http_trace_header_and_traces_endpoint(live_server):
    """Round 18 request tracing through the live stack: every admitted
    POST's reply carries X-Trace-Id, /v1/traces serves the retained span
    timelines as strict Chrome-trace JSON, and /healthz reports the
    flight-recorder retention stats. A tokenize-stage 413 (rejected
    BEFORE admission) correctly carries no trace id — the timeline
    starts at scheduler admission, and the submit-side too_long terminal
    span is pinned in tests/test_request_tracing.py."""
    url = live_server.url
    data = json.dumps({"tokens": ["the", "cat"]}).encode("utf-8")
    req = urllib.request.Request(
        url + "/v1/ner", data=data,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        assert r.status == 200
        tid = r.headers.get("X-Trace-Id")
    assert tid, "2xx reply missing X-Trace-Id"

    # pre-admission 413: no trace was minted, so no header
    data = json.dumps({"tokens": ["cat"] * 80}).encode("utf-8")
    req = urllib.request.Request(
        url + "/v1/ner", data=data,
        headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=30)
    assert ei.value.code == 413
    assert ei.value.headers.get("X-Trace-Id") is None

    # targeted fetch by id: the completed request's full span timeline
    with urllib.request.urlopen(url + f"/v1/traces?id={tid}",
                                timeout=10) as r:
        doc = json.loads(r.read().decode("utf-8"))
    names = {ev["name"] for ev in doc["traceEvents"]
             if ev["args"]["trace_id"] == tid}
    assert {"req/admit", "req/queue_wait", "req/dispatch", "req/compute",
            "req/respond"} <= names, names

    code, hz = _get(url + "/healthz")
    assert code == 200
    rt = hz["request_tracing"]
    assert rt["seen"] >= 1 and rt["retained_slowest"] >= 1
    assert rt["cost_per_device_hour"] > 0
