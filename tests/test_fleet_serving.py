"""Fleet-scale serving: replica scale-out + work stealing + int8 weights.

Pins the PR-17 acceptance surface:
- multi-replica responses equal single-engine serving for the same
  request set, for every registered task, through the work-stealing
  dispatcher: bit-identical with one request a row, and with packing the
  same answers and outputs equal up to one contraction's summation order
  (conftest.assert_packing_invariant);
- an idle replica actually steals queued waves from a busy one (and the
  steal shows up in replica_stats / the metrics registry);
- the compile count stays flat across mixed-bucket multi-replica traffic
  once steady is armed AFTER every replica's warmup (the
  mark-steady-once-globally bugfix);
- int8 weight quantization round-trips within the accuracy gate, and a
  corrupted scale trips it;
- the sharded-serve graphcheck combo carries nonzero collective ceilings
  and a passing sharding_rules floor.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bert_pytorch_tpu.serving.batcher import Scheduler  # noqa: E402
from bert_pytorch_tpu.serving.engine import (  # noqa: E402
    ServingEngine, zero_batch)

SERVE_OPTS = {
    "labels": ["B-X", "I-X", "O"],
    "class_names": ["0", "1"],
    "num_choices": 2,
    "embed_labels": 2,
    "max_segments": 4,
}


def _tiny_config():
    from bert_pytorch_tpu.config import BertConfig

    return BertConfig(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                      num_attention_heads=4, intermediate_size=64,
                      max_position_embeddings=64, hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0, fused_ops=False,
                      attention_impl="xla")


def _all_task_stack():
    """(forwards, params, output_kinds) over EVERY registered task at a
    tiny config — the same construction run_server.serve() does."""
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.tasks import registry
    from bert_pytorch_tpu.training.state import unbox

    config = _tiny_config()
    forwards, params, kinds = {}, {}, {}
    for task in registry.all_tasks():
        spec = registry.get(task)
        model = spec.build_serving_model(config, jnp.float32, SERVE_OPTS)
        s = jnp.zeros((1, 16), jnp.int32)
        params[task] = unbox(
            model.init(jax.random.PRNGKey(3), s, s, s)["params"])
        forwards[task] = spec.forward_builder(model)
        kinds[task] = spec.output_kind
    return forwards, params, kinds


@pytest.fixture(scope="module")
def fleet():
    """Two identical replicas (the fleet) plus their shared stack."""
    forwards, params, kinds = _all_task_stack()
    engines = []
    for i in range(2):
        eng = ServingEngine(forwards, params, buckets=(16, 32),
                            batch_rows=2, max_segments=2,
                            output_kinds=kinds, name=f"r{i}")
        eng.warmup()
        engines.append(eng)
    return engines


def _reference(engine, task, ids):
    """Serve one request alone on ONE engine — the fleet's bit-identity
    reference (same demux the batcher applies)."""
    bucket = engine.select_bucket(len(ids))
    batch = zero_batch(engine.batch_rows, bucket)
    batch["input_ids"][0, :len(ids)] = ids
    batch["attention_mask"][0, :len(ids)] = 1
    batch["segment_ids"][0, :len(ids)] = 1
    batch["position_ids"][0, :len(ids)] = np.arange(len(ids))
    outputs = engine.forward(task, batch)
    return Scheduler._demux(outputs, 0, 0, len(ids), 0,
                            engine.output_kind(task))


def _assert_same(a, b, ctx):
    a = a if isinstance(a, tuple) else (a,)
    b = b if isinstance(b, tuple) else (b,)
    assert len(a) == len(b), ctx
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y)), ctx


def test_multi_replica_bit_identical_all_tasks(fleet,
                                              assert_packing_invariant):
    """Replica choice must not change a single bit: every registered
    task's responses through the 2-replica work-stealing dispatcher equal
    the single-engine single-request reference, exactly with one request a
    row (`packing=False`: the same compiled program).

    With packing a request that lands behind another in its row differs
    from the reference in the last bits on this CPU, on ONE engine as on
    two: the attention core's `probs @ V` is the first operation whose
    output differs (its dot sums the row's keys in vector lanes, and a
    key's lane goes by its index in the row), and a pooled head's sum over
    its segment's tokens is a second one of the same kind
    (conftest.assert_packing_invariant has the account). Packed responses
    are held to the same decoded answers and to what those sums' order
    allows: one a layer and one for the pooling."""
    from bert_pytorch_tpu.tasks import registry

    rng = np.random.RandomState(7)
    requests = []  # (task, ids)
    for task in registry.all_tasks():
        for ln in (5, 16, 11, 32, 8):
            requests.append(
                (task, rng.randint(5, 64, (ln,)).astype(np.int32)))
    refs = [_reference(fleet[0], task, ids) for task, ids in requests]

    sch = Scheduler(fleet, packing=True, batch_wait_ms=1.0).start()
    try:
        handles = [sch.submit(task, ids) for task, ids in requests]
        got = [sch.result(h, timeout=120) for h in handles]
        stats = sch.replica_stats()
    finally:
        sch.close()
    for (task, ids), ref, out in zip(requests, refs, got):
        assert_packing_invariant(
            ref, out, n_keys=fleet[0].select_bucket(len(ids)),
            n_sums=2 + 1,
            ctx=f"{task} len {len(ids)} packed fleet vs single-engine")
    sch = Scheduler(fleet, packing=False, batch_wait_ms=1.0).start()
    try:
        handles = [sch.submit(task, ids) for task, ids in requests]
        got = [sch.result(h, timeout=120) for h in handles]
    finally:
        sch.close()
    for (task, ids), ref, out in zip(requests, refs, got):
        _assert_same(ref, out, f"{task} len {len(ids)} differs "
                               "fleet vs single-engine")
    # both replicas exist in the stats table; all waves accounted for
    assert [s["replica"] for s in stats] == [0, 1]
    assert sum(s["dispatched"] for s in stats) > 0
    assert all(s["compiled_buckets"] == [16, 32] for s in stats)


class _GatedEngine:
    """Engine stub whose forward can be blocked per-instance — makes the
    steal deterministic: replica 0 jams, replica 1 must steal its queue."""

    buckets = (16,)
    batch_rows = 2
    max_segments = 2
    max_bucket = 16

    def __init__(self, name, gate=None):
        self.name = name
        self.gate = gate
        self.served = []

    def select_bucket(self, length):
        return 16 if length <= 16 else None

    def forward(self, task, batch):
        if self.gate is not None:
            assert self.gate.wait(timeout=30)
        self.served.append(task)
        b, s = np.shape(batch["input_ids"])
        return np.zeros((b, s)), np.zeros((b, s))


def test_idle_replica_steals_from_deepest_queue():
    # BOTH engines gated: whichever worker picks a wave jams on it. An
    # idle worker may legally steal a queued wave before its owner wakes
    # (that's the whole point of the dispatcher), so "r0 holds wave 1"
    # cannot be assumed — probe until r0 is the jammed holder, releasing
    # any probe r1 happened to grab first.
    gate0, gate1 = threading.Event(), threading.Event()
    jammed, free = _GatedEngine("r0", gate0), _GatedEngine("r1", gate1)
    sch = Scheduler([jammed, free], packing=True, batch_wait_ms=0.0).start()
    try:
        ids = np.arange(8, dtype=np.int32)
        first = None
        deadline = time.time() + 30
        while first is None and time.time() < deadline:
            # quiesce: a just-flushed probe decrements _inflight[1] only
            # after its result resolves — don't misread it as the next one
            while ((sch._inflight[0] or sch._inflight[1])
                   and time.time() < deadline):
                time.sleep(0.005)
            h = sch.submit("squad", ids)
            while (not sch._inflight[0] and not sch._inflight[1]
                   and time.time() < deadline):
                time.sleep(0.005)
            if sch._inflight[0]:
                first = h                  # r0 jams on this wave
            else:                          # r1 grabbed the probe: flush it
                gate1.set()
                sch.result(h, timeout=30)
                gate1.clear()
        assert first is not None, "replica 0 never held a jammed wave"
        gate1.set()                        # r1 free for the rest of the test
        gate = gate0
        # r0 busy, its queue is the deepest; idle r1 must steal these
        later = [sch.submit("squad", ids) for _ in range(3)]
        for h in later:
            sch.result(h, timeout=30)      # resolves while r0 still jammed
        assert not first.done.is_set()
        gate.set()
        sch.result(first, timeout=30)
        stats = sch.replica_stats()
    finally:
        gate0.set()
        gate1.set()
        sch.close()
    assert stats[1]["steals"] >= 1, stats
    # the 3 later requests coalesce into wave(s) r1 stole and ran
    assert stats[1]["dispatched"] >= 1
    assert sch.registry.counter(
        "bert_serve_steals_total",
        labels=("replica",)).value(replica="1") >= 1
    # per-replica gauges exist for both replicas
    for i in ("0", "1"):
        assert sch.registry.gauge(
            "bert_serve_replica_queue_depth",
            labels=("replica",)).value(replica=i) == 0


def test_fleet_compile_flat_after_global_steady():
    """The mark-steady bugfix pin: steady is armed ONCE, after EVERY
    replica finished warmup — then mixed-bucket multi-replica traffic
    never touches the compiler again (compiles flat, zero post-steady)."""
    import jax.numpy as jnp

    from bert_pytorch_tpu.models import BertForQuestionAnswering
    from bert_pytorch_tpu.tasks import predict
    from bert_pytorch_tpu.telemetry.compile_watch import CompileWatch
    from bert_pytorch_tpu.training.state import unbox

    cw = CompileWatch().install()
    try:
        import jax

        model = BertForQuestionAnswering(_tiny_config(), dtype=jnp.float32)
        s = jnp.zeros((1, 16), jnp.int32)
        params = unbox(
            model.init(jax.random.PRNGKey(0), s, s, s)["params"])
        engines = []
        for i in range(2):
            eng = ServingEngine({"squad": predict.build_qa_forward(model)},
                                {"squad": params}, buckets=(16, 32),
                                batch_rows=2, max_segments=2,
                                compile_watch=cw, name=f"r{i}")
            # the fixed contract: replicas warm WITHOUT arming steady
            eng.warmup(mark_steady=False)
            engines.append(eng)
        warm = cw.compiles
        assert warm >= 4  # 2 buckets x 2 replicas actually compiled
        cw.mark_steady()  # armed once, after the WHOLE fleet is warm
        sch = Scheduler(engines, packing=True, batch_wait_ms=0.5).start()
        try:
            rng = np.random.RandomState(5)
            for _ in range(3):
                handles = [
                    sch.submit("squad",
                               rng.randint(5, 64, (ln,)).astype(np.int32))
                    for ln in (3, 16, 9, 32, 12, 7)]  # hits BOTH buckets
                for h in handles:
                    sch.result(h, timeout=60)
        finally:
            sch.close()
        assert cw.compiles == warm, (
            f"multi-replica steady-state traffic recompiled: {warm} "
            f"after fleet warmup, {cw.compiles} after serving")
        assert cw.compiles_after_steady == 0
    finally:
        cw.uninstall()


# -- int8 quantization --------------------------------------------------------


def test_int8_roundtrip_under_gate_and_broken_scale_trips():
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.models import BertForQuestionAnswering
    from bert_pytorch_tpu.serving import quantize as quant_lib
    from bert_pytorch_tpu.tasks import predict
    from bert_pytorch_tpu.training.state import unbox

    config = _tiny_config()
    model = BertForQuestionAnswering(config, dtype=jnp.float32)
    s = jnp.zeros((1, 16), jnp.int32)
    params = unbox(model.init(jax.random.PRNGKey(1), s, s, s)["params"])
    forward = predict.build_qa_forward(model)

    qparams, stats = quant_lib.quantize_tree(jax.device_get(params))
    assert stats["quantized_leaves"] > 0
    assert stats["bytes_after"] < stats["bytes_before"]

    serve_model = BertForQuestionAnswering(config, dtype=jnp.bfloat16)
    q_forward = quant_lib.wrap_forward(
        predict.build_qa_forward(serve_model), jnp.bfloat16)
    probe = quant_lib.probe_batch(2, 32, config.vocab_size)
    delta = quant_lib.decode_delta(forward, params, q_forward, qparams,
                                   probe)
    # the serving gate criterion (argmax agreement is reported but not
    # asserted: random-init logits are near-ties, so argmax flips on
    # noise a real checkpoint's margins never would)
    assert delta["rel_delta"] <= 0.1, delta

    broken = quant_lib.corrupt_scales(qparams)
    bad = quant_lib.decode_delta(forward, params, q_forward, broken,
                                 probe)
    assert bad["rel_delta"] > 0.1, (
        f"corrupted scales slipped under the gate: {bad}")


# -- sharded-serve graphcheck combo (jax-free artifact pins) ------------------


def test_sharded_serve_combo_has_nonzero_collective_ceilings():
    with open(os.path.join(REPO, "results", "graph_budgets.json"),
              encoding="utf-8") as f:
        budgets = json.load(f)
    combo = budgets["combos"]["serve_qa_b4_s64_mp2"]["expect"]
    ceilings = combo["collective_budget"]
    assert sum(ceilings.values()) > 0, (
        "the sharded serve combo must carry NONZERO collective ceilings "
        "— a zero-collective pin would assert the mesh does nothing")
    assert combo["sharding_rules"]["min_verified"] > 0
    assert combo["replication"]["min_sharded_inputs"] > 0

    with open(os.path.join(REPO, "results", "graph_report.json"),
              encoding="utf-8") as f:
        report = json.load(f)
    rep = report["combos"]["serve_qa_b4_s64_mp2"]
    assert sum(rep["collective_counts"].values()) > 0
    mismatched = [i["path"] for i in rep["inputs"]
                  if not i.get("matches_expected", True)]
    assert not mismatched, mismatched
