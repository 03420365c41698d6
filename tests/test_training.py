"""Training-subsystem tests on the 8-device CPU mesh (conftest.py): sharded
state init, train-step convergence, accumulation equivalence, checkpoint
roundtrip + rolling window, logger sinks, schedule shapes."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bert_pytorch_tpu.config import BertConfig
from bert_pytorch_tpu.models import BertForPreTraining
from bert_pytorch_tpu.optim import lamb, schedulers
from bert_pytorch_tpu.optim.lamb import default_weight_decay_mask
from bert_pytorch_tpu.parallel import mesh as mesh_lib
from bert_pytorch_tpu.training import (
    CheckpointManager,
    MetricLogger,
    TrainState,
    build_pretrain_step,
    make_sharded_state,
)
from bert_pytorch_tpu.training.pretrain import stack_microbatches

TINY = BertConfig(
    vocab_size=128, hidden_size=32, num_hidden_layers=2,
    num_attention_heads=4, intermediate_size=64,
    max_position_embeddings=64, next_sentence=True,
    dtype="float32", fused_ops=False, attention_impl="xla",
    hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
)


def _batch(global_batch=16, seq=16, vocab=128, seed=0, accum=1):
    rng = np.random.RandomState(seed)
    ids = rng.randint(5, vocab, (global_batch, seq)).astype(np.int32)
    labels = np.full((global_batch, seq), -1, np.int32)
    mask_pos = rng.randint(1, seq - 1, (global_batch, 2))
    for b in range(global_batch):
        for p in mask_pos[b]:
            labels[b, p] = ids[b, p]
            ids[b, p] = 3  # pretend mask token
    batch = {
        "input_ids": ids,
        "token_type_ids": np.zeros((global_batch, seq), np.int32),
        "attention_mask": np.ones((global_batch, seq), np.int32),
        "masked_lm_labels": labels,
        "next_sentence_labels": rng.randint(0, 2, (global_batch,)).astype(np.int32),
    }
    return stack_microbatches(batch, accum)


def _make(model_cfg=TINY, lr=1e-3, accum=1):
    model = BertForPreTraining(model_cfg, dtype=jnp.float32)
    sched = schedulers.poly_warmup_schedule(lr, total_steps=100, warmup=0.1)
    tx = lamb(sched, weight_decay=0.01,
              weight_decay_mask=default_weight_decay_mask)
    step_fn = build_pretrain_step(model, tx, schedule=sched,
                                  accum_steps=accum)
    sample = _batch(accum=accum)
    init_fn = lambda rng: model.init(
        rng, jnp.asarray(sample["input_ids"][0]),
        jnp.asarray(sample["token_type_ids"][0]),
        jnp.asarray(sample["attention_mask"][0]))
    return model, tx, step_fn, init_fn


def test_sharded_state_init_and_steps_reduce_loss():
    m = mesh_lib.make_mesh()  # all 8 devices on data
    _, _, step_fn, init_fn = _make()
    with mesh_lib.logical_rules():
        state, shardings = make_sharded_state(
            jax.random.PRNGKey(0), init_fn, _make()[1], mesh=m)
    assert int(state.step) == 0
    # state actually sharded over the mesh (replicated params but mesh-placed)
    leaf = jax.tree.leaves(state.params)[0]
    assert leaf.sharding.mesh.shape["data"] == 8 or leaf.sharding.is_fully_replicated

    jit_step = jax.jit(step_fn, donate_argnums=(0,))
    batch = {k: jnp.asarray(v) for k, v in _batch().items()}
    losses = []
    with m:
        for i in range(5):
            state, metrics = jit_step(state, batch, jax.random.PRNGKey(i))
            losses.append(float(metrics["loss"]))
    assert int(state.step) == 5
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all()


def test_accumulation_matches_full_batch():
    """accum=2 over the same 16 samples must produce the same update as
    accum=1 (dropout off). The reference's accumulation loop pre-divided the
    loss (run_pretraining.py:436); here grads are averaged — same math."""
    _, tx1, step1, init_fn = _make(accum=1)
    _, tx2, step2, _ = _make(accum=2)

    state1, _ = make_sharded_state(jax.random.PRNGKey(0), init_fn, tx1)
    state2 = TrainState(step=state1.step, params=state1.params,
                        opt_state=state1.opt_state)

    b1 = {k: jnp.asarray(v) for k, v in _batch(accum=1).items()}
    b2 = {k: jnp.asarray(v) for k, v in _batch(accum=2).items()}
    s1, m1 = jax.jit(step1)(state1, b1, jax.random.PRNGKey(7))
    s2, m2 = jax.jit(step2)(state2, b2, jax.random.PRNGKey(7))

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    p1 = jax.tree.leaves(s1.params)
    p2 = jax.tree.leaves(s2.params)
    for a, b in zip(p1, p2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)


def test_checkpoint_roundtrip_and_rolling_window(tmp_path):
    _, tx, step_fn, init_fn = _make()
    state, _ = make_sharded_state(jax.random.PRNGKey(0), init_fn, tx)
    batch = {k: jnp.asarray(v) for k, v in _batch().items()}
    jit_step = jax.jit(step_fn)
    for i in range(2):
        state, _ = jit_step(state, batch, jax.random.PRNGKey(i))

    mgr = CheckpointManager(str(tmp_path / "ckpts"), max_to_keep=3)
    sampler_state = {"epoch": 1, "index": 32, "world_size": 1,
                     "total_size": 64, "seed": 0}
    for step in (2, 4, 6, 8):
        mgr.save(step, state, extra={"sampler": sampler_state, "epoch": 1})
    mgr.wait()
    assert mgr.latest_step() == 8

    abstract = jax.eval_shape(lambda: state)
    restored, extra, step = mgr.restore(abstract)
    assert step == 8
    assert extra["sampler"]["index"] == 32
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # rolling window: only 3 most recent kept (reference kept 3,
    # run_pretraining.py:513-516)
    steps = sorted(mgr._mgr.all_steps())
    assert steps == [4, 6, 8]
    mgr.close()


def test_resume_missing_dir_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "empty"))
    assert mgr.latest_step() is None
    with pytest.raises(FileNotFoundError):
        mgr.restore(None)
    mgr.close()


def test_metric_logger_sinks(tmp_path):
    prefix = str(tmp_path / "run")
    lg = MetricLogger(log_prefix=prefix, verbose=True, jsonl=True,
                      stream=open(os.devnull, "w"))
    lg.log("train", 1, loss=2.5, learning_rate=1e-3)
    lg.log("train", 2, loss=2.0, learning_rate=2e-3)
    lg.info("hello")
    lg.close()

    txt = open(prefix + ".txt").read()
    assert "step 1" in txt and "hello" in txt
    rows = open(prefix + "_metrics.csv").read().strip().splitlines()
    assert len(rows) == 3  # header + 2
    recs = [json.loads(l) for l in open(prefix + ".jsonl")]
    assert recs[0]["loss"] == 2.5 and recs[1]["step"] == 2

    silent = MetricLogger(log_prefix=str(tmp_path / "no"), verbose=False)
    silent.log("train", 1, loss=1.0)
    assert not os.path.exists(str(tmp_path / "no.txt"))


def test_schedules_shapes_and_offset():
    s = schedulers.poly_warmup_schedule(6e-3, total_steps=100, warmup=0.1)
    assert float(s(0)) < float(s(9))          # warming up
    # at progress == warmup the decay branch applies (reference semantics:
    # `if progress < warmup` warm else decay, src/schedulers.py:126-139)
    np.testing.assert_allclose(float(s(10)), 6e-3 * (1 - 0.1) ** 0.5,
                               rtol=1e-3)
    assert float(s(50)) < float(s(10))        # decaying
    np.testing.assert_allclose(float(s(50)), 6e-3 * (1 - 0.5) ** 0.5,
                               rtol=1e-2)

    # two-phase: offset shifts the schedule so phase-2 restarts its warmup
    # (replaces the reference's optimizer-state rewrite,
    # run_pretraining.py:288-299)
    s2 = schedulers.poly_warmup_schedule(4e-3, total_steps=100, warmup=0.1,
                                         offset=7038)
    np.testing.assert_allclose(float(s2(7038)), float(
        schedulers.poly_warmup_schedule(4e-3, 100, warmup=0.1)(0)))
    for name in ("linear", "cosine", "constant"):
        sc = schedulers.make_schedule(name, 1e-3, 100, warmup=0.1)
        assert np.isfinite(float(sc(0))) and np.isfinite(float(sc(99)))


def test_gathered_step_matches_dense_step():
    """A train step with max_predictions (gathered MLM head) must produce the
    same loss/metrics/update as the dense step (dropout off, P >= masked)."""
    model, tx, dense_step, init_fn = _make()
    gath_step = build_pretrain_step(
        model, tx, schedule=schedulers.poly_warmup_schedule(
            1e-3, total_steps=100, warmup=0.1),
        accum_steps=1, max_predictions=4)

    state0 = make_sharded_state(jax.random.PRNGKey(0), init_fn, tx)[0]
    state1 = make_sharded_state(jax.random.PRNGKey(0), init_fn, tx)[0]
    batch = {k: jnp.asarray(v) for k, v in _batch().items()}

    sd, md = jax.jit(dense_step)(state0, batch, jax.random.PRNGKey(1))
    sg, mg = jax.jit(gath_step)(state1, batch, jax.random.PRNGKey(1))
    np.testing.assert_allclose(float(mg["loss"]), float(md["loss"]),
                               rtol=1e-5)
    np.testing.assert_allclose(float(mg["mlm_accuracy"]),
                               float(md["mlm_accuracy"]), rtol=1e-6)
    for pd, pg in zip(jax.tree.leaves(sd.params), jax.tree.leaves(sg.params)):
        np.testing.assert_allclose(np.asarray(pg), np.asarray(pd),
                                   rtol=2e-4, atol=2e-5)


def test_chain_steps_matches_sequential():
    """chain_steps(k) (the device-side --steps_per_loop fori_loop) must
    produce the same state and final metrics as k sequential dispatches
    driven with the same fold_in rng derivation."""
    from bert_pytorch_tpu.training.pretrain import chain_steps

    _, tx, step_fn, init_fn = _make()
    base = jax.random.PRNGKey(7)

    state_a, _ = make_sharded_state(jax.random.PRNGKey(0), init_fn, tx)
    batch = {k: jnp.asarray(v) for k, v in _batch().items()}
    for i in range(3):
        state_a, metrics_a = jax.jit(step_fn)(
            state_a, batch, jax.random.fold_in(base, i))

    state_b, _ = make_sharded_state(jax.random.PRNGKey(0), init_fn, tx)
    chained = jax.jit(chain_steps(step_fn, 3))
    state_b, metrics_b = chained(state_b, batch, base)

    assert int(state_b.step) == 3
    np.testing.assert_allclose(float(metrics_a["loss"]),
                               float(metrics_b["loss"]), rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                         atol=1e-6),
                 state_a.params, state_b.params)


def test_chain_steps_per_step_batch():
    """per_step_batch=True consumes a (k, accum, micro, ...) stack — each
    inner step must see ITS slice (verify against manual sequential feed)."""
    from bert_pytorch_tpu.training.pretrain import chain_steps

    _, tx, step_fn, init_fn = _make()
    base = jax.random.PRNGKey(11)
    batches = [_batch(seed=s) for s in range(3)]
    stacked3 = {k: jnp.asarray(np.stack([b[k] for b in batches]))
                for k in batches[0]}

    state_a, _ = make_sharded_state(jax.random.PRNGKey(0), init_fn, tx)
    for i, b in enumerate(batches):
        state_a, metrics_a = jax.jit(step_fn)(
            state_a, {k: jnp.asarray(v) for k, v in b.items()},
            jax.random.fold_in(base, i))

    state_b, _ = make_sharded_state(jax.random.PRNGKey(0), init_fn, tx)
    chained = jax.jit(chain_steps(step_fn, 3, per_step_batch=True))
    state_b, metrics_b = chained(state_b, stacked3, base)

    np.testing.assert_allclose(float(metrics_a["loss"]),
                               float(metrics_b["loss"]), rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                         atol=1e-6),
                 state_a.params, state_b.params)


def test_bf16_grad_step_tracks_fp32():
    """grad_dtype=bfloat16 (grads accumulated in compute dtype against fp32
    masters, the apex-O2 equivalent) must track the fp32-grad trajectory:
    same descending loss within bf16 tolerance after several steps."""
    model = BertForPreTraining(TINY, dtype=jnp.float32)
    sched = schedulers.poly_warmup_schedule(1e-3, total_steps=100, warmup=0.1)
    tx = lamb(sched, weight_decay=0.01,
              weight_decay_mask=default_weight_decay_mask)
    step32 = build_pretrain_step(model, tx, schedule=sched)
    step16 = build_pretrain_step(model, tx, schedule=sched,
                                 grad_dtype=jnp.bfloat16)
    sample = _batch()
    init_fn = lambda rng: model.init(
        rng, jnp.asarray(sample["input_ids"][0]),
        jnp.asarray(sample["token_type_ids"][0]),
        jnp.asarray(sample["attention_mask"][0]))
    batch = {k: jnp.asarray(v) for k, v in sample.items()}

    s32, _ = make_sharded_state(jax.random.PRNGKey(0), init_fn, tx)
    s16, _ = make_sharded_state(jax.random.PRNGKey(0), init_fn, tx)
    l32 = l16 = None
    for i in range(6):
        s32, m32 = jax.jit(step32)(s32, batch, jax.random.PRNGKey(i))
        s16, m16 = jax.jit(step16)(s16, batch, jax.random.PRNGKey(i))
        l32, l16 = float(m32["loss"]), float(m16["loss"])
    # params stay fp32 masters in both cases
    assert jax.tree.leaves(s16.params)[0].dtype == jnp.float32
    assert abs(l32 - l16) / abs(l32) < 0.02, (l32, l16)


def test_lamb_per_layer_trust_ratio():
    """A [L, ...] stacked tensor with trust_batch_axes=1 must get the same
    update as L separate tensors fed through LAMB individually (apex saw L
    tensors; the scan encoder stores one stacked tensor)."""
    from bert_pytorch_tpu.optim.lamb import lamb as make_lamb

    rng = np.random.RandomState(0)
    stacked_p = jnp.asarray(rng.randn(3, 4, 5).astype(np.float32))
    stacked_g = jnp.asarray(rng.randn(3, 4, 5).astype(np.float32) * 0.1)

    tx_stacked = make_lamb(0.1, max_grad_norm=None,
                           trust_batch_axes=lambda p: jax.tree.map(
                               lambda _: 1, p))
    st = tx_stacked.init({"w": stacked_p})
    upd_stacked, _ = tx_stacked.update({"w": stacked_g}, st, {"w": stacked_p})

    tx_single = make_lamb(0.1, max_grad_norm=None)
    for i in range(3):
        sti = tx_single.init({"w": stacked_p[i]})
        upd_i, _ = tx_single.update({"w": stacked_g[i]}, sti,
                                    {"w": stacked_p[i]})
        np.testing.assert_allclose(np.asarray(upd_stacked["w"][i]),
                                   np.asarray(upd_i["w"]), rtol=1e-6)


_SAVE_ON_DEVICES_4_TO_7 = """
import sys
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from bert_pytorch_tpu.training.checkpoint import CheckpointManager
mesh = Mesh(np.asarray(jax.devices()[4:8]), ("data",))
w = jax.device_put(jnp.arange(32.0).reshape(8, 4),
                   NamedSharding(mesh, P("data")))
b = jax.device_put(jnp.ones((3,)), NamedSharding(mesh, P()))
mgr = CheckpointManager(sys.argv[1])
mgr.save(0, {"params": {"w": w, "b": b}})
mgr.close()
"""

_RESTORE_ON_ONE_DEVICE = """
import sys
import jax, jax.numpy as jnp, numpy as np
from bert_pytorch_tpu.training.checkpoint import CheckpointManager
assert jax.device_count() == 1
mgr = CheckpointManager(sys.argv[1])
raw, step = mgr.restore_raw()
template = {"params": {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32),
                       "b": jax.ShapeDtypeStruct((3,), jnp.float32)}}
state, _, _ = mgr.restore_either_layout(template)
mgr.close()
for tree in (raw, state):
    np.testing.assert_array_equal(np.asarray(tree["params"]["w"]),
                                  np.arange(32.0).reshape(8, 4))
    assert tree["params"]["w"].devices() == {jax.devices()[0]}
print("RESTORED_ELSEWHERE_OK")
"""


def test_checkpoint_restores_on_other_devices_than_wrote_it(tmp_path):
    """A checkpoint names the devices that wrote it. Restored with a
    template that names no sharding (the serving restore) or with none at
    all (restore_raw, the transfer load), it must land on THIS process's
    devices — the chip run found a CPU-built serving fixture unservable on
    the TPU ("Device TFRT_CPU_0 was not found"), and a 4-chip training
    checkpoint would seed no 1-chip finetune. Two processes: devices 4-7
    of an 8-device platform write, a 1-device platform reads."""
    import re
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ckpt = str(tmp_path / "ckpt")
    for code, n_devices in ((_SAVE_ON_DEVICES_4_TO_7, 8),
                            (_RESTORE_ON_ONE_DEVICE, 1)):
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       f"--xla_force_host_platform_device_count={n_devices}",
                       os.environ["XLA_FLAGS"])
        proc = subprocess.run(
            [sys.executable, "-c", code, ckpt], capture_output=True,
            text=True, timeout=300, cwd=repo,
            env=dict(os.environ, XLA_FLAGS=flags, PYTHONPATH=repo))
        assert proc.returncode == 0, proc.stderr[-3000:]
    assert "RESTORED_ELSEWHERE_OK" in proc.stdout
