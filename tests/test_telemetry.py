"""Tier-1 telemetry tests on the 8-device CPU mesh (conftest.py): health
pack flags injected non-finite steps, --nonfinite_action=skip preserves
state bit-exact, grad-spike z-score fires, StepWatch MFU matches a
hand-computed value, CompileWatch counts a forced recompile, and a full
run_pretraining.main() run logs perf/health records through every sink."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bert_pytorch_tpu.config import BertConfig
from bert_pytorch_tpu.models import BertForPreTraining
from bert_pytorch_tpu.optim import schedulers
from bert_pytorch_tpu.optim.lamb import lamb, default_weight_decay_mask
from bert_pytorch_tpu.telemetry import (CompileWatch, HealthConfig,
                                        StepWatch, collect_provenance,
                                        flops_per_seq, hbm_snapshot,
                                        init_telemetry_state)
from bert_pytorch_tpu.telemetry.health import health_update
from bert_pytorch_tpu.telemetry.stepwatch import lookup_peak_flops
from bert_pytorch_tpu.training import build_pretrain_step, make_sharded_state
from bert_pytorch_tpu.training.pretrain import (_pretrain_loss_fn,
                                                chain_steps,
                                                stack_microbatches)

TINY = BertConfig(
    vocab_size=128, hidden_size=32, num_hidden_layers=2,
    num_attention_heads=4, intermediate_size=64,
    max_position_embeddings=64, next_sentence=True,
    dtype="float32", fused_ops=False, attention_impl="xla",
    hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
)


def _batch(global_batch=8, seq=16, vocab=128, seed=0, accum=1):
    rng = np.random.RandomState(seed)
    ids = rng.randint(5, vocab, (global_batch, seq)).astype(np.int32)
    labels = np.full((global_batch, seq), -1, np.int32)
    labels[:, 2] = ids[:, 2]
    batch = {
        "input_ids": ids,
        "token_type_ids": np.zeros((global_batch, seq), np.int32),
        "attention_mask": np.ones((global_batch, seq), np.int32),
        "masked_lm_labels": labels,
        "next_sentence_labels": rng.randint(0, 2, (global_batch,)
                                            ).astype(np.int32),
    }
    return {k: jnp.asarray(v)
            for k, v in stack_microbatches(batch, accum).items()}


def _poison_loss_builder(model):
    """Standard pretraining loss, except a batch whose next_sentence_labels
    are all 9 (a value the loader never produces) multiplies the loss by
    inf — the in-graph analog of a data-corruption NaN batch, giving both a
    non-finite loss AND non-finite gradients."""
    base = _pretrain_loss_fn(model, None)

    def loss_fn(params, batch, rng, deterministic=False):
        loss, aux = base(params, batch, rng, deterministic)
        poison = jnp.all(batch["next_sentence_labels"] == 9)
        return loss * jnp.where(poison, jnp.inf, 1.0), aux

    return loss_fn


def _make_step(action: str):
    model = BertForPreTraining(TINY, dtype=jnp.float32)
    sched = schedulers.poly_warmup_schedule(1e-3, total_steps=100,
                                            warmup=0.1)
    tx = lamb(sched, weight_decay=0.01,
              weight_decay_mask=default_weight_decay_mask)
    step_fn = build_pretrain_step(
        model, tx, schedule=sched, accum_steps=1,
        loss_fn_builder=_poison_loss_builder,
        health=HealthConfig(action=action))
    batch = _batch()
    init_fn = lambda r: model.init(r, batch["input_ids"][0],
                                   batch["token_type_ids"][0],
                                   batch["attention_mask"][0])
    state, _ = make_sharded_state(jax.random.PRNGKey(0), init_fn, tx)
    state = state.replace(telemetry=init_telemetry_state())
    return jax.jit(step_fn, donate_argnums=(0,)), state, batch


def _poisoned(batch):
    out = dict(batch)
    out["next_sentence_labels"] = jnp.full_like(
        batch["next_sentence_labels"], 9)
    return out


# -- health pack ------------------------------------------------------------

def test_health_pack_flags_injected_nonfinite():
    jit_step, state, batch = _make_step("log")
    state, m = jit_step(state, batch, jax.random.PRNGKey(0))
    assert float(m["loss_nonfinite"]) == 0
    assert float(m["grad_nonfinite"]) == 0
    # per-group keys present and zero on a clean step
    assert float(m["grad_nonfinite_bert"]) == 0

    state, m = jit_step(state, _poisoned(batch), jax.random.PRNGKey(1))
    assert float(m["loss_nonfinite"]) == 1
    assert float(m["grad_nonfinite"]) > 0
    assert float(m["grad_nonfinite_bert"]) > 0
    assert not np.isfinite(float(m["loss"]))
    # action=log: the poisoned update went through (params now non-finite)
    leaf = np.asarray(jax.tree.leaves(state.params)[0])
    assert not np.isfinite(leaf).all()


def test_nonfinite_action_skip_preserves_state():
    """THE acceptance property: a poisoned batch under action='skip' leaves
    params and optimizer state bit-identical — the guard must be in-graph
    because the host's metric readback is one step behind dispatch."""
    jit_step, state, batch = _make_step("skip")
    for i in range(2):
        state, _ = jit_step(state, batch, jax.random.PRNGKey(i))
    params_before = jax.tree.map(np.asarray, state.params)
    opt_before = jax.tree.map(np.asarray, state.opt_state)
    count_before = int(state.telemetry.count)

    state, m = jit_step(state, _poisoned(batch), jax.random.PRNGKey(9))
    assert float(m["skipped_nonfinite"]) == 1
    for a, b in zip(jax.tree.leaves(params_before),
                    jax.tree.leaves(jax.tree.map(np.asarray, state.params))):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(opt_before),
                    jax.tree.leaves(jax.tree.map(np.asarray,
                                                 state.opt_state))):
        np.testing.assert_array_equal(a, b)
    # bad step did not enter the EMA, step still advanced
    assert int(state.telemetry.count) == count_before
    assert int(state.step) == 3

    # and the run keeps training after the skip
    state, m = jit_step(state, batch, jax.random.PRNGKey(10))
    assert float(m["skipped_nonfinite"]) == 0
    assert np.isfinite(float(m["loss"]))


def test_chain_steps_sticky_health_flags():
    """steps_per_loop>1 returns only the LAST inner step's metrics; a flag
    raised by an earlier inner step must survive via max-accumulation."""
    model = BertForPreTraining(TINY, dtype=jnp.float32)
    sched = schedulers.poly_warmup_schedule(1e-3, total_steps=100,
                                            warmup=0.1)
    tx = lamb(sched, weight_decay=0.01,
              weight_decay_mask=default_weight_decay_mask)
    step_fn = build_pretrain_step(
        model, tx, schedule=sched, accum_steps=1,
        loss_fn_builder=_poison_loss_builder,
        health=HealthConfig(action="skip"))
    batch = _batch()
    init_fn = lambda r: model.init(r, batch["input_ids"][0],
                                   batch["token_type_ids"][0],
                                   batch["attention_mask"][0])
    state, _ = make_sharded_state(jax.random.PRNGKey(0), init_fn, tx)
    state = state.replace(telemetry=init_telemetry_state())
    chained = jax.jit(chain_steps(step_fn, 2, per_step_batch=True),
                      donate_argnums=(0,))
    # inner step 0 poisoned, inner step 1 clean -> last metrics are from
    # the clean step but the sticky flags must still show the poison
    two = {k: jnp.stack([_poisoned(batch)[k], batch[k]]) for k in batch}
    state, m = chained(state, two, jax.random.PRNGKey(5))
    assert float(m["grad_nonfinite"]) > 0
    # per-group localization survives the loop too (is_sticky_metric)
    assert float(m["grad_nonfinite_bert"]) > 0
    assert float(m["skipped_nonfinite"]) == 1
    assert np.isfinite(float(m["loss"]))  # last (clean) step's loss


def test_grad_spike_zscore_fires_after_warmup():
    cfg = HealthConfig(warmup_steps=5, spike_z=4.0, ema_decay=0.9)
    telem = init_telemetry_state()
    params = {"w": jnp.ones((4,))}
    update = jax.jit(lambda t, gn, bad: health_update(
        cfg, t, gn, bad, params))
    bad = jnp.asarray(False)
    for _ in range(20):  # steady stream of ~1.0 norms
        telem, m = update(telem, jnp.float32(1.0), bad)
    assert int(m["grad_spike"]) == 0
    telem, m = update(telem, jnp.float32(100.0), bad)  # 100x spike
    assert int(m["grad_spike"]) == 1
    assert float(m["grad_norm_z"]) > 4.0
    # EMA keeps tracking (spike folded in, no NaN)
    assert np.isfinite(float(telem.grad_norm_ema))


def test_health_update_param_norm_drift():
    cfg = HealthConfig()
    telem = init_telemetry_state()
    telem, m = health_update(cfg, telem, jnp.float32(1.0),
                             jnp.asarray(False), {"w": jnp.full((4,), 3.0)})
    assert m["param_norm"] == pytest.approx(6.0)  # sqrt(4*9)
    assert m["param_norm_drift"] == 0.0           # no previous norm yet
    telem, m = health_update(cfg, telem, jnp.float32(1.0),
                             jnp.asarray(False), {"w": jnp.full((4,), 3.3)})
    assert float(m["param_norm_drift"]) == pytest.approx(0.1, rel=1e-5)


# -- StepWatch / MFU --------------------------------------------------------

def test_flops_per_seq_matches_hand_computed():
    cfg = BertConfig(vocab_size=100, hidden_size=10, num_hidden_layers=3,
                     num_attention_heads=2, intermediate_size=40,
                     max_position_embeddings=64)
    S, n_pred = 8, 4
    # trunk: L * (4*E^2 + 2*E*F) * S = 3 * (400 + 800) * 8 = 28800
    # head: (V*E + E*E) * n_pred = (1000 + 100) * 4 = 4400
    # dense total: 6 * (28800 + 4400) = 199200
    # attention: 12 * L * E * S^2 = 12 * 3 * 10 * 64 = 23040
    assert flops_per_seq(cfg, S, cfg.vocab_size, n_pred) == 199200 + 23040


def test_stepwatch_mfu_and_phases_hand_computed():
    clock = [0.0]
    sw = StepWatch(flops_per_step=2e9, seqs_per_step=32, seq_len=128,
                   peak_flops=1e12, log_freq=4, time_fn=lambda: clock[0])
    rec = None
    for _ in range(4):
        with sw.phase("data_wait"):
            clock[0] += 0.1
        with sw.phase("dispatch"):
            clock[0] += 0.4
        rec = sw.step_done()
    assert rec is not None
    # 4 steps in 2.0s wall: 0.5 s/step, 64 seq/s, 8192 tok/s
    assert rec["steps"] == 4
    assert rec["step_time_ms"] == pytest.approx(500.0)
    assert rec["seq_per_sec"] == pytest.approx(64.0)
    assert rec["tokens_per_sec"] == pytest.approx(64.0 * 128)
    # MFU = 2e9 * 4 / 2.0 / 1e12 = 0.004
    assert rec["mfu"] == pytest.approx(0.004)
    assert rec["data_wait_ms"] == pytest.approx(100.0)
    assert rec["dispatch_ms"] == pytest.approx(400.0)
    # interval reset: next boundary needs another log_freq steps
    assert sw.step_done() is None


class _Spans:
    """An injected annotation factory: records when each span opened and
    closed on the injected clock."""

    def __init__(self, clock):
        self.clock, self.log = clock, []

    def __call__(self, name):
        from contextlib import contextmanager

        @contextmanager
        def span():
            self.log.append((name, "open", self.clock[0]))
            try:
                yield
            finally:
                self.log.append((name, "close", self.clock[0]))
        return span()


def test_stepwatch_phase_is_its_own_trace_annotation():
    """phase(x) opens `host/x` itself, at its own clock readings: no site
    pairs the two by hand, and the span a trace shows is the span the
    record sums (also when the phase is left by an exception)."""
    clock = [0.0]
    spans = _Spans(clock)
    sw = StepWatch(flops_per_step=1.0, seqs_per_step=1, seq_len=1,
                   peak_flops=None, log_freq=1, time_fn=lambda: clock[0],
                   annotate=spans)
    heard = []
    sw.phase_listener = lambda name, entering: heard.append((name, entering))
    clock[0] = 1.0
    with sw.phase("dispatch"):
        clock[0] = 1.25
    with pytest.raises(KeyError):
        with sw.phase("checkpoint"):
            clock[0] = 1.75
            raise KeyError("lost")
    assert spans.log == [("host/dispatch", "open", 1.0),
                         ("host/dispatch", "close", 1.25),
                         ("host/checkpoint", "open", 1.25),
                         ("host/checkpoint", "close", 1.75)]
    assert heard == [("dispatch", True), ("dispatch", False),
                     ("checkpoint", True), ("checkpoint", False)]
    rec = sw.step_done()
    assert rec["dispatch_ms"] == pytest.approx(250.0)
    assert rec["checkpoint_ms"] == pytest.approx(500.0)


def test_stepwatch_loop_unaccounted_is_wall_less_leaf_phases():
    """Every [perf] record carries the residual of the host account; a
    phase entered from inside another counts once (its seconds come off
    the one around it), so the phases never sum past the wall time."""
    clock = [0.0]
    sw = StepWatch(flops_per_step=1.0, seqs_per_step=1, seq_len=1,
                   peak_flops=None, log_freq=2, time_fn=lambda: clock[0],
                   annotate=_Spans(clock))
    for _ in range(2):
        with sw.phase("data_wait"):
            clock[0] += 0.010
        clock[0] += 0.003                   # under no phase
        with sw.phase("log"):
            clock[0] += 0.004
            with sw.phase("metric_flush"):  # entered from inside `log`
                clock[0] += 0.100
            clock[0] += 0.001
        rec = sw.step_done()
    assert rec["step_time_ms"] == pytest.approx(118.0)
    assert rec["data_wait_ms"] == pytest.approx(10.0)
    assert rec["metric_flush_ms"] == pytest.approx(100.0)
    assert rec["log_ms"] == pytest.approx(5.0)      # 105 less the 100 inside
    assert rec["loop_unaccounted_ms"] == pytest.approx(3.0)
    phases = sum(v for k, v in rec.items() if k.endswith("_ms")
                 and k not in ("step_time_ms", "loop_unaccounted_ms"))
    assert phases + rec["loop_unaccounted_ms"] == \
        pytest.approx(rec["step_time_ms"])


def test_setup_watch_counters_add_up_and_only_grow():
    """The set-up account: spans less the compiling inside them, the
    compile counter, and the residual add up to the wall time from
    main()'s entry to the first loss; the counters never fall, an open
    span counts nothing yet, and nothing moves after the account closes."""
    from types import SimpleNamespace

    from bert_pytorch_tpu.telemetry import SetupWatch

    clock = [100.0]
    spans = _Spans(clock)
    setup = SetupWatch(start=100.0, time_fn=lambda: clock[0], annotate=spans)
    compiles = setup.compile_watch = SimpleNamespace(compile_secs=0.0)
    seen = [setup.snapshot()]
    clock[0] = 112.0
    setup.end("backend")                    # 12 s
    clock[0] = 112.5                        # 0.5 s between spans
    with setup.span("data"):
        clock[0] = 114.5                    # 2 s
    seen.append(setup.snapshot())
    with setup.span("state"):
        clock[0] = 120.5                    # 6 s, 1.5 s of them compiling
        compiles.compile_secs = 1.5
    setup.begin("data")                     # the first batch: 0.25 s more
    clock[0] = 120.75
    setup.end("data")
    with setup.span("lower"):
        clock[0] = 123.75                   # 3 s
    setup.begin("first_step")
    seen.append(setup.snapshot())           # first [perf] record: still open
    clock[0] = 133.75                       # 10 s, 8 s of them compiling
    compiles.compile_secs = 9.5
    setup.end("first_step")
    seen.append(setup.snapshot())
    clock[0] = 500.0
    compiles.compile_secs = 11.0            # a later recompile: not set-up
    with setup.span("state"):
        clock[0] = 600.0
    seen.append(setup.snapshot())
    assert seen[3] == {
        "setup_backend_s": 12.0, "setup_data_s": 2.25, "setup_state_s": 4.5,
        "setup_lower_s": 3.0, "setup_first_step_s": 2.0,
        "setup_unaccounted_s": 0.5}
    assert seen[2]["setup_first_step_s"] == 0.0
    assert seen[4] == seen[3]
    assert sum(seen[3].values()) + 9.5 == pytest.approx(133.75 - 100.0)
    for before, after in zip(seen, seen[1:]):
        assert all(after[k] >= before[k] for k in before), (before, after)
    assert [e[:2] for e in spans.log][:4] == [
        ("host/setup/backend", "open"), ("host/setup/backend", "close"),
        ("host/setup/data", "open"), ("host/setup/data", "close")]


def test_stepwatch_steps_per_loop_counting():
    clock = [0.0]
    sw = StepWatch(flops_per_step=1e9, seqs_per_step=8, seq_len=64,
                   peak_flops=1e12, log_freq=4, time_fn=lambda: clock[0])
    clock[0] = 2.0
    rec = sw.step_done(n=4)  # one dispatch, 4 optimization steps
    assert rec["steps"] == 4
    assert rec["step_time_ms"] == pytest.approx(500.0)
    assert rec["seq_per_sec"] == pytest.approx(16.0)


def test_compile_watch_counts_persistent_cache_hits_and_detaches():
    """`compile_cache_hits` = executables JAX loaded from the persistent
    cache instead of compiling (its own monitoring event) — the number
    chip_smoke.py prints so a cache that never hits is visible. uninstall()
    really detaches (jax.monitoring's public unregister)."""
    import jax.monitoring
    from jax._src import monitoring as _m

    cw = CompileWatch().install()
    n_listeners = len(_m.get_event_listeners())
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert cw.snapshot()["compile_cache_hits"] == 2
    cw.uninstall()
    assert len(_m.get_event_listeners()) == n_listeners - 1
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert cw.snapshot()["compile_cache_hits"] == 2
    cw.uninstall()      # idempotent


def test_lookup_peak_flops():
    assert lookup_peak_flops("TPU v5 lite") == 197e12
    assert lookup_peak_flops("TPU v5p chip") == 459e12


@pytest.mark.parametrize("kind", ["cpu", "TPU v9 mega", ""])
def test_lookup_peak_flops_unknown_kind_raises(kind):
    """A device the table does not know is an error, never a default peak:
    MFU against a guessed figure is a wrong number under a trusted name."""
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        lookup_peak_flops(kind)
    from bert_pytorch_tpu.telemetry import stepwatch

    assert not hasattr(stepwatch, "DEFAULT_PEAK")


def test_device_peak_flops_by_platform():
    """The MFU entry points' lookup: no MFU on the CPU backend (None), the
    table on an accelerator, an error for an accelerator it does not know."""
    from types import SimpleNamespace as Dev

    from bert_pytorch_tpu.telemetry import device_peak_flops

    assert device_peak_flops(jax.devices()[0]) is None      # the CPU mesh
    assert device_peak_flops(
        Dev(platform="tpu", device_kind="TPU v5 lite")) == 197e12
    assert device_peak_flops(
        Dev(platform="tpu", device_kind="TPU v5 lite"),
        dtype="float32") == 98.5e12
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        device_peak_flops(Dev(platform="tpu", device_kind="TPU v9 mega"))
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        device_peak_flops(Dev(platform="gpu", device_kind="H100"))


def test_stepwatch_without_peak_reports_no_mfu():
    clock = [0.0]
    sw = StepWatch(flops_per_step=1e9, seqs_per_step=8, seq_len=128,
                   peak_flops=None, log_freq=1, time_fn=lambda: clock[0])
    clock[0] = 1.0
    rec = sw.step_done()
    assert rec["model_flops_per_sec"] == 1e9
    assert "mfu" not in rec and "peak_flops" not in rec


def test_lookup_peak_flops_dtype_aware():
    # MFU must be quoted against the peak of the compute dtype: the MXU
    # runs f32 matmuls at half the bf16 rate on every listed generation.
    # Pin both dtypes on two generations so a table edit that forgets the
    # ratio (or a caller that quotes bf16 runs against f32 peak) fails.
    assert lookup_peak_flops("TPU v4", dtype="bf16") == 275e12
    assert lookup_peak_flops("TPU v4", dtype="f32") == 137.5e12
    assert lookup_peak_flops("TPU v5p chip", dtype="bfloat16") == 459e12
    assert lookup_peak_flops("TPU v5p chip", dtype="float32") == 229.5e12
    # config.dtype strings pass straight through
    assert lookup_peak_flops("TPU v5 lite", dtype="float32") == 98.5e12
    with pytest.raises(ValueError):
        lookup_peak_flops("TPU v4", dtype="int8")


# -- CompileWatch / HBM -----------------------------------------------------

def test_compile_watch_counts_forced_recompile():
    warnings = []
    cw = CompileWatch(warn=warnings.append).install()
    try:
        @jax.jit
        def f(x):
            return x * 2 + 1

        x2, x3 = jnp.zeros((2,)), jnp.zeros((3,))  # helper compiles happen
        f(x2)                                # compile (warmup)
        f(x2)                                # cache hit: no new compile
        n_warm = cw.compiles
        assert n_warm >= 1
        cw.mark_steady()
        assert warnings == []
        f(x3)                                # new shape -> forced recompile
        assert cw.compiles == n_warm + 1
        assert cw.compiles_after_steady == 1
        assert len(warnings) == 1 and "RECOMPILE" in warnings[0]
        assert cw.compile_secs > 0
        snap = cw.snapshot()
        assert snap["recompiles_after_warmup"] == 1
    finally:
        cw.uninstall()
    # uninstalled: further compiles are not counted
    n = cw.compiles

    @jax.jit
    def g(x):
        return x - 1

    g(jnp.zeros((2,)))
    assert cw.compiles == n


def test_hbm_snapshot_cpu_safe():
    # CPU PJRT exposes no memory_stats — must degrade to {} (not raise);
    # on TPU the same call returns hbm_peak_bytes etc.
    snap = hbm_snapshot()
    assert isinstance(snap, dict)
    for v in snap.values():
        assert v >= 0


# -- provenance -------------------------------------------------------------

def test_provenance_collect_fields():
    from bert_pytorch_tpu.parallel import mesh as mesh_lib

    p = collect_provenance(mesh=mesh_lib.make_mesh())
    assert p["jax_version"] == jax.__version__
    assert p["git_sha"]  # "unknown" at worst, never empty
    assert p["platform"] == "cpu"
    assert p["mesh"]["data"] == 8
    assert "libtpu_init_args" in p and "overlap_pack_active" in p


# -- end-to-end: every sink gets perf + health records ----------------------

@pytest.fixture
def workdir(tmp_path):
    from tests.test_data import write_shard

    data = tmp_path / "data"
    data.mkdir()
    for i in range(2):
        write_shard(data / f"shard_{i}.hdf5", 32, seed=i)
    model_cfg = {
        "vocab_size": 128, "hidden_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 64,
        "max_position_embeddings": 64, "next_sentence": True,
        "hidden_dropout_prob": 0.0, "attention_probs_dropout_prob": 0.0,
        "tokenizer": "wordpiece", "fused_ops": False,
        "attention_impl": "xla",
    }
    cfg_path = tmp_path / "model_config.json"
    cfg_path.write_text(json.dumps(model_cfg))
    return tmp_path, data, cfg_path


def test_run_pretraining_logs_perf_and_health_through_sinks(workdir):
    """Acceptance: a CPU-backend pretraining run logs per-interval
    step_time/seq_per_sec/MFU/data_wait and per-step health fields through
    every enabled sink, stamped with a provenance header."""
    tmp_path, data, cfg_path = workdir
    import run_pretraining

    out = tmp_path / "out"
    argv = ["--input_dir", str(data), "--output_dir", str(out),
            "--model_config_file", str(cfg_path),
            "--mask_token_index", "3", "--dtype", "float32",
            "--vocab_pad_multiple", "8", "--learning_rate", "1e-3",
            "--global_batch_size", "32", "--local_batch_size", "2",
            "--max_steps", "4", "--max_predictions_per_seq", "5",
            "--skip_checkpoint", "--log_freq", "2",
            "--nonfinite_action", "skip"]
    final_step, _ = run_pretraining.main(argv)
    assert final_step == 4

    records = [json.loads(l)
               for l in open(out / "logfile.jsonl", encoding="utf-8")]
    by_tag = {}
    for r in records:
        by_tag.setdefault(r["tag"], []).append(r)

    # provenance header first
    assert by_tag["header"][0]["git_sha"]
    assert by_tag["header"][0]["jax_version"] == jax.__version__

    # per-step train records carry the health fields
    train = by_tag["train"]
    assert len(train) == 4
    for r in train:
        assert r["loss_nonfinite"] == 0 and r["grad_nonfinite"] == 0
        assert r["skipped_nonfinite"] == 0
        assert np.isfinite(r["step_loss"]) and r["param_norm"] > 0

    # interval perf records: step_time / seq_per_sec / MFU / data_wait /
    # dispatch / compile counts
    perf = by_tag["perf"]
    assert len(perf) == 2  # steps 2 and 4 at log_freq 2
    for r in perf:
        assert r["step_time_ms"] > 0
        assert r["seq_per_sec"] > 0
        assert r["tokens_per_sec"] > 0
        # the CPU backend has no peak to quote against: no MFU reported
        assert r["model_flops_per_sec"] > 0
        assert "mfu" not in r and "peak_flops" not in r
        assert "data_wait_ms" in r and "dispatch_ms" in r
        assert r["compiles"] >= 1
    # warmup closed at the first interval; no recompiles in this run
    assert perf[-1]["recompiles_after_warmup"] == 0

    # same fields reached the CSV sink (header-union schema)
    header = open(out / "logfile_metrics.csv",
                  encoding="utf-8").readline().strip().split(",")
    for col in ("step_loss", "grad_nonfinite", "seq_per_sec",
                "model_flops_per_sec", "data_wait_ms"):
        assert col in header
    # and the text sink
    txt = (out / "logfile.txt").read_text()
    assert "[header]" in txt and "[perf]" in txt and "[train]" in txt


# -- trace summarizer (round 11) ---------------------------------------------

def test_trace_classify_buckets():
    from bert_pytorch_tpu.telemetry.trace import classify

    assert classify("all-gather-start.12") == "collective"
    assert classify("all-reduce.3") == "collective"
    assert classify("reduce-scatter") == "collective"
    assert classify("collective-permute-done.1") == "collective"
    assert classify("fusion.123") == "compute"
    assert classify("dot.1") == "compute"
    assert classify("transpose_copy_fusion") == "compute"
    assert classify("host/data_wait") == "host/data_wait"
    # framework wrappers and Python frames are excluded, not "compute"
    assert classify("ThunkExecutor::Execute") is None
    assert classify("PjitFunction(train_step)") is None
    assert classify("$profiler.py:91 trace") is None


def test_trace_summarize_events_interval_merge_and_normalization():
    """Nested same-bucket events are merged (no double count), buckets are
    keyed per (pid, tid), and --steps/--devices produce the per-step
    per-device numbers tools/trace_summary.py prints."""
    from bert_pytorch_tpu.telemetry.trace import summarize_events

    us = 1000.0  # 1 ms in trace-event microseconds
    ev = [
        # device thread 1: a 4 ms all-gather with a 2 ms NESTED re-report
        {"ph": "X", "pid": 1, "tid": 1, "name": "all-gather.1",
         "ts": 0.0, "dur": 4 * us},
        {"ph": "X", "pid": 1, "tid": 1, "name": "all-gather.1",
         "ts": 1 * us, "dur": 2 * us},
        # same thread: 6 ms of compute, disjoint
        {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.9",
         "ts": 4 * us, "dur": 6 * us},
        # second device thread: 2 ms collective
        {"ph": "X", "pid": 1, "tid": 2, "name": "all-reduce-start.2",
         "ts": 0.0, "dur": 2 * us},
        # third device thread: an all-gather CONCURRENT with tid 1's —
        # cross-thread same-op time must SUM (device-time), never merge
        {"ph": "X", "pid": 1, "tid": 3, "name": "all-gather.7",
         "ts": 0.0, "dur": 3 * us},
        # host annotation + excluded wrapper + non-X event
        {"ph": "X", "pid": 2, "tid": 9, "name": "host/h2d",
         "ts": 0.0, "dur": 3 * us},
        {"ph": "X", "pid": 1, "tid": 1, "name": "ThunkExecutor::Run",
         "ts": 0.0, "dur": 50 * us},
        {"ph": "M", "pid": 1, "tid": 1, "name": "all-gather.1"},
    ]
    s = summarize_events(ev, steps=2, n_devices=2)
    assert s["collective_ms"] == 9.0   # 4 (merged) + 2 + 3, not 11
    assert s["compute_ms"] == 6.0
    assert s["host_ms"] == {"h2d": 3.0}
    assert s["collective_fraction"] == 0.6
    # per-op: tid 1's nested pair merges to 4, tid 3's concurrent 3 SUMS
    assert s["collective_by_op_ms"] == {"all-gather": 7.0, "all-reduce": 2.0}
    assert s["collective_ms_per_step_device"] == 2.25  # 9 / (2 steps * 2 dev)
    assert s["compute_ms_per_step_device"] == 1.5
    assert s["events_classified"] == 6
