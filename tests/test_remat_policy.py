"""What --checkpoint_activations recomputes (models/bert.py _REMAT_POLICIES,
training/pretrain.resolve_remat_policy): by default the compiled step keeps
the outputs of the qkv and mlp_output projections and reruns the rest of the
layer; where that does not fit the device it falls back to saving nothing;
without the flag the policy is never read."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bert_pytorch_tpu.config import BertConfig  # noqa: E402
from bert_pytorch_tpu.models.bert import (  # noqa: E402
    REMAT_AUTO_ORDER, BertEncoder)
from bert_pytorch_tpu.training.pretrain import (  # noqa: E402
    StepProgram, resolve_remat_policy)
from tests.test_model import _eqns  # noqa: E402
from tests.test_step_scopes import _OP_NAME, _toy_step  # noqa: E402

PROJECTIONS = ("attention/qkv", "attention/output", "mlp/intermediate",
               "mlp/mlp_output")


def _recomputed(**config):
    """op_names of the compiled toy step that sit under the rematted body."""
    step, state, batch = _toy_step(False, **config)
    text = jax.jit(step, donate_argnums=(0,)).lower(
        state, batch, jax.random.PRNGKey(0)).compile().as_text()
    return {m.group(1) for m in map(_OP_NAME.search, text.splitlines())
            if m and "rematted_computation" in m.group(1)}


@pytest.mark.parametrize("policy, run_twice", [
    (None, ("attention/output", "mlp/intermediate")),
    ("dense", ("attention/output", "mlp/intermediate")),
    ("nothing", PROJECTIONS)])
def test_which_operations_run_twice(policy, run_twice):
    """Under the default the qkv and mlp_output matmuls are not recomputed
    while the other two projections, the attention core and the
    dropout-LayerNorms still are; under "nothing" (the fall-back) the
    whole layer is."""
    ops = _recomputed(**({} if policy is None else {"remat_policy": policy}))
    again = tuple(p for p in PROJECTIONS
                  if any(op.endswith(f"/{p}/dot_general") for op in ops))
    assert again == run_twice
    assert any("attention_layer_norm" in op for op in ops)
    assert any("output_layer_norm" in op for op in ops)
    # QK^T and PV: dot_generals of `attention` that are no projection's
    assert any("/attention/attention/" in op
               and op.endswith("dot_general")
               and not op.endswith(("qkv/dot_general",
                                    "output/dot_general"))
               for op in ops), sorted(ops)[:40]


def test_a_policy_that_went_is_refused_at_load_with_the_three_that_exist(
        tmp_path):
    """"dots" lost PR 25's race and "mlp_only" never dropped a wide value
    (PR 29): both went with PR 46. A config that names one fails where it is
    loaded, not where the model is built, and the message lists what the
    three model modules hold."""
    from bert_pytorch_tpu.config import (REMAT_POLICIES, Lfm2MoeConfig,
                                         load_model_config)
    from bert_pytorch_tpu.models import decoder, keye
    from bert_pytorch_tpu.models.bert import _REMAT_POLICIES

    for held in (_REMAT_POLICIES, decoder.LM_REMAT_POLICIES,
                 keye.REMAT_POLICIES):
        assert set(held) == set(REMAT_POLICIES) == {"nothing", "dense",
                                                    "auto"}
    path = tmp_path / "cfg.json"
    for policy in ("dots", "mlp_only"):
        path.write_text(json.dumps({"vocab_size": 128,
                                    "remat_policy": policy}))
        with pytest.raises(ValueError, match=r"nothing.*dense.*auto"):
            load_model_config(str(path))
        with pytest.raises(ValueError, match=policy):
            Lfm2MoeConfig(remat_policy=policy)


def test_policy_is_not_read_without_the_flag():
    texts = set()
    for policy in ("auto", "nothing", "dense"):
        step, state, batch = _toy_step(False, checkpoint_activations=False,
                                       remat_policy=policy)
        texts.add(jax.jit(step, donate_argnums=(0,)).lower(
            state, batch, jax.random.PRNGKey(0)).as_text())
    assert len(texts) == 1
    assert "rematted_computation" not in texts.pop()


@pytest.mark.parametrize("policy, stacked", [
    (None, 2), ("dense", 0), ("nothing", 0)])
def test_wide_values_the_forward_scan_stacks(policy, stacked):
    """What the forward layer scan writes into (L, B, S, F) stacks for the
    backward scan, counted in the jaxpr of value_and_grad. Without remat:
    the activation's output (the mlp_output matmul's residual) and the
    erf-GELU's derivative (ops/activations.py), where autodiff of
    jax.nn.gelu stacked three of its own beside the output. "dense" and
    "nothing" stack nothing that wide."""
    L, B, S, H, F = 3, 2, 16, 32, 80
    cfg = BertConfig(
        vocab_size=128, hidden_size=H, num_hidden_layers=L,
        num_attention_heads=4, intermediate_size=F,
        max_position_embeddings=64, fused_ops=False, attention_impl="xla",
        checkpoint_activations=policy is not None,
        **({} if policy is None else {"remat_policy": policy}))
    encoder = BertEncoder(cfg, dtype=jnp.bfloat16)
    hidden = jnp.ones((B, S, H), jnp.bfloat16)
    bias = jnp.zeros((B, 1, 1, S), jnp.float32)
    params = encoder.init(jax.random.PRNGKey(0), hidden, bias)

    def loss(p, h):
        return encoder.apply(p, h, bias).astype(jnp.float32).sum()

    forward, backward = [
        e for e in _eqns(jax.make_jaxpr(jax.value_and_grad(loss))(
            params, hidden).jaxpr) if e.primitive.name == "scan"]
    wide = [v.aval for v in forward.outvars if v.aval.shape == (L, B, S, F)]
    assert len(wide) == stacked
    assert all(a.dtype == jnp.bfloat16 for a in wide)
    assert not [v for v in backward.outvars
                if v.aval.shape == (L, B, S, F)]
    # the derivative reads the erfc through an optimization barrier (so
    # that XLA evaluates it once); a forward pass that keeps no derivative
    # (the first one under remat) must not carry the barrier either
    assert [e.primitive.name for e in _eqns(forward.params["jaxpr"].jaxpr)
            ].count("optimization_barrier") == (1 if stacked else 0)


class _FakeProgram:
    def __init__(self, peak, error=None):
        self.peak, self.error, self.compiled = peak, error, False

    def compile(self, *args):
        if self.error is not None:
            raise self.error
        self.compiled = True

    def peak_bytes(self):
        return self.peak


OOM = jax.errors.JaxRuntimeError(
    "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
    "memory in memory space hbm. Used 17.0G of 15.75G hbm.")


@pytest.mark.parametrize("limit, dense, want", [
    (None, _FakeProgram(900), "dense"),         # the backend states no limit
    (1000, _FakeProgram(900), "dense"),
    (1000, _FakeProgram(1000), "dense"),
    (1000, _FakeProgram(1001), "nothing"),      # the compiler's peak is over
    (1000, _FakeProgram(0, OOM), "nothing"),    # the compiler refuses it
], ids=["no-limit", "fits", "fits-exactly", "peak-over", "compile-oom"])
def test_resolver_takes_the_first_that_fits(limit, dense, want):
    programs = {"dense": dense, "nothing": _FakeProgram(2000)}
    said = []
    policy, program = resolve_remat_policy(
        programs.__getitem__, (), limit, log=said.append)
    assert policy == want and program is programs[want]
    assert program.compiled
    # the last candidate is taken whatever it needs; a pass-over is said
    assert len(said) == (want != "dense")


def test_resolver_raises_what_is_not_memory():
    boom = jax.errors.JaxRuntimeError("INTERNAL: something else")
    with pytest.raises(jax.errors.JaxRuntimeError, match="something else"):
        resolve_remat_policy({"dense": _FakeProgram(0, boom)}.__getitem__,
                             (), 1000, log=lambda _: None)
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE"):
        resolve_remat_policy(lambda _: _FakeProgram(0, OOM), (), 1000,
                             log=lambda _: None)


def test_resolver_on_the_real_step():
    """Given a limit one byte under the saving policy's own peak, the toy
    step resolves to "nothing", compiled already (at this size the CPU
    compiler states the same peak for both)."""
    def build(policy):
        step, state, batch = _toy_step(False, remat_policy=policy)
        args[:] = [state, batch, jax.random.PRNGKey(0)]
        return StepProgram(step)

    args = []
    policy, program = resolve_remat_policy(
        build, args, None, log=lambda _: None)
    dense_peak = program.peak_bytes()
    assert policy == REMAT_AUTO_ORDER[0] == "dense" and dense_peak > 0
    assert program.compiled is not None
    policy, program = resolve_remat_policy(
        build, args, dense_peak - 1, log=lambda _: None)
    assert policy == "nothing" and 0 < program.peak_bytes() <= dense_peak
    state, metrics = program(*args)         # the compiled program runs
    assert float(metrics["loss"]) > 0


@pytest.mark.parametrize("limit, policy", [(None, "dense"), (1, "nothing")],
                         ids=["fits", "falls-back"])
def test_entry_point_reports_what_it_resolved(tmp_path, monkeypatch, limit,
                                              policy):
    """--checkpoint_activations through run_pretraining.main(): the run's
    program header names the resolved policy, every [perf] record carries
    `remat_saves_dense` and `step_peak_bytes`, and the one compile made in
    set-up is the program the loop runs (no compile after it)."""
    from tests.test_data import write_shard

    import bert_pytorch_tpu.telemetry as telemetry
    import run_pretraining

    data = tmp_path / "data"
    data.mkdir()
    for i in range(2):
        write_shard(data / f"shard_{i}.hdf5", 32, seed=i)
    cfg = tmp_path / "model_config.json"
    cfg.write_text(json.dumps({
        "vocab_size": 128, "hidden_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 64,
        "max_position_embeddings": 64, "next_sentence": True,
        "tokenizer": "wordpiece", "fused_ops": False,
        "attention_impl": "xla"}))
    if limit is not None:
        # the CPU backend states no bytes_limit; the resolver is handed one
        monkeypatch.setattr(telemetry, "hbm_snapshot",
                            lambda: {"hbm_bytes_limit": limit})
    out = tmp_path / "out"
    final_step, _ = run_pretraining.main([
        "--model_config_file", str(cfg), "--input_dir", str(data),
        "--output_dir", str(out), "--mask_token_index", "3",
        "--dtype", "float32", "--vocab_pad_multiple", "8",
        "--learning_rate", "1e-3", "--global_batch_size", "32",
        "--local_batch_size", "2", "--max_steps", "4",
        "--max_predictions_per_seq", "5", "--skip_checkpoint",
        "--log_prefix", "t", "--log_freq", "1", "--tensorboard", "off",
        "--checkpoint_activations"])
    assert final_step == 4
    records = [json.loads(line) for line in open(out / "t.jsonl")]
    headers = [r for r in records if r.get("tag") == "header"]
    assert headers[-1]["remat_policy"] == policy
    perf = [r for r in records if r.get("tag") == "perf"]
    assert len(perf) == 4
    assert {r["remat_saves_dense"] for r in perf} == {int(policy == "dense")}
    peaks = {r["step_peak_bytes"] for r in perf}
    assert len(peaks) == 1 and peaks.pop() > 0
    assert len({r["compiles"] for r in perf}) == 1
    log = (out / "t.txt").read_text()
    assert f"activation checkpointing: remat_policy={policy}" in log
    assert ("remat_policy auto: 'dense' peaks at" in log) \
        == (policy == "nothing")
