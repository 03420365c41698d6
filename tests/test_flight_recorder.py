"""Tier-1 flight-recorder + replay tests on the 8-device CPU mesh.

The acceptance path: an injected-NaN pretraining run trips the health pack,
the flight recorder dumps a repro bundle next to the checkpoints, the run
halts NONZERO printing the bundle path, and tools/replay.py re-executes the
offending step from bundle + checkpoint reproducing the recorded loss and
health flags BIT-identically, with --bisect naming the first non-finite
model scope — under unpacked and packed batches, stacked and unstacked
encoder layouts. Plus: the ring-buffer memory bound (incl. under
prefetch+packing), crash-safe flush on exception and signal, and the
--validate schema check failing loudly on a corrupted bundle.
"""

import json
import math
import os
import shutil
import signal
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bert_pytorch_tpu.telemetry import flight_recorder as fr
from bert_pytorch_tpu.telemetry.flight_recorder import (FlightRecorder,
                                                        validate_bundle)
from tests.test_data import write_shard  # noqa: E402

MODEL_CFG = {
    "vocab_size": 128, "hidden_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 4, "intermediate_size": 64,
    "max_position_embeddings": 64, "next_sentence": True,
    "hidden_dropout_prob": 0.0, "attention_probs_dropout_prob": 0.0,
    "tokenizer": "wordpiece", "fused_ops": False, "attention_impl": "xla",
}


def _workdir(root, varied=False, stacked=None):
    data = root / "data"
    data.mkdir(parents=True)
    for i in range(2):
        write_shard(data / f"shard_{i}.hdf5", 48 if varied else 32,
                    seed=i, varied=varied)
    cfg = dict(MODEL_CFG)
    if stacked is not None:
        cfg["stacked_params"] = stacked
    cfg_path = root / "model_config.json"
    cfg_path.write_text(json.dumps(cfg))
    return data, cfg_path


def _nan_argv(data, cfg_path, out, extra=()):
    """A run wired to blow up at step 3: the fault-injection drill poisons
    layer 0's attention output kernel in-graph, the health pack flags it,
    and --nonfinite_action=halt stops the run after the recorder dumps.
    Checkpoints every step so replay has a base within the ring."""
    return ["--model_config_file", str(cfg_path),
            "--input_dir", str(data), "--output_dir", str(out),
            "--mask_token_index", "3", "--dtype", "float32",
            "--vocab_pad_multiple", "8", "--learning_rate", "1e-3",
            "--global_batch_size", "32", "--local_batch_size", "2",
            "--max_steps", "5", "--max_predictions_per_seq", "5",
            "--num_steps_per_checkpoint", "1", "--log_freq", "2",
            "--zero1", "false", "--recorder_window", "4",
            "--inject_nonfinite_step", "3",
            "--nonfinite_action", "halt"] + list(extra)


def _bundles(out):
    d = os.path.join(out, "repro_bundles")
    return sorted(os.path.join(d, b) for b in os.listdir(d)) \
        if os.path.isdir(d) else []


@pytest.fixture(scope="module")
def nan_run(tmp_path_factory):
    """One injected-NaN e2e run (unpacked, stacked layout), shared by the
    replay / bisect / validate / halt tests below."""
    root = tmp_path_factory.mktemp("fr_nan")
    data, cfg_path = _workdir(root)
    out = root / "out"
    import run_pretraining

    rc = run_pretraining._cli(_nan_argv(data, cfg_path, out))
    bundles = _bundles(out)
    return {"rc": rc, "out": out, "bundles": bundles,
            "log": (out / "logfile.txt").read_text()}


# -- e2e: alarm -> dump -> nonzero halt --------------------------------------

def test_halt_exits_nonzero_and_prints_bundle(nan_run):
    """Satellite: --nonfinite_action=halt exits with the DISTINCT code 71
    (EXIT_NONFINITE_HALT — tools/supervise.py refuses to retry it; clean
    FATAL instead of a traceback) and the dumped bundle's path is in the
    logs."""
    from bert_pytorch_tpu.resilience import EXIT_NONFINITE_HALT

    assert nan_run["rc"] == EXIT_NONFINITE_HALT
    assert len(nan_run["bundles"]) == 1
    bundle = nan_run["bundles"][0]
    assert os.path.basename(bundle).startswith("step00000003_nonfinite")
    assert bundle in nan_run["log"]  # operator can copy-paste the path
    assert os.path.isfile(os.path.join(bundle, "manifest.json"))
    assert os.path.isfile(os.path.join(bundle, "batches.npz"))


def test_bundle_contents(nan_run):
    bundle = nan_run["bundles"][0]
    manifest = json.load(open(os.path.join(bundle, "manifest.json")))
    assert manifest["trigger_step"] == 3
    assert manifest["reason"] == "nonfinite"
    assert manifest["run"]["accum_steps"] == 2
    assert manifest["provenance"]["platform"] == "cpu"
    assert manifest["model_config"]["hidden_size"] == 32
    # ring window 4 held steps 1..3 (only 3 dispatched before the halt)
    assert [r["step"] for r in manifest["records"]] == [1, 2, 3]
    # the metrics tail recorded the flagged step; the NaN loss is
    # serialized as the string 'nan' so manifest.json stays STRICT json
    # (parse_constant fires only on the lenient NaN/Infinity tokens)
    flagged = [m for m in manifest["metrics_tail"] if m["step"] == 3]
    assert flagged and flagged[0]["loss_nonfinite"] == 1
    assert math.isnan(float(flagged[0]["loss"]))
    raw = open(os.path.join(bundle, "manifest.json")).read()
    json.loads(raw, parse_constant=lambda s: pytest.fail(
        f"manifest.json is not strict JSON: bare {s} token"))


def test_bundle_manifest_v2_registry_and_tail_source(nan_run):
    """Manifest schema v2 (satellite): the bundle cross-refs the jsonl
    sink its metrics tail mirrors and carries the metrics-registry
    snapshot at dump time — the run's cumulative counters ride along,
    not just the last few records."""
    bundle = nan_run["bundles"][0]
    manifest = json.load(open(os.path.join(bundle, "manifest.json")))
    assert manifest["schema_version"] == 2
    src = manifest["metrics_tail_source"]
    assert src and src.endswith(".jsonl") and os.path.isfile(src)
    reg = manifest["registry"]
    assert isinstance(reg, dict) and reg, "registry snapshot missing"

    def series_value(name):
        (s,) = reg[name]["series"]
        assert s["labels"]["phase"] == "pretrain"
        return s["value"]

    # the run halted on step 3: the counters saw 3 steps, and the flagged
    # step had been counted by the time the alarm path dumped
    assert series_value("bert_train_steps_total") == 3
    assert series_value("bert_nonfinite_steps_total") >= 1
    assert reg["bert_xla_compiles_total"]["series"][0]["value"] > 0


def test_validate_fails_on_missing_v2_keys(nan_run, tmp_path):
    """--validate schema-checks the v2 cross-refs: a manifest stripped of
    its registry snapshot fails loudly at the door."""
    import tools.replay as replay

    stripped = tmp_path / "stripped_bundle"
    shutil.copytree(nan_run["bundles"][0], stripped)
    manifest = json.load(open(stripped / "manifest.json"))
    del manifest["registry"]
    manifest["metrics_tail_source"] = 12345  # wrong type
    (stripped / "manifest.json").write_text(json.dumps(manifest))
    res = replay.main(["--bundle", str(stripped), "--validate"])
    assert res["valid"] is False
    joined = " ".join(res["errors"])
    assert "registry" in joined
    assert replay._cli(["--bundle", str(stripped), "--validate"]) == 2


@pytest.fixture(scope="module")
def nan_replayed(nan_run):
    """One replay+bisect pass over the shared bundle (--bisect performs
    the full replay first), shared by the assertions below — every
    replay.main call re-jits the whole step program, so fold them."""
    import tools.replay as replay

    return replay.main(["--bundle", nan_run["bundles"][0], "--bisect"])


def test_replay_reproduces_bit_identically(nan_replayed):
    """THE acceptance property: replay from bundle + checkpoint reproduces
    the recorded loss and health flags bit-identically on CPU."""
    res = nan_replayed
    assert res["match"] is True, res["mismatches"]
    assert res["base_checkpoint"] == 2
    assert res["replayed"]["loss_nonfinite"] == 1
    assert res["replayed"]["grad_nonfinite"] > 0
    assert math.isnan(res["replayed"]["loss"])
    # recorded was NaN too (strict-json string), and _values_equal
    # treated NaN==NaN as reproduced
    assert math.isnan(float(res["recorded"]["loss"]))


def test_replay_bisect_names_guilty_scope(nan_replayed):
    """--bisect re-runs the offending forward with debug taps and blames
    layer 0's attention block — exactly where the drill injected the NaN
    (attention output kernel)."""
    res = nan_replayed
    bad = res["bisect"]["first_nonfinite"]
    assert bad is not None
    assert bad["scope"] == "layer_0/attention"
    # execution-order scope list says everything before it was finite
    scopes = res["bisect"]["scopes"]
    names = [s["scope"] for s in scopes]
    assert names.index("embeddings") < names.index("layer_0/attention")
    assert scopes[names.index("embeddings")]["finite"] is True


def test_replay_earlier_clean_step_matches(nan_run):
    """Replay is not NaN-specific: a clean recorded step (2) reproduces
    its finite loss bit-identically from checkpoint 1."""
    import tools.replay as replay

    res = replay.main(["--bundle", nan_run["bundles"][0], "--step", "2"])
    assert res["match"] is True, res["mismatches"]
    assert res["replayed"]["loss_nonfinite"] == 0
    assert math.isfinite(res["replayed"]["loss"])


@pytest.mark.parametrize("recorded, replays", [("off", True),
                                               ("pallas", False)])
def test_replay_of_a_bundle_that_names_fused_optim(nan_run, tmp_path,
                                                   recorded, replays):
    """Bundles written before --fused_optim went carry the key in their run
    block. "off" was the per-leaf update, which is the one path left: it
    replays as before. Any other value was recorded on a path that differed
    from it by ulps and is refused in one sentence."""
    import tools.replay as replay

    old = tmp_path / "old_bundle"
    shutil.copytree(nan_run["bundles"][0], old)
    manifest = json.load(open(old / "manifest.json"))
    assert "fused_optim" not in manifest["run"]
    manifest["run"]["fused_optim"] = recorded
    (old / "manifest.json").write_text(json.dumps(manifest))
    assert validate_bundle(str(old)) == []
    if replays:
        res = replay.main(["--bundle", str(old), "--step", "2"])
        assert res["match"] is True, res["mismatches"]
    else:
        with pytest.raises(replay.ReplayError,
                           match="--fused_optim pallas.*by ulps"):
            replay.main(["--bundle", str(old)])
        assert replay._cli(["--bundle", str(old)]) != 0


# -- --validate schema check -------------------------------------------------

def test_validate_ok(nan_run):
    import tools.replay as replay

    res = replay.main(["--bundle", nan_run["bundles"][0], "--validate"])
    assert res["valid"] is True and res["errors"] == []
    assert replay._cli(["--bundle", nan_run["bundles"][0],
                        "--validate"]) == 0


def test_validate_fails_loudly_on_corrupt_bundle(nan_run, tmp_path):
    """Satellite: stale/corrupt bundles fail at the door with named
    errors, not mysteriously inside replay."""
    import tools.replay as replay

    corrupt = tmp_path / "corrupt_bundle"
    shutil.copytree(nan_run["bundles"][0], corrupt)
    manifest = json.load(open(corrupt / "manifest.json"))
    del manifest["run"]["accum_steps"]           # missing run key
    manifest["records"][0]["fields"].append("ghost_field")  # npz mismatch
    (corrupt / "manifest.json").write_text(json.dumps(manifest))

    res = replay.main(["--bundle", str(corrupt), "--validate"])
    assert res["valid"] is False
    joined = " ".join(res["errors"])
    assert "accum_steps" in joined and "ghost_field" in joined
    assert replay._cli(["--bundle", str(corrupt), "--validate"]) == 2
    # and a non-validate replay refuses up front with the same errors
    with pytest.raises(replay.ReplayError, match="schema"):
        replay.main(["--bundle", str(corrupt)])

    # a bundle missing its arrays entirely is caught too
    (corrupt / "batches.npz").unlink()
    assert validate_bundle(str(corrupt)) == \
        [f"no batches.npz under {corrupt}"]


# -- packed + unstacked acceptance variants ----------------------------------

def test_nan_e2e_replay_packed(tmp_path):
    """Acceptance: the same alarm -> dump -> replay -> bisect loop under
    --packing (segment fields ride the bundle and thread back through
    _packed_kwargs on replay)."""
    data, cfg_path = _workdir(tmp_path, varied=True)
    out = tmp_path / "out_packed"
    import run_pretraining
    import tools.replay as replay

    rc = run_pretraining._cli(_nan_argv(
        data, cfg_path, out,
        extra=["--packing", "--packing_max_segments", "4"]))
    assert rc == 71  # EXIT_NONFINITE_HALT (docs/RESILIENCE.md)
    (bundle,) = _bundles(out)
    manifest = json.load(open(os.path.join(bundle, "manifest.json")))
    assert manifest["run"]["packing"] is True
    assert "segment_ids" in manifest["records"][0]["fields"]
    assert "nsp_positions" in manifest["records"][0]["fields"]

    res = replay.main(["--bundle", bundle, "--bisect"])
    assert res["match"] is True, res["mismatches"]
    assert res["replayed"]["loss_nonfinite"] == 1
    assert res["bisect"]["first_nonfinite"]["scope"] == "layer_0/attention"


@pytest.mark.slow  # re-tiered out of tier-1's 870s wall-clock budget
def test_nan_e2e_chunked_dispatch_unstacked(tmp_path):
    """--steps_per_loop > 1, under the UNSTACKED encoder layout (the
    bundle round-trips through restore_either_layout and the per-layer
    debug taps): the window auto-clamps to 2 chunks so the one-dispatch
    metric lag cannot evict the flagged chunk; the sticky trigger step
    (chunk-final) replays bit-identically through the same chain_steps
    program; and --step reaches the INNER chunk step where the NaN
    actually fired, including --bisect."""
    data, cfg_path = _workdir(tmp_path, stacked=False)
    out = tmp_path / "out_chunked"
    import run_pretraining
    import tools.replay as replay

    # inject at step 3 = inner step of chunk {3,4}; window 1 forces the
    # clamp to 2*steps_per_loop=4; global batch 16 = accum 1 (accum>1
    # replay is the module fixture's job — keep this run's compiles lean)
    rc = run_pretraining._cli(_nan_argv(
        data, cfg_path, out,
        extra=["--steps_per_loop", "2", "--recorder_window", "1",
               "--global_batch_size", "16"]))
    assert rc == 71  # EXIT_NONFINITE_HALT (docs/RESILIENCE.md)
    (bundle,) = _bundles(out)
    manifest = json.load(open(os.path.join(bundle, "manifest.json")))
    assert manifest["model_config"]["stacked_params"] is False
    # sticky chain flags land on the chunk-final step
    assert manifest["trigger_step"] == 4
    recs = {r["step"]: r for r in manifest["records"]}
    # clamp held chunk {3,4} intact despite the step-5 partial dispatch
    assert {3, 4} <= set(recs) and recs[3]["pos"] == 0 \
        and recs[3]["n_steps"] == 2
    # chunk-final target: dispatch-faithful replay with bit-identical
    # sticky metrics — and bisect there sees only the CONSEQUENCE: step
    # 3's applied NaN update poisoned the params (halt != skip), so step
    # 4's forward dies at the first scope
    res = replay.main(["--bundle", bundle, "--bisect"])
    assert res["match"] is True, res["mismatches"]
    assert res["replayed"]["loss_nonfinite"] == 1
    assert res["bisect"]["first_nonfinite"]["scope"] == "embeddings"
    # inner chunk step: reachable via --step (no recorded per-step
    # metrics to compare — match stays None); the NaN fired right there,
    # and bisect names the CAUSE. This asymmetry is exactly why --step
    # must reach inner chunk steps.
    res = replay.main(["--bundle", bundle, "--step", "3", "--bisect"])
    assert res["match"] is None and res["recorded"] is None
    assert res["replayed"]["loss_nonfinite"] == 1
    assert res["bisect"]["first_nonfinite"]["scope"] == "layer_0/attention"


# -- ring-buffer memory bound ------------------------------------------------

def _fake_batch(i, batch=4, seq=8):
    return {"input_ids": np.full((batch, seq), i, np.int32),
            "attention_mask": np.ones((batch, seq), np.int32)}


def test_ring_buffer_bound():
    rec = FlightRecorder("/tmp/unused_fr", window=3)
    per_batch = sum(v.nbytes for v in _fake_batch(0).values())
    for i in range(10):
        rec.capture_batch(_fake_batch(i))
        rec.record_dispatch(i + 1, 1, np.zeros(2, np.uint32))
    assert [r["step"] for r in rec._records] == [8, 9, 10]
    assert rec.nbytes() <= 3 * per_batch
    # staging is cleared by every dispatch bind
    assert rec._staged == []
    # newest batch data survived, oldest evicted
    assert rec._records[-1]["batch"]["input_ids"][0, 0] == 9


def test_ring_buffer_bound_chunked_dispatch():
    """--steps_per_loop n consumes n ring slots per dispatch; the bound is
    still in BATCHES."""
    rec = FlightRecorder("/tmp/unused_fr", window=4)
    step = 0
    for _ in range(3):
        for _ in range(2):
            rec.capture_batch(_fake_batch(step))
            step += 1
        rec.record_dispatch(step - 1, 2, np.zeros(2, np.uint32))
    assert len(rec._records) == 4
    assert [r["pos"] for r in rec._records] == [0, 1, 0, 1]


def test_ring_buffer_bound_under_prefetch_and_packing(tmp_path):
    """Satellite: the bound holds against the real loader with the
    prefetch executor running ahead and the packer's carry-over buffer in
    play — the tap fires at yield, so the ring never sees more than
    `window` batches no matter how far assembly runs ahead."""
    from bert_pytorch_tpu.data.sharded import (HostShardSampler,
                                               PretrainingDataLoader,
                                               ShardIndex)

    for i in range(2):
        write_shard(tmp_path / f"shard_{i}.hdf5", 48, seed=i, varied=True)
    index = ShardIndex(sorted(str(p) for p in tmp_path.glob("*.hdf5")))
    sampler = HostShardSampler(len(index), world_size=1, rank=0, seed=0)
    rec = FlightRecorder(str(tmp_path / "fr"), window=2)
    loader = PretrainingDataLoader(
        index, sampler, batch_size=8, mask_token_index=3,
        max_pred_per_seq=5, masked_lm_prob=0.15, vocab_size=128, seed=0,
        prefetch_batches=2, packing=True, packing_max_segments=4,
        batch_tap=rec.capture_batch)
    try:
        it = iter(loader)
        per_batch = None
        for step in range(1, 6):
            batch = next(it)
            if per_batch is None:
                per_batch = sum(np.asarray(v).nbytes
                                for v in batch.values())
            rec.record_dispatch(step, 1, np.zeros(2, np.uint32))
            assert len(rec._records) <= 2
            # staging + ring together stay within one extra batch of the
            # window (at most one staged batch awaits its dispatch bind)
            assert rec.nbytes() <= 3 * per_batch
    finally:
        loader.close()


# -- the dispatch key: kept as handed over, read at dump time ------------------

class _DeviceKey:
    """Stands for the dispatch key as the train loop hands it over, a
    device array: turning it into numpy IS the device-to-host read, which
    cannot return before the step in flight has ended."""

    def __init__(self, value):
        self.value, self.readable, self.reads = value, False, 0

    def __array__(self, dtype=None, copy=None):
        if not self.readable:
            raise RuntimeError("the dispatch key was read off the device")
        self.reads += 1
        return self.value


def test_record_dispatch_leaves_the_key_on_the_device(tmp_path):
    """record_dispatch must not wait for the device (until PR 24 it did,
    for most of a step, under no span of the loop): the key is converted
    when a bundle is dumped, and the bundle holds the same key."""
    rec = FlightRecorder(str(tmp_path / "fr"), window=4,
                         run_info=dict.fromkeys(fr.REQUIRED_RUN_KEYS, 0),
                         model_config={"hidden_size": 8,
                                       "num_hidden_layers": 1})
    keys = [_DeviceKey(np.array([7, i], np.uint32)) for i in range(3)]
    for i, key in enumerate(keys):
        rec.capture_batch(_fake_batch(i))
        rec.record_dispatch(i + 1, 1, key)      # raises if it converts
    assert [k.reads for k in keys] == [0, 0, 0]
    assert rec.nbytes() > 0
    for key in keys:
        key.readable = True
    bundle = rec.dump("nonfinite", trigger_step=3)
    assert fr.validate_bundle(bundle) == []
    with np.load(os.path.join(bundle, "batches.npz")) as npz:
        for i in range(3):
            np.testing.assert_array_equal(npz[f"s{i + 1:08d}__rng"],
                                          [7, i])


def test_dump_ends_without_a_key_it_cannot_read(tmp_path, monkeypatch):
    """A hung device never hands the key of the step in flight over (the
    watchdog's dump): that step is left out and named, the bundle is
    valid, and dump() returns."""
    monkeypatch.setattr(fr, "KEY_READ_PATIENCE_S", 0.3)
    rec = FlightRecorder(str(tmp_path / "fr"), window=4,
                         run_info=dict.fromkeys(fr.REQUIRED_RUN_KEYS, 0),
                         model_config={"hidden_size": 8,
                                       "num_hidden_layers": 1})
    done, hung = _DeviceKey(np.array([1, 2], np.uint32)), _DeviceKey(None)
    done.readable = True
    for step, key in ((1, done), (2, hung)):
        rec.capture_batch(_fake_batch(step))
        rec.record_dispatch(step, 1, key)
    bundle = rec.dump("watchdog_device_hang")
    assert fr.validate_bundle(bundle) == []
    manifest = json.load(open(os.path.join(bundle, "manifest.json")))
    assert [r["step"] for r in manifest["records"]] == [1]
    assert manifest["unkeyed_steps"] == [2]


def test_bundle_keys_are_the_loops_dispatch_keys(nan_run):
    """End to end: the loop hands the recorder device arrays, and the
    bundle that replays bit-identically (below) holds fold_in(base, step)
    for every recorded step."""
    bundle = nan_run["bundles"][0]
    base = jax.random.PRNGKey(42 + 1000)    # --seed default + 1000 + rank 0
    with np.load(os.path.join(bundle, "batches.npz")) as npz:
        for step in (1, 2, 3):
            np.testing.assert_array_equal(
                npz[f"s{step:08d}__rng"],
                np.asarray(jax.random.fold_in(base, step)))


# -- crash safety ------------------------------------------------------------

def test_crash_flush_dumps_bundle_and_metrics(tmp_path, monkeypatch):
    """Satellite: a mid-run crash (any exception unwinding main) flushes
    the buffered metric record AND dumps a crash bundle before teardown."""
    import run_pretraining
    from bert_pytorch_tpu.parallel import mesh as mesh_lib

    data, cfg_path = _workdir(tmp_path)
    out = tmp_path / "out_crash"
    calls = {"n": 0}
    real = mesh_lib.host_to_device_batch

    def boom(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("simulated mid-run crash")
        return real(*a, **kw)

    monkeypatch.setattr(mesh_lib, "host_to_device_batch", boom)
    argv = ["--model_config_file", str(cfg_path), "--input_dir", str(data),
            "--output_dir", str(out), "--mask_token_index", "3",
            "--dtype", "float32", "--vocab_pad_multiple", "8",
            "--learning_rate", "1e-3", "--global_batch_size", "32",
            "--local_batch_size", "2", "--max_steps", "5",
            "--max_predictions_per_seq", "5", "--skip_checkpoint",
            "--log_freq", "10", "--zero1", "false"]
    with pytest.raises(RuntimeError, match="simulated"):
        run_pretraining.main(argv)

    log = (out / "logfile.txt").read_text()
    # pending metrics of the last dispatched step landed (step 2 was in
    # flight when the crash hit before dispatch 3)
    assert "step 2" in log
    # the partial StepWatch interval flushed (log_freq 10 never reached)
    assert "[perf]" in log
    (bundle,) = _bundles(out)
    assert "runtimeerror" in os.path.basename(bundle)
    manifest = json.load(open(os.path.join(bundle, "manifest.json")))
    assert manifest["reason"] == "runtimeerror"
    assert [r["step"] for r in manifest["records"]] == [1, 2]


def test_signal_handler_maps_to_systemexit(tmp_path):
    """SIGTERM/SIGINT become SystemExit(128+sig) so the crash-flush except
    path runs; handlers restore on close()."""
    old_term = signal.getsignal(signal.SIGTERM)
    rec = FlightRecorder(str(tmp_path / "fr"))
    rec.install_crash_handlers()
    try:
        handler = signal.getsignal(signal.SIGTERM)
        assert handler == rec._on_signal
        with pytest.raises(SystemExit) as e:
            handler(signal.SIGTERM, None)
        assert e.value.code == 128 + signal.SIGTERM
    finally:
        rec.close()
    assert signal.getsignal(signal.SIGTERM) == old_term


def test_atexit_backstop_only_when_armed(tmp_path):
    rec = FlightRecorder(str(tmp_path / "fr"), window=2)
    rec.capture_batch(_fake_batch(0))
    rec.record_dispatch(1, 1, np.zeros(2, np.uint32))
    rec._atexit_dump()           # not armed: no dump
    assert rec.last_dump is None
    rec.arm()
    rec._atexit_dump()
    assert rec.last_dump is not None
    assert os.path.isdir(rec.last_dump)
    rec.close()


# -- StepWatch.flush (crash-safe partial interval) ---------------------------

def test_stepwatch_flush_partial_interval():
    from bert_pytorch_tpu.telemetry import StepWatch

    clock = [0.0]
    sw = StepWatch(flops_per_step=1e9, seqs_per_step=8, seq_len=64,
                   peak_flops=1e12, log_freq=10, time_fn=lambda: clock[0])
    assert sw.flush() is None          # nothing buffered
    with sw.phase("dispatch"):
        clock[0] += 0.5
    assert sw.step_done() is None      # below log_freq: buffered
    rec = sw.flush()
    assert rec is not None and rec["steps"] == 1
    assert rec["step_time_ms"] == pytest.approx(500.0)
    assert sw.flush() is None          # flushed: interval reset


def test_stepwatch_pause_excludes_eval_time():
    """sw.pause() keeps an epoch-boundary eval out of the next interval's
    wall clock (run_ner's val eval would otherwise inflate step_time_ms
    and deflate MFU for every epoch after the first)."""
    from bert_pytorch_tpu.telemetry import StepWatch

    clock = [0.0]
    sw = StepWatch(flops_per_step=1e9, seqs_per_step=8, seq_len=64,
                   peak_flops=1e12, log_freq=1, time_fn=lambda: clock[0])
    with sw.pause():
        clock[0] += 9.0                # eval: must not count
    with sw.phase("dispatch"):
        clock[0] += 0.25
    rec = sw.step_done()
    assert rec["step_time_ms"] == pytest.approx(250.0)


# -- multi-host bundle dirs (round 11) ---------------------------------------

def test_per_host_dir_suffixes_only_multiprocess(monkeypatch):
    """Single-process runs keep the round-10 bundle layout; multi-host runs
    get a per-process subdirectory so two hosts dumping the same trigger
    step never race the same bundle path."""
    import jax

    from bert_pytorch_tpu.telemetry.flight_recorder import per_host_dir

    assert per_host_dir("/out/repro_bundles") == "/out/repro_bundles"
    monkeypatch.setattr(jax, "process_count", lambda: 4)
    monkeypatch.setattr(jax, "process_index", lambda: 2)
    assert per_host_dir("/out/repro_bundles") == \
        "/out/repro_bundles/host00002"
