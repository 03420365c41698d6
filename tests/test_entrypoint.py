"""End-to-end CLI test: run_pretraining.main() over synthesized shards on the
8-device CPU mesh — training runs, logs metrics, checkpoints, auto-resumes."""

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tests.test_data import write_shard  # noqa: E402


@pytest.fixture
def workdir(tmp_path):
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
    run_cfg = {
        "model_config_file": str(cfg_path),
        "learning_rate": 1e-3,
        "global_batch_size": 32,
        "local_batch_size": 2,       # 8 data shards -> micro_global 16, accum 2
        "max_steps": 3,
        "warmup_proportion": 0.1,
        "masked_token_fraction": 0.15,
        "max_predictions_per_seq": 5,
        "num_steps_per_checkpoint": 2,
        "log_prefix": "testlog",
    }
    run_path = tmp_path / "run_config.json"
    run_path.write_text(json.dumps(run_cfg))
    return tmp_path, data, run_path


def test_run_pretraining_end_to_end_and_resume(workdir):
    tmp_path, data, run_path = workdir
    import run_pretraining

    out = tmp_path / "out"
    argv = ["--config_file", str(run_path), "--input_dir", str(data),
            "--output_dir", str(out), "--mask_token_index", "3",
            "--dtype", "float32", "--vocab_pad_multiple", "8"]
    final_step, _ = run_pretraining.main(argv)
    assert final_step == 3

    log = (out / "testlog.txt").read_text()
    assert "step 1" in log and "step 3" in log
    assert "training_seq_per_sec" in log
    csv_rows = (out / "testlog_metrics.csv").read_text().strip().splitlines()
    assert len(csv_rows) >= 4  # header + 3 steps

    ckpts = os.listdir(out / "pretrain_ckpts")
    assert any("2" in c or "3" in c for c in ckpts)

    # auto-resume: bump max_steps, rerun -> continues from 3, not 0
    run_cfg = json.loads(run_path.read_text())
    run_cfg["max_steps"] = 5
    run_path.write_text(json.dumps(run_cfg))
    final_step2, _ = run_pretraining.main(argv)
    assert final_step2 == 5
    assert "auto-resumed from step 3" in (out / "testlog.txt").read_text()


@pytest.mark.slow
def test_run_pretraining_zero1_rs_smoke(workdir):
    """--zero1_rs through the real entrypoint on the
    8-device CPU mesh: the plan reports the psum_scatter exit, training
    completes, metrics flow. Value parity and collective counts are pinned
    elsewhere (tests/test_zero1.py, the zero1_rs_dp8 budget) — this is the
    CLI wiring proof."""
    tmp_path, data, run_path = workdir
    import run_pretraining

    out = tmp_path / "out_rs"
    argv = ["--config_file", str(run_path), "--input_dir", str(data),
            "--output_dir", str(out), "--mask_token_index", "3",
            "--dtype", "float32", "--vocab_pad_multiple", "8",
            "--zero1", "true", "--zero1_rs",
            "--coalesce_reductions", "on"]
    final_step, _ = run_pretraining.main(argv)
    assert final_step == 3
    log = (tmp_path / "out_rs" / "testlog.txt").read_text()
    assert "psum_scatter grads" in log
    assert "--zero1_rs forces --zero1_overlap" in log

    # the K-FAC arm: the rs region emits partial factor statistics, so
    # the CLI must force bucketed factor reductions rather than surface
    # the step builder's ValueError
    out2 = tmp_path / "out_rs_kfac"
    final_step, _ = run_pretraining.main(
        ["--config_file", str(run_path), "--input_dir", str(data),
         "--output_dir", str(out2), "--mask_token_index", "3",
         "--dtype", "float32", "--vocab_pad_multiple", "8",
         "--zero1", "true", "--zero1_rs", "--kfac",
         "--kfac_stats_dtype", "bf16"])
    assert final_step == 3
    log2 = (out2 / "testlog.txt").read_text()
    assert "psum_scatter grads" in log2
    assert "--zero1_rs with --kfac forces --coalesce_reductions on" in log2


def test_init_checkpoint_seeds_weights(workdir):
    """--init_checkpoint seeds pretraining from a reference torch save
    (the GPU->TPU migration path): weights load and are reported, training
    proceeds from step 0, and auto-resume still wins on rerun."""
    torch = pytest.importorskip("torch")
    from tests.test_pretrained import make_tf_vars, tf_vars_to_torch_state

    tmp_path, data, run_path = workdir
    import run_pretraining

    ckdir = tmp_path / "reference_ckpt"
    ckdir.mkdir()
    tf_vars = make_tf_vars()
    state = {f"module.{k}": torch.tensor(v)
             for k, v in tf_vars_to_torch_state(tf_vars).items()}
    torch.save({"model": state}, ckdir / "ckpt_7038.pt")
    # reference layout: bert_config.json next to the .pt (vocab 100 — the
    # loader re-pads to this run's padded 128)
    (ckdir / "bert_config.json").write_text(json.dumps(
        {"vocab_size": 100, "hidden_size": 32, "num_hidden_layers": 2,
         "num_attention_heads": 4, "intermediate_size": 64,
         "max_position_embeddings": 64, "type_vocab_size": 2,
         "hidden_act": "gelu", "hidden_dropout_prob": 0.0,
         "attention_probs_dropout_prob": 0.0}))

    out = tmp_path / "out_seeded"
    argv = ["--config_file", str(run_path), "--input_dir", str(data),
            "--output_dir", str(out), "--mask_token_index", "3",
            "--dtype", "float32", "--vocab_pad_multiple", "8",
            "--init_checkpoint", str(ckdir / "ckpt_7038.pt")]
    final_step, _ = run_pretraining.main(argv)
    assert final_step == 3
    log = (out / "testlog.txt").read_text()
    m = re.search(r"loaded (\d+) param leaves, (\d+) fresh", log)
    assert m, log
    assert int(m.group(1)) > 20  # encoder + heads came across
    assert int(m.group(2)) == 0  # pretraining model: every subtree matched

    # rerun: the existing checkpoint wins over --init_checkpoint
    run_cfg = json.loads(run_path.read_text())
    run_cfg["max_steps"] = 4
    run_path.write_text(json.dumps(run_cfg))
    final2, _ = run_pretraining.main(argv)
    assert final2 == 4
    assert "auto-resumed from step 3" in (out / "testlog.txt").read_text()


@pytest.mark.slow  # re-tiered out of tier-1's 870s wall-clock budget
def test_two_phase_handoff(workdir):
    """Phase-2 resumes phase-1 state from the same output_dir, switches to a
    different-seq dataset (sampler resets via the total_size guard instead of
    restoring a stale cursor), and its schedule restarts warmup at
    previous_phase_end_step — the reference's seq128→seq512 handoff
    (run_pretraining.py:288-299, config/bert_pretraining_phase2_config.json)."""
    tmp_path, data128, run_path = workdir
    import run_pretraining

    data512 = tmp_path / "data512"
    data512.mkdir()
    for i in range(2):
        write_shard(data512 / f"shard_{i}.hdf5", 48, seq=64, seed=10 + i)

    out = tmp_path / "out_2phase"
    base = ["--config_file", str(run_path), "--output_dir", str(out),
            "--mask_token_index", "3", "--dtype", "float32",
            "--vocab_pad_multiple", "8"]
    final1, _ = run_pretraining.main(
        base + ["--input_dir", str(data128)])
    assert final1 == 3

    with pytest.warns(UserWarning, match="total_size"):
        final2, _ = run_pretraining.main(
            base + ["--input_dir", str(data512),
                    "--previous_phase_end_step", "3", "--max_steps", "4",
                    "--learning_rate", "2e-3", "--warmup_proportion", "0.5"])
    assert final2 == 7  # global step: 3 phase-1 + 4 phase-2

    log = (out / "testlog.txt").read_text()
    assert "auto-resumed from step 3" in log
    # schedule offset: the update logged at global step 5 consumed
    # schedule(4) = phase-local step 1 of a 2-step warmup -> lr = 2e-3 / 2;
    # without the offset phase 2 would already be deep into decay
    lr_by_step = {}
    for line in log.splitlines():
        m = re.search(r"step (\d+) .*learning_rate=([0-9.e+-]+)", line)
        if m:
            lr_by_step[int(m.group(1))] = float(m.group(2))
    assert lr_by_step[5] == pytest.approx(1e-3, rel=1e-2)


@pytest.mark.slow  # re-tiered out of tier-1's 870s wall-clock budget
def test_run_pretraining_with_kfac(workdir):
    tmp_path, data, run_path = workdir
    import run_pretraining

    out = tmp_path / "out_kfac"
    argv = ["--config_file", str(run_path), "--input_dir", str(data),
            "--output_dir", str(out), "--mask_token_index", "3",
            "--dtype", "float32", "--vocab_pad_multiple", "8",
            "--kfac", "--kfac_inv_interval", "2", "--max_steps", "2",
            "--skip_checkpoint"]
    final_step, _ = run_pretraining.main(argv)
    assert final_step == 2
    log = (out / "testlog.txt").read_text()
    assert "step 2" in log


def test_run_pretraining_production_pack_smoke(workdir):
    """ONE e2e smoke for the whole round-15 collective pack:
    --mesh_config production on a dp2 x fsdp4 mesh (explicit — 'auto'
    deliberately keeps the forced-CPU harness on base) engages packing +
    ZeRO-1 overlap + fsdp gather-on-use at once, --coalesce_reductions
    buckets the norm all-reduces, the run header records the named
    config, and a short run trains end to end."""
    tmp_path, data, run_path = workdir
    import run_pretraining

    out = tmp_path / "out_prod"
    argv = ["--config_file", str(run_path), "--input_dir", str(data),
            "--output_dir", str(out), "--mask_token_index", "3",
            "--dtype", "float32", "--vocab_pad_multiple", "8",
            "--mesh", "data=2,fsdp=4",
            "--mesh_config", "production",
            "--coalesce_reductions", "on"]
    final_step, _ = run_pretraining.main(argv)
    assert final_step == 3
    log = (out / "testlog.txt").read_text()
    assert "mesh_config=production" in log
    assert "packing=on" in log and "zero1_overlap=on" in log \
        and "fsdp_overlap=on" in log
    assert "fsdp_overlap: per-leaf gather-on-use over the 4-way fsdp " \
           "axis composed with the zero1 overlap" in log
    assert "coalesce_reductions: trust-norm/global-norm all-reduces " \
           "bucketed" in log
    # training completed under the combined plan (the jsonl metric
    # stream carries the per-step records; the run block's round-15 keys
    # are what tools/replay.py rebuilds the program from)
    jsonl = (out / "testlog.jsonl").read_text()
    assert '"step": 3' in jsonl


@pytest.mark.slow  # re-tiered out of tier-1's 870s wall-clock budget
def test_run_pretraining_packing_smoke(tmp_path):
    """Satellite: `run_pretraining.py --packing` over a varied-length corpus
    on the CPU mesh — trains for a few steps, checkpoints the packer state,
    and lands the health-pack and packing-efficiency fields in the metric
    sinks (jsonl + csv)."""
    import run_pretraining

    data = tmp_path / "data"
    data.mkdir()
    for i in range(2):
        write_shard(data / f"shard_{i}.hdf5", 48, seed=i, varied=True)
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

    out = tmp_path / "out_packed"
    argv = ["--model_config_file", str(cfg_path),
            "--input_dir", str(data), "--output_dir", str(out),
            "--mask_token_index", "3", "--dtype", "float32",
            "--vocab_pad_multiple", "8", "--packing",
            "--packing_max_segments", "4", "--learning_rate", "1e-3",
            "--global_batch_size", "32", "--local_batch_size", "2",
            "--max_steps", "3", "--max_predictions_per_seq", "5",
            "--num_steps_per_checkpoint", "2", "--log_freq", "1",
            "--log_prefix", "testlog"]
    final_step, _ = run_pretraining.main(argv)
    assert final_step == 3

    log = (out / "testlog.txt").read_text()
    assert "packing on" in log
    assert "step 3" in log

    # perf records carry the packing-efficiency triple; with a
    # varied-length corpus packed rows beat the unpacked pad fraction
    perf = [json.loads(line)
            for line in (out / "testlog.jsonl").read_text().splitlines()
            if json.loads(line).get("tag") == "perf"]
    assert perf, "no perf records reached the jsonl sink"
    rec = perf[-1]
    # phase-agnostic schema contract: the pretrain perf record carries the
    # same core keys run_squad / run_ner assert on (telemetry/run.py —
    # every entry point wires through the one init_run path)
    from bert_pytorch_tpu.telemetry import PERF_RECORD_CORE_KEYS

    assert set(PERF_RECORD_CORE_KEYS) <= set(rec), rec
    for key in ("packing_efficiency", "pad_fraction",
                "real_tokens_per_sec"):
        assert key in rec, key
    assert 0.0 < rec["packing_efficiency"] <= 1.0
    assert abs(rec["packing_efficiency"] + rec["pad_fraction"] - 1.0) < 1e-5

    # health pack flows through the same sinks on the packed path
    train = [json.loads(line)
             for line in (out / "testlog.jsonl").read_text().splitlines()
             if json.loads(line).get("tag") == "train"]
    assert train and "loss_nonfinite" in train[-1]
    assert train[-1]["loss_nonfinite"] == 0
    csv_header = (out / "testlog_metrics.csv").read_text() \
        .splitlines()[0].split(",")
    assert "loss_nonfinite" in csv_header

    # resume restores the packer (pending buffer rides the checkpoint)
    final2, _ = run_pretraining.main(argv + ["--steps", "1",
                                             "--max_steps", "4"])
    assert final2 == 4
    assert "auto-resumed from step 3" in (out / "testlog.txt").read_text()


def test_cli_precedence(workdir):
    tmp_path, data, run_path = workdir
    import run_pretraining

    # CLI flag overrides run-config value (reference run_pretraining.py:152-166)
    args = run_pretraining.parse_arguments(
        ["--config_file", str(run_path), "--learning_rate", "9e-4"])
    assert args.learning_rate == 9e-4
    assert args.global_batch_size == 32  # from config
    assert args.lr_decay == "poly"       # parser default


def test_mesh_arg_parsing():
    import run_pretraining

    assert run_pretraining.parse_mesh_arg("") is None
    assert run_pretraining.parse_mesh_arg("data=4,model=2") == \
        {"data": 4, "model": 2}
