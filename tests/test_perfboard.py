"""tools/perfboard.py: the cross-run perf index and regression gate.

The acceptance round-trip: a synthetic BENCH json goes through index ->
check -> regression detection; a 15% MFU regression exits nonzero naming
the metric, a within-tolerance drift exits zero; results/runs.jsonl +
RUNS.md regenerate deterministically from the checked-in artifacts; and
scripts/check_perf.sh gates the newest two MULTICHIP artifacts. All
jax-free by construction (perfboard must run on a login host / in CI)."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from tools.perfboard import (  # noqa: E402
    bench_metrics, check_artifacts, extract, finetune_metrics,
    index_records, main as pb_main, metric_direction, multichip_metrics,
    render_markdown, runlog_metrics, serve_metrics)


def _bench_artifact(path, value, mfu, rc=0):
    path.write_text(json.dumps({
        "n": 9, "rc": rc,
        "parsed": {"metric": "bert_large_mlm_seq128_train_throughput",
                   "value": value, "unit": "seq/s/chip",
                   "vs_baseline": round(value / 376.5, 4),
                   "seq512_value": value / 5.6, "seq512_mfu": mfu},
    }))
    return str(path)


# -- extraction ---------------------------------------------------------------

def test_bench_extraction_real_artifact():
    with open(os.path.join(REPO, "BENCH_r05.json")) as f:
        m = bench_metrics(json.load(f))
    assert m["seq128_seq_per_sec_per_chip"] == 546.17
    assert m["seq512_mfu"] == 0.5073


def test_bench_extraction_tolerates_null_parsed():
    # BENCH_r04.json shipped with parsed: null — index, don't crash
    assert bench_metrics({"rc": 0, "parsed": None}) == {}


def test_multichip_extraction_real_artifact():
    with open(os.path.join(REPO, "MULTICHIP_r07.json")) as f:
        m = multichip_metrics(json.load(f))
    assert m["dp.scaling_efficiency"] == 0.1448
    assert m["dp_zero1_overlap.scaling_efficiency"] == 0.2206
    assert m["zero1_overlap_step_time_ratio_vs_zero1"] == 0.5995


def test_metric_directions():
    assert metric_direction("seq512_mfu") == "higher"
    assert metric_direction("dp.scaling_efficiency") == "higher"
    assert metric_direction("data_wait_ms_median") == "lower"
    assert metric_direction("dp.step_time_ms") is None       # index-only
    assert metric_direction("zero1_step_time_ratio_vs_dp") is None
    # runlog shapes: absolute step time stays index-only under the
    # _median suffix, and run-length bookkeeping is never a perf gate
    assert metric_direction("step_time_ms_median") is None
    assert metric_direction("last_step") is None
    assert metric_direction("perf_intervals") is None
    assert metric_direction("seq_per_sec_median") == "higher"


def test_check_runlogs_faster_steps_is_not_a_regression(tmp_path):
    """A run whose median step time IMPROVED must pass the gate (it used
    to be gated higher-is-better and exit 1 on the improvement)."""

    def runlog(path, stms, n=3):
        with open(path, "w") as f:
            for i in range(n):
                f.write(json.dumps({"tag": "perf", "step": 10 * (i + 1),
                                    "step_time_ms": stms,
                                    "seq_per_sec": 6400.0 / stms}) + "\n")
        return str(path)

    base = runlog(tmp_path / "base.jsonl", 120.0)
    fast = runlog(tmp_path / "fast.jsonl", 90.0, n=2)  # fewer intervals too
    regressions, _ = check_artifacts(base, fast, tolerance=0.1)
    assert regressions == []
    # ...and a genuine slowdown is caught through the gated seq/s view
    slow = runlog(tmp_path / "slow.jsonl", 240.0)
    regressions, _ = check_artifacts(base, slow, tolerance=0.1)
    assert any("seq_per_sec_median" in r for r in regressions)
    assert not any("step_time_ms_median" in r for r in regressions)


def test_runlog_extraction(tmp_path):
    log = tmp_path / "run.jsonl"
    with open(log, "w") as f:
        f.write(json.dumps({"tag": "header", "git_sha": "abc"}) + "\n")
        for step, stms in ((10, 100.0), (20, 120.0), (30, 110.0)):
            f.write(json.dumps({
                "tag": "perf", "step": step, "step_time_ms": stms,
                "seq_per_sec": 8.0, "mfu": 0.4,
                "packing_efficiency": 0.9}) + "\n")
        f.write(json.dumps({"tag": "train", "step": 30, "loss": 2.0})
                + "\n")
    m = runlog_metrics(str(log))
    assert m["perf_intervals"] == 3
    assert m["last_step"] == 30
    assert m["step_time_ms_median"] == 110.0
    assert m["packing_efficiency"] == 0.9
    assert runlog_metrics(str(tmp_path / "missing.jsonl")) == {}


# -- the regression gate ------------------------------------------------------

def test_check_flags_15pct_mfu_regression_and_names_it(tmp_path):
    base = _bench_artifact(tmp_path / "baseline.json", 500.0, 0.50)
    cur = _bench_artifact(tmp_path / "current.json", 495.0, 0.425)
    regressions, _ = check_artifacts(base, cur, tolerance=0.1)
    assert len(regressions) == 1
    assert "seq512_mfu" in regressions[0]
    assert "0.425" in regressions[0]
    # CLI exit code 1, naming the metric on stdout
    rc = pb_main(["--check", base, cur, "--tolerance", "0.1"])
    assert rc == 1


def test_check_passes_within_tolerance(tmp_path):
    base = _bench_artifact(tmp_path / "baseline.json", 500.0, 0.50)
    cur = _bench_artifact(tmp_path / "current.json", 480.0, 0.48)  # -4%
    regressions, notes = check_artifacts(base, cur, tolerance=0.1)
    assert regressions == []
    assert any("seq512_mfu" in n for n in notes)
    assert pb_main(["--check", base, cur, "--tolerance", "0.1"]) == 0


def test_check_improvement_never_fails(tmp_path):
    base = _bench_artifact(tmp_path / "baseline.json", 500.0, 0.50)
    cur = _bench_artifact(tmp_path / "current.json", 900.0, 0.95)
    regressions, _ = check_artifacts(base, cur, tolerance=0.1)
    assert regressions == []


def test_check_missing_metric_notes_but_passes(tmp_path):
    base = _bench_artifact(tmp_path / "baseline.json", 500.0, 0.50)
    cur = tmp_path / "current.json"
    cur.write_text(json.dumps({"rc": 0, "parsed": {"value": 505.0}}))
    regressions, notes = check_artifacts(base, str(cur), tolerance=0.1)
    assert regressions == []
    assert any(n.startswith("MISSING") and "seq512_mfu" in n
               for n in notes)


def test_check_refuses_cross_kind_and_empty(tmp_path):
    bench = _bench_artifact(tmp_path / "b.json", 500.0, 0.5)
    mc = tmp_path / "MULTICHIP_x.json"
    mc.write_text(json.dumps({"variants": {
        "dp": {"scaling_efficiency": 0.2}}}))
    with pytest.raises(SystemExit, match="kinds differ"):
        check_artifacts(bench, str(mc), 0.1)
    empty = tmp_path / "empty.json"
    empty.write_text("{}")
    with pytest.raises(SystemExit, match="no comparable"):
        check_artifacts(str(empty), bench, 0.1)


def test_check_multichip_variant_regression(tmp_path):
    def mc(path, eff):
        path.write_text(json.dumps({"variants": {
            "dp": {"scaling_efficiency": eff, "seqs_per_sec": eff * 200,
                   "step_time_ms": 100.0 / eff}}}))
        return str(path)

    base = mc(tmp_path / "MULTICHIP_a.json", 0.20)
    cur = mc(tmp_path / "MULTICHIP_b.json", 0.12)
    regressions, _ = check_artifacts(base, cur, tolerance=0.25)
    names = "\n".join(regressions)
    assert "dp.scaling_efficiency" in names
    assert "dp.seqs_per_sec" in names
    assert "step_time_ms" not in names  # index-only, never gated


# -- the index ----------------------------------------------------------------

def test_index_regenerates_deterministically(tmp_path):
    out1, md1 = tmp_path / "runs1.jsonl", tmp_path / "RUNS1.md"
    out2, md2 = tmp_path / "runs2.jsonl", tmp_path / "RUNS2.md"
    assert pb_main(["--root", REPO, "--out", str(out1),
                    "--md", str(md1)]) == 0
    assert pb_main(["--root", REPO, "--out", str(out2),
                    "--md", str(md2)]) == 0
    assert out1.read_bytes() == out2.read_bytes()
    assert md1.read_bytes() == md2.read_bytes()
    # ...and the checked-in board matches what the checked-in artifacts
    # produce (regenerate via `python tools/perfboard.py` after adding a
    # BENCH/MULTICHIP artifact)
    assert out1.read_bytes() == (
        open(os.path.join(REPO, "results", "runs.jsonl"), "rb").read())
    assert md1.read_bytes() == (
        open(os.path.join(REPO, "RUNS.md"), "rb").read())


def test_index_contents_cover_all_rounds(tmp_path):
    records = index_records(REPO)
    bench = [r for r in records if r["kind"] == "bench"]
    mc = [r for r in records if r["kind"] == "multichip"]
    assert [r["round"] for r in bench] == [1, 2, 3, 4, 5, 6]
    assert [r["round"] for r in mc] == [2, 3, 4, 5, 6, 7, 8, 9]
    r07 = next(r for r in mc if r["round"] == 7)
    assert r07["measured"] and r07["ok"]
    assert r07["metrics"]["dp_zero1_overlap.scaling_efficiency"] == 0.2206
    # round 16: the streaming input-plane pair (scripts/input_bench.py
    # --stream) rides the bench board — tokens/s higher-better, the paced
    # starvation fraction lower-better via the data_wait marker
    r06 = next(r for r in bench if r["round"] == 6)
    assert r06["measured"] and r06["ok"]
    assert r06["metrics"]["stream.tokens_per_sec"] > 0
    assert 0.0 <= r06["metrics"]["stream.data_wait_fraction"] <= 1.0
    from tools.perfboard import metric_direction

    assert metric_direction("stream.tokens_per_sec") == "higher"
    assert metric_direction("stream.data_wait_fraction") == "lower"
    # failed artifacts indexed honestly, not dropped (a dryrun-era record
    # of a run that died: rc != 0, no variants)
    (tmp_path / "MULTICHIP_r01.json").write_text(json.dumps(
        {"n_devices": 8, "rc": 1, "ok": False, "skipped": False,
         "tail": "RuntimeError: 1 devices visible, need 8"}))
    (r01,) = index_records(str(tmp_path))
    assert r01["round"] == 1 and r01["kind"] == "multichip"
    assert not r01["ok"] and not r01["measured"]


def test_finetune_extraction_real_artifact_and_gate_directions():
    """FINETUNE_r01.json (run_finetune.py --perf_artifact across all
    five registered tasks) indexes with per-task real_tokens_per_sec /
    pad_fraction, direction-aware: throughput higher-better, pad
    fraction lower-better, absolute step time index-only."""
    kind, metrics, raw = extract(os.path.join(REPO, "FINETUNE_r01.json"))
    assert kind == "finetune"
    for task in ("squad", "ner", "classify", "choice", "embed"):
        assert metrics[f"{task}.real_tokens_per_sec"] > 0, task
        assert 0.0 <= metrics[f"{task}.pad_fraction"] < 1.0, task
    assert metric_direction("classify.real_tokens_per_sec") == "higher"
    assert metric_direction("classify.pad_fraction") == "lower"
    assert metric_direction("classify.step_time_ms") is None
    # regression gate catches a pad-fraction blowup on the same kind
    worse = {"kind": "finetune",
             "tasks": {t: dict(raw["tasks"][t]) for t in raw["tasks"]}}
    worse["tasks"]["classify"]["pad_fraction"] = min(
        0.99, raw["tasks"]["classify"]["pad_fraction"] * 2 + 0.1)
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        cur = os.path.join(d, "FINETUNE_r02.json")
        with open(cur, "w") as f:
            json.dump(worse, f)
        regressions, _notes = check_artifacts(
            os.path.join(REPO, "FINETUNE_r01.json"), cur, 0.1)
    assert any("classify.pad_fraction" in r for r in regressions)
    # the table renders a finetune section
    md = render_markdown(index_records(REPO))
    assert "## Finetune" in md and "classify" in md
    assert finetune_metrics({"tasks": {"x": {"mfu": None}}}) == {}


def test_index_tolerates_artifact_without_round_suffix(tmp_path):
    """A BENCH_baseline.json (no _rN suffix) must index and render under
    its filename, not crash the whole board on round=None."""
    root = tmp_path / "root"
    root.mkdir()
    _bench_artifact(root / "BENCH_baseline.json", 400.0, 0.40)
    _bench_artifact(root / "BENCH_r01.json", 500.0, 0.50)
    out, md = tmp_path / "runs.jsonl", tmp_path / "RUNS.md"
    assert pb_main(["--root", str(root), "--out", str(out),
                    "--md", str(md)]) == 0
    text = md.read_text()
    assert "BENCH_baseline.json" in text and "r01" in text
    records = [json.loads(l) for l in out.read_text().splitlines()]
    assert {r["artifact"]: r["round"] for r in records} == {
        "BENCH_baseline.json": None, "BENCH_r01.json": 1}


def test_markdown_renders_runlog_section(tmp_path):
    log = tmp_path / "phase1.jsonl"
    with open(log, "w") as f:
        f.write(json.dumps({"tag": "perf", "step": 4,
                            "step_time_ms": 50.0, "mfu": 0.3}) + "\n")
    records = index_records(REPO, runs=[str(log)])
    md = render_markdown(records)
    assert "## Run logs" in md
    assert "phase1.jsonl" in md


def test_serve_metrics_gate_restricts_latency_to_sustained_sampled_rates():
    """The gate's view (for_check=True) of a SERVE artifact drops latency
    percentiles past the saturation knee (open-loop overload measures
    divergent queueing, not the binary) and where the 2xx sample count
    can't support the order statistic (p95 < 100, p99 < 200 samples).
    Throughput/cost keys stay gated at every rate, and the indexing view
    (default) keeps everything."""
    rec = {"p50_ms": 10.0, "p95_ms": 20.0, "p99_ms": 30.0,
           "req_per_sec": 9.0, "batch_occupancy": 0.5,
           "cost_per_1k_tokens": 1e-4}
    doc = {"kind": "serve", "modes": {"m": {
        "saturation": {"at_rate": 20.0, "req_per_sec": 19.0},
        "rates": {
            "10": dict(rec, n_2xx=300),      # sustained, well sampled
            "20": dict(rec, n_2xx=150),      # at the knee, p99-starved
            "40": dict(rec, n_2xx=5000),     # past the knee: overloaded
        }}}}
    idx = serve_metrics(doc)
    gate = serve_metrics(doc, for_check=True)
    # sustained + >=200 samples: all three percentiles survive the gate
    for k in ("p50_ms", "p95_ms", "p99_ms"):
        assert f"m.r10.{k}" in gate
    # 150 samples clears the p95 floor (100) but not the p99 floor (200)
    assert "m.r20.p95_ms" in gate and "m.r20.p99_ms" not in gate
    assert "m.r20.p50_ms" in gate
    # past-knee percentiles are never gated, however well sampled
    for k in ("p50_ms", "p95_ms", "p99_ms"):
        assert f"m.r40.{k}" not in gate
    # throughput, occupancy and cost gate at every rate incl. overload
    for r in ("10", "20", "40"):
        for k in ("req_per_sec", "batch_occupancy", "cost_per_1k_tokens"):
            assert f"m.r{r}.{k}" in gate
    # the knee itself stays gated — a genuine slowdown still trips it
    assert "m.saturation.req_per_sec" in gate
    # indexing keeps every per-rate key the artifact carries
    for r in ("10", "20", "40"):
        for k in rec:
            assert f"m.r{r}.{k}" in idx
    assert set(idx) >= set(gate)


def test_check_artifacts_ignores_overload_latency_but_gates_knee(tmp_path):
    """End-to-end through check_artifacts: a 4x past-knee p99 swing (the
    measured run-to-run noise of the CPU harness) does not flag, while a
    saturation-throughput drop beyond tolerance does."""
    def art(p99_overload, knee_rps):
        return {"kind": "serve", "modes": {"m": {
            "saturation": {"at_rate": 20.0, "req_per_sec": knee_rps},
            "rates": {
                "10": {"p99_ms": 25.0, "req_per_sec": 9.0, "n_2xx": 300},
                "40": {"p99_ms": p99_overload, "req_per_sec": 18.0,
                       "n_2xx": 300},
            }}}}
    base = tmp_path / "SERVE_r01.json"
    cur = tmp_path / "SERVE_r02.json"
    base.write_text(json.dumps(art(200.0, 19.0)))
    cur.write_text(json.dumps(art(800.0, 19.0)))
    regressions, _ = check_artifacts(str(base), str(cur), 0.6)
    assert regressions == []
    cur.write_text(json.dumps(art(200.0, 5.0)))
    regressions, _ = check_artifacts(str(base), str(cur), 0.6)
    assert any("saturation.req_per_sec" in r for r in regressions)


# -- the shell gate -----------------------------------------------------------

def test_check_perf_sh_gates_newest_two_multichip():
    """scripts/check_perf.sh exits 0 on the checked-in artifact pair (the
    r08->r09 wall-clock/efficiency noise is documented and inside the
    CPU-harness tolerance — incl. the compounded single-vs-sweep drift
    the round-15 default tolerance is sized to) and nonzero when handed
    a strict tolerance that the known cross-session noise must trip."""
    script = os.path.join(REPO, "scripts", "check_perf.sh")
    r = subprocess.run(["bash", script], capture_output=True, text=True,
                       cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "MULTICHIP_r08.json -> MULTICHIP_r09.json" in r.stdout
    r_strict = subprocess.run(["bash", script, "0.05"],
                              capture_output=True, text=True, cwd=REPO)
    assert r_strict.returncode == 1, r_strict.stdout + r_strict.stderr
    assert "REGRESSION" in r_strict.stdout
