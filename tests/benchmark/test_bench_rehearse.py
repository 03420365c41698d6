"""The harness end to end on the CPU at toy sizes (`--rehearse`): the whole
of a run but the look for a chip, in cells of every model family. A sound
run comes out correct; with the timed path broken underneath (a step that
returns its state unchanged; in the lfm2 cell also a program whose
selection bias is zero where the reference's is not) it does not; without a
chip and without --rehearse there is no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "benchmark", "run.py")


def _run(args, root=ROOT, timeout=600):
    run = os.path.join(root, "benchmark", "run.py")
    env = dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="0")
    proc = subprocess.run([sys.executable, run] + args, cwd=root, env=env,
                          capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    last = None
    if lines and lines[-1].startswith("{"):
        last = json.loads(lines[-1])
    return proc, last


LFM2 = "lfm2-ep8-clm-8k-packed"


@pytest.mark.parametrize("cell,fault,correct", [
    ("large-pretrain-128", None, True),
    ("large-pretrain-128", "noop_step", False),
    ("large-pretrain-512-packed", None, True),
    (LFM2, None, True),
    (LFM2, "noop_step", False),
    (LFM2, "zero_bias", False),
], ids=["sound-128", "step-returns-state-unchanged", "sound-512-packed",
        "lfm2-sound", "lfm2-step-returns-state-unchanged",
        "lfm2-experts-selected-by-score-alone"])
def test_rehearsed_run(cell, fault, correct):
    args = ["--workload", cell, "--seed", str(2**31 + 17), "--seconds", "1",
            "--trace", "0", "--rehearse"]
    if fault:
        args += ["--fault", fault]
    proc, last = _run(args)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert last["device"]["platform"] == "cpu"       # and says so
    assert last["metrics"] == {}                     # no CPU number is
    assert last["attempted"] >= 1 and last["failed"] == 0    # a metric
    assert last["correct"] is correct, proc.stdout[-4000:]
    # every number compared is printed beside its limit: as it is checked,
    # as the last lines of standard error, and last in the result's line
    compared = [ln for ln in proc.stdout.splitlines() if "correct?" in ln]
    assert len(compared) >= 6 and all("limit" in ln for ln in compared)
    assert len(last["compared"]) == len(compared)
    tail = proc.stderr.splitlines()[-len(compared):]
    assert [ln.split()[2].rstrip(":") for ln in tail] == list(
        last["compared"])
    assert all("limit" in row for row in last["compared"].values())
    assert last["correct"] is all(row["ok"]
                                  for row in last["compared"].values())
    if fault:
        assert any("NOT OK" in ln and "gradient" in ln for ln in compared)
        assert not last["compared"]["grad_gap"]["ok"]
    if fault == "noop_step":
        assert any("NOT OK" in ln and "change" in ln for ln in compared)
    if cell == LFM2:
        # the family's own checks, on top of the driver's
        assert len(compared) >= 12
        assert any("held-expert tokens" in ln for ln in compared)
        assert last["compared"]["dropped_pairs"] == {
            "value": 0, "limit": 0, "ok": True}
    if fault == "zero_bias":
        # the reference selects by score + b: other experts, other counts
        gaps = [row["value"] for name, row in last["compared"].items()
                if name.startswith("experts_l1_")]
        assert min(gaps) > 100, gaps


def test_no_chip_no_result():
    proc, last = _run(["--workload", "large-pretrain-128", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0 and last is None
    assert "need 1 tpu device" in proc.stdout


def test_fails_where_only_the_benchmark_is(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"))
    proc, last = _run(["--workload", "large-pretrain-128", "--seed", "1",
                       "--seconds", "1", "--trace", "0", "--rehearse"],
                      root=root)
    assert proc.returncode != 0 and last is None


def test_unknown_workload_is_an_error():
    proc, last = _run(["--workload", "no-such-cell", "--seed", "1",
                       "--seconds", "1"])
    assert proc.returncode != 0 and last is None
