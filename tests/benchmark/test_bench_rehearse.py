"""The harness end to end on the CPU at toy sizes (`--rehearse`): the whole
of a run but the look for a chip. A sound run comes out correct; with the
timed path broken underneath (a step that returns its state unchanged) it
does not; without a chip and without --rehearse there is no result."""

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


@pytest.mark.parametrize("cell,fault,correct", [
    ("large-pretrain-128", None, True),
    ("large-pretrain-128", "noop_step", False),
    ("large-pretrain-512-packed", None, True),
], ids=["sound-128", "step-returns-state-unchanged", "sound-512-packed"])
def test_rehearsed_run(cell, fault, correct):
    args = ["--workload", cell, "--seed", str(2**31 + 17), "--seconds", "1",
            "--trace", "0", "--rehearse"]
    if fault:
        args += ["--fault", fault]
    proc, last = _run(args)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert sorted(last) == ["attempted", "correct", "device", "failed",
                            "metrics"]
    assert last["device"]["platform"] == "cpu"       # and says so
    assert last["metrics"] == {}                     # no CPU number is
    assert last["attempted"] >= 1 and last["failed"] == 0    # a metric
    assert last["correct"] is correct, proc.stdout[-3000:]
    # every number compared is printed beside its limit
    compared = [ln for ln in proc.stdout.splitlines() if "correct?" in ln]
    assert len(compared) >= 6 and all("limit" in ln for ln in compared)
    if fault:
        assert any("NOT OK" in ln and "gradient" in ln for ln in compared)
        assert any("NOT OK" in ln and "change" in ln for ln in compared)


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
