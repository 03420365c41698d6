"""chip_smoke.py's control flow, rehearsed on the CPU at a tiny size (its
own --rehearse option, kernels in interpret mode): the shape of the last
stdout line, the non-zero exit of a run that found no TPU, and of a run in
which a phase fails. What the phases prove about the CHIP only a chip run
shows; this keeps the script from rotting between chip runs."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _smoke(tmp_path, *argv, timeout=900):
    """Run chip_smoke.py as the driver does; (exit code, stdout lines). The
    suite keeps the persistent compile cache off (conftest): turn it back
    on for this run, in a directory of its own, caching even the toy's
    sub-second compiles — the warm-session check needs hits to count."""
    env = dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        env=env, capture_output=True, text=True, timeout=timeout)
    return proc.returncode, proc.stdout.strip().splitlines()


def _assert_last_line(lines, ok, count):
    """Exactly the contract's keys, the device as JAX reported it."""
    assert json.loads(lines[-1]) == {
        "ok": ok, "device": {"platform": "cpu", "kind": "cpu",
                             "count": count}}, "\n".join(lines[-30:])


def test_rehearsed_train_phase_and_last_line(tmp_path):
    """One rehearsed phase through the real entry point: six steps, a
    checkpoint, a resumed session against the warm compile cache."""
    rc, lines = _smoke(tmp_path, "--rehearse", "--phases", "train-128")
    assert rc == 0, "\n".join(lines[-40:])
    _assert_last_line(lines, True, 1)
    text = "\n".join(lines)
    assert "phase train-128: ok" in text
    assert "checkpoint at step 6" in text
    assert "compile cache: cold session" in text


def test_no_tpu_is_a_failure_without_the_rehearsal_switch(tmp_path):
    """JAX_PLATFORMS=cpu hides the chip: non-zero exit, "ok": false, and no
    phase is attempted."""
    rc, lines = _smoke(tmp_path)
    assert rc != 0
    _assert_last_line(lines, False, 8)     # conftest's eight host devices
    assert any("no TPU" in ln for ln in lines)
    assert not any("--- phase" in ln for ln in lines)


def _boom(run):
    raise chip_smoke.PhaseFailed("boom: the check did not hold")


def _bug(run):
    raise KeyError("a bug in a phase")


@pytest.mark.parametrize("phase", [_boom, _bug])
def test_failing_phase_exits_nonzero(phase, monkeypatch, capsys):
    """No catch lets a failed phase end the run 0, and later phases do not
    run once one failed."""
    ran = []
    monkeypatch.setattr(chip_smoke, "ONE_CHIP_PHASES", {
        "first": ran.append, "broken": phase, "after": ran.append})
    monkeypatch.setattr(
        chip_smoke, "probe_device",
        lambda run, n: {"platform": "cpu", "kind": "cpu", "count": 1})
    assert chip_smoke.main(["--rehearse"]) == 1
    assert len(ran) == 1
    lines = capsys.readouterr().out.strip().splitlines()
    _assert_last_line(lines, False, 1)
    assert any("FAILED" in ln for ln in lines)


def test_child_exit_code_fails_the_phase(monkeypatch):
    run = chip_smoke.Run(chip_smoke.argparse.Namespace(rehearse=True, seed=0))
    try:
        assert "fine" in run.run(
            "good", [sys.executable, "-c", "print('fine')"], run.env)
        with pytest.raises(chip_smoke.PhaseFailed, match="exit code 3"):
            run.run("bad", [sys.executable, "-c",
                            "print('said this'); raise SystemExit(3)"],
                    run.env)
        # a server-like child still running at the end is stopped
        sleeper = run.start("sleeper", [sys.executable, "-c",
                                        "import time; time.sleep(600)"],
                            run.env)
    finally:
        run.close()
    assert sleeper.poll() is not None
    assert not os.path.exists(run.work)


def test_synthesized_shards_follow_the_reference_schema(tmp_path):
    import h5py

    chip_smoke.write_shards(str(tmp_path), 16, 128, 30522, seed=3)
    with h5py.File(tmp_path / "shard_1.hdf5") as f:
        ids = f["input_ids"][:]
        specials = f["special_token_positions"][:]
        assert ids.shape == (8, 128) and specials.shape == (8, 3)
        assert f["next_sentence_labels"].shape == (8,)
    for row, (cls, sep1, sep2) in zip(ids, specials):
        assert row[cls] == chip_smoke.CLS
        assert row[sep1] == row[sep2] == chip_smoke.SEP
        assert (row[sep2 + 1:] == 0).all() and (row[:sep2 + 1] != 0).all()


@pytest.mark.slow
def test_rehearsal_all_one_chip_phases(tmp_path):
    rc, lines = _smoke(tmp_path, "--rehearse")
    assert rc == 0, "\n".join(lines[-40:])
    _assert_last_line(lines, True, 1)
    for phase in chip_smoke.ONE_CHIP_PHASES:
        assert any(f"phase {phase}: ok" in ln for ln in lines)


@pytest.mark.slow
def test_rehearsal_four_chips(tmp_path):
    """--chips 4 runs the data-parallel arm and its one-chip comparison and
    no other phase; the last line's count is 4."""
    rc, lines = _smoke(tmp_path, "--rehearse", "--chips", "4")
    assert rc == 0, "\n".join(lines[-40:])
    _assert_last_line(lines, True, 4)
    assert [ln for ln in lines if "--- phase" in ln] == [
        "[chip_smoke] --- phase dp4-vs-dp1 ---"]
