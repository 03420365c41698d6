"""The multichip dryrun's compile-cache hygiene: cached XLA executables may
only ever come from runs that passed the zero-reshard gate, because the
"Involuntary full rematerialization" warning fires at compile time and a
warm cache hit skips the compile (and the warning) entirely.

These tests drive dryrun_multichip's parent branch with a monkeypatched
child so no real compilation happens; the real child path is covered by the
standalone dryrun."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as graft


class _FakeProc:
    def __init__(self, rc=0, stdout="", stderr=""):
        self.returncode = rc
        self.stdout = stdout
        self.stderr = stderr


@pytest.fixture
def cachedir(tmp_path, monkeypatch):
    """Point the dryrun at a scratch repo dir with a pre-populated private
    cache, on a box with no accelerator and no cache given from outside."""
    here = tmp_path / "repo"
    here.mkdir()
    monkeypatch.setattr(graft, "__file__", str(here / "__graft_entry__.py"))
    cache = Path(graft._dryrun_cache_dir(str(here)))
    cache.mkdir(parents=True)
    (cache / "jit_entry-cache").write_text("fake executable")
    monkeypatch.setattr(graft, "accelerator_count", lambda: 0)
    monkeypatch.delenv(graft._CHILD_MARKER, raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(graft, "_assert_reshard_gate_alive", lambda: None)
    return cache


def _run(monkeypatch, rc=0, stderr=""):
    monkeypatch.setattr(
        subprocess, "run",
        lambda *a, **kw: _FakeProc(rc=rc, stderr=stderr))
    graft.dryrun_multichip(8)


def test_pass_keeps_cache_and_clears_marker(cachedir, monkeypatch):
    _run(monkeypatch, rc=0)
    assert (cachedir / "jit_entry-cache").exists()
    assert not os.path.exists(str(cachedir) + ".dirty")


def test_child_failure_wipes_cache(cachedir, monkeypatch):
    with pytest.raises(RuntimeError, match="child failed"):
        _run(monkeypatch, rc=1)
    assert not cachedir.exists()
    assert not os.path.exists(str(cachedir) + ".dirty")


def test_reshard_warning_wipes_cache(cachedir, monkeypatch):
    with pytest.raises(RuntimeError, match="resharding warnings"):
        _run(monkeypatch, rc=0,
             stderr=f"blah {graft._RESHARD_WARNING} of op %foo\n")
    assert not cachedir.exists()


def test_stale_dirty_marker_wipes_at_launch(cachedir, monkeypatch):
    """A previous run that died before its gate verdict (Ctrl-C, OOM-kill)
    leaves the marker; the next run must not trust the cache."""
    with open(str(cachedir) + ".dirty", "w"):
        pass
    seen = {}

    def fake_run(*a, **kw):
        # by child-launch time the tainted cache must already be gone
        # (recreated empty) — the fake "executable" must not survive
        seen["entry_gone"] = not (cachedir / "jit_entry-cache").exists()
        return _FakeProc(rc=0)

    monkeypatch.setattr(subprocess, "run", fake_run)
    graft.dryrun_multichip(8)
    assert seen["entry_gone"]
    assert not os.path.exists(str(cachedir) + ".dirty")


def test_timeout_wipes_cache(cachedir, monkeypatch):
    def fake_run(*a, **kw):
        raise subprocess.TimeoutExpired(cmd="x", timeout=1800)

    monkeypatch.setattr(subprocess, "run", fake_run)
    with pytest.raises(RuntimeError, match="timed out"):
        graft.dryrun_multichip(8)
    assert not cachedir.exists()


def test_passing_gate_starts_one_child_and_returns(cachedir, monkeypatch):
    """The dryrun is the gate and nothing else: a passing gate has started
    exactly one child (the gated step) and returns with the cache kept."""
    cmds = []

    def fake_run(cmd, *a, **kw):
        cmds.append(cmd)
        return _FakeProc(rc=0)

    monkeypatch.setattr(subprocess, "run", fake_run)
    assert graft.dryrun_multichip(8) is None
    assert len(cmds) == 1 and "-c" in cmds[0]
    assert "g.dryrun_multichip(8)" in cmds[0][-1]
    assert (cachedir / "jit_entry-cache").exists()
    assert not os.path.exists(str(cachedir) + ".dirty")


@pytest.mark.parametrize("rc", [0, 1])
def test_inherited_cache_dir_is_neither_set_nor_wiped(cachedir, monkeypatch,
                                                      tmp_path, rc):
    """A cache directory given from outside is someone else's: the gate
    child runs with the cache off (warm entries would skip the compiles
    that emit the warning), no directory is set over it, and no failure
    path wipes it — nor the private one, which this run never used."""
    outside = tmp_path / "outside_cache"
    outside.mkdir()
    (outside / "entry").write_text("theirs")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(outside))
    seen = {}

    def fake_run(cmd, *a, env=None, **kw):
        seen.update(env)
        return _FakeProc(rc=rc)

    monkeypatch.setattr(subprocess, "run", fake_run)
    if rc:
        with pytest.raises(RuntimeError, match="child failed"):
            graft.dryrun_multichip(8)
    else:
        graft.dryrun_multichip(8)
    assert seen["JAX_COMPILATION_CACHE_DIR"] == str(outside)
    assert seen["JAX_ENABLE_COMPILATION_CACHE"] == "0"
    assert (outside / "entry").read_text() == "theirs"
    assert (cachedir / "jit_entry-cache").exists()
    assert not os.path.exists(str(cachedir) + ".dirty")


def test_failed_device_probe_is_an_error(monkeypatch):
    """A probe that cannot ask JAX is a failure, never "0 chips"."""
    monkeypatch.setattr(graft, "_probe_cache", {})
    monkeypatch.setattr(
        subprocess, "run",
        lambda *a, **kw: _FakeProc(rc=1, stderr="libtpu: no such device"))
    with pytest.raises(RuntimeError, match="device probe failed"):
        graft.accelerator_count()


@pytest.mark.parametrize("stdout,want", [
    ("BPT_PROBE tpu 4\n", 4),
    ("noise\nBPT_PROBE cpu 8\n", 0),   # forced host devices are not chips
])
def test_device_probe_counts_accelerators_only(monkeypatch, stdout, want):
    monkeypatch.setattr(graft, "_probe_cache", {})
    monkeypatch.setattr(subprocess, "run",
                        lambda *a, **kw: _FakeProc(rc=0, stdout=stdout))
    assert graft.accelerator_count() == want


def test_compile_cache_helper_honours_inherited_dir(monkeypatch, tmp_path):
    """bert_pytorch_tpu.compile_cache: JAX_COMPILATION_CACHE_DIR set ->
    the directory is left to JAX and none is set in code."""
    import jax

    from bert_pytorch_tpu import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "given"))
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    assert compile_cache.enable_compile_cache() == str(tmp_path / "given")
    assert updates == []


def test_compile_cache_helper_defaults_to_fixed_checkout_path(monkeypatch):
    """...and without it, the fixed <checkout>/.jax_cache — the same path on
    every call and in every process (no temp name, pid or time)."""
    import jax

    from bert_pytorch_tpu import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)] * 2
