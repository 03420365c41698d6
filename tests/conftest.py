"""Test harness: fake an 8-device TPU-like mesh on CPU.

The reference tested distributed behavior by spinning up a gloo process group
on CPU (src/dataset.py:455); the JAX-native analogue is a single process with
XLA's host platform forced to expose 8 devices, letting every sharding /
collective path compile and run without hardware. Both settings are
environment variables JAX reads when it is imported, so they are set here
before the import and inherited by every subprocess a test starts.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_backend_optimization_level" not in flags:
    # CPU-backend compile is the tier-1 suite's dominant cost and level 0
    # compiles ~3x faster (the test_resilience subprocess sessions have
    # always run with it). Every claim the suite pins — parity, bit-
    # identity, collective counts, donation, budgets — compares programs
    # compiled under the SAME flags, so the level only moves wall-clock.
    # Export XLA_FLAGS with an explicit level to override.
    flags = (flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = flags
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")
# hermetic runs: the entry points turn on the persistent compilation cache
# (bert_pytorch_tpu/compile_cache.py); the suite keeps it off through JAX's
# own switch so no test reads what another run left behind (and the SIGKILL
# drills cannot tear an entry a later session would load)
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")

import jax  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (multi-process cluster spin-up)")


@pytest.fixture(scope="session")
def n_devices():
    return jax.device_count()


@pytest.fixture
def force_flash_path(monkeypatch):
    """Steer the flash kernels onto a layout / backward variant their shape
    gates would not pick at test sizes. The program chooses by shape alone
    (flash_attention._use_native, _FUSED_BWD_MAX_PANEL), so the test patches
    the gates' inputs: returns force(layout="native"|"bh",
    bwd="fused"|"split"); the split kernels exist in the bh layout only."""
    import importlib

    fa = importlib.import_module(
        "bert_pytorch_tpu.ops.pallas.flash_attention")
    heads_per_prog, max_panel = fa._heads_per_prog, fa._FUSED_BWD_MAX_PANEL

    def force(layout="native", bwd="fused"):
        assert (layout, bwd) != ("native", "split")
        monkeypatch.setattr(
            fa, "_heads_per_prog",
            heads_per_prog if layout == "native" else lambda h, d: 0)
        monkeypatch.setattr(fa, "_FUSED_BWD_MAX_PANEL",
                            max_panel if bwd == "fused" else 0)

    return force


@pytest.fixture(scope="session")
def serving_fixture(tmp_path_factory):
    """One shared serving-fixture build (a checkpoint per registered task
    + serve_args.txt) for every module that starts a live server — the
    build costs ~10s, so test_serving and test_slo must not each pay it.
    Servers only read the checkpoints, so sharing is safe. Returns
    (make_serving_fixture module, fixture root, paths dict)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "make_serving_fixture",
        os.path.join(os.path.dirname(os.path.dirname(__file__)),
                     "scripts", "make_serving_fixture.py"))
    msf = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(msf)
    root = tmp_path_factory.mktemp("serving_fixture")
    paths = msf.build(str(root), max_pos=64)
    return msf, str(root), paths


@pytest.fixture(scope="session")
def assert_packing_invariant():
    """What packing may and may not change in a served answer.

    Followed through the serving fixture layer by layer (embeddings, qkv,
    scores, the masked scores, exp, the softmax denominators and the
    probabilities are bit-equal for a request served alone at the start of
    a row and packed behind another request): the FIRST operation whose
    output differs is the attention core's `probs @ V`
    (`einsum("bhqk,bkhd->bqhd")`, ops/attention._xla_attention), a float32
    contraction over the row's keys. Cross-segment probabilities are exact
    zeros, so both sums hold the same n nonzero products, but XLA:CPU's dot
    accumulates the key axis in vector lanes: which products share a lane,
    and so the order they are added in, goes by the key's index in the row.
    A request at keys 7..15 is summed in another association than at keys
    0..8. Everything downstream is per token and carries the difference
    on.

    Two associations of a float32 sum of n terms differ by at most
    2 (n - 1) u sum|terms| to first order, u = 2**-24 (Higham, Accuracy
    and Stability of Numerical Algorithms, section 4.2); there is one such
    contraction a layer, and a pooled head (classify, choice, embed) adds a
    sum over its segment's tokens that is ordered the same way: n_sums in
    all. So the property is: the decoded answer (the
    argmax over an output's last axis: the span's start and end, a token's
    label, a class, a choice) is the same, and every output is equal to
    within n_sums * 2 * (n_keys - 1) * u at the scale of its largest
    element. This CPU reads 1-2 ulp (bound / 30)."""
    import numpy as np

    def check(single, packed, n_keys, n_sums, ctx):
        single = single if isinstance(single, tuple) else (single,)
        packed = packed if isinstance(packed, tuple) else (packed,)
        assert len(single) == len(packed), ctx
        for x, y in zip(single, packed):
            x, y = np.asarray(x), np.asarray(y)
            assert x.shape == y.shape, ctx
            assert np.array_equal(np.argmax(x, -1), np.argmax(y, -1)), ctx
            bound = (n_sums * 2 * (n_keys - 1) * 2.0 ** -24
                     * np.abs(x).max())
            assert np.abs(x - y).max() <= bound, (
                f"{ctx}: differs by {np.abs(x - y).max():.3g}, the "
                f"contraction's order allows {bound:.3g}")

    return check
