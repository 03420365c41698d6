"""The manifest and the files it names: the contract's form, every file
found by name whatever the model family, and that a cell, a configuration,
a traffic mix, a per-layer metric, a reader and a whole model family are
added as NEW files with no edit of an existing one."""

import filecmp
import json
import os
import re
import shutil
import subprocess

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import sys  # noqa: E402

sys.path.insert(0, ROOT)
from benchmark.harness import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFEST = spec.load_manifest(ROOT)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
LAYER = [m["name"] for m in MANIFEST["per_layer"]]


def test_manifest_keys_and_sizes():
    assert sorted(MANIFEST) == sorted([
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"])
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert MANIFEST["paths"] == ["benchmark", "tests/benchmark"]
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    assert {w["config"] for w in MANIFEST["workloads"]} == {
        c["name"] for c in MANIFEST["configs"]}
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in MANIFEST["end_to_end"])
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


def _names():
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[kind]:
            yield kind, entry["name"]
    for w in MANIFEST["workloads"]:
        yield "traffic", w["traffic"]
    for c in MANIFEST["configs"]:
        for key in c["reduced"]:
            yield "reduced", key


@pytest.mark.parametrize("kind,name", sorted(set(_names())))
def test_name_is_of_the_allowed_characters(kind, name):
    assert NAME.match(name), (kind, name)


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"]
                         + MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= ({"bound"} if "bound" in metric else {"layer", "moves"})
    assert set(metric) <= allowed
    for cell in metric.get("workloads", []):
        assert cell in CELLS


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    found = spec.find_cell(MANIFEST, cell, ROOT)
    assert found["family"] == found["config"].get("model_type", "bert")
    spec.load_family(found["family"], ROOT)
    traffic = found["traffic"]
    spec.load_driver(traffic["driver"], ROOT)
    assert traffic["data_shards"] == found["chips"]
    for key in ("loss_rel", "grad_gap", "delta_gap", "grad_diff"):
        assert traffic["limits"][key] > 0
    # every cell reports setup_s, one more end-to-end and one per-layer metric
    e2e = [m["name"] for m in spec.metrics_of_cell(MANIFEST, cell,
                                                   "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.metrics_of_cell(MANIFEST, cell, "per_layer")


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_configuration_file(config):
    """What holds for any family's configuration, and still catches a toy:
    the cut is stated on both sides, and it names no width."""
    cfg = spec.load_json(os.path.join(ROOT, config["file"]))
    assert config["file"].startswith("benchmark/configs/")
    assert cfg["source"] == config["source"]
    assert sorted(cfg["reduced"]) == sorted(config["reduced"])
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "num_key_value_heads",
              "num_experts_per_tok")
    assert not [k for k in config["reduced"]
                if k in widths or k.endswith(("_dim", "_rank"))]
    # a key that is cut is explained where the sizes are
    assert all(cfg["reduced"][k] for k in config["reduced"])
    assert cfg["assumed"] and cfg["layout"]
    spec.load_family(cfg.get("model_type", "bert"), ROOT)


@pytest.mark.parametrize("name", LAYER)
def test_layer_metric_file_matches_the_manifest(name):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    on_file = spec.load_layer_metric(name, ROOT)
    for key in ("layer", "unit", "better", "source", "moves"):
        assert on_file[key] == entry[key], key
    assert callable(spec.load_reader(on_file["reader"], ROOT))
    moved = next(m for m in MANIFEST["end_to_end"]
                 if m["name"] == entry["moves"])
    for cell in entry.get("workloads", CELLS):
        assert "workloads" not in moved or cell in moved["workloads"]


THIRD = "third-lm.cell"


@pytest.fixture(scope="module")
def additions(tmp_path_factory):
    """A later `model_config` PR as it has to look: a copy of the benchmark
    plus a family module under a name of its own (binding the lfm2
    reference and adapter), a configuration of that family at sizes no
    other has, a traffic mix, a cell, a per-layer metric and its reader:
    six new files and four new entries, nothing edited. The copy links the
    program (which trains its `lfm2_moe` model: the traffic's toy
    configuration says so, since the program cannot know the new name)."""
    root = str(tmp_path_factory.mktemp("additions"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("bert_pytorch_tpu", "run_pretraining.py"):
        os.symlink(os.path.join(ROOT, name), os.path.join(root, name))
    base = spec.find_cell(MANIFEST, "lfm2-ep8-clm-8k-packed", ROOT)
    traffic = json.loads(json.dumps(base["traffic"]))
    traffic["rehearse"]["config"].update(
        model_type="lfm2_moe", hidden_size=96, num_attention_heads=2,
        num_key_value_heads=1)
    for path, content in {
        "families/third_lm.py":
            "from benchmark.families.lfm2_moe import *  # noqa: F403\n",
        "configs/third-lm.json": json.dumps(dict(
            base["config"], model_type="third_lm", hidden_size=1536,
            num_attention_heads=16, num_key_value_heads=4,
            source="paper:third")),
        "traffic/third-mix.json": json.dumps(traffic),
        "layer_metrics/dummy_ms.json": json.dumps({
            "layer": "host loop and data plane", "unit": "ms",
            "better": "lower", "source": "program_span",
            "moves": "train_tokens_per_s_chip", "reader": "dummy_reader",
            "args": {"scale": 2.0}}),
        "readers/dummy_reader.py":
            "def read(ctx, scale):\n    return ctx['x'] * scale\n",
    }.items():
        with open(os.path.join(root, "benchmark", path), "w") as f:
            f.write(content)
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append({
        "name": "third-lm", "source": "paper:third",
        "file": "benchmark/configs/third-lm.json",
        "reduced": base["config_entry"]["reduced"], "why": "test"})
    manifest["workloads"].append({
        "name": THIRD, "config": "third-lm", "traffic": "third-mix",
        "chips": 1, "why": "test"})
    manifest["per_layer"].append({
        "name": "dummy_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "host loop and data plane",
        "moves": "train_tokens_per_s_chip", "workloads": [THIRD]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root, manifest


@pytest.mark.parametrize("fault,correct", [(None, True),
                                           ("noop_step", False)],
                         ids=["sound", "step-returns-state-unchanged"])
def test_additions_are_new_files_only(additions, fault, correct):
    root, manifest = additions
    found = spec.find_cell(manifest, THIRD, root)
    cfg = found["config"]
    assert found["family"] == "third_lm"
    # sizes no configuration of the benchmark has: 16 heads of 96
    assert (cfg["hidden_size"], cfg["num_attention_heads"]) == (1536, 16)
    assert spec.load_family("third_lm", root).__file__.startswith(root)
    assert callable(spec.load_driver(found["traffic"]["driver"], root))
    got = spec.read_layer_metrics(
        {**manifest, "per_layer": [m for m in manifest["per_layer"]
                                   if m["name"] == "dummy_ms"]},
        THIRD, {"x": 21.0}, root)
    assert got == {"dummy_ms": {"value": 42.0, "unit": "ms"}}
    # the new cell reports what every cell reports, under the same names
    assert {"setup_lower_s", "step_hbm_share", "attention_core_share.train",
            "device_idle_share.train"} <= {
        m["name"] for m in spec.metrics_of_cell(manifest, THIRD, "per_layer")}
    # the cells that were there do not report the new metric
    assert "dummy_ms" not in [
        m["name"] for m in spec.metrics_of_cell(
            manifest, "large-pretrain-128", "per_layer")]

    # and no file that was there differs from the repository's
    def differing(cmp):
        yield from cmp.diff_files + cmp.left_only
        for sub in cmp.subdirs.values():
            yield from differing(sub)
    assert not list(differing(filecmp.dircmp(
        os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
        ignore=["__pycache__"])))

    # the whole of a run of the new cell but the look for a chip
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", THIRD, "--seed", str(2**31 + 23), "--seconds", "1",
         "--trace", "0", "--rehearse"] + (["--fault", fault] if fault else []),
        cwd=root, env=dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="0"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = json.loads(proc.stdout.splitlines()[-1])
    assert last["correct"] is correct, proc.stdout[-4000:]
    assert "held-expert tokens" in proc.stdout      # the family's own checks


# what each family's module asks of the manifest and its files alone
FAMILY_TESTS = [
    "lfm2.py::test_the_cells_own_metrics",
    "lfm2.py::test_configuration_states_the_cut",
    "kimi_linear.py::test_the_cells_own_metrics",
    "kimi_linear.py::test_configuration_states_the_cut_and_every_width_as_"
    "published",
    "smallthinker.py::test_the_cells_own_metrics",
    "smallthinker.py::test_configuration_states_the_cut_and_every_width_as_"
    "published"]


def test_the_benchmarks_tests_hold_on_the_additions(additions):
    """The guard of benchmark/README.md's last rule under "Adding things":
    a copy of tests/benchmark/ beside the `additions` tree (so that each
    module's ROOT is that tree, with its seventh cell and its appended
    metric), and there, in a process of their own, the tests that read
    only the manifest and its files: all of test_bench_subscopes.py, this
    module without the tests of the additions themselves, and each
    family's cell-and-metrics and configuration tests. A test that counts
    or indexes the manifest, or takes its cells for the ones it knows,
    fails here before a PR that adds a cell meets it."""
    root, manifest = additions
    shutil.copytree(os.path.join(ROOT, "tests", "benchmark"),
                    os.path.join(root, "tests", "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"),
                    dirs_exist_ok=True)
    chosen = ["tests/benchmark/test_bench_subscopes.py",
              "tests/benchmark/test_bench_manifest.py"]
    chosen += ["tests/benchmark/test_bench_" + test for test in FAMILY_TESTS]
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-k", "not additions", *chosen],
        cwd=root, env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    out = proc.stdout[-6000:] + proc.stderr[-2000:]
    assert proc.returncode == 0, out
    passed = re.search(r"(\d+) passed", proc.stdout)
    # at the least a case a cell and a case a per-layer metric, the new
    # ones among them, and nothing left out
    assert passed and int(passed.group(1)) >= len(
        manifest["workloads"]) + len(manifest["per_layer"]), out
    assert "skipped" not in proc.stdout.splitlines()[-1], out


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.find_cell(MANIFEST, "no-such-cell", ROOT)
    with pytest.raises(spec.SpecError):
        spec.load_reader("no_such_reader", ROOT)
    with pytest.raises(spec.SpecError):
        spec.load_family("no_such_family", ROOT)


@pytest.mark.parametrize("peak,limit,rehearse,want", [
    (6_869_903_872, 16_909_336_064, False, 6_869_903_872),
    (0, 16_909_336_064, False, None),            # the compiler states none
    (17_670_484_992, 16_909_336_064, False, None),   # more than the chip has
    (0, 0, True, 0),                             # the CPU states none
], ids=["fits", "none-stated", "over-the-device", "rehearsal"])
def test_memory_peak_is_the_compilers_and_has_to_fit(peak, limit, rehearse,
                                                     want):
    driver = spec._load_module(
        os.path.join(ROOT, "benchmark", "drivers", "train.py"), "driver")
    mem = {"peak_memory": peak, "bytes_limit": limit,
           "runtime_peak_bytes": 1, "argument": 2, "temp": 3}
    assert driver.program_peak_bytes(mem, rehearse) == want
