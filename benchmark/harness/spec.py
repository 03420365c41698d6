"""Finds a cell's files by the names in BENCHMARK.json.

A cell (`workloads` entry) names a configuration and a traffic mix; the
configuration's entry names its file, whose `model_type` names the model
family (`benchmark/families/<model_type>.py`; a file without the key is
`bert`), the traffic mix is `benchmark/traffic/<traffic>.json`, each
per-layer metric is `benchmark/layer_metrics/<name>.json` and its reader is
the function `read` of `benchmark/readers/<reader>.py`. Adding any of them
is adding a file and an entry; nothing here knows a name.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    pass


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(manifest: dict, name: str, root: str = ROOT) -> dict:
    """Everything one cell is made of: its manifest entry, its
    configuration (entry + file contents) and its traffic file."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    if cell["config"] not in configs:
        raise SpecError(f"workload {name!r} names configuration "
                        f"{cell['config']!r}, which BENCHMARK.json lacks")
    config_entry = configs[cell["config"]]
    traffic_path = os.path.join(root, "benchmark", "traffic",
                                cell["traffic"] + ".json")
    for path in (os.path.join(root, config_entry["file"]), traffic_path):
        if not os.path.isfile(path):
            raise SpecError(f"workload {name!r}: missing file {path}")
    config = load_json(os.path.join(root, config_entry["file"]))
    return {
        "name": name, "chips": int(cell["chips"]), "entry": cell,
        "config_entry": config_entry,
        "config_path": os.path.join(root, config_entry["file"]),
        "config": config, "family": config.get("model_type", "bert"),
        "traffic_path": traffic_path, "traffic": load_json(traffic_path),
    }


def metrics_of_cell(manifest: dict, cell_name: str, kind: str) -> list:
    """The manifest's `end_to_end` or `per_layer` entries that this cell
    reports (no `workloads` key: every cell)."""
    return [m for m in manifest[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_layer_metric(name: str, root: str = ROOT) -> dict:
    path = os.path.join(root, "benchmark", "layer_metrics", name + ".json")
    if not os.path.isfile(path):
        raise SpecError(f"per-layer metric {name!r}: missing file {path}")
    return load_json(path)


def _load_module(path: str, kind: str):
    if not os.path.isfile(path):
        raise SpecError(f"missing {kind} file {path}")
    name = f"_bench_{kind}_{os.path.basename(path)[:-3]}"
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(reader: str, root: str = ROOT):
    """`read(ctx, **args)` of benchmark/readers/<reader>.py."""
    module = _load_module(
        os.path.join(root, "benchmark", "readers", reader + ".py"), "reader")
    if not callable(getattr(module, "read", None)):
        raise SpecError(f"reader {reader!r} defines no read(ctx, **args)")
    return module.read


def load_driver(driver: str, root: str = ROOT):
    """`run(cell, args)` of benchmark/drivers/<driver>.py: the traffic file's
    `driver` key says which kind of load the mix is."""
    module = _load_module(
        os.path.join(root, "benchmark", "drivers", driver + ".py"), "driver")
    if not callable(getattr(module, "run", None)):
        raise SpecError(f"driver {driver!r} defines no run(cell, args)")
    return module.run


# What a family module defines. The driver's side, called without JAX:
# `flops` (the module the readers get as ctx["flops"]: `peaks`,
# `roofline_seconds` and the arithmetic of the family's rooflines),
# `window_flops(cell, window)` for the MFU line, `decide(cell, record,
# check)` for checks on top of the driver's. The child's side: `sizes`,
# `program_args`, `weights` (from the seed, in the program's layout),
# `adapter_functions` (leaf norms, norms of a difference, the matrices
# compared whole), `follow` (the reference over the followed steps),
# `followed_by_program`, `window_extras(segs, scalars, cell)` (`cell`: the
# child's spec, which holds the cell's `config` and `traffic`),
# `compare_extras` (what the family adds to the child's record).
# families/bert.py documents each.
FAMILY_NAMES = ("flops", "window_flops", "decide", "sizes", "program_args",
                "weights", "adapter_functions", "follow",
                "followed_by_program", "window_extras", "compare_extras")


def load_family(family: str, root: str = ROOT):
    """benchmark/families/<family>.py: everything the benchmark knows about
    one model family (a cell's is `find_cell(...)["family"]`)."""
    module = _load_module(
        os.path.join(root, "benchmark", "families", family + ".py"), "family")
    missing = [n for n in FAMILY_NAMES if not hasattr(module, n)]
    if missing:
        raise SpecError(f"family {family!r} does not define {missing}")
    return module


def read_layer_metrics(manifest: dict, cell_name: str, ctx: dict,
                       root: str = ROOT) -> dict:
    """{name: {"value", "unit"}} of the cell's per-layer metrics: each
    metric's own reader over `ctx`; a reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in metrics_of_cell(manifest, cell_name, "per_layer"):
        spec = load_layer_metric(m["name"], root)
        value = load_reader(spec["reader"], root)(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
