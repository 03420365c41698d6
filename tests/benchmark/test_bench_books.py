"""What PR 24 added to the benchmark: the program's [perf] records carry
the fields the new metrics read, the two new readers give the right shares
on the recorded chip trace under a hand-made scope map, and the builder's
account (tools/books.py) sorts operations and host spans as it says."""

import glob
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import spec as spec_lib  # noqa: E402
from benchmark.harness import trace_reduce as tr  # noqa: E402

FIXTURE = os.path.join(ROOT, "benchmark", "fixtures",
                       "trace_v5e_large_pretrain_128.json")
# the new metrics that read a span or a counter of the program (the five
# that read the device trace are checked on the recorded fixture below)
RECORD_METRICS = [
    "log_ms.train", "device_wait_ms.train", "host_unaccounted_ms.train",
    "setup_backend_s", "setup_data_s", "setup_state_s",
    "setup_first_step_s", "setup_unaccounted_s"]
SETUP_FIELDS = ["setup_backend_s", "setup_data_s", "setup_state_s",
                "setup_lower_s", "setup_first_step_s", "setup_unaccounted_s"]


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """One rehearsed run (CPU, toy sizes) and the record its child wrote."""
    keep = str(tmp_path_factory.mktemp("keep"))
    env = dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="0")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "base-pretrain-128", "--seed", str(2**31 + 24),
         "--seconds", "1", "--trace", "0", "--rehearse", "--keep", keep],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    with open(glob.glob(os.path.join(keep, "record_*.json"))[0],
              encoding="utf-8") as f:
        return json.load(f)


def test_perf_records_carry_the_new_fields(rehearsed):
    window = rehearsed["window"]
    records = [window["perf_open"]] + window["perf"]
    for rec in records:
        for field in SETUP_FIELDS + ["loop_unaccounted_ms",
                                     "metric_flush_ms", "log_ms"]:
            assert field in rec, (field, sorted(rec))
        leaves = sum(v for k, v in rec.items() if k.endswith("_ms")
                     and k not in ("step_time_ms", "loop_unaccounted_ms"))
        assert leaves + rec["loop_unaccounted_ms"] == \
            pytest.approx(rec["step_time_ms"], abs=0.02)
    # set-up closed before the window opened: the counters stand still,
    # and with the compile counter they stay under the run's set-up time
    for field in SETUP_FIELDS:
        assert len({rec[field] for rec in records}) == 1, field
    last = records[-1]
    assert last["setup_first_step_s"] > 0 and last["setup_state_s"] > 0
    assert (sum(last[f] for f in SETUP_FIELDS) + last["compile_secs"]
            <= window["setup_s"])


@pytest.mark.parametrize("name", RECORD_METRICS)
def test_host_and_setup_metrics_read_the_rehearsed_record(rehearsed, name):
    spec = spec_lib.load_layer_metric(name, ROOT)
    assert spec["source"] in ("program_span", "program_counter")
    value = spec_lib.load_reader(spec["reader"], ROOT)(
        {"record": rehearsed}, **spec["args"])
    assert value is not None and value == value
    if name == "device_wait_ms.train":      # the loop waits HERE
        steps = [p["step_time_ms"] for p in rehearsed["window"]["perf"]]
        assert value > 0.5 * sum(steps) / len(steps)


def _handmade():
    """The recorded trace with a scope map made by hand: its operations,
    by name, dealt out to five paths."""
    with open(FIXTURE, encoding="utf-8") as f:
        events = json.load(f)
    deep = ("jit(train_step)/grad_accum/while/body/closed_call/"
            "transpose(jvp(BertForPreTraining))/bert/encoder/while/body/")
    paths = [deep + "dynamic_update_slice",
             deep + "closed_call/checkpoint/rematted_computation/layers/"
                    "layer/attention/attention/qkv/dot_general",
             deep + "closed_call/checkpoint/layers/layer/mlp/mlp_output/"
                    "dot_general",
             "jit(train_step)/optimizer/lamb/mul",
             "jit(_threefry_fold_in)/threefry2x32"]
    names = sorted({o[0] for o in events["devices"]["/device:TPU:0"]["ops"]})
    events["scopes"] = {n: paths[i % 6] for i, n in enumerate(names)
                        if i % 6 < 5}               # every sixth: no op_name
    return events, paths


def _reader(name):
    return spec_lib.load_reader(name, ROOT)


def test_new_readers_on_the_recorded_trace():
    events, paths = _handmade()
    r = tr.reduce(events)
    ctx = {"trace": r}
    want = {p: 0.0 for p in paths + [""]}
    for name, t in r["by_op"].items():
        want[events["scopes"].get(name, "")] += t
    pct = {p: 100.0 * t / r["busy_s"] for p, t in want.items()}
    assert min(pct.values()) > 0
    carry = spec_lib.load_layer_metric("scan_carry_share.train", ROOT)
    assert _reader("scope_under_share")(ctx, **carry["args"]) == \
        pytest.approx(pct[paths[0]])
    unscoped = spec_lib.load_layer_metric("unscoped_share.train", ROOT)
    assert _reader("unscoped_share")(ctx, **unscoped["args"]) == \
        pytest.approx(pct[paths[4]] + pct[""])
    for metric, path in (("mlp_share.train", paths[2]),
                         ("optimizer_share.train", paths[3]),
                         ("recompute_share.train", paths[1])):
        spec = spec_lib.load_layer_metric(metric, ROOT)
        assert _reader(spec["reader"])(ctx, **spec["args"]) == \
            pytest.approx(pct[path]), metric
    # a run that carried no scopes (the parent of PR 24 carried some, a
    # program without any carries none) reads nothing and does not raise
    bare = tr.reduce(dict(events, scopes={}))
    assert _reader("unscoped_share")({"trace": bare},
                                     **unscoped["args"]) is None
    assert _reader("scope_under_share")({"trace": bare},
                                        **carry["args"]) is None


@pytest.fixture(scope="module")
def books():
    path = os.path.join(ROOT, "benchmark", "tools", "books.py")
    spec = importlib.util.spec_from_file_location("_bench_books", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_books_sorts_operations_by_first_match(books):
    events, paths = _handmade()
    r = tr.reduce(events)
    scopes = spec_lib.load_layer_metric(
        "unscoped_share.train", ROOT)["args"]["scopes"]
    got = books.by_first_match(r["by_scope"], scopes)
    assert set(got) == {"encoder", "attention", "mlp", "optimizer",
                        "(named, unmatched)", "(no op_name)"}
    assert sum(got.values()) == pytest.approx(sum(r["by_scope"].values()))
    # the recorded slice holds nothing that holds another: the 38 fusions
    # with a zero-length marker inside are leaves since PR 40 (9.6267 % of
    # busy time stood here as holders' self time until then); a `while`
    # around an operation of some length is a holder, its gaps its own
    assert books.holders(events, r) == {}
    dev = {"ops": [["while.3", 0, 100], ["fusion.1", 10, 60],
                   ["custom-call.9", 20, 0], ["fusion.2", 100, 20]],
           "async": [], "modules": [["jit_train_step(1)", 0, 120]]}
    looped = {"devices": {"/device:TPU:0": dev}, "host": []}
    assert books.holders(looped, tr.reduce(looped)) == {
        "while": pytest.approx(50.0)}       # 40 ns of gaps over 80 busy


def test_books_clock_check_pairs_spans_with_records(books):
    ms = 1_000_000
    host, t = [], 0
    fields = {"dispatch": 2, "log": 1, "metric_flush": 90, "data_wait": 3}

    def span(name, dur_ms):
        nonlocal t
        host.append(["host/" + name, t, int(dur_ms * ms)])
        t += int(dur_ms * ms) + 1000

    span("dispatch", 2)                 # the trace opens in step 7's turn
    span("log", 0.5)
    ends = []
    for _ in range(3):                  # ... | record 8 | record 9
        span("metric_flush", 90)
        ends.append(t - 1000 - 300_000)     # device ended 0.3 ms earlier
        span("log", 0.5)
        span("log", 0.5)                # the [perf] record's own logging
        span("data_wait", 3)
        span("dispatch", 2)
    record = {"window": {"traced_first_step": 7, "perf": [
        {"step": s, "step_time_ms": 97.0, "loop_unaccounted_ms": 0.0,
         **{k + "_ms": float(v) for k, v in fields.items()}}
        for s in (8, 9)]}}
    events = {"host": host, "devices": {"/device:TPU:0": {
        "modules": [["jit_train_step", e - 50 * ms, 50 * ms] for e in ends],
        "ops": [], "async": []}}}
    check = books.clock_check(events, record)
    assert sorted({row[0] for row in check["phase_rows"]}) == [8, 9]
    assert check["phase_worst_abs_ms"] == pytest.approx(0.0, abs=1e-6)
    waits = check["metric_flush_ms_and_end_after_device_end_ms"]
    assert [w[1] for w in waits] == [pytest.approx(0.3)] * 3
