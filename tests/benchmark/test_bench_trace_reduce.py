"""The reduction from a profiler trace to busy/idle, time by operation and
scope, and collective overlap: on hand-made intervals with known answers,
and on a small trace recorded on the chip (benchmark/fixtures/)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import trace_reduce as tr  # noqa: E402

FIXTURE = os.path.join(ROOT, "benchmark", "fixtures",
                       "trace_v5e_large_pretrain_128.json")


@pytest.mark.parametrize("intervals,want", [
    ([[0, 10], [5, 15], [20, 30]], 25),
    ([[0, 10], [10, 20]], 20),
    ([[3, 4], [0, 10]], 10),
    ([], 0),
])
def test_total_merges_overlaps(intervals, want):
    assert tr.total(intervals) == want


@pytest.mark.parametrize("intervals,cover,want", [
    ([[0, 10]], [[2, 4], [6, 7]], 7),        # 0-2, 4-6, 7-10
    ([[0, 10]], [[0, 10]], 0),
    ([[0, 10], [20, 30]], [[5, 25]], 10),    # 0-5 and 25-30
    ([[0, 10]], [], 10),
    ([[5, 6]], [[0, 1], [2, 3], [4, 5.5]], 0.5),
])
def test_uncovered_is_what_no_compute_hides(intervals, cover, want):
    assert tr.uncovered(intervals, cover) == pytest.approx(want)


@pytest.mark.parametrize("text,want", [
    ("%fusion.795 = bf16[64,128]{1,0:T(8,128)(2,1)} fusion(%a)", "fusion.795"),
    ("ROOT %tuple.3 = (f32[]) tuple(%x)", "tuple.3"),
    ("all-gather-start.12 = bf16[8] all-gather-start(%p)",
     "all-gather-start.12"),
    ("host/dispatch", "host/dispatch"),
])
def test_short_name(text, want):
    assert tr.short_name(text) == want


def test_scopes_from_hlo_reads_instruction_and_op_name():
    text = (
        'HloModule jit_train_step\n'
        '  %custom-call.7 = bf16[8,128]{1,0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", metadata={op_name="jit(train'
        '_step)/jvp(Bert)/attention/layernorm_fwd/pallas_call" '
        'source_file="x.py"}\n'
        '  %add.1 = f32[] add(%b, %c)\n'
        '  ROOT %fusion.2 = f32[8] fusion(%d), kind=kLoop, '
        'metadata={op_name="jit(train_step)/mlm_head/dot_general"}\n')
    assert tr.scopes_from_hlo(text) == {
        "custom-call.7":
            "jit(train_step)/jvp(Bert)/attention/layernorm_fwd/pallas_call",
        "fusion.2": "jit(train_step)/mlm_head/dot_general"}


def _synthetic(n_devices=1):
    """Two whole steps of 100 ns and one cut short; per step a while of
    80 ns holding a 30 ns matmul and a 20 ns collective that the matmul
    half hides, then a 10 ns copy; 10 ns of every step is idle."""
    devices = {}
    for d in range(n_devices):
        ops, modules = [], []
        for step, base in enumerate((1000, 1100, 1200)):
            dur = 100 if step < 2 else 40
            modules.append(["jit_train_step(1)", base, dur])
            if step == 2:
                ops.append(["while.1", base, 40])
                continue
            ops += [["while.1", base, 80], ["fusion.1", base + 5, 30],
                    ["all-reduce.1", base + 25, 20],
                    ["copy.1", base + 80, 10]]
        modules.append(["jit_other(2)", 900, 5])
        devices[f"/device:TPU:{d}"] = {"ops": ops, "async": [],
                                       "modules": modules}
    return {"devices": devices,
            "host": [["host/dispatch", 1085, 30], ["host/h2d", 1000, 3]],
            "scopes": {"fusion.1": "jit(train_step)/bert/attention/dot",
                       "copy.1": "jit(train_step)/mlm_head/copy"}}


@pytest.mark.parametrize("n_devices", [1, 4])
def test_reduce_on_known_intervals(n_devices):
    r = tr.reduce(_synthetic(n_devices))
    assert r["steps"] == 2
    assert r["window_s"] == pytest.approx(200e-9)
    # leaves: fusion 5-35, all-reduce 25-45, copy 80-90 -> 50 ns a step
    assert r["busy_s"] == pytest.approx(100e-9)
    assert r["collective_s"] == pytest.approx(40e-9)
    assert r["collective_exposed_s"] == pytest.approx(20e-9)   # 35-45
    assert r["by_op"]["fusion.1"] == pytest.approx(60e-9)
    assert r["by_op"]["while.1"] == pytest.approx((80 - 30 - 20) * 2e-9)
    assert r["by_scope"]["jit(train_step)/bert/attention/dot"] == \
        pytest.approx(60e-9)
    gaps = dict(r["breakdown"]["idle_gaps"])
    # the gap 1090-1105 lies under host/dispatch (1085-1115)
    assert gaps["host/dispatch"] == pytest.approx(15e-9)
    assert sum(gaps.values()) == pytest.approx(100e-9)
    assert {op[0].split(" ")[0] for op in
            r["breakdown"]["device_ops"][:2]} == {"fusion.1", "while.1"}
    assert r["breakdown"]["device_ops"][0][0].endswith("]") or \
        r["breakdown"]["device_ops"][1][0].endswith("]")   # scope label


def test_cut_keeps_one_step_and_its_scopes():
    small = tr.cut(_synthetic(), max_ops=3)
    dev = small["devices"]["/device:TPU:0"]
    assert len(dev["ops"]) <= 3 and len(dev["modules"]) == 1
    assert set(small["scopes"]) <= {"fusion.1", "copy.1"}


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE, encoding="utf-8") as f:
        return json.load(f)


def test_fixture_is_small_and_from_the_chip(recorded):
    assert os.path.getsize(FIXTURE) < 1_000_000
    assert list(recorded["devices"]) == ["/device:TPU:0"]
    assert recorded["recorded_on"]["kind"] == "TPU v5 lite"


def test_reduce_on_the_recorded_trace(recorded):
    r = tr.reduce(recorded)
    known = recorded["known"]
    assert r["steps"] == 1
    for key in ("window_s", "busy_s", "collective_s"):
        assert r[key] == pytest.approx(known[key], rel=1e-9), key
    assert 0.0 < r["busy_s"] <= r["window_s"]
    top = r["breakdown"]["device_ops"][0]
    assert top[0].split(" ")[0] == known["top_op"]
    kernel = sum(t for scope, t in r["by_scope"].items()
                 if "layernorm_fwd/pallas_call" in scope)
    assert kernel == pytest.approx(known["layernorm_fwd_s"], rel=1e-9)
    assert kernel > 0


def test_a_child_of_no_duration_does_not_make_its_parent_a_holder():
    """The runtime puts zero-length `custom-call` markers inside big
    fusions. Such a fusion is a leaf: busy while it runs, its self time
    whole; a `while` that holds a real operation stays a holder."""
    ops = [["fusion.1", 0, 100], ["custom-call.1", 40, 0],
           ["while.1", 200, 100], ["fusion.2", 210, 40],
           ["custom-call.2", 220, 0]]
    own, holds = tr.self_times(ops)
    assert holds == [False, False, True, False, False]
    assert own == [100.0, 0.0, 60.0, 40.0, 0.0]
    r = tr.reduce({"devices": {"/device:TPU:0": {
        "ops": ops, "async": [],
        "modules": [["jit_train_step(1)", 0, 300]]}}, "host": [],
        "scopes": {"fusion.1": "jit(train_step)/mlp/dot_general",
                   "fusion.2": "jit(train_step)/attention/dot_general"}})
    assert r["busy_s"] == pytest.approx(140e-9)     # both fusions, whole
    assert r["by_scope"]["jit(train_step)/mlp/dot_general"] == \
        pytest.approx(100e-9)
    gaps = sum(t for _, t in r["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx(160e-9)            # 100-210 and 250-300


def test_the_recorded_traces_scopes_sum_to_its_busy_time(recorded):
    """117 of the recorded step's 4,000 operations are such markers, inside
    38 fusions that `reduce` counted as idle until PR 40 (busy 80.38 of
    88.13 ms, the scopes' self times 109.6 % of it)."""
    ops = recorded["devices"]["/device:TPU:0"]["ops"]
    assert sum(1 for o in ops if o[2] == 0) == 117
    r = tr.reduce(recorded)
    by_scope = sum(r["by_scope"].values())
    assert by_scope / r["busy_s"] == pytest.approx(1.0, abs=0.005)
    assert 0.99 < r["busy_s"] / r["window_s"] <= 1.0
