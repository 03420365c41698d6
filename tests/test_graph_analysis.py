"""Static graph analysis (bert_pytorch_tpu/analysis + tools/graphcheck.py).

Fast half: parser + pass-framework units on synthetic HLO text fixtures
(no compile, no jax beyond import) — budget regression names the op,
donation miss detected, replicated-moment leaf detected, fingerprint
compare semantics, budget-file schema, the jax-free --validate-budgets
contract, and the repolint fallback.

Slow half (the acceptance drill): the REAL production step compiled on
the forced 8-device CPU mesh passes the checked-in budgets, and injected
program regressions (dropped donate_argnums; ZeRO-1 state sharding failed
open) make the gate exit nonzero naming the exact rule, op, and leaf.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bert_pytorch_tpu.analysis import hlo, passes  # noqa: E402
from tools import graphcheck  # noqa: E402

# a tiny synthetic compiled-HLO module: 2 all-gathers, 1 all-reduce,
# 1 reduce-scatter, donation table with one aliased and one missed param
FIXTURE_HLO = """\
HloModule jit_step, is_scheduled=true, input_output_alias={ {0}: (0, {}, \
may-alias), {1}: (1, {}, may-alias) }, buffer_donor={ (2, {}) }, \
entry_computation_layout={(f32[4,8]{1,0}, f32[4,8]{1,0}, f32[64,8]{1,0}, \
f32[16,8]{1,0})->(f32[4,8]{1,0}, f32[4,8]{1,0}, f32[])}, num_partitions=8

  %ag1 = f32[32,8]{1,0} all-gather(f32[4,8]{1,0} %p0), channel_id=1, \
replica_groups=[1,8]<=[8], dimensions={0}
  %ag2-start = (f32[4,8]{1,0}, f32[32,8]{1,0}) all-gather-start(\
f32[4,8]{1,0} %p1), replica_groups=[1,8]<=[8], dimensions={0}
  %ag2-done = f32[32,8]{1,0} all-gather-done((f32[4,8]{1,0}, \
f32[32,8]{1,0}) %ag2-start)
  %ar = f32[8]{0} all-reduce(f32[8]{0} %x), channel_id=2, \
replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%sum
  %rs = f32[4,8]{1,0} reduce-scatter(f32[32,8]{1,0} %y), channel_id=3, \
replica_groups=[1,8]<=[8], dimensions={0}
  %cp = f32[8,8]{1,0} copy(f32[8,8]{0,1} %q)
  %tr = f32[8,8]{1,0} transpose(f32[8,8]{1,0} %q), dimensions={1,0}
  %red_fusion = f32[] fusion(f32[32,8]{1,0} %ag1), kind=kLoop, calls=%fc
  ROOT %out = (f32[4,8]{1,0}, f32[4,8]{1,0}, f32[]) tuple(%rs, %p1, \
%red_fusion)
"""


# what the TPU compiler prints: tiled layouts carry parentheses of their
# own (T(8,128)(2,1), memory space S(1)), async collectives return tuples
# of them, and Pallas kernels are tpu_custom_call instructions whose jax op
# path names the kernel (wrapped by autodiff: transpose(jvp(NAME)))
TPU_FIXTURE_HLO = """\
HloModule jit_train_step, is_scheduled=true, num_partitions=4

  %ag-start = (bf16[256,1024]{1,0:T(8,128)(2,1)S(1)}, \
bf16[1024,1024]{1,0:T(8,128)(2,1)}) all-gather-start(%p), \
replica_groups=[1,4]<=[4], dimensions={0}
  %ag-done = bf16[1024,1024]{1,0:T(8,128)(2,1)} all-gather-done(%ag-start)
  %cp-start = (bf16[96,1024]{1,0:T(8,128)(2,1)S(1)}, \
bf16[96,1024]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) \
collective-permute-start(%s), source_target_pairs={{0,1},{1,2},{2,3}}
  %jvp_flash_fwd_.1 = (bf16[16,512,1024]{2,1,0:T(8,128)(2,1)S(1)}, \
f32[16,8,2,512]{3,2,1,0:T(2,128)}) custom-call(%c, %q, %k, %v), \
custom_call_target="tpu_custom_call", metadata={op_name=\
"jit(train_step)/while/body/jvp(flash_fwd)/pallas_call" stack_frame_id=15}
  %bwd.3 = (bf16[16,512,1024]{2,1,0:T(8,128)(2,1)}) custom-call(%c, %q), \
custom_call_target="tpu_custom_call", metadata={op_name=\
"jit(train_step)/transpose(jvp(flash_bwd_dqkv))/pallas_call"}
  %ln.1 = bf16[8192,1024]{1,0:T(8,128)(2,1)} custom-call(%x), \
custom_call_target="tpu_custom_call", metadata={op_name=\
"jit(train_step)/layernorm_fwd/pallas_call"}
  %ln.2 = bf16[8192,1024]{1,0:T(8,128)(2,1)} custom-call(%y), \
custom_call_target="tpu_custom_call", metadata={op_name=\
"jit(train_step)/layernorm_fwd/pallas_call"}
  %anon = f32[8,128]{1,0:T(8,128)} custom-call(%z), \
custom_call_target="tpu_custom_call", metadata={op_name=\
"jit(train_step)/jvp()/pallas_call"}
  %alloc = bf16[24,64]{1,0:T(8,128)(2,1)} custom-call(), \
custom_call_target="AllocateBuffer"
"""


# --- parser units -------------------------------------------------------


def test_parse_tpu_layouts_and_kernel_inventory():
    """TPU tiled layouts must not hide instructions from the parser (they
    did: every collective behind a T(8,128)(2,1) tuple went uncounted), and
    the Pallas kernels are inventoried by the name their pallas_call gave
    them — how chip_smoke.py proves a step took the flash / LayerNorm
    kernels and not the XLA path."""
    rep = hlo.parse_hlo_module(TPU_FIXTURE_HLO)
    assert rep["num_partitions"] == 4
    assert rep["collective_counts"] == {
        "all-gather": 1, "all-reduce": 0, "reduce-scatter": 0,
        "collective-permute": 1, "all-to-all": 0}
    # an async start's tuple: only the LAST element is the collective's
    # output... for all-gather; sized from the parsed result shapes
    assert rep["collective_bytes"]["all-gather"] == 1024 * 1024 * 2
    # ...and not the u32[] context scalars the TPU compiler appends
    assert rep["collective_bytes"]["collective-permute"] == 96 * 1024 * 2
    assert rep["kernel_counts"] == {
        "flash_bwd_dqkv": 1, "flash_fwd": 1, "layernorm_fwd": 2,
        "unnamed": 1}
    assert hlo.kernel_counts(TPU_FIXTURE_HLO) == rep["kernel_counts"]
    fp = hlo.fingerprint_of(rep)
    assert fp["kernel_counts"] == rep["kernel_counts"]
    # ...while a program that runs no Mosaic kernel (every CPU program)
    # keeps the fingerprint it always had
    cpu = hlo.parse_hlo_module(FIXTURE_HLO)
    assert cpu["kernel_counts"] == {}
    assert "kernel_counts" not in hlo.fingerprint_of(cpu)




def test_parse_hlo_counts_collectives_and_ops():
    rep = hlo.parse_hlo_module(FIXTURE_HLO)
    assert rep["collective_counts"] == {
        "all-gather": 2, "all-reduce": 1, "reduce-scatter": 1,
        "collective-permute": 0, "all-to-all": 0}
    assert rep["op_counts"]["copy"] == 1
    assert rep["op_counts"]["transpose"] == 1
    assert rep["op_counts"]["fusion"] == 1
    assert rep["num_partitions"] == 8
    # bytes: each all-gather OUTPUT is 32*8*4 = 1024 B — the async
    # `-start`'s `(operand, output)` tuple counts only its output half
    assert rep["collective_bytes"]["all-gather"] == 2048
    # ring estimate: (g-1)/g of the output per participant
    assert rep["collective_est_bytes_moved"]["all-gather"] == 2 * 896
    assert rep["collective_shapes"]["all-gather f32[32,8]"] == 2


def test_parse_hlo_donation_table():
    don = hlo.parse_hlo_module(FIXTURE_HLO)["donation"]
    assert don["aliased"] == [0, 1]
    assert don["donated_unaliased"] == [2]  # the miss
    assert don["n_aliased"] == 2 and don["n_donated_unaliased"] == 1


def test_stablehlo_dot_dtype_census():
    text = """
      %2 = stablehlo.dot_general %0, %1, contracting_dims = [1] x [0] :
        (tensor<8x8xbf16>, tensor<8x8xbf16>) -> tensor<8x8xbf16>
      %5 = stablehlo.dot_general %3, %4, contracting_dims = [1] x [0] : \
(tensor<4x8xf32>, tensor<8x2xf32>) -> tensor<4x2xf32>
    """
    # multiline form (result type on the next line) is counted only when
    # the arrow is on the op line — the census is line-based; both ops
    # here carry an arrow on an op line
    dd = hlo.stablehlo_dot_dtypes(text)
    assert dd.get("f32") == 1


# --- pass framework on fixtures ----------------------------------------


def test_budget_regression_exits_nonzero_naming_the_op():
    rep = hlo.parse_hlo_module(FIXTURE_HLO)
    budget = {"all-gather": 1, "all-reduce": 1, "reduce-scatter": 1}
    findings = passes.check_collective_budget(rep, budget)
    errs = [f for f in findings if f.severity == "error"]
    assert len(errs) == 1
    assert errs[0].op == "all-gather"
    assert "2 ops compiled, budget is 1" in errs[0].message
    # run through the driver + CLI printer: nonzero error count
    per_combo = {"fix": passes.run_passes(
        rep, {"collective_budget": budget})}
    assert graphcheck.print_findings(
        per_combo, stream=open(os.devnull, "w")) == 1


def test_donation_miss_detected_with_leaf_name():
    rep = hlo.parse_hlo_module(FIXTURE_HLO)
    rep["inputs"] = [
        {"path": ".params['w']", "param": 0, "bytes": 128, "aliased": True},
        {"path": ".opt_state.mu['w']", "param": 1, "bytes": 128,
         "aliased": True},
        {"path": ".opt_state.nu['w']", "param": 2, "bytes": 2048,
         "aliased": False, "donated_unaliased": True},
        {"path": ".batch['x']", "param": 3, "bytes": 512, "aliased": False},
    ]
    findings = passes.check_donation(rep, {"min_aliased": 2})
    errs = [f for f in findings if f.severity == "error"]
    assert len(errs) == 1
    assert errs[0].leaf == ".opt_state.nu['w']"
    assert "never aliased" in errs[0].message
    # min_aliased floor trips when the whole table loses donation
    rep2 = dict(rep, donation=dict(rep["donation"], n_aliased=0))
    errs2 = passes.check_donation(rep2, {"min_aliased": 2})
    assert any("donate_argnums" in f.message for f in errs2)


def test_replicated_moment_leaf_detected():
    leaves = [
        {"path": ".opt_state.mu['embedding']", "shape": [64, 32],
         "replicated": True, "expected_sharded": True,
         "expected_spec": "PartitionSpec('data', None)"},
        {"path": ".params['embedding']", "shape": [64, 32],
         "replicated": True, "expected_sharded": False,
         "expected_spec": None},
        {"path": ".opt_state.nu['embedding']", "shape": [64, 32],
         "replicated": False, "expected_sharded": True,
         "expected_spec": "PartitionSpec('data', None)"},
    ]
    findings = passes.replication_findings(leaves)
    assert len(findings) == 1
    assert findings[0].leaf == ".opt_state.mu['embedding']"
    assert "PartitionSpec('data', None)" in findings[0].message
    # the count floor fires independently of per-leaf expectations
    rep = {"inputs": [dict(r, expected_sharded=False) for r in leaves]}
    errs = passes.check_replication(rep, {"min_sharded_inputs": 2})
    assert any("failed open" in f.message for f in errs)


def test_dtype_and_memory_passes():
    rep = {"dot_dtypes": {"bf16": 30, "f32": 3},
           "memory": {"argument_size_in_bytes": 2**20,
                      "output_size_in_bytes": 2**20,
                      "temp_size_in_bytes": 2**20,
                      "alias_size_in_bytes": 2**20}}
    errs = passes.check_dtype(rep, {"compute_dtype": "bf16",
                                    "max_f32_dots": 0})
    assert errs and errs[0].op == "dot" and "3 f32 matmul" in errs[0].message
    assert not passes.check_dtype(rep, {"compute_dtype": "bf16",
                                        "max_f32_dots": 3})
    assert not passes.check_dtype(rep, {"compute_dtype": "f32"})
    # memory estimate = args + temps + outputs - aliased = 2 MB
    assert passes.estimate_device_bytes(rep) == 2 * 2**20
    bad = passes.check_memory(rep, {"budget_mb": 1})
    assert bad[0].severity == "error" and "exceeds" in bad[0].message
    ok = passes.check_memory(rep, {"budget_mb": 4})
    assert ok[0].severity == "info"


def test_unknown_expectation_key_is_loud():
    findings = passes.run_passes({}, {"collectve_budget": {}})  # typo
    assert passes.has_errors(findings)
    assert "unknown expectation key" in findings[0].message


def test_fingerprint_compare_semantics():
    rep = hlo.parse_hlo_module(FIXTURE_HLO)
    fp = dict(hlo.fingerprint_of(rep), platform="cpu")
    same = dict(fp)
    comparable, diffs = hlo.compare_fingerprints(fp, same)
    assert comparable and not diffs
    # a structural change shows up as a named diff
    drifted = dict(fp, collective_counts=dict(fp["collective_counts"],
                                              **{"all-gather": 5}))
    comparable, diffs = hlo.compare_fingerprints(fp, drifted)
    assert comparable and any("all-gather" in d for d in diffs)
    # cross-platform: not comparable, never a false alarm
    other = dict(fp, platform="tpu")
    comparable, _ = hlo.compare_fingerprints(fp, other)
    assert not comparable
    assert hlo.compare_fingerprints(fp, None) == (False, [])


def test_manifest_fingerprint_schema():
    from bert_pytorch_tpu.telemetry.flight_recorder import (
        MANIFEST_SCHEMA_VERSION, REQUIRED_MANIFEST_KEYS, REQUIRED_RUN_KEYS,
        validate_manifest)

    manifest = {k: {} for k in REQUIRED_MANIFEST_KEYS}
    manifest.update(
        schema_version=MANIFEST_SCHEMA_VERSION, reason="nonfinite",
        trigger_step=3, created_unix=0.0,
        model_config={"hidden_size": 8, "num_hidden_layers": 1},
        run={k: None for k in REQUIRED_RUN_KEYS},
        records=[{"step": 3, "pos": 0, "n_steps": 1, "fields": []}],
        metrics_tail=[], metrics_tail_source=None, registry={})
    # absent key entirely is fine (round-12 bundles) and None is fine
    assert validate_manifest(dict(manifest)) == []
    assert validate_manifest(dict(manifest, program_fingerprint=None)) == []
    good_fp = {"collective_counts": {"all-reduce": 3},
               "donation_hash": "abc", "hash": "x", "platform": "cpu"}
    assert validate_manifest(
        dict(manifest, program_fingerprint=good_fp)) == []
    errs = validate_manifest(dict(manifest, program_fingerprint={"x": 1}))
    assert any("program_fingerprint" in e for e in errs)


# --- budget-file schema + jax-free contract ----------------------------


def test_checked_in_budgets_validate():
    budgets = json.load(open(os.path.join(REPO, "results",
                                          "graph_budgets.json")))
    assert graphcheck.validate_budgets(budgets) == []
    # and the schema check catches real damage
    assert graphcheck.validate_budgets({"schema_version": 99})
    broken = json.loads(json.dumps(budgets))
    broken["combos"]["zero1_dp8"]["expect"]["collective_budget"][
        "all-gather"] = -1
    assert any("all-gather" in e for e in graphcheck.validate_budgets(broken))


def test_validate_budgets_is_jax_free():
    """`graphcheck --validate-budgets` must run on a login host with no
    jax: execute it in a subprocess where importing jax raises."""
    code = (
        "import builtins\n"
        "real = builtins.__import__\n"
        "def guard(name, *a, **k):\n"
        "    if name == 'jax' or name.startswith('jax.'):\n"
        "        raise AssertionError('jax imported in --validate-budgets')\n"
        "    return real(name, *a, **k)\n"
        "builtins.__import__ = guard\n"
        "import sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from tools import graphcheck\n"
        "sys.exit(graphcheck.main(['--validate-budgets']))\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "schema ok" in proc.stdout


def test_repolint_catches_planted_bugs(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import os\n"                      # unused
        "x = f\"no placeholders\"\n"        # F541
        "y = (x is 'literal')\n"            # F632
        "z = undefined_thing + 1\n"         # F821
        "def f(d):\n"
        "    dead = d.pop('k')\n"           # F841: never used
        "    return d\n")
    from tools import repolint

    findings = repolint.lint_file(str(bad))
    codes = {c for _, c, _ in findings}
    assert {"F401", "F541", "F632", "F821", "F841"} <= codes
    # `is None/True/False`, format specs, underscore locals, and
    # assign-then-del (Del is a use, matching pyflakes — ruff stays
    # strictly stronger than the fallback) are NOT flagged
    ok = tmp_path / "ok.py"
    ok.write_text(
        "import math\n"
        "v = math.pi\n"
        "s = f\"{v:.2f}\"\n"
        "t = v is None\n"
        "def f(d):\n"
        "    gone = d.pop('k')\n"
        "    del gone\n"
        "    _scratch = d.copy()\n"
        "    n = 0\n"
        "    n += len(d)\n"        # augmented assign = an implicit load
        "    return d\n")
    assert repolint.lint_file(str(ok)) == []


def test_repo_is_lint_clean():
    """The satellite's 'fix the findings' stays fixed."""
    from tools import repolint

    assert repolint.main(list(repolint.DEFAULT_TARGETS)) == 0


# --- the acceptance drill: real compiled programs ----------------------


def test_gate_passes_on_checked_in_budgets_and_names_injected_regressions(
        tmp_path, capsys):
    """ONE combo (zero1_dp8) compiled three ways on the 8-device CPU mesh:
    clean -> exit 0 against the checked-in budgets; donation dropped ->
    exit 1 naming the donation rule; ZeRO-1 state sharding failed open ->
    exit 1 naming the replication rule and the exact moment leaf."""
    report = str(tmp_path / "graph_report.json")
    budgets = os.path.join(REPO, "results", "graph_budgets.json")

    rc = graphcheck.main(["--combos", "zero1_dp8", "--report", report,
                          "--budgets", budgets])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "within budget" in out

    rc = graphcheck.main(["--combos", "zero1_dp8", "--report", report,
                          "--budgets", budgets, "--inject", "no_donate"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "ERROR [donation]" in out
    assert "donate_argnums" in out

    rc = graphcheck.main(["--combos", "zero1_dp8", "--report", report,
                          "--budgets", budgets,
                          "--inject", "replicated_state"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "ERROR [replication]" in out
    # the exact regressed leaf is named: a ZeRO-1 moment, by path
    assert ".opt_state.mu" in out and "failed open" in out


@pytest.mark.slow
def test_rs_gate_catches_injected_allreduce(tmp_path, capsys):
    """The round-16 acceptance drill: zero1_rs_dp8's checked-in budget
    pins all-reduce as an EXACT ceiling (11 — under half of zero1_dp8's),
    so one smuggled full-tree reduction over a sharded moment leaf must
    flip the gate. Clean compile passes first — proving the failure below
    is the injection, not baseline drift."""
    report = str(tmp_path / "graph_report.json")
    budgets = os.path.join(REPO, "results", "graph_budgets.json")

    rc = graphcheck.main(["--combos", "zero1_rs_dp8", "--report", report,
                          "--budgets", budgets])
    out = capsys.readouterr().out
    assert rc == 0, out

    rc = graphcheck.main(["--combos", "zero1_rs_dp8", "--report", report,
                          "--budgets", budgets,
                          "--inject", "extra_allreduce"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "ERROR [collective_budget]" in out
    assert "all-reduce" in out and "extra all-reduce" in out


def test_step_program_aot_dispatch_and_fingerprint():
    """StepProgram: one AOT compile, compiled dispatch, graceful jit
    fallback on signature drift, and a fingerprint that reflects the
    compiled program."""
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.training.pretrain import StepProgram

    calls = []

    def step(state, batch, rng):
        calls.append(1)
        return {"w": state["w"] + batch.sum()}, {"loss": batch.sum()}

    prog = StepProgram(step)
    state = {"w": jnp.zeros((4,))}
    out_state, m = prog(state, jnp.ones((2, 2)), jax.random.PRNGKey(0))
    assert prog.compiled is not None
    assert prog.as_text() and "HloModule" in prog.as_text()
    fp = prog.fingerprint()
    assert fp is not None and "collective_counts" in fp \
        and "donation_hash" in fp
    # donated state: the carried buffer aliases in
    assert fp["n_aliased"] >= 1
    # same signature -> AOT path (no retrace)
    traces_before = len(calls)
    out_state, m = prog(out_state, jnp.ones((2, 2)), jax.random.PRNGKey(1))
    assert len(calls) == traces_before
    # different shape -> falls back to the jit cache, still correct
    out2, m2 = prog({"w": jnp.zeros((4,))}, jnp.ones((3, 2)),
                    jax.random.PRNGKey(0))
    assert float(m2["loss"]) == 6.0


@pytest.mark.slow
def test_full_combo_matrix_within_budget(tmp_path):
    """Every shipped combo (incl. K-FAC and bf16) against the checked-in
    budgets — the whole scripts/check_graph.sh gate, minus the shell."""
    rc = graphcheck.main(["--report", str(tmp_path / "r.json")])
    assert rc == 0
