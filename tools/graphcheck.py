#!/usr/bin/env python
"""Graph lint: static analysis of the compiled train steps as a CI gate.

The program-structure bug class — fail-open sharding gates (round 7),
GSPMD forking the ZeRO-1 gather into extra all-gathers (round 11),
silently-dropped buffer donation — is invisible to unit tests until a
multichip run. This tool lowers + compiles the PRODUCTION step
builders (build_pretrain_step / build_kfac_pretrain_step, the exact
functions run_pretraining wires) for a named set of config x mesh combos
on a forced 8-device CPU mesh — no TPU — parses the
compiled HLO into structured reports (bert_pytorch_tpu/analysis/hlo.py),
and diffs them against checked-in budgets with the rule framework
(analysis/passes.py):

  python tools/graphcheck.py
      # build reports for every combo, write results/graph_report.json,
      # diff against results/graph_budgets.json; exit 1 naming each
      # error finding (rule, op, leaf). scripts/check_graph.sh wraps this.

  python tools/graphcheck.py --combos pretrain_dp8,zero1_dp8
      # subset (tier-1 tests use this to stay fast)

  python tools/graphcheck.py --write-budgets
      # re-baseline: derive results/graph_budgets.json from the current
      # programs. Run after an INTENTIONAL program change, commit both
      # files, and say why in the commit message.

  python tools/graphcheck.py --validate-budgets
      # jax-free (login host / CI front door):
      # schema-check the budget file, and when results/graph_report.json
      # exists diff it against the budgets without recompiling anything.

  python tools/graphcheck.py --combos zero1_dp8 --inject no_donate
      # regression drill: compile a deliberately-broken program
      # (no_donate drops donate_argnums; replicated_state builds the
      # TrainState with the ZeRO-1 storage sharding failed open;
      # extra_gather adds one unbudgeted all-gather; wrong_axis derives
      # ONE leaf's expected spec with a deliberately swapped mesh axis so
      # the sharding_rules pass must exit 1 naming the rule, the leaf,
      # and both shardings) and prove the gate exits nonzero naming the
      # rule — tests/test_graph_analysis.py + tests/test_sharding_rules.py
      # pin this.

Every expectation the sharding_rules pass gates is DERIVED from the
logical-axis-rules table (bert_pytorch_tpu/parallel/rules.py — the one
source of truth for params, ZeRO-1 moments, K-FAC factors, batch inputs,
and the serving engine's per-bucket specs; docs/SHARDING.md), never
hand-written per combo.

Exit codes: 0 clean, 1 findings with severity=error, 2 unusable input.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bert_pytorch_tpu.analysis import passes as passes_mod  # noqa: E402

BUDGETS_SCHEMA_VERSION = 1
DEFAULT_BUDGETS = os.path.join(REPO, "results", "graph_budgets.json")
DEFAULT_REPORT = os.path.join(REPO, "results", "graph_report.json")

N_DEVICES = 8

# combo name -> step-builder variant. One entry per production program
# shape worth gating: the plain DP step, the bf16-compute step (dtype
# lint), the two ZeRO-1 modes (collective budgets + replication), the
# K-FAC step (its factor state is exactly what a fail-open gate silently
# replicates), a mixed dp x mp mesh (the composition the pre-rules
# ad-hoc specs never covered: zero1's appended data axis stacking onto
# model-sharded leaves), and one bucketed serving forward (kind="serve":
# the AOT inference program run_server.py dispatches — a single-device
# engine must compile ZERO collectives, and nothing may sit in the
# donated-but-never-aliased table). `mesh` overrides the default
# all-data 8-device shape. hbm_budget_mb is the per-device
# static-estimate ceiling for the tiny gate model — generous vs today's
# estimate, tight vs a 2x regression.
COMBOS = {
    "pretrain_dp8": dict(zero1=False, overlap=False, kfac=False,
                         dtype="f32", hbm_budget_mb=64),
    "pretrain_bf16_dp8": dict(zero1=False, overlap=False, kfac=False,
                              dtype="bf16", hbm_budget_mb=64),
    "zero1_dp8": dict(zero1=True, overlap=False, kfac=False,
                      dtype="f32", hbm_budget_mb=64),
    "zero1_overlap_dp8": dict(zero1=True, overlap=True, kfac=False,
                              dtype="f32", hbm_budget_mb=64),
    "zero1_dp2_mp4": dict(zero1=True, overlap=False, kfac=False,
                          dtype="f32", hbm_budget_mb=64,
                          mesh={"data": 2, "model": 4}),
    # fsdp gather-on-use (--fsdp_overlap) composed with the zero1 overlap
    # on a mixed dp x fsdp mesh: every point-of-use gather is an explicit
    # per-leaf node, with the collective budget an exact ceiling (the
    # GSPMD-fork regression class this gate exists for)
    "fsdp_overlap_dp2_fsdp4": dict(zero1=True, overlap=True, kfac=False,
                                   dtype="f32", hbm_budget_mb=64,
                                   mesh={"data": 2, "fsdp": 4},
                                   fsdp_overlap=True),
    "kfac_zero1_dp8": dict(zero1=True, overlap=False, kfac=True,
                           dtype="f32", hbm_budget_mb=96),
    # coalesced reductions (--coalesce_reductions): bucketed K-FAC factor
    # psums + bucketed LAMB trust/global norms. Its budget's all-reduce
    # ceiling is deliberately <= HALF of kfac_zero1_dp8's — the round-15
    # acceptance criterion, enforced as an exact count like every budget
    "kfac_zero1_dp8_bucketed": dict(zero1=True, overlap=False, kfac=True,
                                    dtype="f32", hbm_budget_mb=96,
                                    bucketed=True),
    # reduce-scatter gradient path (--zero1_rs): the ZeRO-1 update
    # consumes a psum_scatter'd gradient SHARD instead of slicing a full
    # all-reduce (half the gradient bytes on the wire). Requires
    # gather-on-use (overlap) and coalesced norms (bucketed) — without
    # the NormReducer the shard_map region's per-leaf trust norms would
    # blow the all-reduce count right back up. The round-16 acceptance
    # criterion rides on this budget: reduce_scatter > 0 AND all-reduce
    # <= HALF of zero1_dp8's 129, enforced as exact counts
    "zero1_rs_dp8": dict(zero1=True, overlap=True, kfac=False,
                         dtype="f32", hbm_budget_mb=64, rs=True,
                         bucketed=True),
    "kfac_zero1_rs_dp8": dict(zero1=True, overlap=True, kfac=True,
                              dtype="f32", hbm_budget_mb=96, rs=True,
                              bucketed=True),
    # 8 layers so the stacked-factor axis DIVIDES the dp8 shard count —
    # the only combo where K-FAC leaves carry sharding_rules
    # expectations (the 2-layer gate model's factors fall back to
    # replicated by the divisibility rule, which would leave K-FAC
    # placement unverified everywhere)
    "kfac_zero1_l8_dp8": dict(zero1=True, overlap=False, kfac=True,
                              dtype="f32", hbm_budget_mb=96, layers=8),
    "serve_qa_b4_s64": dict(kind="serve", dtype="f32", batch_rows=4,
                            bucket=64, hbm_budget_mb=32),
    # per-segment pooled classification forward (registry task
    # 'classify'): the first segment-kind serving program under the
    # lint — the pooled gather must stay collective-free and
    # donation-clean exactly like the token-kind QA forward
    "serve_cls_b4_s64": dict(kind="serve", task="classify", dtype="f32",
                             batch_rows=4, bucket=64, hbm_budget_mb=32),
    # model-parallel serving slice (run_server --serve_mesh model=2):
    # params shard through the SAME rules-table derivation the engine
    # uses (serving_param_shardings), so this forward legitimately
    # carries collectives — its budget pins exact NONZERO per-kind
    # ceilings, growing the serve lint beyond the single-device
    # zero-collective pin while the 1-dev combos keep theirs
    "serve_qa_b4_s64_mp2": dict(kind="serve", dtype="f32", batch_rows=4,
                                bucket=64, hbm_budget_mb=32,
                                mesh={"model": 2}),
    # the shared finetune driver's packed classification train step
    # (build_pretrain_step + tasks/classify.packed_loss_builder — the
    # exact production program run_finetune.py --task classify --packing
    # dispatches), with sharding-rules expectations derived from the
    # logical-axis-rules table for the registry task's batch contract
    "finetune_cls_dp8": dict(kind="finetune", dtype="f32",
                             hbm_budget_mb=64),
}

INJECTIONS = ("none", "no_donate", "replicated_state", "extra_gather",
              "extra_allreduce", "wrong_axis")


# -- jax-free: budget schema + diff -------------------------------------------


def load_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SystemExit(f"graphcheck: unreadable {path}: {e}")


def validate_budgets(budgets: dict) -> list:
    """Schema errors in a budget file (empty list = valid). Pure dict
    work — runs without jax."""
    errors = []
    if not isinstance(budgets, dict):
        return ["budget file is not a JSON object"]
    if budgets.get("schema_version") != BUDGETS_SCHEMA_VERSION:
        errors.append(f"schema_version {budgets.get('schema_version')!r} "
                      f"!= {BUDGETS_SCHEMA_VERSION}")
    combos = budgets.get("combos")
    if not isinstance(combos, dict) or not combos:
        return errors + ["'combos' missing or empty"]
    for name, combo in sorted(combos.items()):
        expect = combo.get("expect") if isinstance(combo, dict) else None
        if not isinstance(expect, dict):
            errors.append(f"combo '{name}': no 'expect' object")
            continue
        unknown = set(expect) - set(passes_mod.PASSES)
        if unknown:
            errors.append(f"combo '{name}': unknown expectation key(s) "
                          f"{sorted(unknown)}")
        cb = expect.get("collective_budget")
        if cb is not None:
            if not isinstance(cb, dict):
                errors.append(f"combo '{name}': collective_budget is not "
                              "an object")
            else:
                for kind, v in cb.items():
                    if not isinstance(v, int) or v < 0:
                        errors.append(
                            f"combo '{name}': collective_budget[{kind}] = "
                            f"{v!r} (want a non-negative int)")
        sr = expect.get("sharding_rules")
        if sr is not None:
            if not isinstance(sr, dict):
                errors.append(f"combo '{name}': sharding_rules is not "
                              "an object")
            else:
                mv = sr.get("min_verified")
                if not isinstance(mv, int) or mv < 0:
                    errors.append(
                        f"combo '{name}': sharding_rules.min_verified = "
                        f"{mv!r} (want a non-negative int)")
    return errors


def diff_reports(reports: dict, budgets: dict) -> dict:
    """{combo: [Finding]} for every combo present in BOTH the report set
    and the budget file; a combo missing from either side is reported as a
    finding on the side that has it (a silently-skipped combo is how gates
    rot)."""
    out = {}
    bcombos = budgets.get("combos", {})
    for name in sorted(set(reports) | set(bcombos)):
        if name not in reports:
            out[name] = [passes_mod.Finding(
                "warning", "coverage",
                "combo is budgeted but no report was built for it "
                "(--combos subset?)")]
            continue
        if name not in bcombos:
            out[name] = [passes_mod.Finding(
                "error", "coverage",
                "combo has a report but no checked-in budget — run "
                "graphcheck --write-budgets and commit the result")]
            continue
        out[name] = passes_mod.run_passes(
            reports[name], bcombos[name].get("expect", {}))
    return out


def print_findings(per_combo: dict, stream=None) -> int:
    """Human gate output; returns the number of error-severity findings."""
    stream = stream or sys.stdout
    n_err = 0
    for name in sorted(per_combo):
        findings = per_combo[name]
        if not findings:
            print(f"graphcheck: {name}: clean", file=stream)
            continue
        for f in findings:
            if f.severity == "error":
                n_err += 1
            print(f"graphcheck: {name}: {f}", file=stream)
    return n_err


def budgets_from_reports(reports: dict, meta: dict) -> dict:
    """Derive a budget file locking in the current programs: exact
    collective counts per kind (zero stays zero — a brand-new collective
    kind is a finding), the donation floor, the sharded-input floor, the
    combo's dtype expectation, and its HBM ceiling."""
    combos = {}
    for name, rep in sorted(reports.items()):
        spec = COMBOS.get(name, {})
        inputs = rep.get("inputs") or []
        n_sharded = sum(1 for r in inputs
                        if r.get("replicated") is False)
        n_verified = sum(1 for r in inputs
                         if r.get("matches_expected") is not None)
        donation_expect = {
            "min_aliased": rep.get("donation", {}).get("n_aliased", 0),
            "undonated_warn_bytes": 8 * 2**20,
        }
        n_orphans = rep.get("donation", {}).get("n_donated_unaliased", 0)
        if n_orphans:
            # budgeted orphan-donor allowance (passes.check_donation) —
            # emitted ONLY when nonzero so clean combos' budget blocks
            # stay byte-identical and keep the strict default
            donation_expect["max_donated_unaliased"] = n_orphans
        expect = {
            "collective_budget": dict(
                sorted(rep.get("collective_counts", {}).items())),
            "donation": donation_expect,
            "replication": {"min_sharded_inputs": n_sharded},
            "sharding_rules": {"min_verified": n_verified},
            "dtype": {"compute_dtype": spec.get("dtype", "f32"),
                      "max_f32_dots": (rep.get("dot_dtypes") or {}
                                       ).get("f32", 0)},
            "memory": {"budget_mb": spec.get("hbm_budget_mb", 64)},
        }
        combos[name] = {"expect": expect}
    return {"schema_version": BUDGETS_SCHEMA_VERSION, **meta,
            "combos": combos}


# -- jax side: build the reports ----------------------------------------------


def _force_cpu_devices() -> None:
    """Script entry only (tests inherit conftest's setup): force the
    8-device CPU host platform BEFORE jax initializes."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={N_DEVICES}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"


def _gate_config(dtype: str, kfac: bool, layers: int = 2):
    """The tiny-but-production-shaped gate model: every structural feature
    of the real step (tied embeddings, NSP head, gathered MLM head, LAMB,
    ZeRO-1) at compile-in-seconds scale. Structure, not scale, is what the
    gate checks. `layers` matters to the K-FAC combos: distributed factor
    ownership only engages when the stacked-layer axis divides the shard
    count (kfac_zero1_l8_dp8)."""
    from bert_pytorch_tpu.config import BertConfig

    return BertConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=layers,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, next_sentence=True,
        dtype="bfloat16" if dtype == "bf16" else "float32",
        fused_ops=False, attention_impl="xla",
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        kfac_taps=kfac)


def _gate_batch(vocab: int = 128, global_batch: int = 16, seq: int = 16,
                max_pred: int = 4):
    """Deterministic synthetic premasked batch (exactly max_pred masked
    positions per row — the gathered-MLM-head contract)."""
    import numpy as np

    from bert_pytorch_tpu.training.pretrain import stack_microbatches

    rng = np.random.RandomState(0)
    ids = rng.randint(5, vocab, (global_batch, seq)).astype(np.int32)
    labels = np.full((global_batch, seq), -1, np.int32)
    for b in range(global_batch):
        for p in rng.choice(np.arange(1, seq - 1), max_pred, replace=False):
            labels[b, p] = ids[b, p]
            ids[b, p] = 3
    return stack_microbatches({
        "input_ids": ids,
        "token_type_ids": np.zeros((global_batch, seq), np.int32),
        "attention_mask": np.ones((global_batch, seq), np.int32),
        "masked_lm_labels": labels,
        "next_sentence_labels": rng.randint(0, 2, (global_batch,)).astype(
            np.int32),
    }, 1)


# the serve_opts the gate hands the registry specs (run_server CLI
# defaults at gate-model scale; graphcheck's serve combos must build the
# same model heads production serving builds)
GATE_SERVE_OPTS = {"labels": ["B-X", "I-X", "O"],
                   "class_names": ["0", "1"], "num_choices": 2,
                   "embed_labels": 2, "max_segments": 4}


def build_serve_report(name: str, spec: dict, inject: str = "none") -> dict:
    """Lower + compile one bucketed serving forward — the PRODUCTION
    inference program (the registry task's forward_builder through the
    same StepProgram the engine dispatches) on a single device, exactly
    as a 1-dev run_server.py engine compiles it — or, with
    `spec['mesh']` (e.g. {"model": 2}), exactly as a `--serve_mesh`
    replica slice compiles it: params placed by the rules-table-derived
    `serving_param_shardings`, so the budget pins NONZERO per-kind
    collective ceilings. `spec['task']` names any tasks/registry.py
    entry (default squad); the single-device budget pins zero
    collectives of every kind and an empty donated-unaliased table."""
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.analysis.hlo import program_report
    from bert_pytorch_tpu.serving.engine import (bucket_input_expectations,
                                                 serving_param_shardings,
                                                 zero_batch)
    from bert_pytorch_tpu.tasks import registry as task_registry
    from bert_pytorch_tpu.training.pretrain import StepProgram
    from bert_pytorch_tpu.training.state import unbox

    if inject != "none":
        raise SystemExit(
            f"graphcheck: injection '{inject}' drills the pretrain "
            "combos; run it with --combos zero1_dp8 (or another "
            "pretrain combo)")

    cfg = _gate_config(spec["dtype"], kfac=False).replace(
        next_sentence=False)
    compute_dtype = jnp.bfloat16 if spec["dtype"] == "bf16" else jnp.float32
    tspec = task_registry.get(spec.get("task", "squad"))
    model = tspec.build_serving_model(cfg, compute_dtype, GATE_SERVE_OPTS)
    bucket, rows = int(spec["bucket"]), int(spec["batch_rows"])
    sample = jnp.zeros((1, bucket), jnp.int32)
    params = unbox(model.init(jax.random.PRNGKey(0), sample, sample,
                              sample)["params"])

    mesh = None
    if spec.get("mesh"):
        from jax.sharding import NamedSharding

        from bert_pytorch_tpu.parallel import rules as rules_lib
        from bert_pytorch_tpu.parallel.mesh import make_mesh

        n_dev = 1
        for v in spec["mesh"].values():
            n_dev *= int(v)
        if jax.device_count() < n_dev:
            raise SystemExit(
                f"graphcheck: combo {name} needs {n_dev} devices, "
                f"have {jax.device_count()}")
        mesh = make_mesh(dict(spec["mesh"]), devices=jax.devices()[:n_dev])
        shardings, _ = serving_param_shardings(model, bucket, mesh)
        params = jax.device_put(params, shardings)
        batch = jax.device_put(
            zero_batch(rows, bucket),
            NamedSharding(mesh, rules_lib.batch_spec(0, mesh)))
    else:
        batch = {k: jnp.asarray(v)
                 for k, v in zero_batch(rows, bucket).items()}

    prog = StepProgram(tspec.forward_builder(model), donate_state=False)
    lowered = prog.lower(params, batch)
    lowered_text = lowered.as_text()
    compiled = prog.compile()

    # the engine's per-bucket specs, derived from the rules table (on
    # the single-device engine: everything replicated; on a serve mesh:
    # model-sharded mlp/heads/vocab leaves — derived, not hand-pinned),
    # verified against the compiled in-shardings by the sharding_rules
    # pass
    expected, exp_rules = bucket_input_expectations(model, bucket, mesh)
    rep = program_report(compiled, args=(params, batch),
                         expected=expected, rules=exp_rules,
                         lowered_text=lowered_text, label=name)
    rep["combo"] = dict(spec, inject=inject)
    return rep


def build_finetune_report(name: str, spec: dict,
                          inject: str = "none") -> dict:
    """Lower + compile the shared finetune driver's PACKED classification
    train step on the 8-device mesh — build_pretrain_step wired with
    tasks/classify.packed_loss_builder, fed a batch assembled by the
    SAME packer + registry label packer the driver uses
    (training/finetune.pack_finetune_batch + classify.pack_labels), so
    the gated batch contract is registry-derived rather than
    hand-written. step_input_expectations verifies every input leaf
    against the logical-axis-rules table (sharding_rules pass)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bert_pytorch_tpu.analysis.hlo import program_report
    from bert_pytorch_tpu.models import BertForSequenceClassification
    from bert_pytorch_tpu.optim import schedulers
    from bert_pytorch_tpu.optim.adam import fused_adam
    from bert_pytorch_tpu.optim.lamb import default_weight_decay_mask
    from bert_pytorch_tpu.parallel import mesh as mesh_lib
    from bert_pytorch_tpu.tasks import classify
    from bert_pytorch_tpu.training import make_sharded_state
    from bert_pytorch_tpu.training.finetune import pack_finetune_batch
    from bert_pytorch_tpu.training.pretrain import (StepProgram,
                                                    build_pretrain_step,
                                                    step_input_expectations)
    from bert_pytorch_tpu.training.state import abstract_train_state

    if inject != "none":
        raise SystemExit(
            f"graphcheck: injection '{inject}' drills the pretrain "
            "combos; run it with --combos zero1_dp8 (or another "
            "pretrain combo)")
    if jax.device_count() < N_DEVICES:
        raise SystemExit(
            f"graphcheck: {jax.device_count()} devices visible, need "
            f"{N_DEVICES}")

    cfg = _gate_config(spec["dtype"], kfac=False)
    compute_dtype = jnp.bfloat16 if spec["dtype"] == "bf16" else jnp.float32
    G, rows, seq = 4, 16, 16
    model = BertForSequenceClassification(cfg, num_labels=2,
                                          max_segments=G,
                                          dtype=compute_dtype)
    sched = schedulers.poly_warmup_schedule(1e-4, total_steps=100,
                                            warmup=0.1)
    import optax

    tx = optax.chain(
        optax.clip_by_global_norm(1.0),
        fused_adam(sched, weight_decay=0.01,
                   weight_decay_mask=default_weight_decay_mask,
                   bias_correction=False))

    # deterministic synthetic pair-classification examples, packed by
    # the production packer (first-fit, per-segment labels)
    rng_np = np.random.RandomState(0)
    n_ex = 48
    lens = 3 + rng_np.randint(0, seq - 3, n_ex)
    arrays = {
        "input_ids": np.zeros((n_ex, seq), np.int32),
        "token_type_ids": np.zeros((n_ex, seq), np.int32),
        "attention_mask": np.zeros((n_ex, seq), np.int32),
        "labels": rng_np.randint(0, 2, n_ex).astype(np.int32),
    }
    for i, ln in enumerate(lens):
        arrays["input_ids"][i, :ln] = rng_np.randint(5, cfg.vocab_size, ln)
        arrays["token_type_ids"][i, ln // 2:ln] = 1
        arrays["attention_mask"][i, :ln] = 1
    batch_fields, placements = pack_finetune_batch(
        arrays, list(range(n_ex)), n_rows=rows, seq_len=seq,
        max_segments=G)
    batch_fields.update(classify.pack_labels(arrays, placements, rows,
                                             seq, G))
    batch_np = {k: v[None] for k, v in batch_fields.items()}  # (1, B, ..)

    mesh = mesh_lib.make_mesh(spec.get("mesh"),
                              devices=jax.devices()[:N_DEVICES])
    sample = jnp.zeros((2, seq), jnp.int32)

    def init_fn(r):
        return model.init(r, sample, sample, sample)

    with mesh_lib.logical_rules():
        state, _shardings = make_sharded_state(
            jax.random.PRNGKey(0), init_fn, tx, mesh=mesh)
    step_fn = build_pretrain_step(
        model, tx, schedule=sched,
        loss_fn_builder=classify.packed_loss_builder)

    batch = mesh_lib.host_to_device_batch(mesh, batch_np)
    rng = jax.random.PRNGKey(0)
    prog = StepProgram(step_fn)
    with mesh, mesh_lib.logical_rules():
        lowered = prog.lower(state, batch, rng)
        lowered_text = lowered.as_text()
        compiled = prog.compile()

    with mesh_lib.logical_rules():
        abstract = abstract_train_state(jax.random.PRNGKey(0), init_fn, tx)
    expected, exp_rules = step_input_expectations(abstract, state, batch,
                                                  mesh)
    rep = program_report(compiled, args=(state, batch, rng),
                         expected=expected, rules=exp_rules,
                         lowered_text=lowered_text, label=name)
    rep["combo"] = dict(spec, inject=inject)
    return rep


def build_report(name: str, spec: dict, inject: str = "none") -> dict:
    """Lower + compile one combo's production step on the 8-device mesh
    and return its program report. `inject` compiles a deliberately
    broken program for gate drills (see module docstring)."""
    import jax
    import jax.numpy as jnp

    if spec.get("kind") == "serve":
        return build_serve_report(name, spec, inject=inject)
    if spec.get("kind") == "finetune":
        return build_finetune_report(name, spec, inject=inject)

    from bert_pytorch_tpu.analysis.hlo import program_report
    from bert_pytorch_tpu.models import BertForPreTraining
    from bert_pytorch_tpu.optim import schedulers
    from bert_pytorch_tpu.optim.lamb import (default_trust_batch_axes,
                                             default_weight_decay_mask, lamb)
    from bert_pytorch_tpu.parallel import mesh as mesh_lib
    from bert_pytorch_tpu.parallel.zero import make_zero1_plan
    from bert_pytorch_tpu.training import make_sharded_state
    from bert_pytorch_tpu.training.pretrain import (StepProgram,
                                                    build_pretrain_step,
                                                    step_input_expectations)
    from bert_pytorch_tpu.training.state import abstract_train_state

    if jax.device_count() < N_DEVICES:
        raise SystemExit(
            f"graphcheck: {jax.device_count()} devices visible, need "
            f"{N_DEVICES} (set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={N_DEVICES})")
    if inject not in INJECTIONS:
        raise SystemExit(f"graphcheck: unknown injection '{inject}'")

    cfg = _gate_config(spec["dtype"], spec["kfac"],
                       layers=spec.get("layers", 2))
    compute_dtype = jnp.bfloat16 if spec["dtype"] == "bf16" else jnp.float32
    grad_dtype = jnp.bfloat16 if spec["dtype"] == "bf16" else None
    model = BertForPreTraining(cfg, dtype=compute_dtype)
    sched = schedulers.poly_warmup_schedule(1e-3, total_steps=100,
                                            warmup=0.1)
    tx = lamb(sched, weight_decay=0.01,
              weight_decay_mask=default_weight_decay_mask,
              trust_batch_axes=default_trust_batch_axes)
    batch_np = _gate_batch(vocab=cfg.vocab_size)
    mesh = mesh_lib.make_mesh(spec.get("mesh"),
                              devices=jax.devices()[:N_DEVICES])

    def init_fn(r):
        return model.init(r, jnp.asarray(batch_np["input_ids"][0]),
                          jnp.asarray(batch_np["token_type_ids"][0]),
                          jnp.asarray(batch_np["attention_mask"][0]))

    # `replicated_state` drill: the TrainState is built with the ZeRO-1
    # storage sharding FAILED OPEN (the PR-2 bug class) while the plan and
    # the budget still expect it — the replication pass must name the
    # replicated moment leaves.
    state_zero1 = spec["zero1"] and inject != "replicated_state"
    with mesh_lib.logical_rules():
        state, shardings = make_sharded_state(
            jax.random.PRNGKey(0), init_fn, tx, mesh=mesh,
            zero1=state_zero1,
            zero1_params=spec["overlap"] and state_zero1)

    plan = (make_zero1_plan(state.params, shardings.params, mesh,
                            gather_on_use=spec["overlap"] and state_zero1,
                            reduce_scatter=spec.get("rs", False)
                            and state_zero1,
                            warn_skipped=False)
            if spec["zero1"] else None)
    if spec.get("fsdp_overlap"):
        from bert_pytorch_tpu.parallel.zero import make_fsdp_plan

        plan = make_fsdp_plan(state.params, shardings.params, mesh,
                              zero1=plan is not None,
                              warn_skipped=False) or plan

    norm_reducer = None
    if spec.get("bucketed") and plan is not None:
        # the --coalesce_reductions wiring, exactly as run_pretraining
        # builds it: one NormReducer shared by LAMB and the grad_norm
        # metric, built from the SAME layout tree the plan derived
        from bert_pytorch_tpu.parallel.coalesce import NormReducer

        norm_reducer = NormReducer(plan.grad_shardings, mesh)
        tx = lamb(sched, weight_decay=0.01,
                  weight_decay_mask=default_weight_decay_mask,
                  trust_batch_axes=default_trust_batch_axes,
                  norm_reducer=norm_reducer)

    kfac = None
    if spec["kfac"]:
        from bert_pytorch_tpu.optim.kfac import KFAC, KFACConfig
        from bert_pytorch_tpu.training.pretrain import (
            build_kfac_pretrain_step, init_kfac_state)

        kfac = KFAC(KFACConfig(learning_rate=sched), mesh=mesh,
                    factor_bucket_bytes=(4 << 20) if spec.get("bucketed")
                    else None)
        state, pert_template = init_kfac_state(
            model, kfac, state,
            (batch_np["input_ids"][0], batch_np["token_type_ids"][0],
             batch_np["attention_mask"][0]))
        step_fn = build_kfac_pretrain_step(
            model, tx, kfac, pert_template, schedule=sched,
            max_predictions=4, grad_dtype=grad_dtype, zero1=plan,
            norm_reducer=norm_reducer)
    else:
        step_fn = build_pretrain_step(
            model, tx, schedule=sched, max_predictions=4,
            grad_dtype=grad_dtype, zero1=plan,
            norm_reducer=norm_reducer)

    if inject == "extra_gather":
        from jax.sharding import NamedSharding, PartitionSpec

        base_step = step_fn

        def step_fn(state, batch, rng):  # noqa: F811 — the drill wrapper
            new_state, metrics = base_step(state, batch, rng)
            leaf = jax.tree.leaves(new_state.opt_state.mu)[0]
            rep = jax.lax.with_sharding_constraint(
                leaf, NamedSharding(mesh, PartitionSpec()))
            metrics["injected_gather_probe"] = jnp.sum(rep)
            return new_state, metrics

    if inject == "extra_allreduce":
        base_step = step_fn

        def step_fn(state, batch, rng):  # noqa: F811 — the drill wrapper
            new_state, metrics = base_step(state, batch, rng)
            # a full-tree reduction over a ZeRO-1-sharded mu leaf: GSPMD
            # partial-sums locally then all-reduces the scalar — one
            # unbudgeted all-reduce the exact ceiling must catch
            leaf = jax.tree.leaves(new_state.opt_state.mu)[0]
            metrics["injected_allreduce_probe"] = jnp.sum(
                leaf.astype(jnp.float32))
            return new_state, metrics

    batch = mesh_lib.host_to_device_batch(mesh, batch_np)
    rng = jax.random.PRNGKey(0)
    prog = StepProgram(step_fn, donate_state=(inject != "no_donate"))
    with mesh, mesh_lib.logical_rules():
        lowered = prog.lower(state, batch, rng)
        lowered_text = lowered.as_text()
        compiled = prog.compile()

    args = (state, batch, rng)
    # expected in-shardings + the rule labels that derived them, straight
    # from the logical-axis-rules table (parallel/rules.py via
    # training/pretrain.step_input_expectations) — NOT read back from the
    # built state, so a state construction failed open (the
    # replicated_state drill, or a real PR-2-class bug) still faces the
    # table's expectations
    with mesh_lib.logical_rules():
        abstract = abstract_train_state(jax.random.PRNGKey(0), init_fn, tx)
    expected, exp_rules = step_input_expectations(
        abstract, state, batch, mesh, zero1=spec["zero1"],
        zero1_params=spec["overlap"] and spec["zero1"],
        kfac_shard_axes=kfac.shard_axes if kfac is not None else None)
    if inject == "wrong_axis":
        expected, exp_rules = _inject_wrong_axis(expected, exp_rules, mesh)

    rep = program_report(compiled, args=args, expected=expected,
                         rules=exp_rules, lowered_text=lowered_text,
                         label=name)
    rep["combo"] = dict(spec, inject=inject)
    return rep


def _inject_wrong_axis(expected: list, labels: list, mesh):
    """The sharding_rules gate drill: re-derive ONE leaf's expected spec
    with its mesh axes deliberately swapped (data <-> model), so the
    compiled in-sharding can no longer match and the pass must exit 1
    naming the rule, the leaf path, and both shardings."""
    from jax.sharding import NamedSharding, PartitionSpec

    def swap(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            return tuple(swap(e) for e in entry)
        return {"data": "model", "model": "data"}.get(entry, entry)

    for i, sh in enumerate(expected):
        spec = getattr(sh, "spec", None)
        if spec is None or "data" not in str(spec):
            continue
        expected, labels = list(expected), list(labels)
        expected[i] = NamedSharding(
            mesh, PartitionSpec(*[swap(e) for e in tuple(spec)]))
        labels[i] = f"{labels[i]}+wrong_axis_drill[data<->model]"
        return expected, labels
    raise SystemExit("graphcheck: wrong_axis inject found no leaf with a "
                     "'data'-sharded expectation to swap")


def build_reports(combos, inject: str = "none",
                  progress=None) -> dict:
    out = {}
    for name in combos:
        if name not in COMBOS:
            raise SystemExit(f"graphcheck: unknown combo '{name}' "
                             f"(known: {', '.join(sorted(COMBOS))})")
        if progress:
            progress(f"graphcheck: compiling {name} ...")
        out[name] = build_report(name, COMBOS[name], inject=inject)
    return out


def _meta() -> dict:
    import jax

    return {"platform": jax.devices()[0].platform,
            "num_partitions": N_DEVICES,
            "jax_version": jax.__version__}


# -- CLI ----------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--combos", default=None,
                    help="comma-separated subset (default: all)")
    ap.add_argument("--budgets", default=DEFAULT_BUDGETS)
    ap.add_argument("--report", default=None,
                    help="report output path. Default: results/"
                         "graph_report.json for a full clean run; a temp "
                         "path for --combos subsets and --inject drills, "
                         "so partial/broken reports never overwrite the "
                         "checked-in artifact")
    ap.add_argument("--write-budgets", action="store_true",
                    help="re-baseline the budget file from the current "
                         "programs instead of gating")
    ap.add_argument("--validate-budgets", action="store_true",
                    help="jax-free: schema-check the budget file and diff "
                         "an existing report against it")
    ap.add_argument("--report-only", action="store_true",
                    help="build + write the report, skip the gate")
    ap.add_argument("--inject", default="none", choices=INJECTIONS,
                    help="compile a deliberately-broken program (gate "
                         "drill; see module docstring)")
    args = ap.parse_args(argv)

    report_path = args.report
    if report_path is None:
        if args.inject != "none" or args.combos:
            # a drill or subset report is partial/deliberately broken —
            # it must never overwrite the checked-in full-matrix artifact
            # (--validate-budgets diffs it)
            import tempfile

            report_path = os.path.join(
                tempfile.mkdtemp(prefix="graphcheck_"),
                "graph_report.json")
            print(f"graphcheck: subset/drill run — report goes to "
                  f"{report_path}, not {DEFAULT_REPORT}", file=sys.stderr)
        else:
            report_path = DEFAULT_REPORT

    if args.validate_budgets:
        budgets = load_json(args.budgets)
        errors = validate_budgets(budgets)
        for e in errors:
            print(f"graphcheck: budget schema: {e}")
        if errors:
            return 2
        print(f"graphcheck: {args.budgets} schema ok "
              f"({len(budgets['combos'])} combo(s))")
        report_path = args.report or DEFAULT_REPORT
        if os.path.exists(report_path):
            reports = load_json(report_path).get("combos", {})
            n_err = print_findings(diff_reports(reports, budgets))
            return 1 if n_err else 0
        print(f"graphcheck: no report at {report_path} — schema check only")
        return 0

    combos = (args.combos.split(",") if args.combos
              else sorted(COMBOS))
    if args.inject != "none" and not args.combos:
        # injections drill the pretrain step builders; an implicit full
        # matrix must skip the serve/finetune combos (an explicitly-
        # requested one still errors loudly in its builder)
        skipped = [c for c in combos
                   if COMBOS[c].get("kind") in ("serve", "finetune")]
        if skipped:
            print(f"graphcheck: inject drill — skipping serve/finetune "
                  f"combo(s) {', '.join(skipped)}", file=sys.stderr)
            combos = [c for c in combos if c not in skipped]
    reports = build_reports(combos, inject=args.inject,
                            progress=lambda m: print(m, file=sys.stderr))

    os.makedirs(os.path.dirname(os.path.abspath(report_path)) or ".",
                exist_ok=True)
    doc = {"schema_version": 1, **_meta(), "combos": reports}
    with open(report_path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"graphcheck: wrote {report_path} ({len(reports)} combo(s))",
          file=sys.stderr)

    if args.write_budgets:
        budgets = budgets_from_reports(reports, _meta())
        with open(args.budgets, "w", encoding="utf-8") as f:
            json.dump(budgets, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"graphcheck: re-baselined {args.budgets} — commit it with "
              "a note on WHY the program changed")
        return 0
    if args.report_only:
        return 0

    if not os.path.exists(args.budgets):
        print(f"graphcheck: no budget file at {args.budgets} — run "
              "graphcheck --write-budgets to create one", file=sys.stderr)
        return 2
    budgets = load_json(args.budgets)
    errors = validate_budgets(budgets)
    if errors:
        for e in errors:
            print(f"graphcheck: budget schema: {e}")
        return 2
    n_err = print_findings(diff_reports(reports, budgets))
    if n_err:
        print(f"graphcheck: FAILED — {n_err} error finding(s); if the "
              "program change is intentional, re-baseline with "
              "--write-budgets and commit the new budgets")
        return 1
    print("graphcheck: all combos within budget")
    return 0


if __name__ == "__main__":
    _force_cpu_devices()
    sys.exit(main())
