#!/usr/bin/env python
"""Per-device K-FAC state footprint: distributed ownership vs replicated.

BERT-Large + K-FAC does not fit one 16G chip with replicated factors
(batch 8, accum 8, un-rematted, was seen to need 28.6G on an earlier
runtime); the reference hit the same wall on GPUs and
distributed inverse ownership (HYBRID_OPT, grad_worker_fraction,
run_pretraining.py:325-327). This audit builds the production-shape
KFACState for BERT-Large on an 8-device virtual mesh in both layouts and
prints the PER-DEVICE bytes for factors and inverses — the number that
decides HBM fit on a pod slice.

Run: python scripts/kfac_shard_audit.py    (CPU; ~1 min)
Writes results/kfac_shard_audit.json.
"""

from __future__ import annotations

import json
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def state_bytes(tree) -> dict:
    """(total_bytes, per_device_bytes) over every array leaf — from the
    analyzer's shared per-leaf sharding table (analysis/hlo.sharding_leaves,
    the same walk behind graphcheck's replication pass and
    parallel/zero.assert_moments_sharded), not a private shard loop."""
    from bert_pytorch_tpu.analysis.hlo import sharding_leaves

    leaves = sharding_leaves(tree)
    total = sum(row["bytes"] for row in leaves)
    per_dev = sum(row["per_device_bytes"] for row in leaves)
    return {"total_mb": round(total / 2**20, 1),
            "per_device_mb": round(per_dev / 2**20, 1)}


def unexpected_replication(tree, mesh) -> list:
    """Findings for every leaf that SHOULD be distributed but is fully
    replicated. The expectation comes from the SAME placement derivation
    KFAC.init applies — optim/kfac.state_shardings, which routes through
    the logical-axis-rules table (parallel/rules.stacked_spec): leaves
    whose leading stacked-layer axis the table distributes are expected
    sharded, everything the table deliberately leaves replicated
    (pooler/NSP 2D sites, non-divisible stacks) carries no expectation.
    The audit's former private rank>=3 + min-bytes heuristic is retired
    into that one derivation, so the audit, the live state, and the
    graphcheck sharding_rules gate can never disagree. This is the
    unexpected-replication pass from bert_pytorch_tpu/analysis — the
    audit's former eyeball check, now the same rule CI runs over the
    compiled train step (tools/graphcheck.py)."""
    from bert_pytorch_tpu.analysis.hlo import sharding_leaves
    from bert_pytorch_tpu.analysis.passes import replication_findings
    from bert_pytorch_tpu.optim.kfac import state_shardings

    leaves = sharding_leaves(tree, expected=state_shardings(tree, mesh))
    return [f.to_dict() for f in
            replication_findings(leaves, rule="kfac_shard_audit")]


def main() -> None:
    from bert_pytorch_tpu.config import BertConfig, pad_vocab_size
    from bert_pytorch_tpu.models import BertForPreTraining
    from bert_pytorch_tpu.optim.kfac import KFAC, KFACConfig
    from bert_pytorch_tpu.parallel import mesh as mesh_lib

    cfg = BertConfig.from_json_file(
        os.path.join(REPO, "configs/bert_large_uncased_config.json"))
    cfg = cfg.replace(vocab_size=pad_vocab_size(cfg.vocab_size, 128),
                      kfac_taps=True, fused_ops=False, attention_impl="xla",
                      hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0)
    model = BertForPreTraining(cfg, dtype=jnp.bfloat16)

    ids = np.ones((2, 8), np.int32)
    variables = jax.eval_shape(
        lambda r: model.init(r, jnp.asarray(ids), jnp.asarray(ids),
                             jnp.asarray(ids)), jax.random.PRNGKey(0))
    pert = jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype),
                        variables["perturbations"])
    params = jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype),
                          variables["params"])
    acts_shape = jax.eval_shape(
        lambda p, pe: model.apply(
            {"params": p, "perturbations": pe}, jnp.asarray(ids),
            jnp.asarray(ids), jnp.asarray(ids),
            mutable=["kfac_in"])[1]["kfac_in"],
        params, pert)
    acts0 = jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype),
                         acts_shape, is_leaf=lambda x: hasattr(x, "shape"))

    mesh = mesh_lib.make_mesh({"data": 4, "fsdp": 2})
    out = {"mesh": dict(mesh.shape), "model": "bert_large (24 layers)"}
    for label, kf in (
            ("replicated", KFAC(KFACConfig())),
            ("sharded", KFAC(KFACConfig(), mesh=mesh))):
        state = kf.init(acts0, pert)
        out[label] = {
            "factors": state_bytes(state.factors),
            "inverses": state_bytes(state.inverses),
        }
        if label == "sharded":
            # distributed ownership must actually distribute: any MB-scale
            # factor/inverse leaf left fully replicated is a fail-open gate
            findings = (unexpected_replication(state.factors, mesh)
                        + unexpected_replication(state.inverses, mesh))
            out[label]["unexpected_replication"] = findings
            for f in findings:
                print(f"WARNING: {f['rule']}: {f['leaf']}: {f['message']}",
                      file=sys.stderr)
        del state
    rep = out["replicated"]
    sh = out["sharded"]
    out["per_device_reduction"] = round(
        (rep["factors"]["per_device_mb"] + rep["inverses"]["per_device_mb"])
        / max(sh["factors"]["per_device_mb"]
              + sh["inverses"]["per_device_mb"], 1e-9), 2)
    os.makedirs(os.path.join(REPO, "results"), exist_ok=True)
    with open(os.path.join(REPO, "results/kfac_shard_audit.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
