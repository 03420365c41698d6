"""The `kimi_linear` family (`"model_type": "kimi_linear"`): causal-LM
pretraining of gated delta-rule linear-attention (KDA) and latent-attention
(MLA, no positions) blocks with sigmoid-routed experts beside a shared one,
over packed rows.

Everything the benchmark knows about the family is named here: its
reference (reference/kimi_linear_ref.py) with the weights it makes from the
seed, its adapter (harness/kimi_adapter.py), its FLOPs (harness/
kimi_flops.py: the slots' products, the chunked scan and the documents'
causal pairs), and how the followed steps are followed (a row at a time).
What a causal-LM family of routed experts over packed rows needs whatever
its layers (the held experts' counts against the reference's near ties with
the padding slots taken out, a batch's fields, the program's counters, the
documents' causal pairs) is families/lfm2_moe.py's, used as it is.
`harness/spec.load_family` says which names a family module defines.
"""

from __future__ import annotations

import numpy as np

from benchmark.families import lfm2_moe as routed_lm
from benchmark.harness import kimi_flops as flops  # the readers' ctx["flops"]

TOPK_KEY = "num_experts_per_token"  # this family's spelling (routed_lm's)
followed_by_program = routed_lm.followed_by_program
compare_extras = routed_lm.compare_extras
program_args = routed_lm.program_args


# -- the driver's side (this process stays off JAX) ------------------------

def window_flops(cell: dict, window: dict):
    """(forward + backward FLOPs the window's steps need, what they count)."""
    pairs = sum(window["causal_pairs"][str(s)] for s in range(
        window["first_step"], window["last_step"] + 1))
    return (flops.train_flops(cell["config"], window["slot_tokens"], pairs),
            "of the slots, the chunked KDA scan and the documents' causal "
            "latent attention")


def decide(cell: dict, record: dict, check) -> None:
    """The routed layers' checks of families/lfm2_moe.decide (told this
    family's `TOPK_KEY`), and the scans' counter: the tokens the program
    counted on the device (slots that are no padding, times the KDA layers)
    against the real tokens the harness counted in the same steps' inputs."""
    routed_lm.decide(cell, record, check, TOPK_KEY)
    w = record["window"]
    kda_layers = sum(1 for mixer, _ in flops.layer_kinds(cell["config"])
                     if mixer == "kda")
    want = w["real_tokens"] * kda_layers
    got = sum(w["kda_tokens"].get(str(s), 0) for s in range(
        w["first_step"], w["last_step"] + 1))
    check("kda_tokens", "tokens that are no padding the program counted for "
          "its KDA scans in the window, all layers", got, want, got == want)


# -- the child's side ------------------------------------------------------

def sizes(config: dict, traffic: dict) -> dict:
    from benchmark.reference import kimi_linear_ref

    return kimi_linear_ref.sizes_from_config(config)


def weights(spec: dict, sz: dict) -> dict:
    """The benchmark's weights from the seed, in the program's layout."""
    import jax

    from benchmark.reference import kimi_linear_ref

    tree = kimi_linear_ref.init_params(spec["seed"], sz)
    if spec.get("fault") == "zero_bias":
        # tests only: a program that selects its experts by score alone
        tree = jax.tree_util.tree_map_with_path(
            lambda path, x: x * 0 if getattr(
                path[-1], "key", None) == "expert_bias" else x, tree)
    return tree


def adapter_functions(sz: dict):
    """(leaf_norms, leaf_diff_norms, sample_matrices) of trees in the
    program's layout."""
    from benchmark.harness import kimi_adapter as a

    return (a.leaf_norms, a.leaf_diff_norms,
            lambda tree: a.sample_matrices(tree, sz["kinds"]))


def follow(spec: dict, sz: dict, batches: list, keys: list,
           quant=None) -> dict:
    """The reference's losses, first clipped gradient, parameter change and
    expert counts over the observed steps' own inputs."""
    import jax

    from benchmark.harness import kimi_adapter as adapter
    from benchmark.harness.adapter import place_for_reference
    from benchmark.reference import kimi_linear_ref as ref

    t = spec["traffic"]
    params = place_for_reference(ref.init_params(spec["seed"], sz), False)
    opt = ref.lamb_init(params)
    losses, counts, ties, padding = [], [], [], []
    grad_norms = grad_sample = None
    tie_tol = float(t["limits"]["tie_tol"])
    for batch in batches:
        padding.append(routed_lm.pad_slots(ref, params, batch, sz, quant,
                                           tie_tol))
        micros = [place_for_reference(
            {k: batch[k][i] for k in ("input_ids", "segment_ids")}, False)
            for i in range(batch["input_ids"].shape[0])]
        loss, grads, count, tie = ref.step_loss_and_grad(
            params, micros, sz, quant, tie_tol)
        losses.append(float(loss))
        counts.append(np.asarray(jax.device_get(count)).tolist())
        ties.append(np.asarray(jax.device_get(tie)).tolist())
        if grad_norms is None:
            clipped, _ = jax.jit(ref.clipped_gradient)(grads)
            grad_norms = adapter.leaf_norms(clipped)
            grad_sample = adapter.sample_matrices(clipped, sz["kinds"])
            del clipped
        params, opt = ref.lamb_step(
            params, grads, opt, float(t["learning_rate"]),
            int(t["max_steps"]), float(t["warmup_proportion"]))
        del grads
        if batch is not batches[-1]:
            # the moments wait on the host: beside them (4.8 GB at the
            # cell's size) the next step's row pass does not fit
            opt = jax.device_get(opt)
    del opt
    start = place_for_reference(ref.init_params(spec["seed"], sz), False)
    delta_norms = adapter.leaf_diff_norms(params, start)
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_sample": grad_sample, "delta_norms": delta_norms,
            "expert_counts": counts, "near_ties": ties, "padding": padding}


def window_extras(segs: dict, scalars: dict, cell: dict) -> dict:
    """What the family adds to the window's record: lfm2's (each timed
    step's causal pairs, the held pairs left out over the whole run) and
    the tokens that are no padding each timed step counted for its KDA
    scans."""
    return dict(routed_lm.window_extras(segs, scalars, cell),
                kda_tokens={n: int(scalars[n].get("kda_tokens", 0))
                            for n in segs if n in scalars})
