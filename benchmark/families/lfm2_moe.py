"""The `lfm2_moe` family (`"model_type": "lfm2_moe"`): causal-LM pretraining
of gated short-convolution and grouped-query attention blocks with
sigmoid-routed experts, over packed rows.

Everything the benchmark knows about the family is named here and nowhere
else: its reference (reference/lfm2_moe_ref.py) with the weights it makes
from the seed, its adapter (harness/lm_adapter.py), what of a batch the
reference needs (ids and segments; no masking, no dropout key), how the
followed steps are followed (a row at a time), its FLOPs (harness/
lm_flops.py: the slots' products and the documents' causal pairs), and, on
top of the driver's `decide_correct`, the routed layers' checks: held-expert
token counts of the followed steps against the reference's, and no held pair
left out. `harness/spec.load_family` says which names a family module has
to define.
"""

from __future__ import annotations

import re

import numpy as np

from benchmark.harness import lm_flops as flops  # the readers' ctx["flops"]

_EXPERT_LOAD = re.compile(r"^moe_l(\d+)_e(\d+)$")
# the configuration's key for the experts a token selects: a family of
# routed experts that spells it otherwise keeps a value of its own under
# this name and hands it to `decide` with its cell (families/kimi_linear.py)
TOPK_KEY = "num_experts_per_tok"


# -- the driver's side (this process stays off JAX) ------------------------

def window_flops(cell: dict, window: dict):
    """(forward + backward FLOPs the window's steps need, what they count)."""
    pairs = sum(window["causal_pairs"][str(s)] for s in range(
        window["first_step"], window["last_step"] + 1))
    return (flops.train_flops(cell["config"], window["slot_tokens"], pairs),
            "of the slots and of the documents' causal attention")


def decide(cell: dict, record: dict, check,
           topk_key: str = TOPK_KEY) -> None:
    """Top-k selection is discrete: a token whose k-th and (k+1)-th
    selection scores lie within rounding of each other may pick another
    expert than the reference. Per followed step and routed layer: the
    held experts' token counts beside the reference's, their L1 gap, and
    the reference's count of tokens within `tie_tol` of a tie; the gap has
    to stay under the latter (of all 64 experts' flips only those that
    touch a held expert move a count here). A step's padding slots are ONE
    token repeated some hundreds of times (`pad_slots`), so one selection
    that rounding turns moves them all: they are taken off the reference's
    counts and its near ties, and off the program's at whichever k experts
    or fewer that leaves the smallest gap. And no held pair may have been
    left out. `topk_key`: where the cell's configuration says how many
    experts a token selects."""
    e = record["compare"]["experts"]
    k = int(cell["config"][topk_key])
    for step, (got, want, ties, pad) in enumerate(zip(
            e["program"], e["reference"], e["near_ties"], e["padding"])):
        n = pad["slots"]
        for layer, (g, w, tie, at, near) in enumerate(zip(
                got, want, ties, pad["counts"], pad["near_ties"])):
            over = [a - (b - n * c) for a, b, c in zip(g, w, at)]
            fits = sorted((abs(x) - abs(x - n) for x in over), reverse=True)
            gap = sum(abs(x) for x in over) - sum(
                f for f in fits[:k] if f > 0)
            tie -= n * near
            check(f"experts_l1_step{step + 1}_layer{layer}",
                  f"step {step + 1} routed layer {layer} held-expert tokens "
                  f"{g} vs reference {w}, {n} of them padding slots at "
                  f"{at}: L1 gap without those", gap,
                  f"{tie} near-tie tokens", gap <= tie)
    dropped = record["window"]["dropped_pairs"]
    check("dropped_pairs", "held (token, expert) pairs not computed, whole "
          "run", dropped, 0, dropped == 0)


# -- the child's side ------------------------------------------------------

def sizes(config: dict, traffic: dict) -> dict:
    from benchmark.reference import lfm2_moe_ref

    return lfm2_moe_ref.sizes_from_config(config)


def program_args(traffic: dict) -> list:
    """No argument of the program's is this family's alone."""
    return []


def weights(spec: dict, sz: dict) -> dict:
    """The benchmark's weights from the seed, in the program's layout."""
    import jax

    from benchmark.harness import lm_adapter
    from benchmark.reference import lfm2_moe_ref

    tree = lm_adapter.to_program_tree(
        lfm2_moe_ref.init_params(spec["seed"], sz))
    if spec.get("fault") == "zero_bias":
        # tests only: a program that selects its experts by score alone
        tree = jax.tree_util.tree_map_with_path(
            lambda path, x: x * 0 if getattr(
                path[-1], "key", None) == "expert_bias" else x, tree)
    return tree


def adapter_functions(sz: dict):
    """(leaf_norms, leaf_diff_norms, sample_matrices) of trees in the
    program's layout."""
    from benchmark.harness import lm_adapter as a

    return (a.leaf_norms, a.leaf_diff_norms,
            lambda tree: a.sample_matrices(tree, sz["kinds"]))


def pad_slots(ref, params, batch, sz, quant, tie_tol) -> dict:
    """A step's padding slots (segment 0) all hold one id at position 0 and
    see no other token, so they are one token: how many there are, and
    through the reference which held experts that token selects in each
    routed layer (1 or 0 an expert) and whether it sits within `tie_tol` of
    a tie. Padding of several ids is left in the counts (0 slots)."""
    import jax
    import jax.numpy as jnp

    ids, seg = np.asarray(batch["input_ids"]), np.asarray(batch["segment_ids"])
    pads = ids[seg == 0]
    if pads.size == 0 or (pads != pads[0]).any():
        routed = sum(ffn == "moe" for _, ffn in sz["kinds"])
        return {"slots": 0, "near_ties": [0] * routed,
                "counts": [[0] * (sz["held"][1] - sz["held"][0])] * routed}
    with jax.default_matmul_precision("highest"):
        _, counts, ties = jax.jit(
            ref.row_forward, static_argnames=("sz", "quant", "tie_tol"))(
                params, jnp.full((1,), pads[0]), jnp.zeros((1,), jnp.int32),
                ref._Sizes(sz), quant, tie_tol)
    return {"slots": int(pads.size),
            "counts": np.asarray(jax.device_get(counts)).tolist(),
            "near_ties": np.asarray(jax.device_get(ties)).tolist()}


_pad_slots = pad_slots      # the name it had until PR 40


def follow(spec: dict, sz: dict, batches: list, keys: list,
           quant=None) -> dict:
    """The reference's losses, first clipped gradient, parameter change and
    expert counts over the observed steps' own inputs."""
    import jax

    from benchmark.harness import lm_adapter
    from benchmark.harness.adapter import place_for_reference
    from benchmark.reference import lfm2_moe_ref as ref

    t = spec["traffic"]
    params = place_for_reference(ref.init_params(spec["seed"], sz), False)
    opt = ref.lamb_init(params)
    losses, counts, ties, padding = [], [], [], []
    grad_norms = grad_sample = None
    tie_tol = float(t["limits"]["tie_tol"])
    for batch in batches:
        padding.append(pad_slots(ref, params, batch, sz, quant, tie_tol))
        accum = batch["input_ids"].shape[0]
        micros = [place_for_reference(
            {k: batch[k][i] for k in ("input_ids", "segment_ids")}, False)
            for i in range(accum)]
        loss, grads, count, tie = ref.step_loss_and_grad(
            params, micros, sz, quant, tie_tol)
        losses.append(float(loss))
        counts.append(np.asarray(jax.device_get(count)).tolist())
        ties.append(np.asarray(jax.device_get(tie)).tolist())
        if grad_norms is None:
            clipped, _ = jax.jit(ref.clipped_gradient)(grads)
            clipped = lm_adapter.to_program_tree(clipped)
            grad_norms = lm_adapter.leaf_norms(clipped)
            grad_sample = lm_adapter.sample_matrices(clipped, sz["kinds"])
            del clipped
        params, opt = ref.lamb_step(
            params, grads, opt, float(t["learning_rate"]),
            int(t["max_steps"]), float(t["warmup_proportion"]))
        del grads
    del opt
    start = place_for_reference(ref.init_params(spec["seed"], sz), False)
    delta_norms = lm_adapter.leaf_diff_norms(
        lm_adapter.to_program_tree(params),
        lm_adapter.to_program_tree(start))
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_sample": grad_sample, "delta_norms": delta_norms,
            "expert_counts": counts, "near_ties": ties, "padding": padding}


def _expert_counts(vals: dict) -> list:
    """[[tokens of each held expert] per routed layer] from a step's
    scalars."""
    loads = {}
    for key, value in vals.items():
        m = _EXPERT_LOAD.match(key)
        if m:
            loads.setdefault(int(m.group(1)), {})[int(m.group(2))] = int(value)
    return [[loads[layer][e] for e in sorted(loads[layer])]
            for layer in sorted(loads)]


def followed_by_program(scalars: dict, steps: int) -> dict:
    """The program's own counters of the followed steps, under the name
    `follow` gives the reference's."""
    return {"expert_counts": [_expert_counts(scalars[s])
                              for s in range(1, steps + 1)]}


def _causal_pairs(seg) -> int:
    """Sum over the documents of a step's rows of len * (len + 1) / 2: the
    (query, key) pairs causal attention inside documents needs."""
    total = 0
    for row in seg.reshape(-1, seg.shape[-1]):
        lens = np.bincount(row, minlength=2)[1:].astype(np.int64)
        total += int((lens * (lens + 1) // 2).sum())
    return total


def window_extras(segs: dict, scalars: dict, cell: dict) -> dict:
    """What the family adds to the window's record (`segs`: the timed
    steps' segment ids, `scalars`: every step's logged values, `cell`: the
    cell's `config` and `traffic` as the child has them): each timed
    step's causal pairs, and the held pairs left out over the whole run."""
    return {"causal_pairs": {n: _causal_pairs(seg)
                             for n, seg in segs.items()},
            "dropped_pairs": sum(
                int(v) for vals in scalars.values() for k, v in vals.items()
                if k.startswith("moe_l") and k.endswith("_dropped"))}


def compare_extras(got: dict, ref: dict) -> dict:
    return {"experts": {"program": got["expert_counts"],
                        "reference": ref["expert_counts"],
                        "near_ties": ref["near_ties"],
                        "padding": ref["padding"]}}
