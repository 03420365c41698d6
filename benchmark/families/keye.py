"""The `keye` family (`"model_type": "keye"`): causal-LM pretraining of a
decoder whose every layer attends over the keys a learned index selects (an
indexer scores every earlier token of the document; a query attends to its
`sa_config.topk` best, one selection for all of its heads) and routes over
softmax-scored experts, over packed rows, with a second loss term from which
the indexer alone learns.

Everything the benchmark knows about the family is named here: its
reference (reference/keye_ref.py, which keeps its weights under the
program's names, so nothing is renamed) with the weights it makes from the
seed, the matrices compared whole (the indexer's among them), its FLOPs
(harness/keye_flops.py: the slots' products, the selected pairs of the main
attention, the causal pairs of the index scores), how the followed steps are
followed (a row at a time), and, on top of the routed layers' checks of
families/lfm2_moe.py, what is discrete and new here: the SELECTION. The
program counts on the device, per layer, the selected pairs by key block
(`dsa_l<L>_kb<j>`, scalars of the step as the routers' counts are); per
followed step and layer the L1 gap between those and the reference's own
has to stay under `select_gap_share` of the reference's count of pairs
whose index score lies within `select_tie_tol` of its row's K-th (a score
that rounding carries across the K-th swaps one key for another; the
tolerance is one of its own beside the routers' `tie_tol`, the two being of
different scores). The share is set from readings, between what bfloat16
index scores move and what the lower-precision control moves: the count of
near ties alone lets the control through (PERF.md section 2).
A selection of the wrong keys (another score, another K, a boundary
ignored) moves whole blocks' counts; one of the right NUMBER of wrong keys
inside the right blocks would not, and is what the gradients' comparison is
for. Over the window the program's selected pairs have to equal the
harness's own sum of min(topk, position + 1) over the real tokens, times the
layers, exactly. Both loss terms are compared, each against the
reference's. `harness/spec.load_family` says which names a family module
defines.
"""

from __future__ import annotations

import re

import numpy as np

from benchmark.families import lfm2_moe as routed_lm
# a step's (query, key) pairs under a band, from its segment ids: the pairs
# a selection of K keys holds are the pairs a band of K holds
from benchmark.families.smallthinker import document_pairs
from benchmark.harness import keye_flops as flops  # the readers' ctx["flops"]

TOPK_KEY = "num_experts_per_tok"    # this family's spelling (routed_lm's)
program_args = routed_lm.program_args

_BLOCK_PAIRS = re.compile(r"^dsa_l(\d+)_kb(\d+)$")
_ATTENTION = ("attention/q_proj", "attention/out_proj/kernel",
              "attention/index_q_proj", "attention/index_k_proj")
_ROUTED = ("moe/experts_w1", "moe/experts_w2", "moe/router")
FAULTS = ("no_select", "no_indexer_loss")


# -- the driver's side (this process stays off JAX) ------------------------

def _window_sum(window: dict, key: str) -> int:
    return sum(window[key][str(s)] for s in range(
        window["first_step"], window["last_step"] + 1))


def window_flops(cell: dict, window: dict):
    """(forward + backward FLOPs the window's steps need, what they count)."""
    return (flops.train_flops(cell["config"], window["slot_tokens"],
                              _window_sum(window, "causal_pairs"),
                              _window_sum(window, "selected_pairs")),
            "of the slots, of the selected pairs' attention (and the KL "
            "term's reading of them) and of the index scores over the "
            "documents' causal pairs")


def decide(cell: dict, record: dict, check) -> None:
    """The routed layers' checks of families/lfm2_moe.decide, both loss
    terms against the reference's, and the selection's (the module
    docstring)."""
    routed_lm.decide(cell, record, check, TOPK_KEY)
    lim = cell["traffic"]["limits"]
    terms = record["compare"]["loss_terms"]
    for name, limit in (("lm_loss", lim["loss_rel"]),
                        ("indexer_kl", lim["kl_rel"])):
        for step, (got, want) in enumerate(zip(terms["program"][name],
                                               terms["reference"][name])):
            rel = abs(got - want) / abs(want)
            check(f"{name}_rel_step{step + 1}",
                  f"step {step + 1} {name} vs reference, relative "
                  f"({got:.6f} vs {want:.6f})", rel, limit,
                  limit is not None and rel <= limit)
    s = record["compare"]["selection"]
    share = float(lim["select_gap_share"])
    for step, (got, want, near) in enumerate(zip(
            s["program"], s["reference"], s["near_pairs"])):
        for layer, (g, w, tie) in enumerate(zip(got, want, near)):
            gap = (sum(abs(a - b) for a, b in zip(g, w))
                   if len(g) == len(w) else float("inf"))
            check(f"selected_l1_step{step + 1}_layer{layer}",
                  f"step {step + 1} layer {layer} selected pairs by key "
                  f"block, L1 gap to the reference's (of {sum(w)} pairs)",
                  gap, f"{share} x {tie} near-tie pairs", gap <= share * tie)
    w = record["window"]
    want = _window_sum(w, "selected_pairs") * int(
        cell["config"]["num_hidden_layers"])
    got = _window_sum(w, "selected_by_program")
    check("dsa_selected_pairs", "selected pairs the program counted over "
          "the window's steps, all layers", got,
          f"== {want} (sum of min(topk, position + 1) over the real tokens "
          "x layers)", got == want)


# -- the child's side ------------------------------------------------------

def sizes(config: dict, traffic: dict) -> dict:
    from benchmark.reference import keye_ref

    return keye_ref.sizes_from_config(config)


def _break_program(fault: str) -> None:
    """Tests and the builder's planted faults only (`--fault`): the PROGRAM
    under test is built wrong, by replacing a name its model module looks up
    when the step is traced (the weights are handed over before that).
    `no_select`: the main attention runs over every causal key of the
    document (the selection is computed, counted and learnt from, and the
    kernels are handed one that selects everything). `no_indexer_loss`: L_I
    is left out of the loss (it is still logged): the indexer's leaves take
    no gradient. Each has to come out as not correct."""
    from bert_pytorch_tpu.models import keye as program

    if getattr(program, "_bench_fault", None) == fault:
        return
    if fault == "no_select":
        import jax
        import jax.numpy as jnp

        attend = program.dot_product_attention

        def every_key(*a, select=None, **kw):
            """The same kernels, told that every key is selected (every bit
            set), no sooner than the true selection is made: the plain
            causal call, and words that wait for nothing, schedule into 17.1
            GB, which the chip's compiler refuses (PR 43)."""
            ones, _ = jax.lax.optimization_barrier(
                (tuple(jnp.full_like(x, -1) for x in select), select))
            return attend(*a, select=ones, **kw)

        program.dot_product_attention = every_key
    elif fault == "no_indexer_loss":
        program.total_loss = lambda lm_loss, indexer_kl: lm_loss
    else:
        raise ValueError(f"unknown fault {fault!r}")
    program._bench_fault = fault


def weights(spec: dict, sz: dict) -> dict:
    """The benchmark's weights from the seed, in the program's layout."""
    from benchmark.reference import keye_ref

    if spec.get("fault") in FAULTS:
        _break_program(spec["fault"])
    return keye_ref.init_params(spec["seed"], sz)


def sample_matrices(tree, kinds) -> dict:
    """{name: float32 host array} of the matrices `correct` compares whole:
    of the first and the last layer Wq, Wo, the indexer's WIq and WIk,
    expert 0's W1 and W2 and the router. `tree` is in the program's
    layout."""
    import jax

    out = {}
    for layer in sorted({0, len(kinds) - 1}):
        for path in _ATTENTION + _ROUTED:
            leaf = tree[f"layer_{layer}"]
            for key in path.split("/"):
                leaf = leaf[key]
            if path.startswith("moe/experts_"):
                leaf = leaf[0]
            out[f"layer_{layer}/{path}"] = np.asarray(jax.device_get(leaf),
                                                      np.float32)
    return out


def adapter_functions(sz: dict):
    """(leaf_norms, leaf_diff_norms, sample_matrices) of trees in the
    program's layout; the norms are harness/kimi_adapter.py's (one per
    expert of a stack, LAMB's tensors)."""
    from benchmark.harness import kimi_adapter as a

    return (a.leaf_norms, a.leaf_diff_norms,
            lambda tree: sample_matrices(tree, sz["kinds"]))


def follow(spec: dict, sz: dict, batches: list, keys: list,
           quant=None) -> dict:
    """The reference's losses (the sum and both terms), first clipped
    gradient, parameter change, expert counts and selected pairs by key
    block over the observed steps' own inputs."""
    import jax

    from benchmark.harness.adapter import place_for_reference
    from benchmark.harness.kimi_adapter import leaf_diff_norms, leaf_norms
    from benchmark.reference import keye_ref as ref

    t = spec["traffic"]
    params = place_for_reference(ref.init_params(spec["seed"], sz), False)
    opt = ref.lamb_init(params)
    out = {k: [] for k in ("losses", "lm_loss", "indexer_kl", "expert_counts",
                           "near_ties", "padding", "block_pairs",
                           "near_pairs")}
    grad_norms = grad_sample = None
    tie_tol = float(t["limits"]["tie_tol"])
    select_tol = float(t["limits"]["select_tie_tol"])
    host = lambda x: np.asarray(jax.device_get(x)).tolist()  # noqa: E731
    for batch in batches:
        out["padding"].append(routed_lm.pad_slots(ref, params, batch, sz,
                                                  quant, tie_tol))
        micros = [place_for_reference(
            {k: batch[k][i] for k in ("input_ids", "segment_ids")}, False)
            for i in range(batch["input_ids"].shape[0])]
        loss, grads, details = ref.step_loss_and_grad(
            params, micros, sz, quant, tie_tol, select_tol)
        out["losses"].append(float(loss))
        for key in ("lm_loss", "indexer_kl"):
            out[key].append(float(details[key]))
        for key in ("expert_counts", "near_ties", "block_pairs",
                    "near_pairs"):
            out[key].append(host(details[key]))
        if grad_norms is None:
            clipped, _ = jax.jit(ref.clipped_gradient)(grads)
            grad_norms = leaf_norms(clipped)
            grad_sample = sample_matrices(clipped, sz["kinds"])
            del clipped
        params, opt = ref.lamb_step(
            params, grads, opt, float(t["learning_rate"]),
            int(t["max_steps"]), float(t["warmup_proportion"]))
        del grads
        if batch is not batches[-1]:
            # the moments wait on the host: beside them the next step's row
            # pass has less room than the first had
            opt = jax.device_get(opt)
    del opt
    start = place_for_reference(ref.init_params(spec["seed"], sz), False)
    delta_norms = leaf_diff_norms(params, start)
    return dict(out, grad_norms=grad_norms, grad_sample=grad_sample,
                delta_norms=delta_norms)


def _block_pairs(vals: dict) -> list:
    """[[selected pairs of each key block] per layer] from a step's
    scalars."""
    pairs = {}
    for key, value in vals.items():
        m = _BLOCK_PAIRS.match(key)
        if m:
            pairs.setdefault(int(m.group(1)), {})[int(m.group(2))] = int(value)
    return [[pairs[layer][j] for j in sorted(pairs[layer])]
            for layer in sorted(pairs)]


def followed_by_program(scalars: dict, steps: int) -> dict:
    """The program's own counters and loss terms of the followed steps,
    under the names `follow` gives the reference's."""
    followed = range(1, steps + 1)
    return dict(
        routed_lm.followed_by_program(scalars, steps),
        block_pairs=[_block_pairs(scalars[s]) for s in followed],
        lm_loss=[float(scalars[s]["lm_loss"]) for s in followed],
        indexer_kl=[float(scalars[s]["indexer_kl"]) for s in followed])


def window_extras(segs: dict, scalars: dict, cell: dict) -> dict:
    """What the family adds to the window's record (`segs`: the timed
    steps' segment ids, `scalars`: every step's logged values, `cell`: the
    cell's `config` and `traffic`): each timed step's causal pairs and held
    pairs left out (lfm2's counts), the pairs a layer's queries select by
    the harness's own count (`selected_pairs`: min(topk, position + 1)
    summed over the real tokens) and the program's counters of the same
    steps summed over its layers and key blocks (`selected_by_program`)."""
    topk = int(cell["config"]["sa_config"]["topk"])
    return dict(
        routed_lm.window_extras(segs, scalars, cell),
        selected_pairs={n: document_pairs(seg, topk)
                        for n, seg in segs.items()},
        selected_by_program={
            n: sum(int(v) for k, v in scalars[n].items()
                   if _BLOCK_PAIRS.match(k))
            for n in segs if n in scalars})


def compare_extras(got: dict, ref: dict) -> dict:
    terms = ("lm_loss", "indexer_kl")
    return dict(
        routed_lm.compare_extras(got, ref),
        loss_terms={"program": {k: got[k] for k in terms},
                    "reference": {k: ref[k] for k in terms}},
        selection={"program": got["block_pairs"],
                   "reference": ref["block_pairs"],
                   "near_pairs": ref["near_pairs"]})
