"""The `laguna` family (`"model_type": "laguna"`): causal-LM pretraining of
a decoder whose layers all attend (over a band of the last `sliding_window`
tokens or over the whole document, with a head count and a rotary table of
the layer's own and a sigmoid gate per head on the output) and, after a
leading dense layer, all route (sigmoid scores over 256, the 8 largest of
score + bias, beside one shared expert), over packed rows.

Everything the benchmark knows about the family is named here: its
reference (reference/laguna_ref.py, which keeps its weights under the
program's names, so nothing is renamed) with the weights it makes from the
seed, the matrices compared whole, its FLOPs (harness/laguna_flops.py: the
slots' products and the documents' pairs, counted apart for the two kinds of
layer and at each layer's own head count), and how the followed steps are
followed (a row at a time). What a causal-LM family of routed experts over
packed rows needs whatever its layers (the held experts' counts against the
reference's near ties with the padding slots taken out, a batch's fields,
the program's counters, the documents' causal pairs) is
families/lfm2_moe.py's, used as it is. `harness/spec.load_family` says which
names a family module defines.
"""

from __future__ import annotations

import numpy as np

from benchmark.families import lfm2_moe as routed_lm
# a step's (query, key) pairs under a band, from its segment ids
from benchmark.families.smallthinker import document_pairs
from benchmark.harness import laguna_flops as flops  # the readers' ctx["flops"]

TOPK_KEY = "num_experts_per_tok"    # this family's spelling (routed_lm's)
followed_by_program = routed_lm.followed_by_program
compare_extras = routed_lm.compare_extras
program_args = routed_lm.program_args

_ATTENTION = ("attention/q_proj", "attention/gate_proj",
              "attention/out_proj/kernel")
_ROUTED = ("moe/experts_w1", "moe/experts_w2", "shared_expert/w1/kernel",
           "shared_expert/w2/kernel", "moe/router")


# -- the driver's side (this process stays off JAX) ------------------------

def _window_sum(window: dict, key: str) -> int:
    return sum(window[key][str(s)] for s in range(
        window["first_step"], window["last_step"] + 1))


def window_flops(cell: dict, window: dict):
    """(forward + backward FLOPs the window's steps need, what they count):
    the full layers by the documents' causal pairs, the windowed layers by
    the pairs inside the band, each layer at its own head count."""
    return (flops.train_flops(cell["config"], window["slot_tokens"],
                              _window_sum(window, "causal_pairs"),
                              _window_sum(window, "window_pairs")),
            "of the slots and of the documents' causal attention, whole in "
            "the full layers and inside the band in the windowed ones")


def decide(cell: dict, record: dict, check) -> None:
    """The routed layers' checks of families/lfm2_moe.decide (told this
    family's `TOPK_KEY`)."""
    routed_lm.decide(cell, record, check, TOPK_KEY)


# -- the child's side ------------------------------------------------------

def sizes(config: dict, traffic: dict) -> dict:
    from benchmark.reference import laguna_ref

    return laguna_ref.sizes_from_config(config)


def _break_program(fault: str) -> None:
    """Tests and the builder's planted faults only (`--fault`): the PROGRAM
    under test is built wrong, by replacing a name its model module looks up
    when the step is traced (the weights are handed over before that).
    `no_band`: the windowed layers attend to the whole document.
    `whole_head_rotary`: the full layers rotate all of the head at the
    windowed layers' table (no YaRN, no part that passes). Each has to come
    out as not correct."""
    from bert_pytorch_tpu.models import laguna as program

    if getattr(program, "_bench_fault", None) == fault:
        return
    if fault == "no_band":
        attend = program.dot_product_attention
        program.dot_product_attention = (
            lambda *a, window=None, **kw: attend(*a, **kw))
    elif fault == "whole_head_rotary":
        table = program.rotary_table
        program.rotary_table = lambda d, params: table(d, {
            "rope_theta": 10000.0, "rope_type": "default"})
    else:
        raise ValueError(f"unknown fault {fault!r}")
    program._bench_fault = fault


def weights(spec: dict, sz: dict) -> dict:
    """The benchmark's weights from the seed, in the program's layout."""
    import jax

    from benchmark.reference import laguna_ref

    fault = spec.get("fault")
    if fault in ("no_band", "whole_head_rotary"):
        _break_program(fault)
    tree = laguna_ref.init_params(spec["seed"], sz)
    if fault == "zero_bias":
        # a program that selects its experts by score alone
        tree = jax.tree_util.tree_map_with_path(
            lambda path, x: x * 0 if getattr(
                path[-1], "key", None) == "expert_bias" else x, tree)
    return tree


def sample_matrices(tree, kinds) -> dict:
    """{name: float32 host array} of the matrices `correct` compares whole:
    Wq, Wg and Wo of the first full-attention and of the first windowed
    layer, the dense layer's W1 and W2, and of the first and the last routed
    layer expert 0's W1 and W2, the shared expert's W1 and W2 and the
    router. `tree` is in the program's layout, `kinds` the stack's
    (attention, ffn)."""
    import jax

    def first(pred):
        return next((i for i, k in enumerate(kinds) if pred(k)), None)

    want = [(i, p) for i in (first(lambda k: k[0] == "full"),
                             first(lambda k: k[0] == "sliding"))
            if i is not None for p in _ATTENTION]
    dense = first(lambda k: k[1] == "dense")
    if dense is not None:
        want += [(dense, "mlp/w1/kernel"), (dense, "mlp/w2/kernel")]
    routed = [i for i, k in enumerate(kinds) if k[1] == "moe"]
    for i in sorted({routed[0], routed[-1]}) if routed else []:
        want += [(i, p) for p in _ROUTED]
    out = {}
    for layer, path in want:
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
    """The reference's losses, first clipped gradient, parameter change and
    expert counts over the observed steps' own inputs."""
    import jax

    from benchmark.harness.adapter import place_for_reference
    from benchmark.harness.kimi_adapter import leaf_diff_norms, leaf_norms
    from benchmark.reference import laguna_ref as ref

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
            grad_norms = leaf_norms(clipped)
            grad_sample = sample_matrices(clipped, sz["kinds"])
            del clipped
        params, opt = ref.lamb_step(
            params, grads, opt, float(t["learning_rate"]),
            int(t["max_steps"]), float(t["warmup_proportion"]))
        del grads
        if batch is not batches[-1]:
            # the moments wait on the host: beside them (5.5 GB at the
            # cell's size) the next step's row pass has less room than the
            # first had
            opt = jax.device_get(opt)
    del opt
    start = place_for_reference(ref.init_params(spec["seed"], sz), False)
    delta_norms = leaf_diff_norms(params, start)
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_sample": grad_sample, "delta_norms": delta_norms,
            "expert_counts": counts, "near_ties": ties, "padding": padding}


def window_extras(segs: dict, scalars: dict, cell: dict) -> dict:
    """What the family adds to the window's record (`segs`: the timed
    steps' segment ids, `scalars`: every step's logged values, `cell`: the
    cell's `config` and `traffic`): each timed step's pairs of a full layer
    (`causal_pairs`) and of a windowed layer (`window_pairs`, under the
    configuration's band), and the held pairs left out over the whole run
    (lfm2's count)."""
    band = int(cell["config"]["sliding_window"])
    return dict(
        routed_lm.window_extras(segs, scalars, cell),
        window_pairs={n: document_pairs(seg, band)
                      for n, seg in segs.items()})
