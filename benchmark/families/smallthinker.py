"""The `smallthinker` family (`"model_type": "smallthinker"`): causal-LM
pretraining of a decoder whose layers all attend (over the whole document
without positions, or over a band of the last `sliding_window_size` tokens
with rotary positions) and all route (softmax over the selected of 64,
ReLU-gated experts, the router fed the layer's input), over packed rows.

Everything the benchmark knows about the family is named here: its
reference (reference/smallthinker_ref.py, which keeps its weights under the
program's names, so nothing is renamed) with the weights it makes from the
seed, the matrices compared whole, its FLOPs (harness/smallthinker_flops.py:
the slots' products and the documents' pairs, counted apart for the two
kinds of layer), and how the followed steps are followed (a row at a time).
What a causal-LM family of routed experts over packed rows needs whatever
its layers (the held experts' counts against the reference's near ties, a
batch's fields, the program's counters) is families/lfm2_moe.py's, used as
it is. `harness/spec.load_family` says which names a family module defines.
"""

from __future__ import annotations

import numpy as np

from benchmark.families import lfm2_moe as routed_lm
from benchmark.harness import smallthinker_flops as flops  # ctx["flops"]

TOPK_KEY = "moe_num_active_primary_experts"     # routed_lm's, as spelt here
followed_by_program = routed_lm.followed_by_program
compare_extras = routed_lm.compare_extras
program_args = routed_lm.program_args

_ATTENTION = ("attention/q_proj", "attention/out_proj/kernel")
_ROUTED = ("moe/experts_w1", "moe/experts_w2", "moe/router")


# -- the driver's side (this process stays off JAX) ------------------------

def _window_sum(window: dict, key: str) -> int:
    return sum(window[key][str(s)] for s in range(
        window["first_step"], window["last_step"] + 1))


def window_flops(cell: dict, window: dict):
    """(forward + backward FLOPs the window's steps need, what they count):
    the full layers by the documents' causal pairs, the windowed layers by
    the pairs inside the band."""
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
    from benchmark.reference import smallthinker_ref

    return smallthinker_ref.sizes_from_config(config)


def _break_program(fault: str) -> None:
    """Tests only (`--fault`): the PROGRAM under test is built wrong, by
    replacing a name its model module looks up when the step is traced (the
    weights are handed over before that). `no_band`: the windowed layers
    attend to the whole document. `router_after_attention`: the router reads
    the tokens the experts compute on (the post-attention norm's output),
    not the layer's input. Each has to come out as not correct."""
    from bert_pytorch_tpu.models import smallthinker as program

    if getattr(program, "_bench_fault", None) == fault:
        return
    if fault == "no_band":
        attend = program.dot_product_attention
        program.dot_product_attention = (
            lambda *a, window=None, **kw: attend(*a, **kw))
    elif fault == "router_after_attention":
        routed = program.RoutedExperts

        def experts(*args, **kwargs):
            module = routed(*args, **kwargs)
            return lambda x, router_input=None: module(x)

        program.RoutedExperts = experts
    else:
        raise ValueError(f"unknown fault {fault!r}")
    program._bench_fault = fault


def weights(spec: dict, sz: dict) -> dict:
    """The benchmark's weights from the seed, in the program's layout."""
    from benchmark.reference import smallthinker_ref

    if spec.get("fault") in ("no_band", "router_after_attention"):
        _break_program(spec["fault"])
    return smallthinker_ref.init_params(spec["seed"], sz)


def sample_matrices(tree, kinds) -> dict:
    """{name: float32 host array} of the matrices `correct` compares whole:
    Wq and Wo of the first full-attention and of the first windowed layer,
    and of the first and the last layer expert 0's W1 and W2 and the
    router. `tree` is in the program's layout, `kinds` the stack's (window,
    rope)."""
    import jax

    def first(pred):
        return next((i for i, k in enumerate(kinds) if pred(k)), None)

    want = [(i, p) for i in (first(lambda k: not k[0]),
                             first(lambda k: k[0]))
            if i is not None for p in _ATTENTION]
    want += [(i, p) for i in sorted({0, len(kinds) - 1}) for p in _ROUTED]
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


def _norm_functions():
    from benchmark.harness.adapter import norm_functions

    # a stack of experts: one norm per expert (LAMB's tensors)
    return norm_functions(lambda path: str(getattr(
        path[-1], "key", path[-1])).startswith("experts_"))


def adapter_functions(sz: dict):
    """(leaf_norms, leaf_diff_norms, sample_matrices) of trees in the
    program's layout."""
    leaf_norms, leaf_diff_norms = _norm_functions()
    return (leaf_norms, leaf_diff_norms,
            lambda tree: sample_matrices(tree, sz["kinds"]))


def _pad_slots(ref, params, batch, sz, quant, tie_tol) -> dict:
    """families/lfm2_moe.pad_slots (a step's padding slots are one token,
    counted once through the reference) for a stack whose every layer
    routes: where a step has no such slots, as in the cell's full rows,
    lfm2's counts the routed layers by its own kinds and finds none here."""
    out = routed_lm.pad_slots(ref, params, batch, sz, quant, tie_tol)
    if not out["slots"]:
        layers, held = len(sz["kinds"]), sz["held"][1] - sz["held"][0]
        out = dict(out, near_ties=[0] * layers,
                   counts=[[0] * held] * layers)
    return out


def follow(spec: dict, sz: dict, batches: list, keys: list,
           quant=None) -> dict:
    """The reference's losses, first clipped gradient, parameter change and
    expert counts over the observed steps' own inputs."""
    import jax

    from benchmark.harness.adapter import place_for_reference
    from benchmark.reference import smallthinker_ref as ref

    leaf_norms, leaf_diff_norms = _norm_functions()
    t = spec["traffic"]
    params = place_for_reference(ref.init_params(spec["seed"], sz), False)
    opt = ref.lamb_init(params)
    losses, counts, ties, padding = [], [], [], []
    grad_norms = grad_sample = None
    tie_tol = float(t["limits"]["tie_tol"])
    for batch in batches:
        padding.append(_pad_slots(ref, params, batch, sz, quant, tie_tol))
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
            # the moments wait on the host: beside them (5.2 GB at the
            # cell's size) the next step's row pass has less room than the
            # first had
            opt = jax.device_get(opt)
    del opt
    start = place_for_reference(ref.init_params(spec["seed"], sz), False)
    delta_norms = leaf_diff_norms(params, start)
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_sample": grad_sample, "delta_norms": delta_norms,
            "expert_counts": counts, "near_ties": ties, "padding": padding}


def document_pairs(seg, window: int) -> int:
    """Sum over the documents of a step's rows (segment ids 1..n, 0 =
    padding) of `band_pairs(len, window)`: the (query, key) pairs attention
    inside documents needs, under a band of `window` tokens or (0) none."""
    total = 0
    for row in seg.reshape(-1, seg.shape[-1]):
        total += sum(flops.band_pairs(n, window)
                     for n in np.bincount(row, minlength=2)[1:])
    return int(total)


def window_extras(segs: dict, scalars: dict, cell: dict) -> dict:
    """What the family adds to the window's record (`segs`: the timed
    steps' segment ids, `scalars`: every step's logged values, `cell`: the
    cell's `config` and `traffic`): each timed step's pairs of a full layer
    (`causal_pairs`) and of a windowed layer (`window_pairs`, under the
    configuration's band), and the held pairs left out over the whole run
    (lfm2's count)."""
    band = int(cell["config"]["sliding_window_size"])
    return dict(
        routed_lm.window_extras(segs, scalars, cell),
        window_pairs={n: document_pairs(seg, band)
                      for n, seg in segs.items()})

