"""Between the lfm2_moe reference's weight layout (reference/
lfm2_moe_ref.py) and the program's parameter tree (models/lfm2_moe.py), and
the matrices compared whole. Renames only: the two sides store every tensor
in the same shape. Placement, the per-leaf norms, the worst-leaf gap and the
norm of a difference are harness/adapter.py's (families/lfm2_moe.py binds
both).
"""

from __future__ import annotations

import jax
import numpy as np

from benchmark.harness.adapter import norm_functions

_CONV = {"w_in": ("in_proj", "kernel"), "conv_w": ("conv_weight",),
         "w_out": ("out_proj", "kernel")}
_ATTENTION = {"wq": ("q_proj",), "wk": ("k_proj",), "wv": ("v_proj",),
              "q_norm": ("q_norm", "scale"), "k_norm": ("k_norm", "scale"),
              "wo": ("out_proj", "kernel")}
_DENSE = {"w1": ("w1", "kernel"), "w3": ("w3", "kernel"),
          "w2": ("w2", "kernel")}
_MOE = {"wg": ("router",), "b": ("expert_bias",), "ew1": ("experts_w1",),
        "ew3": ("experts_w3",), "ew2": ("experts_w2",)}


def _put(tree: dict, path: tuple, leaf) -> None:
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = leaf


def to_program_tree(ref: dict) -> dict:
    """The reference's tree (weights, gradients or changes) under the
    program's names."""
    tree = {"embed_tokens": ref["embed"],
            "embedding_norm": {"scale": ref["final_norm"]}}
    for i, lp in enumerate(ref["layers"]):
        layer = tree.setdefault(f"layer_{i}", {})
        layer["operator_norm"] = {"scale": lp["op_norm"]}
        layer["ffn_norm"] = {"scale": lp["ffn_norm"]}
        for module, names in (("conv", _CONV), ("attention", _ATTENTION),
                              ("mlp", _DENSE), ("moe", _MOE)):
            for name, path in names.items():
                if name in lp and (module != "mlp" or "wg" not in lp):
                    _put(layer, (module,) + path, lp[name])
    return tree


# a stack of experts: one norm per expert (LAMB's tensors)
leaf_norms, leaf_diff_norms = norm_functions(lambda path: str(getattr(
    path[-1], "key", path[-1])).startswith("experts_"))


def sample_matrices(tree, kinds) -> dict:
    """{name: float32 host array} of the matrices `correct` compares whole:
    W_in and W_out of the first convolution layer, Wq and Wo of the first
    attention layer, the dense W1 and W2, one held expert's W1 and W2 and
    the router of the first and the last routed layer. `tree` is in the
    program's layout, `kinds` the stack's (operator, ffn) pairs."""
    def first(pred):
        return next((i for i, k in enumerate(kinds) if pred(k)), None)

    conv = first(lambda k: k[0] == "conv")
    attn = first(lambda k: k[0] == "attention")
    dense = first(lambda k: k[1] == "dense")
    routed = [i for i, k in enumerate(kinds) if k[1] == "moe"]
    want = []
    if conv is not None:
        want += [(conv, ("conv", "in_proj", "kernel"), None),
                 (conv, ("conv", "out_proj", "kernel"), None)]
    if attn is not None:
        want += [(attn, ("attention", "q_proj"), None),
                 (attn, ("attention", "out_proj", "kernel"), None)]
    if dense is not None:
        want += [(dense, ("mlp", "w1", "kernel"), None),
                 (dense, ("mlp", "w2", "kernel"), None)]
    for i in sorted({routed[0], routed[-1]}) if routed else []:
        want += [(i, ("moe", "experts_w1"), 0), (i, ("moe", "experts_w2"), 0),
                 (i, ("moe", "router"), None)]
    out = {}
    for layer, path, expert in want:
        leaf = tree[f"layer_{layer}"]
        for key in path:
            leaf = leaf[key]
        if expert is not None:
            leaf = leaf[expert]
        out[f"layer_{layer}/" + "/".join(path)] = np.asarray(
            jax.device_get(leaf), np.float32)
    return out
