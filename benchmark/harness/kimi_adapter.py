"""The `kimi_linear` family's side of harness/adapter.py: the reference
(reference/kimi_linear_ref.py) keeps its weights under the program's own
names (models/kimi_linear.py), so there is nothing to rename; what is here
are the per-leaf norms (one per expert in a stack of experts) and the
matrices compared whole (families/kimi_linear.py binds both).
"""

from __future__ import annotations

import jax
import numpy as np

from benchmark.harness.adapter import norm_functions


# a stack of experts: one norm per expert (LAMB's tensors)
leaf_norms, leaf_diff_norms = norm_functions(lambda path: str(getattr(
    path[-1], "key", path[-1])).startswith("experts_"))

_KDA = ("q_proj", "out_proj/kernel", "f_b_proj", "b_proj", "g_b_proj")
_MLA = ("kv_b_proj/kernel", "q_proj/kernel")
_ROUTED = ("moe/experts_w1", "moe/experts_w2", "shared_expert/w1/kernel",
           "shared_expert/w2/kernel", "moe/router")


def sample_matrices(tree, kinds) -> dict:
    """{name: float32 host array} of the matrices `correct` compares whole:
    Wq, Wo, W_f2, W_b and W_g2 of the first KDA layer, W_kvb and Wq of the
    first MLA layer, the dense W1 and W2, and of the first and the last
    routed layer expert 0's W1 and W2, the shared expert's and the router.
    `tree` is in the program's layout, `kinds` the stack's (mixer, ffn)."""
    def first(pred):
        return next((i for i, k in enumerate(kinds) if pred(k)), None)

    kda, mla = first(lambda k: k[0] == "kda"), first(lambda k: k[0] == "mla")
    dense = first(lambda k: k[1] == "dense")
    routed = [i for i, k in enumerate(kinds) if k[1] == "moe"]
    want = []
    if kda is not None:
        want += [(kda, "kda/" + p) for p in _KDA]
    if mla is not None:
        want += [(mla, "attention/" + p) for p in _MLA]
    if dense is not None:
        want += [(dense, "mlp/w1/kernel"), (dense, "mlp/w2/kernel")]
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
