"""Operations the `lfm2_moe` family's algorithms need (families/
lfm2_moe.py binds it): the arithmetic of the MFU line and of the rooflines of
the kernels the family brought (the grouped expert products, causal
grouped-head flash attention). The chip's peaks and the roofline's form are
harness/flops.py's. Recomputed operations (activation checkpointing, flash
attention's backward recompute of the scores) are NOT counted.
"""

from __future__ import annotations

from benchmark.harness.flops import peaks, roofline_seconds  # noqa: F401


def layer_kinds(cfg: dict) -> list:
    """[(operator, ffn)] of the stack as the configuration file cuts it."""
    kept = cfg.get("layers_kept") or list(range(cfg["num_hidden_layers"]))
    return [("conv" if cfg["layer_types"][i] == "conv" else "attention",
             "dense" if j < cfg["num_dense_layers"] else "moe")
            for j, i in enumerate(kept)]


def dense_weights_per_token(cfg: dict) -> float:
    """Matrix elements one token is multiplied with, forward, on this rank:
    every operator and dense-MLP matrix, the router, the tied head's slice
    of the vocabulary, and of the routed experts num_experts_per_tok times
    the share of the experts held here (num_experts of experts_total)."""
    e = cfg["hidden_size"]
    d = e // cfg["num_attention_heads"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    total = cfg.get("experts_total") or cfg["num_experts"]
    weights = float(cfg["vocab_size"] * e)
    for operator, ffn in layer_kinds(cfg):
        weights += (4 * e * e if operator == "conv"
                    else e * (h + 2 * hkv) * d + h * d * e)
        if ffn == "dense":
            weights += 3 * e * cfg["intermediate_size"]
        else:
            weights += e * total + (
                3 * e * cfg["moe_intermediate_size"]
                * cfg["num_experts_per_tok"] * cfg["num_experts"] / total)
    return weights


def causal_attention_flops(cfg: dict, causal_pairs: float,
                           backward: bool = True) -> float:
    """Score and value products of causal attention inside documents, all
    attention layers: per (query, key) pair with key <= query and per query
    head 4 D operations forward (q.k and p.v), 8 D more backward.
    `causal_pairs` = sum over documents of len (len + 1) / 2."""
    layers = sum(1 for op, _ in layer_kinds(cfg) if op == "attention")
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    return ((12.0 if backward else 4.0) * layers
            * cfg["num_attention_heads"] * d * causal_pairs)


def moe_expert_flops(cfg: dict, pairs: float, backward: bool = True) -> float:
    """The three products of the routed SwiGLU experts over `pairs` (token,
    held expert) pairs: 2 x 3 x hidden x width forward a pair, twice that
    backward."""
    return ((6.0 if backward else 2.0) * pairs * 3 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"])


def train_flops(cfg: dict, slots: float, causal_pairs: float) -> float:
    """Forward + backward operations of `slots` token slots holding
    documents with `causal_pairs` attention pairs."""
    return (6.0 * dense_weights_per_token(cfg) * slots
            + causal_attention_flops(cfg, causal_pairs))
