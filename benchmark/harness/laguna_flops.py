"""Operations the `laguna` family's algorithms need (families/laguna.py
binds it): the arithmetic of the MFU line, of the rooflines of its two kinds
of attention layer (causal over the whole document at the full layers' head
count; causal inside a band of `sliding_window` tokens at the windowed
layers') and of the held routed experts' grouped products, counted as the
lfm2 family counts them. Head counts are PER LAYER
(`num_attention_heads_per_layer`): a kind's operations are summed over its
layers, each at its own count. The chip's peaks and the roofline's form are
harness/flops.py's. Recomputed operations (activation checkpointing, flash
attention's recompute of the scores) are NOT counted, nor are the masked
parts of a tile a kernel visits: the (query, key) pairs the mathematics
needs, whatever implements them.
"""

from __future__ import annotations

from benchmark.harness.flops import peaks, roofline_seconds  # noqa: F401
# (query, key) pairs of a document under a band, or (0) its causal triangle
from benchmark.harness.smallthinker_flops import band_pairs  # noqa: F401


def layer_kinds(cfg: dict) -> list:
    """[(the band's width or 0, query heads, "dense" | "moe")] of the stack
    as the configuration file cuts it."""
    return [(int(cfg["sliding_window"]) if kind == "sliding_attention" else 0,
             int(heads), "dense" if ffn == "dense" else "moe")
            for kind, heads, ffn in zip(cfg["layer_types"],
                                        cfg["num_attention_heads_per_layer"],
                                        cfg["mlp_layer_types"])]


def dense_weights_per_token(cfg: dict) -> float:
    """Matrix elements one token is multiplied with, forward, on this rank:
    every attention matrix of every layer at its own head count (q, k, v,
    the gate, the output), the dense MLP, the router, the shared expert, the
    untied head's slice of the vocabulary, and of the routed experts
    num_experts_per_tok times the share of the experts held here."""
    e, d, hkv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    total = cfg.get("experts_total") or cfg["num_experts"]
    expert = 3 * e * cfg["moe_intermediate_size"]
    weights = float(cfg["vocab_size"] * e)
    for _, h, ffn in layer_kinds(cfg):
        weights += e * (h + 2 * hkv) * d + e * h + h * d * e
        if ffn == "dense":
            weights += 3 * e * cfg["intermediate_size"]
        else:
            weights += (e * total
                        + 3 * e * cfg["shared_expert_intermediate_size"]
                        + expert * cfg["num_experts_per_tok"]
                        * cfg["num_experts"] / total)
    return weights


def _attention_flops(cfg: dict, windowed: bool, pairs: float,
                     backward: bool) -> float:
    """Per (query, key) pair and query head 2 x 128 operations for q.k and
    2 x 128 for p.v forward, twice that backward, over the query heads of
    the layers of one kind."""
    heads = sum(h for window, h, _ in layer_kinds(cfg)
                if bool(window) == windowed)
    return ((6.0 if backward else 2.0) * heads * 2 * cfg["head_dim"] * pairs)


def causal_attention_flops(cfg: dict, causal_pairs: float,
                           backward: bool = True) -> float:
    """Score and value products of the FULL-attention layers only:
    `causal_pairs` = sum over documents of len (len + 1) / 2.
    (readers/flash_causal_roofline.py calls it by this name, against the
    `flash_fwd` / `flash_bwd_*` kernels, which only these layers run.)"""
    return _attention_flops(cfg, False, causal_pairs, backward)


def window_attention_flops(cfg: dict, window_pairs: float,
                           backward: bool = True) -> float:
    """Score and value products of the WINDOWED layers only: `window_pairs`
    = sum over documents of `band_pairs(len, sliding_window)`.
    (readers/flash_window_roofline.py calls it by this name.)"""
    return _attention_flops(cfg, True, window_pairs, backward)


def moe_expert_flops(cfg: dict, pairs: float, backward: bool = True) -> float:
    """The three products of the routed SwiGLU experts over `pairs` (token,
    held expert) pairs: 2 x 3 x hidden x width forward a pair, twice that
    backward; the shared expert is not among them.
    (readers/moe_experts_roofline.py calls it by this name.)"""
    return ((6.0 if backward else 2.0) * pairs * 3 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"])


def train_flops(cfg: dict, slots: float, causal_pairs: float,
                window_pairs: float) -> float:
    """Forward + backward operations of `slots` token slots holding
    documents with `causal_pairs` pairs in each full layer and
    `window_pairs` in each windowed one."""
    return (6.0 * dense_weights_per_token(cfg) * slots
            + causal_attention_flops(cfg, causal_pairs)
            + window_attention_flops(cfg, window_pairs))
