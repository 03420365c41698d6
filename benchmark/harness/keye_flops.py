"""Operations the `keye` family's algorithms need (families/keye.py binds
it): the arithmetic of the MFU line, of the roofline of the attention under a
learned selection, and of the held routed experts' grouped products, counted
as the lfm2 family counts them. The chip's peaks and the roofline's form are
harness/flops.py's. What is counted is the (query, key) pairs the
mathematics needs, never the tiles a kernel visits: the main attention by
the SELECTED pairs (sum over tokens of min(topk, position in the document +
1)), the index scores by the causal pairs inside documents (every earlier
token is scored), the KL term by its second reading of the selected pairs'
scores (forward only: its target is detached) and the index scores'
backward pass. Recomputed operations (activation checkpointing, flash
attention's recompute of the scores) are NOT counted.
"""

from __future__ import annotations

from benchmark.harness.flops import peaks, roofline_seconds  # noqa: F401
# sum over a document's tokens of min(window, position + 1): the pairs a
# band of `window` holds are the pairs a selection of `window` keys holds
from benchmark.harness.smallthinker_flops import band_pairs


def selected_pairs(length: int, topk: int) -> int:
    """sum over the tokens t of one document of min(topk, t + 1)."""
    return band_pairs(length, topk)


def layers(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def dense_weights_per_token(cfg: dict) -> float:
    """Matrix elements one token is multiplied with, forward, on this rank:
    every attention matrix, the indexer's three, the router, the untied
    head's slice of the vocabulary, and of the routed experts
    num_experts_per_tok times the share of the experts held here."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    total = cfg.get("experts_total") or cfg["num_experts"]
    layer = (e * (h + 2 * hkv) * d + h * d * e + e * (j * di + di + j)
             + e * total + 3 * e * cfg["moe_intermediate_size"]
             * cfg["num_experts_per_tok"] * cfg["num_experts"] / total)
    return float(cfg["vocab_size"] * e + layers(cfg) * layer)


def select_attention_flops(cfg: dict, pairs: float,
                           backward: bool = True) -> float:
    """Score and value products of the main attention over `pairs` selected
    (query, key) pairs of ONE layer, all layers: per pair and query head
    2 x 128 for q.k and 2 x 128 for p.v forward, twice that backward."""
    return ((12.0 if backward else 4.0) * layers(cfg)
            * cfg["num_attention_heads"] * cfg["head_dim"] * pairs)


def select_attention_bytes(cfg: dict, slots: float) -> float:
    """HBM bytes the three kernels cannot avoid over `slots` token slots,
    all layers: q, k, v and the output once forward; q, k, v, the output's
    cotangent and dq, dk, dv once backward (bfloat16); and the packed
    selection, 4 bytes x slots x tile width (512) once in each of the three
    kernels."""
    d = cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    head_bytes = 2.0 * d * slots
    forward = head_bytes * (2 * h + 2 * hkv)
    backward = head_bytes * (3 * h + 4 * hkv)
    mask = 3 * 4.0 * slots * cfg["sa_config"]["q_chunk_size"]
    return layers(cfg) * (forward + backward + mask)


def index_flops(cfg: dict, causal_pairs: float) -> float:
    """The index scores over `causal_pairs` (query, earlier-or-equal key of
    its document) pairs of one layer, all layers: 2 x heads x width forward
    a pair, twice that backward (the KL term's gradient)."""
    sa = cfg["sa_config"]
    return (6.0 * layers(cfg) * sa["indexer_num_heads"]
            * sa["indexer_head_dim"] * causal_pairs)


def indexer_loss_flops(cfg: dict, pairs: float) -> float:
    """The KL term's own reading of the main attention's scores over the
    selected pairs of one layer, all layers: 2 x heads x 128 a pair,
    forward only (the target is detached)."""
    return (2.0 * layers(cfg) * cfg["num_attention_heads"] * cfg["head_dim"]
            * pairs)


def moe_expert_flops(cfg: dict, pairs: float, backward: bool = True) -> float:
    """The three products of the routed SwiGLU experts over `pairs` (token,
    held expert) pairs: 2 x 3 x hidden x width forward a pair, twice that
    backward. (readers/moe_experts_roofline.py calls it by this name.)"""
    return ((6.0 if backward else 2.0) * pairs * 3 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"])


def train_flops(cfg: dict, slots: float, causal_pairs: float,
                pairs: float) -> float:
    """Forward + backward operations of `slots` token slots holding
    documents with `causal_pairs` causal and `pairs` selected pairs a
    layer."""
    return (6.0 * dense_weights_per_token(cfg) * slots
            + select_attention_flops(cfg, pairs)
            + index_flops(cfg, causal_pairs)
            + indexer_loss_flops(cfg, pairs))
