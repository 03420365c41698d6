"""Operations and bytes the `kimi_linear` family's algorithms need
(families/kimi_linear.py binds it): the arithmetic of the MFU line, of the
rooflines of what the family brought (the chunked gated delta-rule scan;
causal flash attention at keys of 192 and values of 128) and of the held
routed experts' grouped products, counted as the lfm2 family counts them.
The chip's peaks and the roofline's form are harness/flops.py's. Recomputed
operations (activation checkpointing, the scan's second forward pass inside
its backward, flash attention's recompute of the scores) are NOT counted,
nor is padding a kernel adds to its operands: useful operations only.
"""

from __future__ import annotations

from benchmark.harness.flops import peaks, roofline_seconds  # noqa: F401


def layer_kinds(cfg: dict) -> list:
    """[(mixer, ffn)] of the stack as the configuration file cuts it."""
    kept = cfg.get("layers_kept") or list(
        range(1, cfg["num_hidden_layers"] + 1))
    kda = cfg["linear_attn_config"]["kda_layers"]
    return [("kda" if i in kda else "mla",
             "dense" if j < cfg["first_k_dense_replace"] else "moe")
            for j, i in enumerate(kept)]


def _kda_channels(cfg: dict) -> int:
    lin = cfg["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"]


def dense_weights_per_token(cfg: dict) -> float:
    """Matrix elements one token is multiplied with, forward, on this rank:
    every mixer, dense-MLP and shared-expert matrix, the router, the untied
    head's slice of the vocabulary, and of the routed experts
    num_experts_per_token times the share of the experts held here."""
    e, hd = cfg["hidden_size"], _kda_channels(cfg)
    h = cfg["num_attention_heads"]
    rank = cfg.get("kda_gate_rank") or cfg["linear_attn_config"]["head_dim"]
    total = cfg.get("experts_total") or cfg["num_experts"]
    expert = 3 * e * cfg["moe_intermediate_size"]
    weights = float(cfg["vocab_size"] * e)
    for mixer, ffn in layer_kinds(cfg):
        if mixer == "kda":
            weights += (4 * e * hd + 2 * (e + hd) * rank
                        + e * cfg["linear_attn_config"]["num_heads"])
        else:
            weights += (
                e * h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
                + e * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
                + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                             + cfg["v_head_dim"])
                + h * cfg["v_head_dim"] * e)
        if ffn == "dense":
            weights += 3 * e * cfg["intermediate_size"]
        else:
            weights += e * total + expert * (
                cfg["num_shared_experts"] + cfg["num_experts_per_token"]
                * cfg["num_experts"] / total)
    return weights


def kda_scan_flops(cfg: dict, tokens: float, backward: bool = True) -> float:
    """The chunked recurrence over `tokens` (token, KDA layer) pairs, every
    head: per token and head, with C the chunk and D the head, forward:
    the causal halves of K K^T, of Q K^T and of the triangle times U
    (C D each), the unit-triangular solve for the two right-hand sides
    (2 C D), and three products with the D x D state (6 D^2); backward
    twice that."""
    lin = cfg["linear_attn_config"]
    c, d = cfg.get("kda_chunk_size", 64), lin["head_dim"]
    return ((3.0 if backward else 1.0) * tokens * lin["num_heads"]
            * (5 * c * d + 6 * d * d))


def kda_scan_bytes(cfg: dict, tokens: float, backward: bool = True) -> float:
    """HBM bytes the recurrence has to move: forward it reads q, k, v
    (bfloat16), the log-decays (float32) and writes the outputs (float32):
    14 bytes a channel; backward it reads those and the outputs' cotangent
    and writes four cotangents (bfloat16 q, k, v; float32 decays): 24 more.
    beta and the carried state are small beside them."""
    return (38.0 if backward else 14.0) * tokens * _kda_channels(cfg)


def causal_attention_flops(cfg: dict, causal_pairs: float,
                           backward: bool = True) -> float:
    """Score and value products of causal latent attention inside
    documents, all MLA layers: per (query, key <= query) pair and head
    2 x 192 operations for q.k and 2 x 128 for p.v forward, twice that
    backward. `causal_pairs` = sum over documents of len (len + 1) / 2.
    (readers/flash_causal_roofline.py calls it by this name.)"""
    layers = sum(1 for mixer, _ in layer_kinds(cfg) if mixer == "mla")
    widths = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
              + cfg["v_head_dim"])
    return ((6.0 if backward else 2.0) * layers
            * cfg["num_attention_heads"] * widths * causal_pairs)


def moe_expert_flops(cfg: dict, pairs: float, backward: bool = True) -> float:
    """The three products of the routed SwiGLU experts over `pairs` (token,
    held expert) pairs: 2 x 3 x hidden x width forward a pair, twice that
    backward; the shared expert is not among them.
    (readers/moe_experts_roofline.py calls it by this name.)"""
    return ((6.0 if backward else 2.0) * pairs * 3 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"])


def train_flops(cfg: dict, slots: float, causal_pairs: float) -> float:
    """Forward + backward operations of `slots` token slots holding
    documents with `causal_pairs` attention pairs."""
    kda_layers = sum(1 for mixer, _ in layer_kinds(cfg) if mixer == "kda")
    return (6.0 * dense_weights_per_token(cfg) * slots
            + kda_scan_flops(cfg, slots * kda_layers)
            + causal_attention_flops(cfg, causal_pairs))
