"""Operations the `smallthinker` family's algorithms need (families/
smallthinker.py binds it): the arithmetic of the MFU line, of the rooflines
of its two kinds of attention layer (causal over the whole document; causal
inside a band of `sliding_window_size` tokens) and of the held routed
experts' grouped products, counted as the lfm2 family counts them. The
chip's peaks and the roofline's form are harness/flops.py's. Recomputed
operations (activation checkpointing, flash attention's recompute of the
scores) are NOT counted, nor are the masked parts of a tile a kernel visits:
the (query, key) pairs the mathematics needs, whatever implements them.
"""

from __future__ import annotations

from benchmark.harness.flops import peaks, roofline_seconds  # noqa: F401


def layer_kinds(cfg: dict) -> list:
    """[(the band's width or 0, rotary positions or not)] of the stack as
    the configuration file cuts it."""
    return [(int(cfg["sliding_window_size"]) if w else 0, bool(p))
            for w, p in zip(cfg["sliding_window_layout"], cfg["rope_layout"])]


def band_pairs(length: int, window: int) -> int:
    """(query, key) pairs of one document of `length` tokens: key <= query,
    and under a band (`window` > 0) query - key < window: L (L + 1) / 2, or
    for L > W: W (W + 1) / 2 + (L - W) W."""
    length = int(length)
    if not window or length <= window:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def dense_weights_per_token(cfg: dict) -> float:
    """Matrix elements one token is multiplied with, forward, on this rank:
    every attention matrix, the router, the untied head's slice of the
    vocabulary, and of the routed experts moe_num_active_primary_experts
    times the share of the experts held here."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held = cfg["moe_num_primary_experts"]
    total = cfg.get("experts_total") or held
    layer = (e * (h + 2 * hkv) * d + h * d * e + e * total
             + 3 * e * cfg["moe_ffn_hidden_size"]
             * cfg["moe_num_active_primary_experts"] * held / total)
    return float(cfg["vocab_size"] * e + len(layer_kinds(cfg)) * layer)


def _attention_flops(cfg: dict, layers: int, pairs: float,
                     backward: bool) -> float:
    """Per (query, key) pair and query head 2 x 128 operations for q.k and
    2 x 128 for p.v forward, twice that backward."""
    return ((6.0 if backward else 2.0) * layers * cfg["num_attention_heads"]
            * 2 * cfg["head_dim"] * pairs)


def causal_attention_flops(cfg: dict, causal_pairs: float,
                           backward: bool = True) -> float:
    """Score and value products of the FULL-attention layers only:
    `causal_pairs` = sum over documents of len (len + 1) / 2.
    (readers/flash_causal_roofline.py calls it by this name, against the
    `flash_fwd` / `flash_bwd_*` kernels, which only these layers run.)"""
    layers = sum(1 for window, _ in layer_kinds(cfg) if not window)
    return _attention_flops(cfg, layers, causal_pairs, backward)


def window_attention_flops(cfg: dict, window_pairs: float,
                           backward: bool = True) -> float:
    """Score and value products of the WINDOWED layers only: `window_pairs`
    = sum over documents of `band_pairs(len, sliding_window_size)`.
    (readers/flash_window_roofline.py calls it by this name.)"""
    layers = sum(1 for window, _ in layer_kinds(cfg) if window)
    return _attention_flops(cfg, layers, window_pairs, backward)


def moe_expert_flops(cfg: dict, pairs: float, backward: bool = True) -> float:
    """The three products of the routed ReLU-gated experts over `pairs`
    (token, held expert) pairs: 2 x 3 x hidden x width forward a pair,
    twice that backward.
    (readers/moe_experts_roofline.py calls it by this name.)"""
    return ((6.0 if backward else 2.0) * pairs * 3 * cfg["hidden_size"]
            * cfg["moe_ffn_hidden_size"])


def train_flops(cfg: dict, slots: float, causal_pairs: float,
                window_pairs: float) -> float:
    """Forward + backward operations of `slots` token slots holding
    documents with `causal_pairs` pairs in each full layer and
    `window_pairs` in each windowed one."""
    return (6.0 * dense_weights_per_token(cfg) * slots
            + causal_attention_flops(cfg, causal_pairs)
            + window_attention_flops(cfg, window_pairs))
