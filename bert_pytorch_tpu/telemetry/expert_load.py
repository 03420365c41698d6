"""Cumulative counters of the routed (mixture-of-experts) layers for the
`[perf]` record, summed on the host from the per-expert scalars a step of
the decoder families returns (models/decoder.expert_scalars:
`moe_l<layer>_e<expert>`, `moe_l<layer>_dropped`, `moe_l<layer>_windows`,
`moe_pairs_routed`), and
of the gated delta-rule scans (models/kimi_linear.py: `kda_tokens`,
`kda_kernel_tokens`, `kda_resets`, passed on as they are summed), and of
the learned-sparse attention layers (models/keye.selection_scalars:
`dsa_tokens`, `dsa_l<layer>_kl|_candidates_hi|_candidates_lo|_kb<block>`).

Per routed layer L, since the run began: `moe_l<L>_pairs` (token, expert)
pairs routed to the experts held here; `moe_l<L>_load_min/_mean/_max` the
held experts' tokens; `moe_l<L>_dropped` held pairs not computed (the layer
is dropless: always 0); `moe_l<L>_windows` windows of sorted pairs the
layer's loop ran (ops/moe.live_windows: one a layer pass, a micro-batch, at
an even load; more only where the held experts drew more than a window's
pairs); `moe_l<L>_held_share` the share of ALL routed pairs that stayed on
this rank (near held / total experts).

Of the selection, since the run began: `dsa_selected_pairs` and
`dsa_candidate_pairs` over all layers (the pairs the queries selected, and
the causal pairs inside documents they selected from); per layer L
`dsa_l<L>_kl`, the KL term's mean over the real tokens seen, and
`dsa_l<L>_kb<j>`, the selected pairs by key block.
"""

from __future__ import annotations

import re
from typing import Dict

_LOAD = re.compile(r"^moe_l(\d+)_e(\d+)$")
_SUMMED = re.compile(r"^moe_l(\d+)_(dropped|windows)$")
_KDA = ("kda_tokens", "kda_kernel_tokens", "kda_resets")
_DSA = re.compile(r"^dsa_l(\d+)_(kl|candidates_hi|candidates_lo|kb\d+)$")
# the candidate pairs arrive in two halves (ops/sparse_index.COUNT_UNIT:
# an int32 sum of the count itself wraps at 16 rows of 16,384 tokens a step)
_CANDIDATES = {"candidates_hi": 1 << 16, "candidates_lo": 1}


class ExpertLoadCounters:
    def __init__(self):
        self.load: Dict[int, Dict[int, float]] = {}
        self.dropped: Dict[int, float] = {}
        self.windows: Dict[int, float] = {}
        self.routed = 0.0
        self.kda: Dict[str, float] = {}
        self.dsa: Dict[str, float] = {}

    def update(self, vals: Dict[str, float]) -> None:
        """Add one step's scalars (a dict of the step's metrics; keys that
        are not counters are ignored)."""
        for key, value in vals.items():
            m = _LOAD.match(key)
            if m:
                layer = self.load.setdefault(int(m.group(1)), {})
                expert = int(m.group(2))
                layer[expert] = layer.get(expert, 0.0) + float(value)
                continue
            m = _SUMMED.match(key)
            if m:
                layer, sums = int(m.group(1)), getattr(self, m.group(2))
                sums[layer] = sums.get(layer, 0.0) + float(value)
            elif key == "moe_pairs_routed":
                self.routed += float(value)
            elif key in _KDA:
                self.kda[key] = self.kda.get(key, 0.0) + float(value)
            elif key == "dsa_tokens" or _DSA.match(key):
                self.dsa[key] = self.dsa.get(key, 0.0) + float(value)

    def fields(self) -> Dict[str, float]:
        out = dict(self.kda)
        tokens = max(self.dsa.get("dsa_tokens", 0.0), 1.0)
        for key, value in sorted(self.dsa.items()):
            m = _DSA.match(key)
            if m is None:
                continue
            kind = m.group(2)
            if kind == "kl":
                out[key] = value / tokens
                continue
            if kind in _CANDIDATES:     # the halves of all layers' pairs
                total, value = "dsa_candidate_pairs", value * _CANDIDATES[kind]
            else:           # a key block's pairs: all layers' add up too
                total, out[key] = "dsa_selected_pairs", value
            out[total] = out.get(total, 0.0) + value
        for layer, loads in sorted(self.load.items()):
            values = [loads[e] for e in sorted(loads)]
            pairs = sum(values)
            out[f"moe_l{layer}_pairs"] = pairs
            out[f"moe_l{layer}_load_min"] = min(values)
            out[f"moe_l{layer}_load_mean"] = pairs / len(values)
            out[f"moe_l{layer}_load_max"] = max(values)
            out[f"moe_l{layer}_dropped"] = self.dropped.get(layer, 0.0)
            out[f"moe_l{layer}_windows"] = self.windows.get(layer, 0.0)
            out[f"moe_l{layer}_held_share"] = pairs / max(self.routed, 1.0)
        return out
