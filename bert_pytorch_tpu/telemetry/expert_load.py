"""Cumulative counters of the routed (mixture-of-experts) layers for the
`[perf]` record, summed on the host from the per-expert scalars a step of
the decoder families returns (models/lfm2_moe.expert_scalars:
`moe_l<layer>_e<expert>`, `moe_l<layer>_dropped`, `moe_l<layer>_windows`,
`moe_pairs_routed`), and
of the gated delta-rule scans (models/kimi_linear.py: `kda_tokens`,
`kda_kernel_tokens`, `kda_resets`, passed on as they are summed).

Per routed layer L, since the run began: `moe_l<L>_pairs` (token, expert)
pairs routed to the experts held here; `moe_l<L>_load_min/_mean/_max` the
held experts' tokens; `moe_l<L>_dropped` held pairs not computed (the layer
is dropless: always 0); `moe_l<L>_windows` windows of sorted pairs the
layer's loop ran (ops/moe.live_windows: one a layer pass, a micro-batch, at
an even load; more only where the held experts drew more than a window's
pairs); `moe_l<L>_held_share` the share of ALL routed pairs that stayed on
this rank (near held / total experts).
"""

from __future__ import annotations

import re
from typing import Dict

_LOAD = re.compile(r"^moe_l(\d+)_e(\d+)$")
_SUMMED = re.compile(r"^moe_l(\d+)_(dropped|windows)$")
_KDA = ("kda_tokens", "kda_kernel_tokens", "kda_resets")


class ExpertLoadCounters:
    def __init__(self):
        self.load: Dict[int, Dict[int, float]] = {}
        self.dropped: Dict[int, float] = {}
        self.windows: Dict[int, float] = {}
        self.routed = 0.0
        self.kda: Dict[str, float] = {}

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

    def fields(self) -> Dict[str, float]:
        out = dict(self.kda)
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
