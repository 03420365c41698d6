"""The yardstick's arithmetic: operations and bytes an algorithm needs, and
the chip's peaks. Kept with the benchmark so that it does not move when the
program does (copied from the program's telemetry/stepwatch.flops_per_seq,
PERF.md Open questions lists the original).

Recomputed operations (activation checkpointing, flash attention's backward
recompute of the scores) are NOT counted: a utilisation or roofline share
is against what the mathematics needs.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of `device_kind`; a kind the table lacks is an
    error, never a default."""
    with open(_PEAKS, encoding="utf-8") as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"{_PEAKS} (have {sorted(table)})")
    return table[device_kind]


def train_flops_per_row(cfg: dict, seq_len: int, vocab_rows: int,
                        n_pred: int) -> float:
    """Forward + backward FLOPs of one row of seq_len slots: 6 x weights x
    positions for the dense products (the MLM transform and tied decoder
    only on the n_pred gathered positions) + 12 x L x E x S^2 for the
    attention score and value products."""
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    n = cfg["num_hidden_layers"]
    trunk = n * (4 * e * e + 2 * e * f) * seq_len
    head = (vocab_rows * e + e * e) * n_pred
    return 6.0 * (trunk + head) + 12.0 * n * e * seq_len * seq_len


def attention_flops(hidden: int, layers: int, sum_len_sq: float,
                    backward: bool = True) -> float:
    """Score and value products over documents of the given lengths
    (sum_len_sq = sum of length squared: a token attends only inside its
    document): 4 E len^2 forward, 8 E len^2 more backward, per layer."""
    return (12.0 if backward else 4.0) * layers * hidden * sum_len_sq


def roofline_seconds(flops: float, bytes_: float, peak: dict) -> dict:
    """Least time the chip could take, and which peak bounds it."""
    t_flops = flops / peak["flops_per_s_bf16"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "flops" if t_flops >= t_bytes else "bytes"}
