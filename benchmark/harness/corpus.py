"""The training traffic generator: a seeded corpus in the reference's HDF5
shard schema (input_ids, special_token_positions, next_sentence_labels), from
the `corpus` block of a traffic file.

One generator for every mix: `lengths` names a distribution and its
parameters; every seed gets the SAME multiset of document lengths (quantiles
of the distribution) and its own random tokens. The seed also orders the
documents, unless the mix states an `order_seed`: the program reads a corpus
front to back, and where a window holds a small part of it (a tenth of the
heavy-tailed 16k mix) the order decides how many real tokens and causal
pairs the window draws, so tokens/s moved with the seed by more than the
bound (PERF.md section 6, PR 40). With `order_seed` the order is drawn from
that constant, every seed's run packs the same rows, and the work of a
window does not move with the seed.
"""

from __future__ import annotations

import math
import os
from statistics import NormalDist

import numpy as np

CLS, SEP, MASK = 101, 102, 103      # bert-*-uncased vocabulary ids
FIRST_WORD_ID = 1000                # below: [unused*] and specials


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """n document lengths (tokens, specials included): the distribution's
    quantiles at (i + 0.5) / n, so the set is the same for every seed."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    kind = spec["kind"]
    if kind == "full_or_uniform":
        # BERT's create_pretraining_data: short_seq_prob of the sequences
        # get a uniform target length, the rest fill the row
        p = float(spec["short_prob"])
        short = lo + (u / p) * (hi - lo)
        lengths = np.where(u < p, short, hi)
    elif kind == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(x)) for x in u])
        lengths = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(lengths), max(lo, 5), hi).astype(np.int64)


def write_shards(out_dir: str, corpus: dict, seq_len: int, vocab_size: int,
                 seed: int) -> dict:
    """Write the corpus; returns its totals. Each sequence is
    [CLS] a [SEP] b [SEP] with random word ids, zero-padded to seq_len."""
    import h5py

    os.makedirs(out_dir, exist_ok=True)
    n, n_shards = int(corpus["samples"]), int(corpus.get("shards", 2))
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    order = rng
    if "order_seed" in corpus:
        order = np.random.Generator(np.random.PCG64(int(corpus["order_seed"])))
    lengths = order.permutation(quantile_lengths(corpus["lengths"], n))
    ids = rng.integers(FIRST_WORD_ID, vocab_size, (n, seq_len),
                       dtype=np.int32)
    col = np.arange(seq_len)[None, :]
    last = lengths - 1                                  # second [SEP]
    sep1 = 1 + (rng.random(n) * (last - 2)).astype(np.int64)   # in [1, last)
    sep1 = np.clip(sep1, 2, last - 2)
    ids[:, 0] = CLS
    ids[np.arange(n), sep1] = SEP
    ids[np.arange(n), last] = SEP
    ids[col > last[:, None]] = 0
    specials = np.stack([np.zeros(n, np.int64), sep1, last],
                        axis=1).astype(np.int32)
    nsp = rng.integers(0, 2, n).astype(np.int8)
    per = math.ceil(n / n_shards)
    for s in range(n_shards):
        sl = slice(s * per, min(n, (s + 1) * per))
        with h5py.File(os.path.join(out_dir, f"shard_{s}.hdf5"), "w") as f:
            f.create_dataset("input_ids", data=ids[sl])
            f.create_dataset("special_token_positions", data=specials[sl])
            f.create_dataset("next_sentence_labels", data=nsp[sl])
    return {"samples": n, "real_tokens": int(lengths.sum()),
            "slot_tokens": n * seq_len}
