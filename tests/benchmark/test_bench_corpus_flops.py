"""The training traffic generator and the yardstick's arithmetic."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import corpus, flops  # noqa: E402

FULL = {"kind": "full_or_uniform", "short_prob": 0.1, "min": 8, "max": 128}
DOCS = {"kind": "lognormal", "median": 180, "sigma": 0.9, "min": 16,
        "max": 512}


@pytest.mark.parametrize("spec,n,seq", [(FULL, 1000, 128), (DOCS, 1000, 512)],
                         ids=["full_or_uniform", "lognormal"])
def test_lengths_follow_the_distribution(spec, n, seq):
    lengths = corpus.quantile_lengths(spec, n)
    assert lengths.min() >= spec["min"] and lengths.max() <= spec["max"]
    if spec["kind"] == "full_or_uniform":
        assert (lengths == 128).mean() == pytest.approx(0.9, abs=0.005)
        short = lengths[lengths < 128]
        assert short.mean() == pytest.approx((8 + 128) / 2, rel=0.05)
    else:
        assert np.median(lengths) == pytest.approx(180, rel=0.02)
        assert (lengths == 512).mean() == pytest.approx(0.123, abs=0.01)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_shards_schema_and_same_work_for_every_seed(tmp_path, seed):
    import h5py

    spec = {"samples": 64, "shards": 2, "lengths": FULL}
    totals = corpus.write_shards(str(tmp_path / "a"), spec, 128, 30522, seed)
    other = corpus.write_shards(str(tmp_path / "b"), spec, 128, 30522,
                                seed + 1)
    assert totals == other           # same multiset of lengths, another order
    again = corpus.write_shards(str(tmp_path / "c"), spec, 128, 30522, seed)
    assert again == totals
    ids, specials = [], []
    for s in range(2):
        with h5py.File(tmp_path / "a" / f"shard_{s}.hdf5") as f:
            assert sorted(f) == ["input_ids", "next_sentence_labels",
                                 "special_token_positions"]
            ids.append(f["input_ids"][:])
            specials.append(f["special_token_positions"][:])
        with h5py.File(tmp_path / "c" / f"shard_{s}.hdf5") as f:
            assert (f["input_ids"][:] == ids[-1]).all()   # same seed
        with h5py.File(tmp_path / "b" / f"shard_{s}.hdf5") as f:
            assert (f["input_ids"][:] != ids[-1]).any()   # another seed
    ids, specials = np.concatenate(ids), np.concatenate(specials)
    assert ids.shape == (64, 128) and (ids[:, 0] == corpus.CLS).all()
    rows = np.arange(64)
    assert (ids[rows, specials[:, 1]] == corpus.SEP).all()
    assert (ids[rows, specials[:, 2]] == corpus.SEP).all()
    assert ((ids != 0).sum(1) == specials[:, 2] + 1).all()
    assert (ids != 0).sum() == totals["real_tokens"]
    assert 0 < specials[:, 1].min() and (specials[:, 1] < specials[:, 2]).all()


def _lengths(path):
    import h5py

    out = []
    for s in range(2):
        with h5py.File(path / f"shard_{s}.hdf5") as f:
            out.append(f["special_token_positions"][:][:, 2] + 1)
    return np.concatenate(out)


@pytest.mark.parametrize("order_seed", [None, 0, 5])
def test_an_order_seed_takes_the_documents_order_from_the_seed(tmp_path,
                                                                order_seed):
    """With `order_seed` every seed reads the same documents in the same
    order (a window of the run then holds the same work), with its own
    tokens; without it the seed orders them."""
    import h5py

    spec = {"samples": 64, "shards": 2, "lengths": DOCS}
    if order_seed is not None:
        spec["order_seed"] = order_seed
    a = corpus.write_shards(str(tmp_path / "a"), spec, 512, 30522, 3)
    b = corpus.write_shards(str(tmp_path / "b"), spec, 512, 30522, 2**31 + 4)
    assert a == b
    la, lb = _lengths(tmp_path / "a"), _lengths(tmp_path / "b")
    assert sorted(la) == sorted(lb) == sorted(
        corpus.quantile_lengths(DOCS, 64))
    assert (la == lb).all() == (order_seed is not None)
    with h5py.File(tmp_path / "a" / "shard_0.hdf5") as f, \
            h5py.File(tmp_path / "b" / "shard_0.hdf5") as g:
        assert (f["input_ids"][:] != g["input_ids"][:]).any()
    if order_seed is not None:          # and another constant, another order
        other = dict(spec, order_seed=order_seed + 1)
        corpus.write_shards(str(tmp_path / "c"), other, 512, 30522, 3)
        assert (_lengths(tmp_path / "c") != la).any()


LARGE = {"hidden_size": 1024, "intermediate_size": 4096,
         "num_hidden_layers": 24}


def test_train_flops_against_hand_worked_bert_large():
    # per layer 4*1024^2 + 2*1024*4096 = 12,582,912 weights in matmuls;
    # 24 layers x 128 positions; head (30592*1024 + 1024^2) x 20 positions;
    # attention 12 x 24 x 1024 x 128^2
    trunk = 24 * 12_582_912 * 128
    head = (30592 * 1024 + 1024 * 1024) * 20
    attn = 12 * 24 * 1024 * 128 * 128
    assert flops.train_flops_per_row(LARGE, 128, 30592, 20) == \
        6 * (trunk + head) + attn
    assert flops.train_flops_per_row(LARGE, 128, 30592, 20) == \
        pytest.approx(2.40645e11, rel=1e-5)


@pytest.mark.parametrize("backward,factor", [(True, 12.0), (False, 4.0)])
def test_attention_work_counts_documents_not_rows(backward, factor):
    # two documents of 100 and 412 tokens in a row of 512: 100^2 + 412^2,
    # not 512^2
    sq = 100 ** 2 + 412 ** 2
    assert flops.attention_flops(1024, 24, sq, backward) == \
        factor * 24 * 1024 * sq


def test_roofline_picks_the_binding_peak_and_unknown_kind_is_an_error():
    peak = flops.peaks("TPU v5 lite")
    assert peak["flops_per_s_bf16"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert peak["ici_bits_per_s"] == 1600e9
    r = flops.roofline_seconds(197e12, 1.0, peak)
    assert r == {"seconds": 1.0, "bound": "flops"}
    r = flops.roofline_seconds(1.0, 819e9 * 2, peak)
    assert r == {"seconds": 2.0, "bound": "bytes"}
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
