"""The `bert` family (configurations without a `model_type`, or with
`"model_type": "bert"`): MLM + NSP pretraining of a BERT encoder.

Everything the benchmark knows about the family is named here and nowhere
else: its reference (reference/bert_ref.py) with the weights it makes from
the seed, its adapter (harness/adapter.py), what of a batch the reference
needs (every field, the step's key for the dropout masks), how the followed
steps are followed, its FLOPs (harness/flops.py), and what it adds to the
child's record. `harness/spec.load_family` says which names a family module
has to define; drivers/train.py (no JAX: the top of this file imports none)
and harness/train_child.py call them.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness import flops  # also the readers' ctx["flops"]


# -- the driver's side (this process stays off JAX) ------------------------

def window_flops(cell: dict, window: dict):
    """(forward + backward FLOPs the window's steps need, what they count)."""
    t, cfg = cell["traffic"], cell["config"]
    vocab_rows = (int(cfg["vocab_size"]) + 127) // 128 * 128
    rows = t["local_batch"] * t["accum"] * t.get("data_shards", 1)
    per_step = rows * flops.train_flops_per_row(
        cfg, int(t["seq_len"]), vocab_rows, int(t["max_predictions"]))
    return per_step * window["steps"], "of the slots"


def decide(cell: dict, record: dict, check) -> None:
    """Nothing on top of the driver's `decide_correct`."""


# -- the child's side ------------------------------------------------------

def sizes(config: dict, traffic: dict) -> dict:
    from benchmark.reference import bert_ref

    return bert_ref.sizes_from_config(
        config, int(traffic.get("vocab_pad_multiple", 128)))


def program_args(traffic: dict) -> list:
    """The program's arguments that only this family's objective has."""
    return ["--max_predictions_per_seq", str(traffic["max_predictions"]),
            "--masked_token_fraction", str(traffic["masked_lm_prob"]),
            "--mask_token_index", "103"]


def weights(spec: dict, sz: dict) -> dict:
    """The benchmark's weights from the seed, in the program's layout."""
    from benchmark.harness import adapter
    from benchmark.reference import bert_ref

    return adapter.to_program_tree(
        bert_ref.init_params(spec["seed"], sz), sz["heads"])


def adapter_functions(sz: dict):
    """(leaf_norms, leaf_diff_norms, sample_matrices) of trees in the
    program's layout."""
    from benchmark.harness import adapter as a

    return a.leaf_norms, a.leaf_diff_norms, a.sample_matrices


def _max_pred_row(t: dict, packed: bool) -> int:
    """The per-row budget of the gathered MLM head, as the program sets it."""
    if not packed:
        return int(t["max_predictions"])
    seq_len, seg = int(t["seq_len"]), int(t.get("packing_max_segments", 8))
    return min(seq_len, seg * int(t["max_predictions"]),
               int(seq_len * float(t["masked_lm_prob"])) + seg)


def follow(spec: dict, sz: dict, batches: list, keys: list,
           quant=None) -> dict:
    """The reference's losses, first clipped gradient and parameter change
    over the observed steps' own inputs."""
    import jax

    from benchmark.harness import adapter
    from benchmark.reference import bert_ref

    t, cfg, heads = spec["traffic"], spec["config"], sz["heads"]
    max_pred = _max_pred_row(t, "segment_ids" in batches[0])
    params = adapter.place_for_reference(
        bert_ref.init_params(spec["seed"], sz), False)
    opt = bert_ref.lamb_init(params)
    losses, grad_norms, grad_sample = [], None, None
    rates = (float(cfg.get("hidden_dropout_prob", 0.0)),
             float(cfg.get("attention_probs_dropout_prob", 0.0)))
    # the program drops attention probabilities inside its flash kernel
    # beyond 256 positions (ops/attention.py, impl "auto")
    flash = int(t["seq_len"]) > 256
    for batch, key in zip(batches, keys):
        accum = next(iter(batch.values())).shape[0]
        micros = [adapter.place_for_reference(
            {k: v[i] for k, v in batch.items()}, True)
            for i in range(accum)]
        dropout = None
        if max(rates) > 0.0:
            dropout = rates + (flash, bert_ref.dropout_seeds(
                jax.numpy.asarray(key), accum, sz["layers"], flash))
        loss, grads = bert_ref.step_loss_and_grad(
            params, micros, heads, max_pred, quant, dropout)
        losses.append(float(loss))
        if grad_norms is None:
            clipped, _ = jax.jit(bert_ref.clipped_gradient)(grads)
            clipped = adapter.to_program_tree(clipped, heads)
            grad_norms = adapter.leaf_norms(clipped)
            grad_sample = adapter.sample_matrices(clipped)
            del clipped
        params, opt = bert_ref.lamb_step(
            params, grads, opt, float(t["learning_rate"]),
            int(t["max_steps"]), float(t["warmup_proportion"]))
        del grads
    del opt
    start = adapter.place_for_reference(
        bert_ref.init_params(spec["seed"], sz), False)
    delta_norms = adapter.leaf_diff_norms(
        adapter.to_program_tree(params, heads),
        adapter.to_program_tree(start, heads))
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_sample": grad_sample, "delta_norms": delta_norms}


def followed_by_program(scalars: dict, steps: int) -> dict:
    """Nothing of the followed steps beyond what the child observes."""
    return {}


def window_extras(segs: dict, scalars: dict, cell: dict) -> dict:
    """What the family adds to the window's record (`segs`: the timed
    steps' segment ids where rows are packed, `scalars`: every step's
    logged values, `cell`: the cell's `config` and `traffic` as the child
    has them; not needed here): per step the sum over documents of length
    squared, what attention needs when a token attends only inside its
    document."""
    doc_sq = {}
    for n, seg in segs.items():
        seg = seg.reshape(-1, seg.shape[-1])
        counts = np.stack([np.bincount(row, minlength=int(seg.max()) + 1)
                           for row in seg])[:, 1:]
        doc_sq[n] = int((counts.astype(np.int64) ** 2).sum())
    return {"doc_len_sq": doc_sq}


def compare_extras(got: dict, ref: dict) -> dict:
    """Nothing compared beyond the losses, the gradient and the change."""
    return {}
