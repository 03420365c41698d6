"""Loss functions for every task head.

The reference computed losses inside each head's forward when labels were
given (e.g. BertPretrainingCriterion at run_pretraining.py:53-67, SQuAD loss at
run_squad.py:1089-1092). Functional JAX separates them: heads return logits,
these functions turn (logits, labels) into scalars. All cross-entropies are
computed in fp32 with masked mean semantics identical to torch's
CrossEntropyLoss(ignore_index=...) — sum over valid positions divided by the
count of valid positions.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  ignore_index: int = -1) -> jax.Array:
    """Mean CE over positions where labels != ignore_index.

    logits: (..., C) fp32; labels: (...) int. Matches
    torch.nn.CrossEntropyLoss(ignore_index=) mean reduction, returning 0.0
    when no positions are valid (torch returns NaN there; 0 keeps grad clean
    when a microbatch happens to contain no masked tokens).
    """
    logits = logits.astype(jnp.float32)
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, nll, 0.0)
    count = jnp.maximum(valid.sum(), 1)
    return nll.sum() / count


def cross_entropy_terms(logits: jax.Array, labels: jax.Array,
                        ignore_index: int = -1
                        ) -> Tuple[jax.Array, jax.Array]:
    """(nll sum, valid count) — `cross_entropy` stopped before the final
    max/divide, for callers that must reduce across devices BEFORE the
    normalization (the ZeRO-1 reduce-scatter gradient path wraps the
    fwd/bwd in a shard_map region, psums these local sums, and applies
    maximum(count, 1) after the psum — the exact grouping the GSPMD
    lowering of `cross_entropy` uses, so the metric stays bit-identical).
    The per-position arithmetic is _nll's, which is cross_entropy's."""
    nll, valid = _nll(logits, labels, ignore_index)
    return nll.sum(), valid.sum()


def pretraining_loss_terms(
    mlm_logits: jax.Array,
    masked_lm_labels: jax.Array,
    nsp_logits: Optional[jax.Array] = None,
    next_sentence_labels: Optional[jax.Array] = None,
) -> Tuple[Tuple[jax.Array, jax.Array],
           Optional[Tuple[jax.Array, jax.Array]]]:
    """pretraining_loss decomposed into its per-term (nll sum, count)
    pairs: ((mlm_sum, mlm_count), (nsp_sum, nsp_count) | None). The
    caller owns the cross-device reduction and the
    sum/maximum(count, 1) division per term — summing the two finished
    quotients reproduces `pretraining_loss` exactly."""
    mlm = cross_entropy_terms(mlm_logits, masked_lm_labels, ignore_index=-1)
    nsp = None
    if nsp_logits is not None and next_sentence_labels is not None:
        nsp = cross_entropy_terms(nsp_logits, next_sentence_labels,
                                  ignore_index=-1)
    return mlm, nsp


def pretraining_loss(
    mlm_logits: jax.Array,                    # (B, S, V)
    masked_lm_labels: jax.Array,              # (B, S), -1 = unmasked
    nsp_logits: Optional[jax.Array] = None,   # (B, 2) or packed (B, G, 2)
    next_sentence_labels: Optional[jax.Array] = None,  # (B,) or (B, G)
) -> jax.Array:
    """MLM + NSP summed, ignore_index=-1 (reference BertPretrainingCriterion,
    run_pretraining.py:53-67).

    Packed batches (--packing) arrive with per-segment NSP terms: logits
    (B, G, 2) against labels (B, G), -1 marking empty segment slots. The
    masked-mean reduction weights every real segment equally — a packed
    batch's MLM+NSP loss equals its unpacked equivalent's exactly, because
    both pool the same masked-token set and the same NSP example set (the
    invariant tests/test_packing.py pins down)."""
    loss = cross_entropy(mlm_logits, masked_lm_labels, ignore_index=-1)
    if nsp_logits is not None and next_sentence_labels is not None:
        loss = loss + cross_entropy(nsp_logits, next_sentence_labels,
                                    ignore_index=-1)
    return loss


def qa_loss(start_logits: jax.Array, end_logits: jax.Array,
            start_positions: jax.Array, end_positions: jax.Array
            ) -> jax.Array:
    """(CE(start) + CE(end)) / 2; answer positions outside [0, S) contribute
    no loss — the reference clamps them to ignored_index=seq_len and uses
    CrossEntropyLoss(ignore_index=seq_len) (run_squad.py:1080-1092), so
    truncated-answer windows are ignored, not trained toward a wrong token."""
    seq_len = start_logits.shape[-1]

    def drop_out_of_window(pos):
        return jnp.where((pos >= 0) & (pos < seq_len), pos, -1)

    loss_s = cross_entropy(start_logits, drop_out_of_window(start_positions),
                           ignore_index=-1)
    loss_e = cross_entropy(end_logits, drop_out_of_window(end_positions),
                           ignore_index=-1)
    return (loss_s + loss_e) / 2.0


def token_classification_loss(logits: jax.Array, labels: jax.Array,
                              ignore_index: int = -100) -> jax.Array:
    """Per-token CE; -100 ignores subword/[SPC] positions
    (reference src/ner_dataset.py label propagation + torch default)."""
    return cross_entropy(logits, labels, ignore_index=ignore_index)


def classification_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return cross_entropy(logits, labels, ignore_index=-1)


def _ordered_sum(x: jax.Array) -> jax.Array:
    """Strict left-to-right (row-major flat) sequential sum via lax.scan.

    jnp.sum's reduction grouping depends on the array SHAPE, so a packed
    batch and its one-segment-per-row equivalent — identical loss terms,
    different shapes — drift in the last float32 bits under the default
    reduce. Empty slots add exact zeros, so the sequential partial-sum
    sequence is a pure function of the real values in traversal order —
    the property the packed-vs-unpadded bit-equality pin rests on
    (tests/test_finetune_packing.py). Only ever used on tiny
    per-segment aggregates ((B, G)-sized), where a sequential loop is
    free; the big (B, S, V)-scale reductions keep the fast default."""
    flat = x.reshape(-1)
    total, _ = jax.lax.scan(lambda acc, v: (acc + v, None),
                            jnp.zeros((), flat.dtype), flat)
    return total


def _nll(logits: jax.Array, labels: jax.Array, ignore_index: int
         ) -> Tuple[jax.Array, jax.Array]:
    """(per-position nll with ignored slots exactly 0, valid mask)."""
    logits = logits.astype(jnp.float32)
    valid = labels != ignore_index
    safe = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return jnp.where(valid, nll, 0.0), valid


def segment_onehot(segment_ids: jax.Array, max_segments: int) -> jax.Array:
    """(B, S) packed segment ids (1..G, 0 = pad) -> (B, G, S) boolean
    segment-membership mask. The ONE construction every packed gather and
    reduction shares — the [CLS] position gather
    (models/bert.positions_from_segment_ids), the sentence-embedding mean,
    and the packed token/QA losses below. Packed-vs-unpadded bit-equality
    (tests/test_finetune_packing.py) depends on all of them masking with
    identical bits, so build the mask here, never inline."""
    want = jnp.arange(1, max_segments + 1, dtype=segment_ids.dtype)
    return segment_ids[:, None, :] == want[None, :, None]


def segment_classification_loss(logits: jax.Array, labels: jax.Array
                                ) -> jax.Array:
    """Classification CE over per-segment pooled logits ((B, G, C)
    against (B, G) labels, -1 = empty slot), reduced with the
    order-canonical sequential sum so packed and one-segment-per-row
    batches produce the same bits. Degenerates to plain classification
    on (B, C)/(B,) shapes."""
    nll, valid = _nll(logits, labels, ignore_index=-1)
    return _ordered_sum(nll) / jnp.maximum(valid.sum(), 1)


def choice_loss(scores: jax.Array, labels: jax.Array,
                num_choices: int) -> jax.Array:
    """Multiple-choice CE. `scores` is (B, C) (the reference shape,
    src/modeling.py:1112-1179) or packed (B, G) with each example's C
    choices in C consecutive segments — regrouped to (B, G/C, C) here.
    `labels` is the matching (B,) / (B, G/C) chosen-index array, -1 for
    empty packed groups. Ordered-sum reduction: packed and plain batches
    of the same examples agree bit-for-bit.

    Shape rule: labels with the SAME rank as scores mark the packed
    per-segment form (scores (B, G) vs labels (B, G/C) — even when G/C
    happens to equal num_choices), so scores regroup to (B, G/C, C);
    labels one rank below scores mean the choice axis is already last
    (the plain (B, C)/(B,) pair)."""
    if labels.ndim == scores.ndim:
        scores = scores.reshape(*scores.shape[:-1], -1, num_choices)
    return segment_classification_loss(scores, labels)


def packed_token_loss(logits: jax.Array, labels: jax.Array,
                      segment_ids: jax.Array, max_segments: int,
                      ignore_index: int = -100) -> jax.Array:
    """Per-token CE for packed rows, reduced SEGMENT-FIRST: per-token
    nll is contracted against the segment one-hot (an einsum whose
    zero-slot terms are exactly 0.0) before the tiny (B, G) sum, so a
    packed batch's scalar equals the same examples one-segment-per-row
    bit-for-bit — a flat (B, S) sum regroups the reduction tree when the
    tokens move and drifts in the last float32 bits (per-token values
    are identical; only the summation grouping moved)."""
    nll, valid = _nll(logits, labels, ignore_index)
    onehot = segment_onehot(segment_ids, max_segments).astype(jnp.float32)
    seg_nll = jnp.einsum("bgs,bs->bg", onehot, nll)
    return _ordered_sum(seg_nll) / jnp.maximum(valid.sum(), 1)


def packed_qa_loss(start_logits: jax.Array, end_logits: jax.Array,
                   start_positions: jax.Array, end_positions: jax.Array,
                   segment_ids: jax.Array, max_segments: int) -> jax.Array:
    """Per-segment span CE for packed rows: each segment's softmax runs
    over ITS OWN positions only (cross-segment and pad logits are exactly
    excluded via a -inf mask, exp(-inf) == 0.0), so a packed row's loss
    equals the same examples' loss one-segment-per-row bit-for-bit —
    a full-row softmax would mix denominators across co-packed strangers.

    start/end_positions are (B, G) ABSOLUTE row positions (example
    position + packing offset), -1 for empty slots or answers outside
    the window (the qa_loss clamp, reference run_squad.py:1080-1092).
    """
    seg_mask = segment_onehot(segment_ids, max_segments)       # (B, G, S)

    def seg_ce(logits, positions):
        logits = logits.astype(jnp.float32)[:, None, :]        # (B, 1, S)
        masked = jnp.where(seg_mask, logits, -jnp.inf)
        logp = jax.nn.log_softmax(masked, axis=-1)             # (B, G, S)
        valid = positions >= 0
        safe = jnp.where(valid, positions, 0)
        nll = -jnp.take_along_axis(logp, safe[..., None],
                                   axis=-1)[..., 0]
        nll = jnp.where(valid, nll, 0.0)
        return _ordered_sum(nll) / jnp.maximum(valid.sum(), 1)

    return (seg_ce(start_logits, start_positions)
            + seg_ce(end_logits, end_positions)) / 2.0


def mlm_accuracy(mlm_logits: jax.Array, labels: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
    """(num_correct, num_masked) for masked-token accuracy tracking."""
    valid = labels != -1
    pred = jnp.argmax(mlm_logits, axis=-1)
    correct = jnp.logical_and(pred == labels, valid)
    return correct.sum(), valid.sum()


def _next_token_labels(input_ids: jax.Array,
                       segment_ids: jax.Array) -> jax.Array:
    """The successor's id where it lies in the same document, else -1."""
    nxt_ids = jnp.pad(input_ids[:, 1:], ((0, 0), (0, 1)))
    nxt_seg = jnp.pad(segment_ids[:, 1:], ((0, 0), (0, 1)))
    return jnp.where((segment_ids > 0) & (nxt_seg == segment_ids),
                     nxt_ids, -1)


def next_token_loss(logits: jax.Array, input_ids: jax.Array,
                    segment_ids: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Causal language modelling over packed rows: the mean, over positions
    whose successor lies in the same document, of the cross-entropy of the
    successor's id. logits (B, S, V); input_ids, segment_ids (B, S) (the
    packing contract's segments, 0 = pad). Returns (loss, the number of
    such positions)."""
    labels = _next_token_labels(input_ids, segment_ids)
    # logsumexp minus the label's logit, not log_softmax then a gather: over
    # (32768, 8192) float32 logits XLA:TPU runs the latter's fused
    # exp-reduce six times slower (133 against 22 ms forward and backward on
    # a v5e, PERF.md PR 26), and this form keeps no (T, V) log-probabilities
    valid = labels >= 0
    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(
        logits, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, jax.nn.logsumexp(logits, axis=-1) - picked, 0.0)
    count = valid.sum()
    return nll.sum() / jnp.maximum(count, 1), count.astype(jnp.int32)


def next_token_loss_blocked(hidden: jax.Array, head: jax.Array,
                            input_ids: jax.Array, segment_ids: jax.Array,
                            block_rows: int) -> Tuple[jax.Array, jax.Array]:
    """`next_token_loss` of logits = hidden @ head^T (hidden (B, S, E), head
    (V, E), float32 accumulation) without the (B S, V) logits: the head and
    the cross-entropy run over `block_rows` tokens at a time, each block
    rematerialised in the backward pass, so one block's logits and their
    gradient are alive. B S that `block_rows` does not divide is one block.
    Scopes `lm_head` and `loss` (training/pretrain.LM_STEP_SCOPES)."""
    tokens = input_ids.size
    rows = block_rows if tokens % block_rows == 0 else tokens
    labels = _next_token_labels(input_ids, segment_ids).reshape(-1, rows)
    hidden = hidden.reshape(-1, rows, hidden.shape[-1])

    @jax.checkpoint
    def block(total, inputs):
        x, labels = inputs
        with jax.named_scope("lm_head"):
            logits = jnp.dot(x, head.T, preferred_element_type=jnp.float32)
        with jax.named_scope("loss"):
            valid = labels >= 0
            picked = jnp.take_along_axis(
                logits, jnp.where(valid, labels, 0)[:, None], axis=-1)[:, 0]
            nll = jnp.where(
                valid, jax.nn.logsumexp(logits, axis=-1) - picked, 0.0)
            return total + nll.sum(), None

    total, _ = jax.lax.scan(block, jnp.zeros([], jnp.float32),
                            (hidden, labels))
    with jax.named_scope("loss"):
        count = (labels >= 0).sum()
        return total / jnp.maximum(count, 1), count.astype(jnp.int32)
