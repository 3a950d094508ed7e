"""Loss functions for the model family — the functional JAX counterpart of the
reference's in-module loss branches.

Parity targets:
  - ``BertPretrainingCriterion`` (run_pretraining.py:58-72): masked-LM CE with
    ignore_index −1 plus NSP CE, summed.
  - SQuAD span loss (run_squad.py:1085-1092): positions clamped to sequence
    length, (start CE + end CE) / 2.
  - Token classification CE with ignore_index −100 for special tokens
    (ner_dataset.py:13-44, modeling.py:1200-1271).

All cross-entropies are computed in fp32 regardless of logit dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax


def _xent_ignore(logits: jnp.ndarray, labels: jnp.ndarray, ignore_index: int):
    """Mean CE over positions where label != ignore_index (torch CE semantics:
    mean over non-ignored elements; 0 if none)."""
    logits = logits.astype(jnp.float32)
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    per_pos = optax.softmax_cross_entropy_with_integer_labels(logits, safe_labels)
    per_pos = jnp.where(valid, per_pos, 0.0)
    count = jnp.maximum(jnp.sum(valid), 1)
    return jnp.sum(per_pos) / count


def masked_lm_loss(prediction_logits, masked_lm_labels, ignore_index: int = -1):
    """CE over [B, S, V] logits with ignore_index (run_pretraining.py:64-69)."""
    vocab = prediction_logits.shape[-1]
    with jax.named_scope("mlm_loss"):
        return _xent_ignore(
            prediction_logits.reshape(-1, vocab),
            masked_lm_labels.reshape(-1),
            ignore_index,
        )


def next_sentence_loss(seq_relationship_logits, next_sentence_labels):
    """CE over [B, 2] NSP logits (run_pretraining.py:70-71)."""
    with jax.named_scope("nsp_loss"):
        return _xent_ignore(
            seq_relationship_logits.reshape(-1, 2),
            next_sentence_labels.reshape(-1),
            ignore_index=-1,
        )


def pretraining_loss(
    prediction_logits,
    seq_relationship_logits,
    masked_lm_labels,
    next_sentence_labels=None,
):
    """MLM + NSP total (run_pretraining.py:58-72); MLM-only when NSP is off."""
    loss = masked_lm_loss(prediction_logits, masked_lm_labels)
    if seq_relationship_logits is not None and next_sentence_labels is not None:
        loss = loss + next_sentence_loss(seq_relationship_logits, next_sentence_labels)
    return loss


def next_token_loss(logits, input_ids, shift: int = 1, scope: str = "lm"):
    """Causal-LM objective: the mean fp32 cross entropy of position t's
    logits against token t + ``shift``, over the S - ``shift`` predicted
    positions of every row, and the share of them whose arg-max is right. The
    vocabulary is the logits' last axis (a slice of a published vocabulary is
    a smaller vocabulary: ids, logits and loss are over the slice). ``shift``
    2 is a multi-token-prediction module's target (models/joyai.py); the loss
    runs under the scope ``<scope>_loss``."""
    with jax.named_scope(scope + "_loss"):
        labels = jnp.roll(input_ids, -shift, axis=-1)
        predicted = (jnp.arange(input_ids.shape[-1])
                     < input_ids.shape[-1] - shift)
        per_pos = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels)
        count = jnp.maximum(jnp.sum(predicted) * (labels.size // labels.shape[-1]), 1)
        loss = jnp.sum(jnp.where(predicted, per_pos, 0.0)) / count
        right = (jnp.argmax(logits, axis=-1) == labels) & predicted
        return loss, jnp.sum(right) / count


def _piece_over_axis(h, lab, head_kernel, axis: str, scope: str):
    """One piece's (cross entropy, arg-max) per position when the head's
    COLUMNS lie on the chips of the mesh axis ``axis`` (inside a ``shard_map``
    manual over it): ``h`` [B, s, H] and ``lab`` [B, s] are this chip's rows,
    ``head_kernel`` [H, V / chips] its columns. The chips' rows of the piece
    are gathered (``<scope>_head_gather``; bfloat16 hidden states, far fewer
    bytes than the head's columns), every chip scores all of them against its
    own columns, and the softmax's maximum, its sum and the target's logit
    are reduced over the axis; the arg-max is the lowest id among the chips'
    largest. Each chip keeps its own rows' numbers."""
    me, cols = jax.lax.axis_index(axis), head_kernel.shape[1]
    with jax.named_scope(scope + "_head_gather"):
        h = jax.lax.all_gather(h, axis)                      # [chips, B, s, H]
        labels = jax.lax.all_gather(lab, axis)
    with jax.named_scope(scope + "_head"):
        logits = jnp.matmul(h, head_kernel.astype(h.dtype))
    with jax.named_scope(scope + "_loss"):
        logits = logits.astype(jnp.float32)
        top = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
        highest = jax.lax.pmax(top, axis)
        total = jax.lax.psum(
            jnp.sum(jnp.exp(logits - highest[..., None]), axis=-1), axis)
        local = labels - me * cols
        here = (local >= 0) & (local < cols)
        target = jax.lax.psum(jnp.where(here, jnp.take_along_axis(
            logits, jnp.clip(local, 0, cols - 1)[..., None], axis=-1)[..., 0],
            0.0), axis)
        per_pos = jnp.log(total) + highest - target
        best = jax.lax.pmin(jnp.where(
            top >= highest, jnp.argmax(logits, axis=-1) + me * cols,
            jnp.iinfo(jnp.int32).max), axis)
        mine = lambda t: jax.lax.dynamic_index_in_dim(t, me, 0, keepdims=False)
        return mine(per_pos), mine(best)


def chunked_next_token_loss(hidden, head_kernel, input_ids, chunks: int,
                            shift: int = 1, scope: str = "lm", axis=None):
    """:func:`next_token_loss` of ``hidden @ head_kernel`` without ever
    holding every position's logits: the head and the cross entropy run over
    ``chunks`` equal pieces of the sequence, one after the other, each
    rematerialized, so the backward pass holds one piece's logits at a time
    (at 8192 x 16384 in float32 the whole is 0.5 GB, several times over).
    Same loss, same accuracy, the head's forward once more in the backward
    pass. ``chunks`` must divide the sequence length. The head runs under the
    scope ``<scope>_head`` and the loss under ``<scope>_loss``. ``axis``: the
    head's columns lie on the chips of that mesh axis and ``head_kernel`` is
    this chip's [H, V / chips] (:func:`_piece_over_axis`); the loss and the
    accuracy returned are of this chip's rows."""
    batch, seq, width = hidden.shape
    labels = jnp.roll(input_ids, -shift, axis=-1)
    predicted = jnp.broadcast_to(jnp.arange(seq) < seq - shift, labels.shape)
    pieces = lambda t: jnp.moveaxis(
        t.reshape((batch, chunks, seq // chunks) + t.shape[2:]), 1, 0)

    @jax.checkpoint
    def piece(carry, xs):
        h, lab, keep = xs
        if axis:
            per_pos, best = _piece_over_axis(h, lab, head_kernel, axis, scope)
            with jax.named_scope(scope + "_loss"):
                return (carry[0] + jnp.sum(jnp.where(keep, per_pos, 0.0)),
                        carry[1] + jnp.sum((best == lab) & keep)), None
        with jax.named_scope(scope + "_head"):
            logits = jnp.matmul(h, head_kernel.astype(h.dtype))
        with jax.named_scope(scope + "_loss"):
            per_pos = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), lab)
            right = (jnp.argmax(logits, axis=-1) == lab) & keep
            return (carry[0] + jnp.sum(jnp.where(keep, per_pos, 0.0)),
                    carry[1] + jnp.sum(right)), None

    (total, right), _ = jax.lax.scan(
        piece, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (pieces(hidden), pieces(labels), pieces(predicted)))
    count = jnp.maximum(batch * (seq - shift), 1)
    return total / count, right / count


def span_loss(start_logits, end_logits, start_positions, end_positions):
    """SQuAD loss: clamp positions into [0, S], CE on start and end, averaged
    (run_squad.py:1085-1092 — clamped index == ignored index S)."""
    seq_len = start_logits.shape[-1]
    start_positions = jnp.clip(start_positions, 0, seq_len)
    end_positions = jnp.clip(end_positions, 0, seq_len)
    # The reference sets ignored_index = seq_len and clamps into it; emulate by
    # padding logits with one extra (ignored) class.
    pad = jnp.full(start_logits.shape[:-1] + (1,), -10000.0, start_logits.dtype)
    start_l = jnp.concatenate([start_logits, pad], axis=-1).astype(jnp.float32)
    end_l = jnp.concatenate([end_logits, pad], axis=-1).astype(jnp.float32)
    s = _xent_ignore(start_l, start_positions, ignore_index=seq_len)
    e = _xent_ignore(end_l, end_positions, ignore_index=seq_len)
    return (s + e) / 2.0


def token_classification_loss(logits, labels, ignore_index: int = -100):
    """Per-token CE skipping special-token labels (run_ner.py via
    modeling.py:1200-1271)."""
    num_labels = logits.shape[-1]
    return _xent_ignore(
        logits.reshape(-1, num_labels), labels.reshape(-1), ignore_index
    )


def mlm_accuracy(prediction_logits, masked_lm_labels, ignore_index: int = -1):
    """Fraction of masked positions predicted correctly (for eval logging)."""
    with jax.named_scope("mlm_loss"):
        preds = jnp.argmax(prediction_logits, axis=-1)
        valid = masked_lm_labels != ignore_index
        correct = jnp.logical_and(preds == masked_lm_labels, valid)
        return jnp.sum(correct) / jnp.maximum(jnp.sum(valid), 1)
