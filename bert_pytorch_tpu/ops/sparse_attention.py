"""Softmax attention over the keys a learned indexer chooses for each query
(DeepSeek-V3.2-Exp's sparse attention, as the ``KeyeVL2`` family carries it:
``models/keye_vl.py``).

For one row of S positions, in three parts that share nothing but the choice:

1. **The choice** (``choose``; no gradient passes it). The indexer's score of
   key s for query t over its J heads of width E against ONE key head,

       I[t, s] = (1 / sqrt(J E)) sum_j w[t, j] relu(qI[t, j] . kI[s]),  s <= t

   in float32 (operands in the compute dtype, sums in float32), and ``S_t``,
   the ``min(t + 1, topk)`` causal positions of largest ``I[t, :]``, ties to
   the lower position: ONE set a query for every head. The set is EXACT: the
   k-th largest score of a row is found bit by bit on the scores' ordered
   integer image (32 counting passes, ``kth_largest``), then, among the
   scores equal to it, the position of the last one taken (``log2 S``
   counting passes, only where a row has such a tie): no sort, no
   approximation.
2. **The core** (``attend``): query head i on key-value head ``i // (H /
   KV)``, ``o[t, i] = sum_{s in S_t} softmax_s(q[t, i] . k[s] / sqrt(D))
   v[s]``, and each head's log-sum-exp over ``S_t``.
3. **The indexer's objective** (``index_loss``): with ``p_t`` the core's
   probabilities summed over the heads on ``S_t`` and normalised to sum one
   (rebuilt from q, k and the log-sum-exps, all under ``stop_gradient``),
   ``KL(p_t || softmax_{s in S_t} I[t, s])`` a token. Its gradient reaches
   qI, kI and w and nothing else; the core's reaches q, k and v and nothing
   else.

Two forms of each part. **Plain XLA** (any shape, the CPU path): blocks of
query rows, one after the other (``lax.map``), each rematerialized, so that
no [H, S, S] tensor ever exists whole. **Pallas kernels**
(``ops/pallas/sparse_attention.py``) where their ``fits`` says the shapes are
theirs and the caller asks for them (``backend`` ``pallas``): the choice is
made inside the scoring kernel, whose scores never reach HBM, and handed on
as one bit a pair (kept across remat by name, ``ops/remat.py DSA_CHOICE``:
33.5 MB a layer and row of 16,384, so the backward pass does not choose a
second time); the core's forward and two backward kernels take a tile's mask
from those bits and run the dense tiles up to the diagonal (a gather of
``topk`` rows a query is the vector unit's slow operation: PERF.md 6), and
the forward kernel's output and log-sum-exps, which the backward kernels
read, are kept across remat by name as well (``DSA_CORE_OUT``,
``DSA_CORE_LSE``: the forward kernel runs once a gradient step in the layers
whose policy keeps them, models/keye_vl.py ``CORE_KEPT_LAYERS``); the
objective's kernel rebuilds scores and probabilities a tile at a time and
returns the KL with its whole backward (three gradients, kept across remat
by name too, ``DSA_INDEX_GRADS``: the kernel runs once a gradient step).

Scopes: ``dsa_scores`` and ``dsa_select`` (the choice), ``dsa_core``,
``dsa_index_loss``; the caller wraps them in ``dsa``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bert_pytorch_tpu.ops.pallas import sparse_attention as kernels
from bert_pytorch_tpu.ops.pallas.common import pick_block
from bert_pytorch_tpu.ops.remat import DSA_CHOICE

NEG = -1e30
QUERY_BLOCK = 256


def index_scores(qi, ki, w):
    """qi [B, T, J, E], ki [B, S, E], w [B, T, J] float32 -> I [B, T, S]
    float32 (every pair; the caller masks)."""
    heads, width = qi.shape[-2:]
    dots = jnp.einsum("btje,bse->btjs", qi, ki,
                      preferred_element_type=jnp.float32)
    return jnp.sum(w[..., None] * jax.nn.relu(dots), axis=2) / math.sqrt(
        heads * width)


def ordered_key(x):
    """float32 -> int32 with the same order (``-0.0`` below ``0.0``)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def kth_largest(keys, k):
    """keys [..., S] int32, k [...] >= 1 -> the k-th largest key of each row,
    one bit at a time: the largest v with ``count(keys >= v) >= k``."""
    lowest = jnp.full(k.shape, jnp.iinfo(jnp.int32).min, jnp.int32)

    def bit(i, found):
        # the sign bit first (from the lowest int to 0), then 30 .. 0
        step = jnp.left_shift(jnp.int32(1), 31 - i)
        trial = jnp.where(i == 0, found ^ step, found | step)
        enough = jnp.sum(keys >= trial[..., None], axis=-1) >= k
        return jnp.where(enough, trial, found)

    return jax.lax.fori_loop(0, 32, bit, lowest)


def largest_k_mask(scores, k):
    """scores [..., S] float32, k [...] (1 <= k <= the row's finite scores)
    -> bool [..., S]: the k largest of each row, ties to the lower position."""
    keys = ordered_key(scores)
    kth = kth_largest(keys, k)[..., None]
    above, equal = keys > kth, keys == kth
    wanted = k - jnp.sum(above, axis=-1)          # of the equal ones, >= 1
    size = scores.shape[-1]
    position = jnp.arange(size, dtype=jnp.int32)

    def last_taken():
        """The position of the ``wanted``-th equal score: the largest X with
        ``count(equal & position < X) < wanted``, one bit at a time."""
        bits = max(1, (size - 1).bit_length())

        def bit(i, found):
            trial = found | jnp.left_shift(jnp.int32(1), bits - 1 - i)
            before = jnp.sum(equal & (position < trial[..., None]), axis=-1)
            return jnp.where(before < wanted, trial, found)

        return jax.lax.fori_loop(0, bits, bit, jnp.zeros_like(wanted))

    tied = jnp.any(jnp.sum(equal, axis=-1) > wanted)
    last = jax.lax.cond(tied, last_taken,
                        lambda: jnp.full(wanted.shape, size, jnp.int32))
    return above | (equal & (position <= last[..., None]))


def _blocks(t, block):
    """[B, S, ...] -> [S / block, B, block, ...]."""
    batch, seq = t.shape[:2]
    return jnp.moveaxis(
        t.reshape((batch, seq // block, block) + t.shape[2:]), 1, 0)


def _rows(t):
    """The inverse of :func:`_blocks`."""
    t = jnp.moveaxis(t, 0, 1)
    return t.reshape((t.shape[0], -1) + t.shape[3:])


def query_block(seq: int) -> int:
    return pick_block(seq, (QUERY_BLOCK, 128, 64, 32, 16, 8))


def choose(qi, ki, w, topk: int):
    """The choice: bool [B, S, S], row t true at the ``min(t + 1, topk)``
    causal positions of largest score. No gradient."""
    qi, ki, w = (jax.lax.stop_gradient(t) for t in (qi, ki, w))
    seq = qi.shape[1]
    block = query_block(seq)
    cols = jnp.arange(seq, dtype=jnp.int32)

    def one(args):
        start, qi_b, w_b = args
        rows = start + jnp.arange(block, dtype=jnp.int32)
        with jax.named_scope("dsa_scores"):
            scores = jnp.where(cols[None, :] <= rows[:, None],
                               index_scores(qi_b, ki, w_b), -jnp.inf)
        with jax.named_scope("dsa_select"):
            count = jnp.broadcast_to(jnp.minimum(rows + 1, topk),
                                     scores.shape[:2])
            return largest_k_mask(scores, count)

    starts = jnp.arange(0, seq, block, dtype=jnp.int32)
    return _rows(jax.lax.map(one, (starts, _blocks(qi, block),
                                   _blocks(w, block))))


def _head_scores(q_b, k):
    """q_b [B, T, H, D], k [B, S, KV, D] -> [B, KV, G, T, S] float32, scaled
    (both operands with their batch axes first: the CPU backend's dot)."""
    batch, rows, heads, depth = q_b.shape
    kv = k.shape[2]
    q_b = q_b.reshape(batch, rows, kv, heads // kv, depth).transpose(
        0, 2, 3, 1, 4)
    return jnp.einsum("bngtd,bnsd->bngts", q_b, k.transpose(0, 2, 1, 3),
                      preferred_element_type=jnp.float32) / math.sqrt(depth)


def attend_xla(q, k, v, mask):
    """The core in plain XLA: (ctx [B, S, H, D], lse [B, S, H] float32)."""
    batch, seq, heads, depth = q.shape
    block = query_block(seq)

    @jax.checkpoint
    def one(args):
        q_b, mask_b = args
        scores = jnp.where(mask_b[:, None, None], _head_scores(q_b, k), NEG)
        lse = jax.nn.logsumexp(scores, axis=-1)
        probs = jnp.exp(scores - lse[..., None])
        ctx = jnp.einsum("bngts,bnsd->bngtd", probs.astype(v.dtype),
                         v.transpose(0, 2, 1, 3),
                         preferred_element_type=jnp.float32)
        back = lambda t: t.transpose((0, 3, 1, 2) + tuple(range(4, t.ndim)))
        return (back(ctx).reshape(batch, block, heads, depth).astype(q.dtype),
                back(lse).reshape(batch, block, heads))

    ctx, lse = jax.lax.map(one, (_blocks(q, block), _blocks(mask, block)))
    return _rows(ctx), _rows(lse)


def index_loss_xla(qi, ki, w, q, k, lse, mask):
    """The indexer's objective in plain XLA: KL [B, S] a token. ``q``, ``k``
    and ``lse`` are the core's, already detached."""
    batch, seq, heads, _ = q.shape
    block = query_block(seq)

    @jax.checkpoint
    def one(args):
        qi_b, w_b, q_b, lse_b, mask_b = args
        lse_b = lse_b.reshape(batch, block, k.shape[2], -1).transpose(
            0, 2, 3, 1)
        target = jnp.where(
            mask_b, jnp.sum(jnp.exp(_head_scores(q_b, k) - lse_b[..., None]),
                            axis=(1, 2)) / heads, 0.0)
        logits = jnp.where(mask_b, index_scores(qi_b, ki, w_b), NEG)
        log_index = jax.nn.log_softmax(logits, axis=-1)
        log_target = jnp.log(jnp.maximum(target, jnp.finfo(jnp.float32).tiny))
        return jnp.sum(jnp.where(mask_b, target * (log_target - log_index),
                                 0.0), axis=-1)

    return _rows(jax.lax.map(one, tuple(
        _blocks(t, block) for t in (qi, w, q, lse, mask))))


def sparse_attention(q, k, v, qi, ki, w, topk: int, backend: str = "xla"):
    """q [B, S, H, D], k, v [B, S, KV, D], qi [B, S, J, E], ki [B, S, E],
    w [B, S, J] float32 -> (ctx [B, S, H, D], the indexer's KL: its mean over
    the tokens, the chosen pairs of the call: an int32 scalar, the choice:
    bool [B, S, S], which costs nothing where nobody reads it).
    ``backend``: ``pallas`` takes the kernels where the shapes are theirs
    (``kernels.fits``), anything else the XLA form."""
    batch, seq = q.shape[:2]
    detached = [jax.lax.stop_gradient(t) for t in (q, k)]
    if backend == "pallas" and kernels.fits(q.shape, k.shape, q.dtype, k.dtype,
                                            qi.shape[-1]):
        with jax.named_scope("dsa_select"):
            # kept across remat by name (ops/remat.py): one bit a pair
            words = checkpoint_name(kernels.select(
                *(jax.lax.stop_gradient(t) for t in (qi, ki, w)), topk),
                DSA_CHOICE)
        with jax.named_scope("dsa_core"):
            ctx, lse = kernels.masked_attention(q, k, v, words)
        with jax.named_scope("dsa_index_loss"):
            kl = kernels.index_loss(qi, ki, w, *detached,
                                    jax.lax.stop_gradient(lse), words
                                    ) / (batch * seq)
        pairs = jnp.sum(jax.lax.population_count(words), dtype=jnp.int32)
        return ctx, kl, pairs, kernels.unpack(words, seq)
    mask = choose(qi, ki, w, topk)
    with jax.named_scope("dsa_core"):
        ctx, lse = attend_xla(q, k, v, mask)
    with jax.named_scope("dsa_index_loss"):
        kl = jnp.mean(index_loss_xla(qi, ki, w, *detached,
                                     jax.lax.stop_gradient(lse), mask))
    return ctx, kl, jnp.sum(mask, dtype=jnp.int32), mask
