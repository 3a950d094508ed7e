"""Pallas kernels of ``ops/sparse_attention.py``: the exact choice of each
query's keys, softmax attention over the chosen keys with its backward, and
the indexer's KL with ITS backward.

**The choice travels as bits.** Column ``c`` of a row's ``WORD_LANES`` = 512
int32 words holds, in bit ``j``, whether the row's query chose key ``j * 512 +
c``: [B, S, 512] int32 for rows of up to 32 x 512 = 16,384 keys, one bit a
pair (33.5 MB a row of 16,384). A [rows, 512] tile of words is then the choice
of those rows for EVERY key tile of 512: a kernel loads it once a row block
and takes tile ``j``'s mask as ``(words >> j) & 1``: int32 shifts and ands on
the vector unit, no byte or bit layouts. ``pack`` / ``unpack`` change between
this and the XLA form's [B, S, S] booleans.

* ``dsa_select`` (``select``): one program a block of 128 query rows. The
  indexer's scores of the block against every causal key tile (J products of
  [128, E] x [E, 512], relu, the head's weight, the sum over heads; float32)
  are kept IN VMEM as their ordered integer image and never reach HBM; the
  k-th largest of each row is found one bit at a time (32 counting passes
  over the kept tiles), then the position of the last of the scores equal to
  it that is taken (``log2 S`` passes): ``ops/sparse_attention.py
  largest_k_mask``'s rule, so both forms choose the same set bit for bit; the
  words are written once.
* ``dsa_core_fwd``, ``dsa_core_bwd_dq``, ``dsa_core_bwd_dkv``
  (``masked_attention``): flash attention under the words. A program of the
  forward and of dq holds a block of 512 query rows of ALL the query heads
  of one key-value head (they share K, V and the words), K and V whole in
  VMEM, and walks the key tiles up to the diagonal ONCE, the group's heads
  inside the walk: a trip takes tile ``j``'s K and V and makes its mask from
  the words once, and every head uses them, as dk/dv does. What a head
  carries from tile to tile (the forward's running maximum and sum, a row's
  number in all 128 lanes of a lane tile, and its accumulator; dq's sum) is
  float32 VMEM scratch for the whole group, written out after the last
  tile. dk/dv walks the query blocks from the diagonal down for one key tile
  of 512, summing over the group's heads in float32 scratch. The dense tiles
  up to the diagonal are run and masked: the model's work is the chosen
  pairs, a quarter of that at 16,384
  (``benchmarks/trace/flops_keye.py``). The forward RULE names the
  forward kernel's output and log-sum-exps, the residuals the two backward
  kernels read (``ops/remat.py DSA_CORE_OUT``, ``DSA_CORE_LSE``), so under a
  remat policy that keeps the names the recompute does not run the forward
  kernel again: once a gradient step.
* ``dsa_index_loss`` (``index_loss``): a token's KL from the core's
  probabilities summed over the heads (rebuilt from q, k and the log-sum-exps
  a tile at a time) to the softmax of the indexer's scores over the chosen
  keys (scored again, a tile at a time; their log-sum-exp in a first walk of
  the tiles), and in the same walk its gradient to qI, kI and w: the KL's
  inputs from the core are constants, so its whole backward is known in the
  forward. Under a gradient the forward pass runs the forward RULE (also
  inside ``jax.checkpoint``, whose recompute runs it again): it keeps the
  three gradients, named ``ops/remat.py DSA_INDEX_GRADS`` so that a remat
  policy which keeps the name does not run the kernel a second time, and the
  backward rule scales them by the cotangent. The plain call, which skips
  them, is what evaluation runs.

Operands in the activations' dtype, sums, softmaxes and the KL in float32, as
the flash kernels of ``ops/pallas/attention.py``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bert_pytorch_tpu.ops.pallas import common
from bert_pytorch_tpu.ops.remat import (DSA_CORE_LSE, DSA_CORE_OUT,
                                        DSA_INDEX_GRADS)
from bert_pytorch_tpu.utils import trace_parts

WORD_LANES = 512          # keys a bit plane covers: the kernels' key tile
WORD_BITS = 32
SELECT_ROWS = 128
CORE_ROWS = 512           # (at 256 the core's calls read 5% / 2% / 9% longer)
LOSS_ROWS = 128
NEG = -1e30
VMEM_LIMIT_BYTES = 100 * 1024 * 1024
_INT_MIN = np.iinfo(np.int32).min


def fits(q_shape, k_shape, q_dtype, k_dtype, index_width: int = 64) -> bool:
    """The shapes the kernels take: one dtype, heads of a whole lane tile,
    whole groups of query heads, rows in whole tiles of 512 and no more of
    them than a word has bits."""
    _, seq, heads, depth = q_shape
    kv = k_shape[2]
    return (q_dtype == k_dtype and depth % 128 == 0 and heads % kv == 0
            and seq % WORD_LANES == 0 and seq // WORD_LANES <= WORD_BITS
            and index_width % 8 == 0)


def pack(mask):
    """bool [B, S, S] -> words [B, S, 512] int32 (the module's docstring)."""
    batch, rows, seq = mask.shape
    planes = mask.reshape(batch, rows, seq // WORD_LANES, WORD_LANES)
    bits = jnp.left_shift(jnp.int32(1), jnp.arange(planes.shape[2],
                                                   dtype=jnp.int32))
    # (distinct bits: a sum is the or; int32 wraps at bit 31 as the or does)
    return jnp.sum(jnp.where(planes, bits[:, None], 0), axis=2,
                   dtype=jnp.int32)


def unpack(words, seq: int):
    """words [B, S, 512] int32 -> bool [B, S, seq]."""
    shifts = jnp.arange(seq // WORD_LANES, dtype=jnp.int32)
    planes = (words[:, :, None, :] >> shifts[:, None]) & 1
    return planes.reshape(words.shape[:2] + (seq,)) != 0


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _keep(words, plane):
    """The mask of key tile ``plane`` from a [rows, 512] tile of words."""
    return ((words >> plane) & 1) != 0


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))   # a @ b.T
_NN = ((1,), (0,))   # a @ b
_TN = ((0,), (0,))   # a.T @ b


def _across(t, width):
    """[rows, 128] with a row's number in every lane -> [rows, width]."""
    return t if width == t.shape[1] else jnp.tile(t, (1, width // t.shape[1]))


def _index_tile(qi_ref, ki_tile, weights, scale):
    """A [rows, 512] tile of the indexer's scores: sum over heads of the
    head's weight times relu(qI_j kI^T), times ``scale``."""
    total = None
    for j, weight in enumerate(weights):
        term = weight * jnp.maximum(_dot(qi_ref[0, j], ki_tile, _NT), 0.0)
        total = term if total is None else total + term
    return total * scale


def _columns(weights_ref, heads):
    """Each head's weights of the block's rows as a [rows, 1] column."""
    return [weights_ref[0, j][:, None] for j in range(heads)]


# ------------------------------------------------------------------ choice

def _lane_counts(flags):
    """bool [rows, 512] -> float32 [rows, 128]: the four lane tiles added
    (counts of up to 16,384 are whole in float32, and the row sum after them
    is then a float reduction)."""
    ones = flags.astype(jnp.float32)
    return (ones[:, :128] + ones[:, 128:256] + ones[:, 256:384]
            + ones[:, 384:])


def _select_kernel(qi_ref, ki_ref, w_ref, words_ref, keys_scr, *, topk,
                   position_bits, scale):
    # qi_ref [1, J, rows, E]; ki_ref [1, S, E]; w_ref [1, J, rows];
    # words_ref [1, rows, 512]; keys_scr [S / 512, rows, 512] int32
    rows = words_ref.shape[1]
    row0 = pl.program_id(1) * rows
    seen = (row0 + rows + WORD_LANES - 1) // WORD_LANES
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, WORD_LANES), 1)
    weights = _columns(w_ref, qi_ref.shape[1])

    def score(j, carry):
        tile = _index_tile(qi_ref, ki_ref[0, pl.ds(j * WORD_LANES, WORD_LANES), :],
                           weights, scale)
        tile = jnp.where(j * WORD_LANES + lane <= row, tile, -jnp.inf)
        bits = jax.lax.bitcast_convert_type(tile, jnp.int32)
        keys_scr[j] = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
        return carry

    jax.lax.fori_loop(0, seen, score, 0)

    def count(flags_of):
        """Of the kept tiles' entries, how many ``flags_of(j, tile)`` holds
        for, a row: [rows, 1]."""
        partial_sums = jax.lax.fori_loop(
            0, seen, lambda j, acc: acc + _lane_counts(flags_of(j, keys_scr[j])),
            jnp.zeros((rows, 128), jnp.float32))
        return jnp.sum(partial_sums, axis=-1, keepdims=True)

    wanted = jnp.minimum(row + 1, topk).astype(jnp.float32)

    def key_bit(i, found):
        step = jnp.left_shift(jnp.int32(1), 31 - i)
        trial = jnp.where(i == 0, found ^ step, found | step)
        enough = count(lambda j, tile: tile >= trial) >= wanted
        return jnp.where(enough, trial, found)

    kth = jax.lax.fori_loop(0, 32, key_bit,
                            jnp.full((rows, 1), _INT_MIN, jnp.int32))
    of_equal = wanted - count(lambda j, tile: tile > kth)

    def position_bit(i, found):
        trial = found | jnp.left_shift(jnp.int32(1), position_bits - 1 - i)
        before = count(lambda j, tile: (tile == kth)
                       & (j * WORD_LANES + lane < trial))
        return jnp.where(before < of_equal, trial, found)

    last = jax.lax.fori_loop(0, position_bits, position_bit,
                             jnp.zeros((rows, 1), jnp.int32))

    def emit(j, words):
        tile = keys_scr[j]
        chosen = (tile > kth) | ((tile == kth)
                                 & (j * WORD_LANES + lane <= last))
        return words | jnp.left_shift(chosen.astype(jnp.int32), j)

    words_ref[0] = jax.lax.fori_loop(
        0, seen, emit, jnp.zeros((rows, WORD_LANES), jnp.int32))


def _heads_first(t):
    """[B, S, heads, ...] -> [B, heads, S, ...]."""
    return jnp.swapaxes(t, 1, 2)


@partial(jax.jit, static_argnames=("topk",))
def select(qi, ki, w, topk: int):
    """qi [B, S, J, E], ki [B, S, E], w [B, S, J] float32 -> the choice as
    words [B, S, 512] int32: row t's ``min(t + 1, topk)`` causal keys of
    largest score, ties to the lower position."""
    batch, seq, heads, width = qi.shape
    rows = min(SELECT_ROWS, seq)
    with trace_parts.kernel_build("dsa_select"):
        return pl.pallas_call(
            partial(_select_kernel, topk=topk,
                    position_bits=max(1, (seq - 1).bit_length()),
                    scale=1.0 / math.sqrt(heads * width)),
            grid=(batch, seq // rows),
            in_specs=[
                pl.BlockSpec((1, heads, rows, width), lambda b, i: (b, 0, i, 0)),
                pl.BlockSpec((1, seq, width), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, heads, rows), lambda b, i: (b, 0, i)),
            ],
            out_specs=pl.BlockSpec((1, rows, WORD_LANES), lambda b, i: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((batch, seq, WORD_LANES), jnp.int32),
            scratch_shapes=[pltpu.VMEM((seq // WORD_LANES, rows, WORD_LANES),
                                       jnp.int32)],
            compiler_params=_params("parallel", "parallel"),
            name="dsa_select", interpret=common.interpret_mode(),
        )(_heads_first(qi), ki, _heads_first(w))


# -------------------------------------------------------------------- core

def _core_fwd_kernel(q_ref, k_ref, v_ref, words_ref, out_ref, lse_ref, m_scr,
                     l_scr, acc_scr, *, scale):
    # q_ref, out_ref [1, G, rows, D]; k_ref, v_ref [1, S, D]; words_ref
    # [1, rows, 512]; lse_ref [1, G, 1, rows]. Scratch, float32, for the whole
    # group: the running maxima and sums m_scr, l_scr [G, rows, 128], a row's
    # number in every lane of a lane tile (it meets the accumulator element
    # for element and the scores by tiling, with no turn from a column), and
    # the accumulators acc_scr [G, rows, D]. ONE walk of the key tiles up to
    # the diagonal: a trip takes the tile's K, V and mask once, and every
    # head of the group uses them.
    group, rows, depth = q_ref.shape[1:]
    seen = ((pl.program_id(1) + 1) * rows + WORD_LANES - 1) // WORD_LANES
    m_scr[...] = jnp.full(m_scr.shape, NEG, jnp.float32)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def trip(j, carry):
        k = k_ref[0, pl.ds(j * WORD_LANES, WORD_LANES), :]
        v = v_ref[0, pl.ds(j * WORD_LANES, WORD_LANES), :]
        keep = _keep(words_ref[0], j)
        for g in range(group):
            s = jnp.where(keep, _dot(q_ref[0, g], k, _NT) * scale, NEG)
            m_prev = m_scr[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # (a row with no chosen key yet sums ones under m = NEG; the
            # first chosen key's alpha = exp(NEG - m) = 0 wipes them, a head
            # at a time: each head has its own m)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - _across(m_new, WORD_LANES))
            m_scr[g] = m_new
            l_scr[g] = l_scr[g] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[g] = (acc_scr[g] * _across(alpha, depth)
                          + _dot(p.astype(v.dtype), v, _NN))
        return carry

    jax.lax.fori_loop(0, seen, trip, 0)
    for g in range(group):
        l = l_scr[g]
        out_ref[0, g] = (acc_scr[g] / _across(l, depth)).astype(out_ref.dtype)
        lse_ref[0, g, 0] = (m_scr[g] + jnp.log(l))[:, 0]


def _core_dq_kernel(q_ref, k_ref, v_ref, words_ref, lse_ref, delta_ref,
                    do_ref, dq_ref, dq_scr, *, scale):
    # the forward's operands and walk; do_ref, dq_ref [1, G, rows, D];
    # lse_ref, delta_ref [1, G, 1, rows]; the group's dq in dq_scr
    # [G, rows, D] float32
    group, rows, _ = q_ref.shape[1:]
    seen = ((pl.program_id(1) + 1) * rows + WORD_LANES - 1) // WORD_LANES
    lse = [lse_ref[0, g, 0][:, None] for g in range(group)]
    delta = [delta_ref[0, g, 0][:, None] for g in range(group)]
    dq_scr[...] = jnp.zeros_like(dq_scr)

    def trip(j, carry):
        k = k_ref[0, pl.ds(j * WORD_LANES, WORD_LANES), :]
        v = v_ref[0, pl.ds(j * WORD_LANES, WORD_LANES), :]
        keep = _keep(words_ref[0], j)
        for g in range(group):
            s = jnp.where(keep, _dot(q_ref[0, g], k, _NT) * scale, NEG)
            ds = jnp.exp(s - lse[g]) * (_dot(do_ref[0, g], v, _NT) - delta[g])
            dq_scr[g] += _dot(ds.astype(k.dtype), k, _NN)
        return carry

    jax.lax.fori_loop(0, seen, trip, 0)
    dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _core_dkv_kernel(q_ref, k_ref, v_ref, words_ref, lse_ref, delta_ref,
                     do_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale):
    # one key tile of 512 (grid axis 1), the query blocks from the diagonal
    # down (grid axis 2, innermost): q_ref, do_ref [1, G, rows, D]
    group, rows, _ = q_ref.shape[1:]
    plane, i = pl.program_id(1), pl.program_id(2)
    first = (plane * WORD_LANES) // rows

    @pl.when(i == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(i >= first)
    def _():
        k, v = k_ref[0], v_ref[0]
        keep = _keep(words_ref[0], plane)
        for g in range(group):
            q, do = q_ref[0, g], do_ref[0, g]
            s = jnp.where(keep, _dot(q, k, _NT) * scale, NEG)
            p = jnp.exp(s - lse_ref[0, g, 0][:, None])
            ds = p * (_dot(do, v, _NT) - delta_ref[0, g, 0][:, None])
            dv_scr[...] += _dot(p.astype(do.dtype), do, _TN)
            dk_scr[...] += _dot(ds.astype(q.dtype), q, _TN)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _core_specs(kv, group, rows, seq, depth):
    """The block specs the forward and dq kernels share (grid: key-value
    head of a row, query block)."""
    return {
        "q": pl.BlockSpec((1, group, rows, depth), lambda b, i: (b, 0, i, 0)),
        "kv": pl.BlockSpec((1, seq, depth), lambda b, i: (b, 0, 0)),
        "words": pl.BlockSpec((1, rows, WORD_LANES),
                              lambda b, i: (b // kv, i, 0)),
        "row": pl.BlockSpec((1, group, 1, rows), lambda b, i: (b, 0, 0, i)),
    }


def _core_forward(q4, k3, v3, words, scale):
    bkv, group, seq, depth = q4.shape
    kv = bkv // words.shape[0]
    rows = min(CORE_ROWS, seq)
    spec = _core_specs(kv, group, rows, seq, depth)
    with trace_parts.kernel_build("dsa_core_fwd"):
        return pl.pallas_call(
            partial(_core_fwd_kernel, scale=scale),
            grid=(bkv, seq // rows),
            in_specs=[spec["q"], spec["kv"], spec["kv"], spec["words"]],
            out_specs=[spec["q"], spec["row"]],
            out_shape=[jax.ShapeDtypeStruct(q4.shape, q4.dtype),
                       jax.ShapeDtypeStruct((bkv, group, 1, seq), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((group, rows, 128), jnp.float32),
                            pltpu.VMEM((group, rows, 128), jnp.float32),
                            pltpu.VMEM((group, rows, depth), jnp.float32)],
            compiler_params=_params("parallel", "parallel"),
            name="dsa_core_fwd", interpret=common.interpret_mode(),
        )(q4, k3, v3, words)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _core(q4, k3, v3, words, scale):
    return _core_forward(q4, k3, v3, words, scale)


def _core_fwd(q4, k3, v3, words, scale):
    out, lse = _core_forward(q4, k3, v3, words, scale)
    # Named here, in the forward RULE and in the kernel's own layout: the
    # named tensors ARE the residuals the backward rule reads, so a policy
    # that keeps the names (ops/remat.py) leaves the recompute's forward
    # kernel without a reader and it is not run a second time.
    out = checkpoint_name(out, DSA_CORE_OUT)
    lse = checkpoint_name(lse, DSA_CORE_LSE)
    return (out, lse), (q4, k3, v3, words, out, lse)


def _core_bwd(scale, residuals, cotangents):
    q4, k3, v3, words, out, lse = residuals
    do, _ = cotangents  # (the log-sum-exps go on under stop_gradient)
    bkv, group, seq, depth = q4.shape
    kv = bkv // words.shape[0]
    rows = min(CORE_ROWS, seq)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, :, None, :]
    spec = _core_specs(kv, group, rows, seq, depth)
    with trace_parts.kernel_build("dsa_core_bwd_dq"):
        dq = pl.pallas_call(
            partial(_core_dq_kernel, scale=scale),
            grid=(bkv, seq // rows),
            in_specs=[spec["q"], spec["kv"], spec["kv"], spec["words"],
                      spec["row"], spec["row"], spec["q"]],
            out_specs=spec["q"],
            out_shape=jax.ShapeDtypeStruct(q4.shape, q4.dtype),
            scratch_shapes=[pltpu.VMEM((group, rows, depth), jnp.float32)],
            compiler_params=_params("parallel", "parallel"),
            name="dsa_core_bwd_dq", interpret=common.interpret_mode(),
        )(q4, k3, v3, words, lse, delta, do)
    # the query blocks above a key tile's diagonal are neither computed nor
    # fetched: their index is held at the first block the tile's keys see
    at = lambda j, i: jnp.maximum(i, (j * WORD_LANES) // rows)
    rows_spec = pl.BlockSpec((1, group, rows, depth),
                             lambda b, j, i: (b, 0, at(j, i), 0))
    tile_spec = pl.BlockSpec((1, WORD_LANES, depth), lambda b, j, i: (b, j, 0))
    row_spec = pl.BlockSpec((1, group, 1, rows),
                            lambda b, j, i: (b, 0, 0, at(j, i)))
    with trace_parts.kernel_build("dsa_core_bwd_dkv"):
        dk, dv = pl.pallas_call(
            partial(_core_dkv_kernel, scale=scale),
            grid=(bkv, seq // WORD_LANES, seq // rows),
            in_specs=[rows_spec, tile_spec, tile_spec,
                      pl.BlockSpec((1, rows, WORD_LANES),
                                   lambda b, j, i: (b // kv, at(j, i), 0)),
                      row_spec, row_spec, rows_spec],
            out_specs=[tile_spec, tile_spec],
            out_shape=[jax.ShapeDtypeStruct(k3.shape, k3.dtype),
                       jax.ShapeDtypeStruct(v3.shape, v3.dtype)],
            scratch_shapes=[pltpu.VMEM((WORD_LANES, depth), jnp.float32),
                            pltpu.VMEM((WORD_LANES, depth), jnp.float32)],
            compiler_params=_params("parallel", "parallel", "arbitrary"),
            name="dsa_core_bwd_dkv", interpret=common.interpret_mode(),
        )(q4, k3, v3, words, lse, delta, do)
    return dq, dk, dv, np.zeros(words.shape, dtype=jax.dtypes.float0)


_core.defvjp(_core_fwd, _core_bwd)


@jax.jit
def masked_attention(q, k, v, words):
    """q [B, S, H, D], k, v [B, S, KV, D], the choice as words [B, S, 512]
    -> (ctx [B, S, H, D], each head's log-sum-exp over its chosen keys
    [B, S, H] float32)."""
    batch, seq, heads, depth = q.shape
    kv = k.shape[2]
    flat = lambda t: _heads_first(t).reshape(batch * kv, seq, depth)
    # a key-value head's query heads together: [B * KV, G, S, D]
    grouped = _heads_first(q).reshape(batch * kv, heads // kv, seq, depth)
    out, lse = _core(grouped, flat(k), flat(v), words, 1.0 / math.sqrt(depth))
    return (_heads_first(out.reshape(batch, heads, seq, depth)),
            _heads_first(lse.reshape(batch, heads, seq)))


# ---------------------------------------------------- the indexer's objective

def _index_loss_kernel(qi_ref, ki_ref, w_ref, q_ref, k_ref, lse_ref,
                       words_ref, kl_ref, *grads, index_scale, core_scale):
    # qi_ref [1, J, rows, E]; ki_ref [1, S, E]; w_ref [1, J, rows]; q_ref
    # [1, H, rows, D]; k_ref [1, KV, S, D]; lse_ref [1, H, 1, rows];
    # words_ref [1, rows, 512]; kl_ref [1, 1, rows]; grads: dqi_ref
    # [1, J, rows, E], dki_ref [1, S, E] (the whole row's, added to by every
    # program of the row, one after the other), dw_ref [1, J, rows], float32
    index_heads, rows, _ = qi_ref.shape[1:]
    heads, kv = q_ref.shape[1], k_ref.shape[1]
    block = pl.program_id(1)
    seen = ((block + 1) * rows + WORD_LANES - 1) // WORD_LANES
    words = words_ref[0]
    weights = _columns(w_ref, index_heads)
    tile_of = lambda ref, j: ref[0, pl.ds(j * WORD_LANES, WORD_LANES), :]

    def masked_index(j):
        return jnp.where(_keep(words, j), _index_tile(
            qi_ref, tile_of(ki_ref, j), weights, index_scale), NEG)

    def normaliser(j, carry):
        m_prev, l_prev = carry
        s = masked_index(j)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        return m_new, (l_prev * jnp.exp(m_prev - m_new)
                       + jnp.sum(jnp.exp(s - m_new[:, None]), axis=-1))

    m, l = jax.lax.fori_loop(0, seen, normaliser, (
        jnp.full((rows,), NEG, jnp.float32), jnp.zeros((rows,), jnp.float32)))
    index_lse = (m + jnp.log(l))[:, None]
    core_lse = [lse_ref[0, h, 0][:, None] for h in range(heads)]
    if grads:
        dqi_ref, dki_ref, dw_ref = grads
        dqi_ref[...] = jnp.zeros_like(dqi_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

        @pl.when(block == 0)
        def _():
            dki_ref[...] = jnp.zeros_like(dki_ref)

    def walk(j, kl):
        keep = _keep(words, j)
        total = None
        for h in range(heads):
            k = k_ref[0, h // (heads // kv), pl.ds(j * WORD_LANES, WORD_LANES), :]
            p = jnp.exp(_dot(q_ref[0, h], k, _NT) * core_scale - core_lse[h])
            total = p if total is None else total + p
        target = jnp.where(keep, total / heads, 0.0)
        log_index = masked_index(j) - index_lse
        log_target = jnp.log(jnp.maximum(target, jnp.finfo(jnp.float32).tiny))
        kl = kl + jnp.sum(jnp.where(keep, target * (log_target - log_index),
                                    0.0), axis=-1)
        if grads:
            ki = tile_of(ki_ref, j)
            d_index = jnp.where(keep, jnp.exp(log_index) - target,
                                0.0) * index_scale
            d_ki = jnp.zeros(ki.shape, jnp.float32)
            for h in range(index_heads):
                qi = qi_ref[0, h]
                dots = _dot(qi, ki, _NT)
                dw_ref[0, h] += jnp.sum(d_index * jnp.maximum(dots, 0.0),
                                        axis=-1)
                d_dots = jnp.where(dots > 0.0, d_index * weights[h],
                                   0.0).astype(ki.dtype)
                dqi_ref[0, h] += _dot(d_dots, ki, _NN)
                d_ki = d_ki + _dot(d_dots, qi, _TN)
            dki_ref[0, pl.ds(j * WORD_LANES, WORD_LANES), :] += d_ki
        return kl

    kl_ref[0, 0] = jax.lax.fori_loop(0, seen, walk,
                                     jnp.zeros((rows,), jnp.float32))


def _index_loss_call(qi, ki, w, q, k, lse, words, with_grads: bool):
    """qi [B, J, S, E], ki [B, S, E], w [B, J, S], q [B, H, S, D], k
    [B, KV, S, D], lse [B, H, 1, S], words -> [kl [B, 1, S]] and, with
    ``with_grads``, the KL's SUM's gradients to qi, ki and w in float32."""
    batch, index_heads, seq, width = qi.shape
    heads, depth, kv = q.shape[1], q.shape[3], k.shape[1]
    rows = min(LOSS_ROWS, seq)
    by_rows = lambda n, last: pl.BlockSpec(
        (1, n, rows, last), lambda b, i: (b, 0, i, 0))
    vectors = lambda n: pl.BlockSpec((1, n, rows), lambda b, i: (b, 0, i))
    whole = pl.BlockSpec((1, seq, width), lambda b, i: (b, 0, 0))
    out_specs = [vectors(1)]
    out_shape = [jax.ShapeDtypeStruct((batch, 1, seq), jnp.float32)]
    if with_grads:
        out_specs += [by_rows(index_heads, width), whole, vectors(index_heads)]
        out_shape += [jax.ShapeDtypeStruct(t.shape, jnp.float32)
                      for t in (qi, ki, w)]
    with trace_parts.kernel_build("dsa_index_loss"):
        return pl.pallas_call(
            partial(_index_loss_kernel,
                    index_scale=1.0 / math.sqrt(index_heads * width),
                    core_scale=1.0 / math.sqrt(depth)),
            grid=(batch, seq // rows),
            in_specs=[
                by_rows(index_heads, width), whole, vectors(index_heads),
                by_rows(heads, depth),
                pl.BlockSpec((1, kv, seq, depth), lambda b, i: (b, 0, 0, 0)),
                pl.BlockSpec((1, heads, 1, rows), lambda b, i: (b, 0, 0, i)),
                pl.BlockSpec((1, rows, WORD_LANES), lambda b, i: (b, i, 0)),
            ],
            out_specs=out_specs, out_shape=out_shape,
            # (every program of a row adds to the row's dki: one after the other)
            compiler_params=_params("parallel", "arbitrary"),
            name="dsa_index_loss", interpret=common.interpret_mode(),
        )(qi, ki, w, q, k, lse, words)


@jax.custom_vjp
def _index_loss(qi, ki, w, q, k, lse, words):
    return jnp.sum(_index_loss_call(qi, ki, w, q, k, lse, words, False)[0])


def _index_loss_fwd(qi, ki, w, q, k, lse, words):
    kl, dqi, dki, dw = _index_loss_call(qi, ki, w, q, k, lse, words, True)
    # kept across remat by name (ops/remat.py): the recompute would run the
    # kernel a second time to make them again
    grads = tuple(checkpoint_name(t, DSA_INDEX_GRADS) for t in (
        dqi.astype(qi.dtype), dki.astype(ki.dtype), dw))
    return jnp.sum(kl), grads + (q, k, lse, words)


def _index_loss_bwd(residuals, g):
    dqi, dki, dw, q, k, lse, words = residuals
    scaled = lambda t: (g * t.astype(jnp.float32)).astype(t.dtype)
    # q, k and the log-sum-exps reach the KL as constants
    return (scaled(dqi), scaled(dki), scaled(dw), jnp.zeros_like(q),
            jnp.zeros_like(k), jnp.zeros_like(lse),
            np.zeros(words.shape, dtype=jax.dtypes.float0))


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


@jax.jit
def index_loss(qi, ki, w, q, k, lse, words):
    """qi [B, S, J, E], ki [B, S, E], w [B, S, J] float32; the core's q
    [B, S, H, D], k [B, S, KV, D] and log-sum-exps [B, S, H] (constants:
    no gradient reaches them); the choice as words -> the SUM over the
    tokens of KL(the heads' summed probabilities || the softmax of the
    indexer's scores), over each token's chosen keys."""
    return _index_loss(_heads_first(qi), ki, _heads_first(w), _heads_first(q),
                       _heads_first(k), _heads_first(lse)[:, :, None, :], words)
