"""The chunked gated delta rule (``ops/delta_rule.py``: the WY form and its
names) as two Pallas kernels, ``delta_rule_fwd`` and ``delta_rule_bwd``.

In XLA every factor of a chunk is a tensor in HBM between the products that
make and use it: the [C, C] decay matrix, A, the inverse T, U, W and the
scores, for every head and chunk, and the state passes through a scan of as
many dependent steps as the row has chunks. Here a chunk's factors live in
VMEM from the products that make them to those that use them, and the running
state is a float32 scratch that leaves the chip once a chunk, for the
backward. ``ops/pallas/ssd_scan.py`` is the model; what differs is the rule's.

* **Layouts are the neighbours' own**: q, k [B, S, Hk * 128] and v, o [B, S,
  Hv * 128] as the convolution writes them and the gated norm reads them,
  positions on sublanes, a head one lane tile: no transpose on either side.
  The per-head numbers (the log decays' running sum inside a chunk, G, and
  beta; float32) come twice: with the heads on 128 lanes ([B, S, 128]: G_i
  and beta_i, a row of a chunk's matrices) and with the positions of a chunk
  on the lanes ([B, S / C, Hv / 2, 2 C]: G_j and beta_j, a column; two value
  heads a row); ``ops/delta_rule.py`` makes them (and the running sum) in
  XLA.
* **grid (rows, head blocks, chunks)**, the chunks innermost and in order (the
  backward from the last to the first); a step holds one chunk of a block of
  key heads with their value heads. Inside, a ``lax.fori_loop`` over the key
  heads, ``FWD_UNROLL`` / ``BWD_UNROLL`` of them a body and those IN STEP: the
  matrix unit takes products in the order the program gives them, and a
  head's products hang on each other (T, then U and W, then V', then O and
  the state), so the body writes every head's product of one kind before any
  head's next (``_in_step``), and the inverses of the body's pairs block by
  block together. A forward call takes 7.05 ms head after head and 3.85 in
  step, four key heads a body (on the chip: PERF.md 6, PR 42).
* **A pair** of value heads shares a key head, and its two [C, C] matrices
  lie side by side on the 128 lanes: K [K; K]^T gives K K^T twice over in one
  product; the decays, A, T, the scores and their cotangents are [C, 2 C]. A
  head's operand [C, 128] stands in a product with a pair as the rows of its
  own half of [2 C, 128], zeros in the other's (``_of_half``), so nothing is
  ever cut along the lanes. ``U = T diag(beta) V`` and ``W = T diag(beta
  exp(G)) K`` scale T's columns (a row vector a pair) and take V and K as
  they came.
* **The inverse** of I + A (A strictly lower) is forward substitution in
  float32, sixteen rows at a time (``unit_lower_inverse_pairs``): a block's
  rows by the rows already final are one float32 product at ``highest`` on
  the matrix unit, the block's own sixteen by columns on the vector unit.
  Its backward is ``dA = -T^T dT T^T``, two float32 products at ``highest``,
  both heads of the pair at once.
* **State** [Hv, 128 (key), 128 (value)] float32 in scratch, zeroed at a
  row's first chunk; where a backward will follow, the forward writes it at
  each chunk's start ([B, S / C, Hv, 128, 128]); the backward reads it
  there, makes the chunk's factors again, and carries the state's cotangent
  in scratch the same way.
* **Precision** is the XLA form's: G, the exponentials (only of differences
  <= 0: masked BEFORE the exponential), A, the inverse, the state and its
  cotangent float32; every product but the inverse's own takes operands in
  q's dtype and sums in float32, and T's scaled copies, U, W, the scores and
  the corrected values are rounded to that dtype where they become operands.
  The cotangents of q and k are summed in float32 over a key head's value
  heads and all their uses before they are cast.

``fits`` says which shapes the kernels take; ``ops/delta_rule.py`` runs the
others in XLA.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bert_pytorch_tpu.ops.pallas import common
from bert_pytorch_tpu.utils import trace_parts

LANES = 128
SUBLANES = 8
CHUNK = LANES // 2       # two heads' [C, C] matrices fill the lanes
KEY_BLOCK = 8            # key heads a grid step, where there are more
# key heads in step in the loop's body: eight are 9% faster still in the
# forward and double Mosaic's compile and the trace (5 s a kernel with four)
FWD_UNROLL = BWD_UNROLL = 4
# the backward at 8 key / 16 value heads: blocks twice over ~6 MB (the
# states a chunk started from are 1 MB), scratch 1 MB, a pair's temporaries
VMEM_LIMIT_BYTES = 64 * 1024 * 1024

_ROWS, _LANES_OF_BOTH = (((0,), (0,)), ((), ())), (((1,), (1,)), ((), ()))
_EXACT = jax.lax.Precision.HIGHEST


def key_block(key_heads: int) -> int:
    """Key heads a grid step holds: all of them, or ``KEY_BLOCK`` of more."""
    return KEY_BLOCK if key_heads > KEY_BLOCK else key_heads


def fits(k_shape: tuple, v_shape: tuple, chunk: int) -> bool:
    """Whether the kernels take k (and q) [B, S, Hk, Dk] with v [B, S, Hv,
    Dv] in chunks of ``chunk`` (S a multiple of it): chunks of half a lane
    tile, heads of a whole one, the value heads of a key head in pairs, every
    value head on a lane of its own, key heads in whole blocks."""
    key_heads, dk = k_shape[2:]
    value_heads, dv = v_shape[2:]
    return (chunk == CHUNK and dk == LANES and dv == LANES
            and value_heads % (2 * key_heads) == 0 and value_heads <= LANES
            and key_heads % key_block(key_heads) == 0)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _exact(a, b, dims):
    return jax.lax.dot_general(a, b, dims, precision=_EXACT,
                               preferred_element_type=jnp.float32)


def _per_lane(numbers, head):
    """numbers [C, 128], a number a head on the lanes -> [C, 128]: on lane l
    the number of head ``head`` (an index, or one a lane)."""
    index = jnp.zeros(numbers.shape, jnp.int32) + head
    return jnp.take_along_axis(numbers, index, axis=1,
                               mode="promise_in_bounds")


def _halves(shape):
    """(row, column inside its half, which half) of a [C, 2 C] pair."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return row, lane % CHUNK, lane // CHUNK


def _pieces(t):
    """float32 t as three bfloat16 terms whose sum is t to its last bit (8
    bits of mantissa each): what a float32 product at ``highest`` makes of an
    operand, made once here for every product that reads it."""
    terms = []
    for _ in range(3):
        term = t.astype(jnp.bfloat16)
        terms.append(term)
        t = t - term.astype(jnp.float32)
    return terms


def unit_lower_inverse_pairs(pairs):
    """``(I + a)^-1`` of every ``a`` of ``pairs``, each two strictly
    lower-triangular [C, C] matrices side by side, a [C, 2 C] float32:
    forward substitution, a block of 16 rows at a time, all the pairs in step
    (what one pair's chain of dependent steps leaves idle the next pair
    fills). A block's rows of T are ``inv(I + a_II) (E_I - a_I T_before)``:

    * ``a_I T_before`` is one float32 product at ``highest`` on the matrix
      unit ([16, 2 C] by the rows of both T that are final, each on its own
      half of the lanes: [2 C, 2 C], zero elsewhere), spelled out in its six
      bfloat16 passes so that the terms of T are made once a block and the
      passes with a common right side are one product;
    * ``inv(I + a_II)`` is applied by columns on the vector unit: row j of
      the block is final once the columns before j have been taken from it;
      column j of a, spread over the lanes, times that row is then taken from
      the block's rows below j. A spread is a lane gather, the one thing here
      the vector unit is slow at (5 cycles a tile: PERF.md 6, PR 42): blocks
      of 16 need 88 of them a pair where the whole matrix by columns needs
      288."""
    rows = 2 * SUBLANES  # a block: one packed bfloat16 tile of rows
    row, column, half = _halves((rows, 2 * CHUNK))
    blocks, every = CHUNK // rows, range(len(pairs))
    a = [[pair[rows * b:rows * (b + 1)] for b in range(blocks)]
         for pair in pairs]
    zeros = jnp.zeros((rows, 2 * CHUNK), jnp.bfloat16)
    # T's final rows in three terms: head 0's blocks, then head 1's
    final = [[[zeros] * (2 * blocks) for _ in range(3)] for _ in every]
    inverse = [[] for _ in every]
    for b in range(blocks):
        block = [(row + rows * b == column).astype(jnp.float32)] * len(pairs)
        if b:
            terms = [_pieces(a[i][b]) for i in every]
            right = [[jnp.concatenate(term, axis=0) for term in final[i]]
                     for i in every]
            by1 = [_dot(jnp.concatenate(terms[i], axis=0), right[i][0])
                   for i in every]
            by2 = [_dot(jnp.concatenate(terms[i][:2], axis=0), right[i][1])
                   for i in every]
            by3 = [_dot(terms[i][0], right[i][2]) for i in every]
            block = [block[i] - (
                (by3[i] + by1[i][2 * rows:] + by2[i][rows:])
                + (by2[i][:rows] + by1[i][rows:2 * rows]) + by1[i][:rows])
                for i in every]
        # (a is 0 on and above the diagonal: the rows above j stay as they
        # are, and the upper tile is done once j reaches its last row)
        upper = [t[:SUBLANES] for t in block]
        lower = [t[SUBLANES:] for t in block]
        for j in range(rows - 1):
            spread = half[:SUBLANES] * CHUNK + rows * b + j
            for i in every:
                final_row = jnp.broadcast_to(
                    (upper[i] if j < SUBLANES else lower[i])[
                        j % SUBLANES:j % SUBLANES + 1], spread.shape)
                take = lambda t: jnp.take_along_axis(
                    t, spread, axis=1, mode="promise_in_bounds") * final_row
                if j < SUBLANES - 1:
                    upper[i] = upper[i] - take(a[i][b][:SUBLANES])
                lower[i] = lower[i] - take(a[i][b][SUBLANES:])
        for i in every:
            done = jnp.concatenate([upper[i], lower[i]], axis=0)
            inverse[i].append(done)
            if b < blocks - 1:
                for h in range(2):
                    for term, piece in zip(final[i], _pieces(
                            jnp.where(half == h, done, 0.0))):
                        term[h * blocks + b] = piece
    return [jnp.concatenate(blocks_, axis=0) for blocks_ in inverse]


def _key_head(refs, j):
    """What both passes make of key head ``j`` of the block again: its lanes,
    q, k, [K; K], and K K^T and Q K^T twice over ([C, 2 C], a pair's two
    halves)."""
    q_ref, k_ref = refs
    lanes = _head_lanes(j)
    q, k = q_ref[0, :, lanes], k_ref[0, :, lanes]
    twice = jnp.concatenate([k, k], axis=0)                       # [2 C, Dk]
    return (lanes, q, k, twice, _dot(k, twice, _LANES_OF_BOTH),
            _dot(q, twice, _LANES_OF_BOTH))


def _pairs(refs, pairs, dtype):
    """The factors of every pair of ``pairs`` ((first value head of the
    layer, row of the block's pairs, K K^T, Q K^T) each), side by side on the
    lanes, each [C, 2 C]: beta_i, the masked decays, A, T and the decayed
    scores in float32; T with its columns scaled by beta_j (so that ``U = T
    diag(beta) V`` is one product with V as it came) and by beta_j exp(G_j)
    (``W``, with K as it came), in the operands' dtype; and beta_j and
    exp(G_j) themselves [1, 2 C]. The inverses are made in step."""
    run_ref, beta_ref, runrow_ref, betarow_ref = refs
    row, column, half = _halves((CHUNK, 2 * CHUNK))
    before = []
    for first, slot, kk, qk in pairs:
        beta = _per_lane(beta_ref[0], first + half)
        runrow = runrow_ref[0, 0, pl.ds(slot, 1), :]              # G_j
        betarow = betarow_ref[0, 0, pl.ds(slot, 1), :]
        span = _per_lane(run_ref[0], first + half) - runrow       # G_i - G_j
        decay = jnp.exp(jnp.where(row >= column, span, -jnp.inf))
        before.append((beta, decay,
                       jnp.where(row > column, beta * kk * decay, 0.0),
                       qk * decay, betarow, jnp.exp(runrow)))
    inverses = unit_lower_inverse_pairs([a for _, _, a, _, _, _ in before])
    return [(beta, decay, a, inverse, scores,
             (inverse * betarow).astype(dtype),
             (inverse * (betarow * introw)).astype(dtype), betarow, introw)
            for (beta, decay, a, scores, betarow, introw), inverse in zip(
                before, inverses)]


def _head(run_ref, head):
    """A value head's decays over all 128 lanes: exp(G), exp(G_last - G) and,
    [1, 128], exp(G_last)."""
    run = _per_lane(run_ref[0], head)
    last = run[CHUNK - 1:CHUNK]
    return jnp.exp(run), jnp.exp(last - run), jnp.exp(last)


def _of_half(t, r):
    """t [C, 128] as the rows of half ``r`` of a pair's [2 C, 128], zeros in
    the other half's: a product of a pair's [C, 2 C] with it reads half r's
    columns alone (no slice along the lanes)."""
    zeros = jnp.zeros_like(t)
    return jnp.concatenate([t, zeros] if r == 0 else [zeros, t], axis=0)


def _half_rows(t, r):
    """Rows of half ``r`` of t [2 C, 128]."""
    return t[r * CHUNK:(r + 1) * CHUNK]


def _head_lanes(head):
    """The lane tile of head ``head`` of a block [C, heads * 128]."""
    return pl.ds(pl.multiple_of(head * LANES, LANES), LANES)


def _in_step(refs, first_key, together, ratio, base, dtype):
    """What both passes make again of ``together`` key heads from
    ``first_key`` on, taken IN STEP: the key heads' own (``_key_head``), their
    pairs' factors (``_pairs``), and the heads as (pair, half, head of the
    block, its lanes). The matrix unit takes products in the order the
    program gives them, so a pass writes every head's product of one kind
    before any head's next: one head's chain of dependent products then
    waits behind the others' and not behind its own (a forward call 7.05 ms
    head after head, 3.85 in step: PERF.md 6, PR 42)."""
    q_ref, k_ref, run_ref, beta_ref, runrow_ref, betarow_ref = refs
    keys = [_key_head((q_ref, k_ref), first_key + t) for t in range(together)]
    slots = [(n, (first_key + n) * (ratio // 2) + p)
             for n in range(together) for p in range(ratio // 2)]
    pairs = _pairs((run_ref, beta_ref, runrow_ref, betarow_ref),
                   [(base + 2 * slot, slot, keys[n][4], keys[n][5])
                    for n, slot in slots], dtype)
    heads = [(m, r, 2 * slot + r, _head_lanes(2 * slot + r))
             for m, (_, slot) in enumerate(slots) for r in range(2)]
    return keys, slots, pairs, heads


def _groups(body, heads, together):
    """``body(first key head)`` for the block's key heads, ``together`` at a
    time."""
    together = math.gcd(heads, together)

    def step(i, _):
        body(i * together, together)
        return 0

    jax.lax.fori_loop(0, heads // together, step, 0)


def _fwd_kernel(q_ref, k_ref, v_ref, run_ref, beta_ref, runrow_ref,
                betarow_ref, o_ref, *rest, block, ratio):
    # q_ref, k_ref [1, C, block * 128]; v_ref, o_ref [1, C, block * ratio *
    # 128]; run_ref, beta_ref [1, C, 128] float32 (value head h of the layer
    # on lane h); runrow_ref, betarow_ref [1, 1, block * ratio / 2, 2 C] (a
    # pair a row); rest: start_ref [1, 1, block * ratio, 128, 128], where the
    # caller keeps the states for a backward, then state_scr [block * ratio,
    # 128, 128] float32
    *start_ref, state_scr = rest

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_scr[...] = jnp.zeros(state_scr.shape, jnp.float32)

    dtype = q_ref.dtype
    f32 = jnp.float32
    base = pl.program_id(1) * block * ratio  # the block's first value head
    refs = (q_ref, k_ref, run_ref, beta_ref, runrow_ref, betarow_ref)

    def group(first_key, together):
        keys, slots, pairs, heads = _in_step(refs, first_key, together, ratio,
                                             base, dtype)
        q = lambda m: keys[slots[m][0]][1]
        k = lambda m: keys[slots[m][0]][2]
        scores = [pair[4].astype(dtype) for pair in pairs]
        u = [_dot(pairs[m][5], _of_half(v_ref[0, :, lanes], r)).astype(dtype)
             for m, r, _, lanes in heads]
        w = [_dot(pairs[m][6], _of_half(k(m), r)).astype(dtype)
             for m, r, _, _ in heads]
        state = [state_scr[head] for _, _, head, _ in heads]  # [Dk, Dv]
        for ref in start_ref:
            for (_, _, head, _), s in zip(heads, state):
                ref[0, 0, head] = s
        state_c = [s.astype(dtype) for s in state]
        corrected = [u[h].astype(f32) - _dot(w[h], state_c[h])
                     for h in range(len(heads))]
        read = [_dot(q(m), state_c[h]) for h, (m, _, _, _) in enumerate(heads)]
        inside = [_dot(scores[m], _of_half(corrected[h].astype(dtype), r))
                  for h, (m, r, _, _) in enumerate(heads)]
        decays = [_head(run_ref, base + head) for _, _, head, _ in heads]
        for h, (_, _, _, lanes) in enumerate(heads):
            o_ref[0, :, lanes] = (read[h] * decays[h][0]
                                  + inside[h]).astype(dtype)
        added = [_dot(k(m), (corrected[h] * decays[h][1]).astype(dtype),
                      _ROWS) for h, (m, _, _, _) in enumerate(heads)]
        for h, (_, _, head, _) in enumerate(heads):
            state_scr[head] = state[h] * decays[h][2] + added[h]

    _groups(group, block, FWD_UNROLL)


def _bwd_kernel(q_ref, k_ref, v_ref, run_ref, beta_ref, runrow_ref,
                betarow_ref, start_ref, do_ref, dq_ref, dk_ref, dv_ref,
                drun_ref, dbeta_ref, drunrow_ref, dbetarow_ref, dstate_scr, *,
                block, ratio):
    # as _fwd_kernel; do_ref, dv_ref [1, C, block * ratio * 128]; dq_ref,
    # dk_ref [1, C, block * 128]; drun_ref, dbeta_ref [1, 1, C, 128] float32
    # (this block's value heads on their lanes, zeros on the others: the
    # caller adds the blocks) and drunrow_ref, dbetarow_ref [1, 1, block *
    # ratio / 2, 2 C] (the cotangents of G and beta in the two forms they
    # came in: the caller adds them); dstate_scr [block * ratio, 128, 128]:
    # the cotangent of the state AFTER this chunk, carried from the chunk
    # after it
    @pl.when(pl.program_id(2) == 0)  # the LAST chunk: the walk starts there
    def _():
        dstate_scr[...] = jnp.zeros(dstate_scr.shape, jnp.float32)

    drun_ref[...] = jnp.zeros(drun_ref.shape, jnp.float32)
    dbeta_ref[...] = jnp.zeros(dbeta_ref.shape, jnp.float32)
    dtype = q_ref.dtype
    f32 = jnp.float32
    base = pl.program_id(1) * block * ratio
    refs = (q_ref, k_ref, run_ref, beta_ref, runrow_ref, betarow_ref)
    row, column, half = _halves((CHUNK, 2 * CHUNK))
    lane = column + half * CHUNK
    over_lanes = lambda t: jnp.sum(t, axis=1, keepdims=True)     # [C, 1]
    over_rows = lambda t: jnp.sum(t, axis=0, keepdims=True)      # [1, 2 C]
    both = lambda t: _half_rows(t, 0) + _half_rows(t, 1)

    def group(first_key, together):
        keys, slots, pairs, heads = _in_step(refs, first_key, together, ratio,
                                             base, dtype)
        every = range(len(heads))
        of = lambda h: slots[heads[h][0]][0]        # a head's key head
        q, k = (lambda h: keys[of(h)][1]), (lambda h: keys[of(h)][2])
        by_v, by_k = (lambda h: pairs[heads[h][0]][5]), (
            lambda h: pairs[heads[h][0]][6])
        scores_c = [pair[4].astype(dtype) for pair in pairs]
        decays = [_head(run_ref, base + head) for _, _, head, _ in heads]
        into, out_of, whole = zip(*decays)
        v_r = [_of_half(v_ref[0, :, lanes], r) for _, r, _, lanes in heads]
        k_r = [_of_half(k(h), heads[h][1]) for h in every]
        u = [_dot(by_v(h), v_r[h]).astype(dtype) for h in every]
        w = [_dot(by_k(h), k_r[h]).astype(dtype) for h in every]
        start = [start_ref[0, 0, head] for _, _, head, _ in heads]
        start_c = [s.astype(dtype) for s in start]
        do = [do_ref[0, :, lanes] for _, _, _, lanes in heads]
        do32 = [t.astype(f32) for t in do]
        dstate = [dstate_scr[head] for _, _, head, _ in heads]
        dstate_c = [t.astype(dtype) for t in dstate]
        corrected = [u[h].astype(f32) - _dot(w[h], start_c[h]) for h in every]
        corrected_c = [t.astype(dtype) for t in corrected]
        # the state handed on: exp(G_last) S0 + K^T (V' to the end)
        dto_end = [_dot(k(h), dstate_c[h]) for h in every]        # [C, Dv]
        on_state = [_dot((corrected[h] * out_of[h]).astype(dtype),
                         dstate_c[h], _LANES_OF_BOTH) for h in every]
        dwhole = [jnp.sum(over_lanes(dstate[h] * start[h]), axis=0,
                          keepdims=True) for h in every]          # [1, 1]
        dout_of = [over_lanes(dto_end[h] * corrected[h]) for h in every]
        # the outputs: exp(G) Q S0 + scores V'
        dread = [(do32[h] * into[h]).astype(dtype) for h in every]
        on_read = [_dot(dread[h], start_c[h], _LANES_OF_BOTH) for h in every]
        dinto = [over_lanes(do32[h] * _dot(q(h), start_c[h])) for h in every]
        dscores_h = [_dot(do[h], _of_half(corrected_c[h], heads[h][1]),
                          _LANES_OF_BOTH) for h in every]
        back = [_dot(scores_c[heads[h][0]], do[h], _ROWS) for h in every]
        dcorrected_c = [(dto_end[h] * out_of[h] + _half_rows(
            back[h], heads[h][1])).astype(dtype) for h in every]
        # V' = U - W S0
        dw_c = [(-_dot(dcorrected_c[h], start_c[h], _LANES_OF_BOTH)).astype(
            dtype) for h in every]
        from_read = [_dot(q(h), dread[h], _ROWS) for h in every]
        from_w = [_dot(w[h], dcorrected_c[h], _ROWS) for h in every]
        for h, (_, _, head, _) in enumerate(heads):
            dstate_scr[head] = dstate[h] * whole[h] + from_read[h] - from_w[h]
        # U = (T beta_j) V, W = (T beta_j exp(G_j)) K
        dby_v_h = [_dot(dcorrected_c[h], v_r[h], _LANES_OF_BOTH)
                   for h in every]
        dby_k_h = [_dot(dw_c[h], k_r[h], _LANES_OF_BOTH) for h in every]
        dv = [_dot(by_v(h), dcorrected_c[h], _ROWS) for h in every]
        on_k = [_dot(by_k(h), dw_c[h], _ROWS) for h in every]
        for h, (_, r, head, lanes) in enumerate(heads):
            dv_ref[0, :, lanes] = _half_rows(dv[h], r).astype(dtype)
            # the cotangent of G_i but for the part through G_i - G_j
            drun_ref[0, 0] += jnp.where(
                lane == base + head,
                dinto[h] * into[h] - dout_of[h] * out_of[h] + jnp.where(
                    row == CHUNK - 1, over_rows(dout_of[h] * out_of[h])
                    + dwhole[h] * whole[h], 0.0), 0.0)
        # T = (I + A)^-1 under dT, both heads of a pair at once: T^T dT of the
        # two on the diagonal blocks of [2 C, 2 C], then (.) T^T by T's halves
        # each on its own rows
        of_pair = lambda t, m: t[2 * m] + t[2 * m + 1]
        dby_v = [of_pair(dby_v_h, m) for m in range(len(pairs))]
        dby_k = [of_pair(dby_k_h, m) for m in range(len(pairs))]
        dscores = [of_pair(dscores_h, m) for m in range(len(pairs))]
        left = [_exact(pair[3], (dby_v[m] + dby_k[m] * pair[8]) * pair[7],
                       _ROWS) for m, pair in enumerate(pairs)]
        da = [-_exact(
            jnp.where(half == 0, _half_rows(left[m], 0),
                      _half_rows(left[m], 1)),
            jnp.concatenate([jnp.where(half == 0, pair[3], 0.0),
                             jnp.where(half == 1, pair[3], 0.0)], axis=0),
            _LANES_OF_BOTH) for m, pair in enumerate(pairs)]
        dkk = [jnp.zeros((CHUNK, LANES), f32)] * together
        dqk = list(dkk)
        for m, (beta, decay, a, inverse, scores, _, _, betarow,
                introw) in enumerate(pairs):
            n, slot = slots[m]
            da_m = jnp.where(row > column, da[m], 0.0)
            # A = beta_i (K K^T) decay below the diagonal
            dkk[n] = dkk[n] + da_m * beta * decay
            dqk[n] = dqk[n] + dscores[m] * decay
            dspan = da_m * a + dscores[m] * scores       # of G_i - G_j
            dbeta = da_m * keys[n][4] * decay
            for r in range(2):
                of_head = lane == base + 2 * slot + r
                mine = lambda t: over_lanes(jnp.where(half == r, t, 0.0))
                drun_ref[0, 0] += jnp.where(of_head, mine(dspan), 0.0)
                dbeta_ref[0, 0] += jnp.where(of_head, mine(dbeta), 0.0)
            dcoef = over_rows(dby_k[m] * inverse) * introw  # of beta_j e^G_j
            drunrow_ref[0, 0, pl.ds(slot, 1), :] = (
                dcoef * betarow - over_rows(dspan))
            dbetarow_ref[0, 0, pl.ds(slot, 1), :] = (
                over_rows(dby_v[m] * inverse) + dcoef)
        # K K^T and Q K^T twice over: a pair's halves add up in the products
        dkk = [t.astype(dtype) for t in dkk]
        dqk = [t.astype(dtype) for t in dqk]
        last = [(_dot(dqk[n], key[3]), _dot(dkk[n], key[3]),
                 _dot(dkk[n], key[2], _ROWS), _dot(dqk[n], key[1], _ROWS))
                for n, key in enumerate(keys)]
        for n, (key_lanes, _, _, _, _, _) in enumerate(keys):
            mine = [h for h in every if of(h) == n]
            dq_ref[0, :, key_lanes] = (
                sum(on_read[h] for h in mine) + last[n][0]).astype(dtype)
            dk_ref[0, :, key_lanes] = (
                sum(on_state[h] + _half_rows(on_k[h], heads[h][1])
                    for h in mine)
                + last[n][1] + both(last[n][2]) + both(last[n][3])
            ).astype(dtype)

    _groups(group, block, BWD_UNROLL)


def _call(kernel, name, operands, results, args, key_heads, value_heads,
          backwards):
    """One pass as a ``pallas_call``: ``operands`` names the kind of each of
    ``args`` and ``results`` the (kind, dtype) of each result; a kind is an
    array's shape, its block a grid step and where that block lies (the
    backward walks the chunks from the last)."""
    batch, seq, _ = args[0].shape
    block, ratio = key_block(key_heads), value_heads // key_heads
    blocks, chunks = key_heads // block, seq // CHUNK
    held, pairs = block * ratio, block * ratio // 2
    at = (lambda c: chunks - 1 - c) if backwards else (lambda c: c)
    kinds = {
        "key": ((batch, seq, key_heads * LANES), (1, CHUNK, block * LANES),
                lambda i, h, c: (i, at(c), h)),
        "value": ((batch, seq, value_heads * LANES), (1, CHUNK, held * LANES),
                  lambda i, h, c: (i, at(c), h)),
        "heads": ((batch, seq, LANES), (1, CHUNK, LANES),
                  lambda i, h, c: (i, at(c), 0)),
        "rows": ((batch, chunks, value_heads // 2, 2 * CHUNK),
                 (1, 1, pairs, 2 * CHUNK), lambda i, h, c: (i, at(c), h, 0)),
        "start": ((batch, chunks, value_heads, LANES, LANES),
                  (1, 1, held, LANES, LANES),
                  lambda i, h, c: (i, at(c), h, 0, 0)),
        "dheads": ((batch, blocks, seq, LANES), (1, 1, CHUNK, LANES),
                   lambda i, h, c: (i, h, at(c), 0)),
    }
    spec = lambda kind: pl.BlockSpec(*kinds[kind][1:])
    with trace_parts.kernel_build(name):
        return pl.pallas_call(
            partial(kernel, block=block, ratio=ratio),
            grid=(batch, blocks, chunks),
            in_specs=[spec(kind) for kind in operands],
            out_specs=[spec(kind) for kind, _ in results],
            out_shape=[jax.ShapeDtypeStruct(kinds[kind][0], dtype)
                       for kind, dtype in results],
            scratch_shapes=[pltpu.VMEM((held, LANES, LANES), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            name=name, interpret=common.interpret_mode(),
        )(*args)


_OPERANDS = ("key", "key", "value", "heads", "heads", "rows", "rows")


@partial(jax.jit, static_argnames=("key_heads", "value_heads", "keep"))
def delta_rule_forward(q, k, v, run, beta, run_rows, beta_rows,
                       key_heads: int, value_heads: int, keep: bool = True):
    """q, k [B, S, Hk * 128], v [B, S, Hv * 128] in one dtype; run (G, the log
    decays summed inside each chunk) and beta [B, S, 128] float32 (value head
    h on lane h); run_rows, beta_rows [B, S / C, Hv / 2, 2 C] (both again:
    value heads 2p and 2p + 1 of a chunk side by side on row p); S a multiple
    of C = 64 ->
    [o [B, S, Hv * 128] in q's dtype and, with ``keep`` (what a backward
    needs), the state at every chunk's start [B, S / C, Hv, 128, 128]
    float32]. (jitted, as the backward: a model's layers then trace and lower
    each kernel once.)"""
    return _call(_fwd_kernel, "delta_rule_fwd", _OPERANDS,
                 (("value", q.dtype),) + (("start", jnp.float32),) * keep,
                 (q, k, v, run, beta, run_rows, beta_rows), key_heads,
                 value_heads, backwards=False)


@partial(jax.jit, static_argnames=("key_heads", "value_heads"))
def delta_rule_backward(q, k, v, run, beta, run_rows, beta_rows, starts, do,
                        key_heads: int, value_heads: int):
    """The cotangents of :func:`delta_rule_forward`'s o under ``do``: (dq,
    dk, dv; drun and dbeta [B, key blocks, S, 128], a block's value heads on
    their lanes; drun_rows and dbeta_rows [B, S / C, Hv / 2, 2 C], the parts
    of G's and beta's cotangents that belong to ``run_rows`` and
    ``beta_rows``)."""
    f32 = jnp.float32
    return _call(
        _bwd_kernel, "delta_rule_bwd", _OPERANDS + ("start", "value"),
        (("key", q.dtype), ("key", k.dtype), ("value", v.dtype),
         ("dheads", f32), ("dheads", f32), ("rows", f32), ("rows", f32)),
        (q, k, v, run, beta, run_rows, beta_rows, starts, do), key_heads,
        value_heads, backwards=True)
