"""Flash-style fused attention Pallas kernels (forward AND backward).

The fused-attention path of the framework (SURVEY.md §7 stage 8): scores,
masking, online softmax, dropout, and the value contraction happen in one
kernel, so neither the [B, H, S, S] score matrix nor the dropout mask ever
touches HBM. This is the capability Apex's fused kernels give the reference
on GPU (SURVEY §2.3) — built TPU-native:

  - **In-kernel dropout from the TPU hardware PRNG** (``pltpu.prng_seed`` /
    ``prng_random_bits``). The reference's attention dropout
    (modeling.py:424-427) materializes a [B, H, S, S] mask; at seq 512 that
    mask traffic alone costs ~30% of the training step. Here each
    [block_q, block_k] tile's mask is (re)generated from
    ``seed ^ (batch*head, q_block, k_block)`` on demand — the backward pass
    regenerates bit-identical masks instead of loading them.
  - **Pallas backward**: two kernels (dq; dk/dv/dbias) recompute
    probabilities from (q, k, bias, lse) blockwise — O(S) memory end to end,
    replacing the v1 XLA backward that materialized [B*H, S, S].
  - **Packed-batch block-diagonal masking** (``sequence_ids``; sequence
    packing, data/packing.py): each tile regenerates its
    cross-contamination mask from the per-token sequence-id vectors
    ([BH, 1, S] fp32, the bias layout) — the [B, 1, S, S] mask the XLA
    path materializes never exists in HBM, exactly like the dropout mask;
    the backward kernels rebuild the identical mask when recomputing
    probabilities. Statically gated (``segmented``), so unpacked callers
    compile the same kernel as before.
  - **Causal masking** (``causal``, a static flag like ``segmented``): the
    tiles the diagonal crosses are masked from the tile's row and column
    numbers, the tiles above it are never visited (the k loop of the forward
    and dq kernels ends at the diagonal, the q loop of the dk/dv kernel starts
    there), and the tiles below it run the unmasked body. With the flag off
    the kernels trace exactly as before: the bidirectional callers pay
    nothing.
  - **A window on the causal mask** (``window``, static, with ``causal``):
    position i sees j with ``i - window < j <= i``. The k loop of a q block
    starts at the first k tile that meets the band and ends at the diagonal
    (the dk/dv kernel's q loop likewise, from the diagonal down to the band's
    far edge); the tiles either edge crosses are masked, those wholly inside
    run the unmasked body. So the work follows the band, not the triangle: at
    8192 positions and a window of 512 the kernels visit 31 tiles a head
    where the causal ones visit 136 (``tiles_visited``). The flag is passed
    to the kernels only when set, and the windowed calls carry their own
    names (``flash_window_fwd`` ...), so a trace tells them from the full
    ones and every other caller compiles the kernels it compiled before.

Derivation with dropout (rate r, keep mask D ∈ {0,1}, P = softmax(S)):
  out   = (D ⊙ P) V / (1-r)
  dV    = (D ⊙ P)ᵀ dO / (1-r)
  dA    = dO Vᵀ;   delta = rowsum(dO ⊙ out)
  dS    = P ⊙ (D ⊙ dA / (1-r) − delta)       (softmax vjp; delta absorbs the
  dQ    = dS K · scale;  dK = dSᵀ Q · scale    rowsum(P ⊙ dP) term exactly as
  dbias = Σ_q dS                               in the dropout-free case)

The streaming forward accumulates ``l`` with *unmasked* probabilities (so
lse stays the true log-sum-exp) and the output accumulator with masked ones;
the 1/(1-r) scale is applied once in the final normalization.

Interpret-mode (CPU) limitation: the TPU PRNG primitives have no CPU
lowering, so ``dropout_rate > 0`` requires a real TPU; rate 0 runs everywhere
(tests compare it against the XLA path, and the dropout statistics are
validated on-chip).

What the kernels cost on the chip (seq 512: forward, dq, dk/dv per update,
and the wrapper round them) is in PERF.md (sections 5 and 6), from the
benchmark's cells; seq 128 still favors the XLA path. See ops/attention.py
for routing.

Across remat: the forward rule names its two residuals, the output ([B*H, S,
D] in the activation dtype) and the log-sum-exp ([B*H, 1, S] fp32)
(``ops/remat.py`` ``FLASH_OUT``, ``FLASH_LSE``), and ``remat='dots'`` keeps
them: B x S x hidden x 2 + B x H x S x 4 bytes a layer and micro-batch in
bf16 (16.8 + 0.5 MB at the phase-2 shape 16 x 512, 0.41 GB over 24 layers),
so the backward pass does not run the forward kernel a second time only to
reproduce them. q/k/v are still rebuilt from the kept projections.
``remat='full'`` keeps nothing.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bert_pytorch_tpu.ops.pallas import autotune
from bert_pytorch_tpu.ops.pallas.common import interpret_mode, pick_block
from bert_pytorch_tpu.ops.remat import FLASH_LSE, FLASH_OUT
from bert_pytorch_tpu.utils import trace_parts

_NEG_INF = -1e30


def _keep_mask(seed_ref, tile_id, shape, rate):
    """Regenerable [block_q, block_k] keep mask for one score tile.

    Seeding per tile (rather than streaming one generator) is what lets the
    backward kernels iterate tiles in any order and still reproduce the
    forward's draws. ``tile_id`` linearizes (batch*head, q_block, k_block);
    Mosaic supports at most 2 seed words, hence the fold.
    """
    pltpu.prng_seed(seed_ref[0], tile_id)
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    threshold = jnp.uint32(min(int(rate * (1 << 32)), (1 << 32) - 1))
    return bits >= threshold


def _tile_id(bh, qb, kb, num_qb, num_kb):
    return (bh * num_qb + qb) * num_kb + kb


def _pick_blocks(seq):
    """(block_q, block_k) for a sequence length. Forward and backward MUST
    use the same blocks: the dropout keep-mask is regenerated per tile from
    (bh, q_block, k_block), so differing tile boundaries would silently
    compute gradients under a different mask than the forward applied."""
    # 512-wide tiles win at seq 512 (5.0 vs 7.2 ms fwd+bwd for the
    # BERT-large shape with 256x256): fewer grid steps amortize the
    # pipeline, and VMEM stays modest (512x512 fp32 scores = 1MB).
    # pick_block's default candidate ladder tops out at 512 for this reason.
    return pick_block(seq), pick_block(seq)


def _pick_bh_block(seq, bh):
    """How many (batch*head) pairs each program processes (an unrolled loop
    in the kernel). Short sequences make per-bh tiles tiny, so the grid —
    not the MXU — bounds throughput; batching pairs per program amortizes
    it. G does NOT affect the dropout masks: tile ids are derived from the
    recovered global bh index and the block_q/block_k grid, so any G (even
    different ones for forward and backward) regenerates identical masks —
    the load-bearing invariant is block agreement, documented on
    _pick_blocks.

    The cap is 16 pairs, fewer where the tiles of 16 would not fit VMEM
    (the footprint scales with G x seq, hence the 4096 budget: 8 at seq
    512); what the kernels cost in the phase-2 step is in PERF.md 5."""
    target = min(16, max(1, 4096 // max(seq, 1)))
    g = 1
    while g * 2 <= target and bh % (g * 2) == 0:
        g *= 2
    return g


def _infer_geometry(kernel, seq, bh, geometry):
    """Resolve the (block_q, block_k, bh_block) triple for one inference
    kernel call: an explicit ``geometry`` (the autotune measurement loop
    forcing a candidate) wins, then a persisted autotune winner
    (ops/pallas/autotune.py — read at TRACE time, so winners must load
    before the first forward traces), then the hand-written heuristic.
    Divisibility is validated here because a winner loaded from a file
    is data, not code: a ragged grid must fail at trace with a real
    message, not inside Mosaic."""
    if geometry is not None:
        block_q, block_k, g = geometry
    else:
        cached = autotune.lookup(kernel, seq, bh)
        if cached is not None:
            block_q, block_k, g = cached
        else:
            block_q, block_k = _pick_blocks(seq)
            g = _pick_bh_block(seq, bh)
    if seq % block_q or seq % block_k or bh % g:
        raise ValueError(
            f"attention geometry (block_q={block_q}, block_k={block_k}, "
            f"bh_block={g}) does not tile seq={seq}, bh={bh}")
    return int(block_q), int(block_k), int(g)


def _seg_mask(q_seg, k_seg):
    """Additive block-diagonal tile mask from per-token sequence-id
    vectors (packing, data/packing.py): q may attend to k iff both carry
    the same NONZERO id. Ids travel as fp32 [G, 1, S] rows — the exact
    layout of bias_ref, so Mosaic sees nothing new — and small-int
    equality in fp32 is exact. The -10000 additive convention matches
    make_attention_bias, keeping the XLA and Pallas packed paths
    numerically aligned (masked scores underflow to exactly 0 after the
    fp32 exp in both)."""
    same = (q_seg[:, None] == k_seg[None, :]) & (q_seg[:, None] > 0.5)
    return jnp.where(same, 0.0, -10000.0)


def _causal_keep(row0, col0, shape, window=None):
    """[block_q, block_k] bool: column <= row (and, under ``window``, row -
    window < column), for the tile whose first row and column are ``row0``
    and ``col0``."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if window is None:
        return cols <= rows
    return (cols <= rows) & (cols > rows - window)


def _causal_k_loop(body, init, qb, block_q, block_k):
    """The k loop of one q block under the causal mask: the unmasked body
    over the k blocks wholly below the diagonal, the masked one over those it
    crosses, none above."""
    n_full = (qb * block_q) // block_k
    n_seen = ((qb + 1) * block_q + block_k - 1) // block_k
    carry = jax.lax.fori_loop(0, n_full, body, init)
    return jax.lax.fori_loop(n_full, n_seen, partial(body, masked=True), carry)


def _band_k_loop(body, init, qb, block_q, block_k, window):
    """:func:`_causal_k_loop` under a window: no k block before the first
    that a row of this q block still sees, the masked body over those the
    band's far edge crosses, the unmasked one over those wholly inside, the
    masked one over those the diagonal crosses."""
    row0 = qb * block_q
    row1 = row0 + block_q - 1
    first = jnp.maximum(row0 - window + 1, 0) // block_k
    n_full = row0 // block_k
    n_seen = (row1 + block_k) // block_k
    inside = jnp.clip(
        (jnp.maximum(row1 - window + 1, 0) + block_k - 1) // block_k,
        first, n_full)
    edge = partial(body, masked=True)
    carry = jax.lax.fori_loop(first, inside, edge, init)
    carry = jax.lax.fori_loop(inside, n_full, body, carry)
    return jax.lax.fori_loop(n_full, n_seen, edge, carry)


def _k_loop(body, init, qb, block_q, block_k, num_kb, causal, window):
    if window:
        return _band_k_loop(body, init, qb, block_q, block_k, window)
    if causal:
        return _causal_k_loop(body, init, qb, block_q, block_k)
    return jax.lax.fori_loop(0, num_kb, body, init)


def tiles_visited(seq: int, causal: bool = False, window=None) -> int:
    """[block_q, block_k] score tiles each of the three kernels computes for
    ONE (batch, head) pair at this length: the whole square, the tiles up to
    the diagonal under ``causal``, the band's under ``window``. From shapes
    alone (what ``_k_loop`` and the dk/dv kernel's q loop visit)."""
    block_q, block_k = _pick_blocks(seq)
    tiles = 0
    for qb in range(seq // block_q):
        first, seen = 0, seq // block_k
        if causal:
            seen = ((qb + 1) * block_q + block_k - 1) // block_k
        if window:
            first = max(qb * block_q - window + 1, 0) // block_k
        tiles += seen - first
    return tiles


def _flash_fwd_kernel(
    seed_ref, q_ref, k_ref, v_ref, bias_ref, seg_ref, out_ref, lse_ref,
    *, block_k, scale, rate, bh_block, segmented, causal=False, window=None
):
    # q_ref: [G, block_q, D]; k_ref/v_ref: [G, S, D]; bias_ref/seg_ref:
    # [G, 1, S], where G = bh_block (batch*head) pairs per program — an
    # unrolled loop that amortizes the grid at short sequence lengths
    # (_pick_bh_block). ``segmented`` statically gates the packed
    # block-diagonal mask (_seg_mask); unpacked callers pay nothing.
    # Matmul operands stay in the input dtype (bf16 in training) with fp32
    # accumulation — a single MXU pass per dot; casting inputs up to fp32
    # first would decompose each matmul into several passes. The softmax
    # chain (max/exp/sum) runs in fp32 throughout.
    qb = pl.program_id(1)
    seq_k = k_ref.shape[1]
    num_kb = seq_k // block_k

    for g in range(bh_block):
        bh = pl.program_id(0) * bh_block + g
        q = q_ref[g]
        block_q = q.shape[0]
        if segmented:
            q_seg = seg_ref[g, 0, pl.ds(qb * block_q, block_q)]

        def body(j, carry, masked=False):
            m_prev, l_prev, acc = carry
            k = k_ref[g, pl.ds(j * block_k, block_k), :]
            v = v_ref[g, pl.ds(j * block_k, block_k), :]
            b = bias_ref[g, 0, pl.ds(j * block_k, block_k)].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [block_q, block_k]
            s = s + b[None, :]
            if segmented:
                k_seg = seg_ref[g, 0, pl.ds(j * block_k, block_k)]
                s = s + _seg_mask(q_seg, k_seg)
            if masked:
                s = jnp.where(_causal_keep(qb * block_q, j * block_k, s.shape,
                                           window), s, _NEG_INF)
            m_cur = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])
            # l accumulates the TRUE softmax denominator (unmasked) so lse
            # is exact; only the value accumulation sees the dropout mask.
            l_new = l_prev * alpha + jnp.sum(p, axis=-1)
            if rate > 0.0:
                tid = _tile_id(bh, qb, j, pl.num_programs(1), num_kb)
                p_v = jnp.where(_keep_mask(seed_ref, tid, p.shape, rate), p, 0.0)
            else:
                p_v = p
            acc = acc * alpha[:, None] + jax.lax.dot_general(
                p_v.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_new, l_new, acc

        m0 = jnp.full((q.shape[0],), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((q.shape[0],), jnp.float32)
        acc0 = jnp.zeros((q.shape[0], v_ref.shape[-1]), jnp.float32)
        m, l, acc = _k_loop(body, (m0, l0, acc0), qb, block_q, block_k,
                            num_kb, causal, window)
        out_ref[g] = (acc / (l[:, None] * (1.0 - rate))).astype(out_ref.dtype)
        lse_ref[g, 0] = m + jnp.log(l)


def _flash_dq_kernel(
    seed_ref, q_ref, k_ref, v_ref, bias_ref, seg_ref, lse_ref, delta_ref,
    do_ref, dq_ref, *, block_k, scale, rate, bh_block, segmented,
    causal=False, window=None
):
    """dq for [G, block_q, D] tiles (G bh pairs/program); loops over k blocks."""
    qb = pl.program_id(1)
    seq_k = k_ref.shape[1]
    num_kb = seq_k // block_k
    inv_keep = 1.0 / (1.0 - rate)

    for g in range(bh_block):
        bh = pl.program_id(0) * bh_block + g
        q = q_ref[g]
        lse = lse_ref[g, 0]  # [block_q]
        delta = delta_ref[g, 0]  # [block_q]
        do = do_ref[g]  # [block_q, D]
        if segmented:
            q_seg = seg_ref[g, 0, pl.ds(qb * q.shape[0], q.shape[0])]

        def body(j, dq_acc, masked=False):
            k = k_ref[g, pl.ds(j * block_k, block_k), :]
            v = v_ref[g, pl.ds(j * block_k, block_k), :]
            b = bias_ref[g, 0, pl.ds(j * block_k, block_k)].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale + b[None, :]
            if segmented:
                # Identical mask regeneration as the forward — the
                # probabilities below must be the ones the forward used.
                s = s + _seg_mask(
                    q_seg, seg_ref[g, 0, pl.ds(j * block_k, block_k)])
            if masked:
                s = jnp.where(
                    _causal_keep(qb * q.shape[0], j * block_k, s.shape,
                                 window), s, _NEG_INF)
            p = jnp.exp(s - lse[:, None])  # normalized probabilities
            da = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [block_q, block_k]
            if rate > 0.0:
                tid = _tile_id(bh, qb, j, pl.num_programs(1), num_kb)
                keep = _keep_mask(seed_ref, tid, p.shape, rate)
                da = jnp.where(keep, da * inv_keep, 0.0)
            ds = p * (da - delta[:, None])
            return dq_acc + jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        dq0 = jnp.zeros(q.shape, jnp.float32)
        dq = _k_loop(body, dq0, qb, q.shape[0], block_k, num_kb, causal,
                     window)
        dq_ref[g] = (dq * scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(
    seed_ref, q_ref, k_ref, v_ref, bias_ref, seg_ref, lse_ref, delta_ref,
    do_ref, dk_ref, dv_ref, dbias_ref, *, block_q, scale, rate, bh_block,
    segmented, causal=False, window=None
):
    """dk/dv/dbias for [G, block_k, D] tiles; loops over q blocks."""
    kb = pl.program_id(1)
    seq_q = q_ref.shape[1]
    num_qb = seq_q // block_q
    inv_keep = 1.0 / (1.0 - rate)

    for g in range(bh_block):
        bh = pl.program_id(0) * bh_block + g
        k = k_ref[g]  # [block_k, D]
        v = v_ref[g]
        b = bias_ref[g, 0].astype(jnp.float32)  # [block_k]
        block_k, depth = k.shape
        if segmented:
            k_seg = seg_ref[g, 0, pl.ds(kb * block_k, block_k)]

        def body(i, carry, masked=False):
            dk_acc, dv_acc, db_acc = carry
            q = q_ref[g, pl.ds(i * block_q, block_q), :]
            lse = lse_ref[g, 0, pl.ds(i * block_q, block_q)]
            delta = delta_ref[g, 0, pl.ds(i * block_q, block_q)]
            do = do_ref[g, pl.ds(i * block_q, block_q), :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale + b[None, :]
            if segmented:
                s = s + _seg_mask(
                    seg_ref[g, 0, pl.ds(i * block_q, block_q)], k_seg)
            if masked:
                s = jnp.where(
                    _causal_keep(i * block_q, kb * block_k, s.shape, window),
                    s, _NEG_INF)
            p = jnp.exp(s - lse[:, None])  # [block_q, block_k]
            da = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if rate > 0.0:
                tid = _tile_id(bh, i, kb, num_qb, pl.num_programs(1))
                keep = _keep_mask(seed_ref, tid, p.shape, rate)
                p_v = jnp.where(keep, p * inv_keep, 0.0)
                da = jnp.where(keep, da * inv_keep, 0.0)
            else:
                p_v = p
            # dV += (D ⊙ P)ᵀ dO / (1-r)
            dv_acc = dv_acc + jax.lax.dot_general(
                p_v.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (da - delta[:, None])
            dk_acc = dk_acc + jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return dk_acc, dv_acc, db_acc + jnp.sum(ds, axis=0)

        zeros = (
            jnp.zeros((block_k, depth), jnp.float32),
            jnp.zeros(v.shape, jnp.float32),
            jnp.zeros((block_k,), jnp.float32),
        )
        if causal:
            # q blocks wholly above this k block are skipped, those the
            # diagonal crosses are masked, those wholly below are not.
            first = (kb * block_k) // block_q
            whole = ((kb + 1) * block_k + block_q - 2) // block_q
            edge = partial(body, masked=True)
            carry = jax.lax.fori_loop(first, whole, edge, zeros)
            if window:
                # ... and none past the last q block that still sees this k
                # block; those the band's far edge crosses are masked.
                last_col = (kb + 1) * block_k - 1
                end = jnp.minimum(num_qb,
                                  (last_col + window - 1) // block_q + 1)
                inside = jnp.clip((kb * block_k + window) // block_q,
                                  whole, end)
                carry = jax.lax.fori_loop(whole, inside, body, carry)
                dk, dv, db = jax.lax.fori_loop(inside, end, edge, carry)
            else:
                dk, dv, db = jax.lax.fori_loop(whole, num_qb, body, carry)
        else:
            dk, dv, db = jax.lax.fori_loop(0, num_qb, body, zeros)
        dk_ref[g] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[g] = dv.astype(dv_ref.dtype)
        dbias_ref[g, 0] = db.astype(dbias_ref.dtype)


def _seed_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


# Mosaic's default scoped VMEM a kernel: what the blocks above were sized for.
_DEFAULT_SCOPED_VMEM = 16 * 1024 ** 2


def _wide_head_params(g, seq, depth, depth_v, itemsize):
    """``compiler_params`` for a call whose two whole-sequence operands (K and
    V in the forward and dq kernels, Q and dO in the dkv kernel: [g, seq,
    depth] and [g, seq, depth_v], each double-buffered, a head's width rounded
    up to whole lane tiles as VMEM holds it) leave the other blocks a quarter
    of the default scoped VMEM or less: a head of 256 at 8192 positions (16
    MiB of the 16), and a head of 192 against values of 128 there (12 MiB:
    Mosaic asked for 16.11 of the 16). The limit is then raised to twice those
    operands (the tiles and the float32 scores take the rest; the chip has
    128 MiB). Every narrower call passes nothing and lowers as before."""
    lanes = lambda width: -(-width // 128) * 128
    whole = 2 * g * seq * (lanes(depth) + lanes(depth_v)) * itemsize
    if whole < 3 * _DEFAULT_SCOPED_VMEM // 4:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=2 * whole)}


def _static(segmented, causal, window=None):
    """The kernels' static flags. ``causal`` and ``window`` are passed only
    when set, so the call sites without them stay as they were."""
    return dict(segmented=segmented, **({"causal": True} if causal else {}),
                **({"window": window} if window else {}))


def _name(kernel, window, label=""):
    """``flash_fwd`` / ``flash_window_fwd``: a trace tells the windowed
    calls from the full ones by name, and a caller's ``label`` (``diff``,
    ``diff_cross``: models/phi4flash.py) its calls from every other's:
    ``flash_diff_window_fwd``, ``flash_diff_cross_bwd_dq`` ..."""
    return ("flash_" + (label + "_" if label else "")
            + ("window_" if window else "") + kernel)


def _flash_forward(q3, k3, v3, bias3, seg3, seed, scale, rate, segmented,
                   causal=False, window=None, label=""):
    """q3/k3: [BH, S, D]; v3: [BH, S, Dv] (the values may be wider or narrower
    than the keys: the output is as wide as they are); bias3: [BH, 1, S]
    additive key bias; seg3: [BH, 1, S] fp32 sequence ids (all-zero dummy when
    not segmented)."""
    bh, seq, depth = q3.shape
    depth_v = v3.shape[-1]
    block_q, block_k = _pick_blocks(seq)
    g = _pick_bh_block(seq, bh)
    grid = (bh // g, seq // block_q)
    name = _name("fwd", window, label)
    with trace_parts.kernel_build(name):
        out, lse = pl.pallas_call(
            partial(_flash_fwd_kernel, block_k=block_k, scale=scale, rate=rate,
                    bh_block=g, **_static(segmented, causal, window)),
            grid=grid,
            in_specs=[
                _seed_spec(),
                pl.BlockSpec((g, block_q, depth), lambda b, i: (b, i, 0)),
                pl.BlockSpec((g, seq, depth), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((g, seq, depth_v), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((g, 1, seq), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((g, 1, seq), lambda b, i: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((g, block_q, depth_v), lambda b, i: (b, i, 0)),
                pl.BlockSpec((g, 1, block_q), lambda b, i: (b, 0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, seq, depth_v), q3.dtype),
                jax.ShapeDtypeStruct((bh, 1, seq), jnp.float32),
            ],
            name=name,
            interpret=interpret_mode(),
            **_wide_head_params(g, seq, depth, depth_v, q3.dtype.itemsize),
        )(seed, q3, k3, v3, bias3, seg3)
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash(q3, k3, v3, bias3, seg3, seed, scale, rate, segmented,
           causal=False, window=None, label=""):
    out, _ = _flash_forward(q3, k3, v3, bias3, seg3, seed, scale, rate,
                            segmented, causal, window, label)
    return out


def _flash_fwd(q3, k3, v3, bias3, seg3, seed, scale, rate, segmented, causal,
               window, label):
    out, lse = _flash_forward(q3, k3, v3, bias3, seg3, seed, scale, rate,
                              segmented, causal, window, label)
    # Named here, in the forward RULE: remat='dots' keeps both (ops/remat.py),
    # which leaves the recomputed pallas_call without a live output, so the
    # backward pass does not run the forward kernel a second time.
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return out, (q3, k3, v3, bias3, seg3, seed, out, lse)


def _flash_bwd(scale, rate, segmented, causal, window, label, residuals, g):
    q3, k3, v3, bias3, seg3, seed, out, lse = residuals
    bh, seq, depth = q3.shape
    depth_v = v3.shape[-1]
    block_q, block_k = _pick_blocks(seq)
    # delta = rowsum(dO ⊙ O): one cheap fused XLA reduction, [BH, 1, S].
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )[:, None, :]

    gb = _pick_bh_block(seq, bh)
    wide = _wide_head_params(gb, seq, depth, depth_v, q3.dtype.itemsize)
    name = _name("bwd_dq", window, label)
    with trace_parts.kernel_build(name):
        dq = pl.pallas_call(
            partial(_flash_dq_kernel, block_k=block_k, scale=scale, rate=rate,
                    bh_block=gb, **_static(segmented, causal, window)),
            grid=(bh // gb, seq // block_q),
            in_specs=[
                _seed_spec(),
                pl.BlockSpec((gb, block_q, depth), lambda b, i: (b, i, 0)),
                pl.BlockSpec((gb, seq, depth), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((gb, seq, depth_v), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((gb, 1, seq), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((gb, 1, seq), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((gb, 1, block_q), lambda b, i: (b, 0, i)),
                pl.BlockSpec((gb, 1, block_q), lambda b, i: (b, 0, i)),
                pl.BlockSpec((gb, block_q, depth_v), lambda b, i: (b, i, 0)),
            ],
            out_specs=pl.BlockSpec((gb, block_q, depth), lambda b, i: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, seq, depth), q3.dtype),
            name=name,
            interpret=interpret_mode(),
            **wide,
        )(seed, q3, k3, v3, bias3, seg3, lse, delta, g)

    name = _name("bwd_dkv", window, label)
    with trace_parts.kernel_build(name):
        dk, dv, dbias = pl.pallas_call(
            partial(_flash_dkv_kernel, block_q=block_q, scale=scale, rate=rate,
                    bh_block=gb, **_static(segmented, causal, window)),
            grid=(bh // gb, seq // block_k),
            in_specs=[
                _seed_spec(),
                pl.BlockSpec((gb, seq, depth), lambda b, j: (b, 0, 0)),
                pl.BlockSpec((gb, block_k, depth), lambda b, j: (b, j, 0)),
                pl.BlockSpec((gb, block_k, depth_v), lambda b, j: (b, j, 0)),
                pl.BlockSpec((gb, 1, block_k), lambda b, j: (b, 0, j)),
                # seg needs the k tile AND every q block: full row, like lse.
                pl.BlockSpec((gb, 1, seq), lambda b, j: (b, 0, 0)),
                pl.BlockSpec((gb, 1, seq), lambda b, j: (b, 0, 0)),
                pl.BlockSpec((gb, 1, seq), lambda b, j: (b, 0, 0)),
                pl.BlockSpec((gb, seq, depth_v), lambda b, j: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((gb, block_k, depth), lambda b, j: (b, j, 0)),
                pl.BlockSpec((gb, block_k, depth_v), lambda b, j: (b, j, 0)),
                pl.BlockSpec((gb, 1, block_k), lambda b, j: (b, 0, j)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, seq, depth), k3.dtype),
                jax.ShapeDtypeStruct((bh, seq, depth_v), v3.dtype),
                jax.ShapeDtypeStruct((bh, 1, seq), jnp.float32),
            ],
            name=name,
            interpret=interpret_mode(),
            **wide,
        )(seed, q3, k3, v3, bias3, seg3, lse, delta, g)

    dseed = np.zeros(seed.shape, dtype=jax.dtypes.float0)
    dseg = jnp.zeros_like(seg3)  # ids are data, not parameters
    return dq, dk, dv, dbias.astype(bias3.dtype), dseg, dseed


_flash.defvjp(_flash_fwd, _flash_bwd)


def _infer_stream(score_fn, v_ref, g, block_k, num_kb, q_shape, out_dtype):
    """The shared online-softmax + PV stream of the inference kernels:
    ``score_fn(j)`` returns the j-th fully-masked fp32
    [block_q, block_k] score tile, and everything downstream — the
    running max/exp/sum bookkeeping, the PV contraction in the value
    dtype with fp32 accumulation, the final normalization — is ONE body
    shared by the fp and int8 score paths, so a fix to the stream can
    never silently diverge between them."""

    def body(j, carry):
        m_prev, l_prev, acc = carry
        s = score_fn(j)
        v = v_ref[g, pl.ds(j * block_k, block_k), :]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc

    m0 = jnp.full((q_shape[0],), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((q_shape[0],), jnp.float32)
    acc0 = jnp.zeros(q_shape, jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, num_kb, body, (m0, l0, acc0))
    return (acc / l[:, None]).astype(out_dtype)


def _infer_fwd_kernel(
    q_ref, k_ref, v_ref, bias_ref, seg_ref, out_ref,
    *, block_k, scale, bh_block, segmented
):
    """INFERENCE-ONLY forward (docs/serving.md "Inference fast path").

    The training kernel (:func:`_flash_fwd_kernel`) carries three things
    a serving forward never uses: the dropout PRNG plumbing (seed ref,
    per-tile mask regeneration), the ``lse`` output written for the
    backward kernels, and the unmasked-``l`` bookkeeping that keeps that
    lse exact. This variant drops all of it — no seed input, no second
    output, one accumulator pair (:func:`_infer_stream`) — while
    keeping the packed block-diagonal tile mask (``segmented``;
    serve-side request packing reuses it). Same tile geometry as
    training (_pick_blocks / _pick_bh_block) unless an autotune winner
    overrides it, so the VMEM/grid reasoning there carries over.
    """
    qb = pl.program_id(1)
    seq_k = k_ref.shape[1]
    num_kb = seq_k // block_k

    for g in range(bh_block):
        q = q_ref[g]
        if segmented:
            block_q = q.shape[0]
            q_seg = seg_ref[g, 0, pl.ds(qb * block_q, block_q)]

        def score(j, g=g, q=q):
            k = k_ref[g, pl.ds(j * block_k, block_k), :]
            b = bias_ref[g, 0, pl.ds(j * block_k, block_k)].astype(
                jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale + b[None, :]
            if segmented:
                k_seg = seg_ref[g, 0, pl.ds(j * block_k, block_k)]
                s = s + _seg_mask(q_seg, k_seg)
            return s

        out_ref[g] = _infer_stream(score, v_ref, g, block_k, num_kb,
                                   q.shape, out_ref.dtype)


def _infer_bias_seg(bias, sequence_ids, batch, seq, heads, name):
    """(bias3, seg3, segmented) — the shared [BH, 1, S] key-bias and
    sequence-id rows of the inference wrappers."""
    segmented = sequence_ids is not None
    if segmented and bias is not None:
        raise ValueError(
            f"{name}: pass either bias (padded batches) or "
            "sequence_ids (packed batches), not both")
    if segmented:
        seg3 = jnp.repeat(
            sequence_ids.astype(jnp.float32), heads, axis=0)[:, None, :]
    else:
        seg3 = jnp.zeros((batch * heads, 1, seq), jnp.float32)
    if bias is None:
        bias3 = jnp.zeros((batch * heads, 1, seq), jnp.float32)
    else:
        key_bias = bias.reshape(batch, -1)[:, -seq:]  # [B, S]
        bias3 = jnp.repeat(
            key_bias.astype(jnp.float32), heads, axis=0)[:, None, :]
    return bias3, seg3, segmented


def flash_attention_infer(q, k, v, bias=None, sequence_ids=None,
                          geometry=None):
    """Forward-only fused attention over [B, S, H, D] tensors — the
    serving path's kernel (``backend='pallas_infer'``,
    ops/attention.py). Contract matches :func:`flash_attention` at
    ``dropout_rate=0`` minus everything the backward needs: no residuals
    are saved, no lse is written, and no vjp is defined (differentiating
    through it is an error by design — training keeps its own kernel).
    ``sequence_ids`` retains the packed block-diagonal tile mask so
    packed serve batches (serve/engine.py) stay contamination-free
    without a [B, 1, S, S] mask in HBM. Runs in interpret mode on CPU
    (no PRNG primitives involved), which is how tier-1 tests parity.

    ``geometry`` forces one (block_q, block_k, bh_block) triple — the
    autotune measurement loop's hook; normal callers leave it None and
    get the persisted winner or the heuristic (:func:`_infer_geometry`).
    """
    batch, seq, heads, depth = q.shape
    scale = 1.0 / float(depth) ** 0.5

    def to3(t):
        return t.transpose(0, 2, 1, 3).reshape(batch * heads, seq, depth)

    bias3, seg3, segmented = _infer_bias_seg(
        bias, sequence_ids, batch, seq, heads, "flash_attention_infer")
    q3, k3, v3 = to3(q), to3(k), to3(v)
    bh = batch * heads
    block_q, block_k, g = _infer_geometry("infer", seq, bh, geometry)
    with trace_parts.kernel_build("flash_infer_fwd"):
        out3 = pl.pallas_call(
            partial(_infer_fwd_kernel, block_k=block_k, scale=scale,
                    bh_block=g, segmented=segmented),
            grid=(bh // g, seq // block_q),
            in_specs=[
                pl.BlockSpec((g, block_q, depth), lambda b, i: (b, i, 0)),
                pl.BlockSpec((g, seq, depth), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((g, seq, depth), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((g, 1, seq), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((g, 1, seq), lambda b, i: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((g, block_q, depth), lambda b, i: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, seq, depth), q3.dtype),
            name="flash_infer_fwd",
            interpret=interpret_mode(),
        )(q3, k3, v3, bias3, seg3)
    return out3.reshape(batch, heads, seq, depth).transpose(0, 2, 1, 3)


def _infer_fwd_kernel_int8(
    q_ref, k_ref, v_ref, qs_ref, ks_ref, bias_ref, seg_ref, out_ref,
    *, block_k, scale, bh_block, segmented
):
    """Int8-score inference forward (ZeroQuant into the attention path,
    docs/serving.md "Raw-speed kernels").

    q_ref/k_ref are PRE-QUANTIZED int8 tiles ([G, block_q, D] /
    [G, S, D]) with one symmetric fp32 scale per (batch*head) row
    (qs_ref/ks_ref, [G, 1, 1] — the per-token dynamic-scale machinery
    of ops/quant.py ``int8_matmul`` generalized to a per-head grain:
    one head's q/k rows share dynamics, so one scale per head keeps the
    rescale a scalar per program instead of a [block_q, block_k] outer
    product). QK^T runs int8 x int8 -> int32 on the MXU; the rescale by
    ``q_scale * k_scale * softmax_scale`` happens once per tile in
    fp32, and everything downstream — the online softmax, the PV
    contraction (v untouched: P·V stays in the input dtype with fp32
    accumulation), the normalization — IS :func:`_infer_stream`, the
    same body the fp kernel runs; only the score tile differs.
    """
    qb = pl.program_id(1)
    seq_k = k_ref.shape[1]
    num_kb = seq_k // block_k

    for g in range(bh_block):
        q8 = q_ref[g]
        rescale = (qs_ref[g, 0, 0] * ks_ref[g, 0, 0]).astype(jnp.float32) \
            * scale
        if segmented:
            block_q = q8.shape[0]
            q_seg = seg_ref[g, 0, pl.ds(qb * block_q, block_q)]

        def score(j, g=g, q8=q8, rescale=rescale):
            k8 = k_ref[g, pl.ds(j * block_k, block_k), :]
            b = bias_ref[g, 0, pl.ds(j * block_k, block_k)].astype(
                jnp.float32)
            s32 = jax.lax.dot_general(
                q8, k8, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32,
            )  # [block_q, block_k] int32
            s = s32.astype(jnp.float32) * rescale + b[None, :]
            if segmented:
                k_seg = seg_ref[g, 0, pl.ds(j * block_k, block_k)]
                s = s + _seg_mask(q_seg, k_seg)
            return s

        out_ref[g] = _infer_stream(score, v_ref, g, block_k, num_kb,
                                   q8.shape, out_ref.dtype)


def flash_attention_infer_int8(q, k, v, bias=None, sequence_ids=None,
                               geometry=None):
    """Forward-only fused attention with INT8 QK^T over [B, S, H, D]
    tensors (``backend='pallas_infer_int8'``, ops/attention.py).

    Same contract as :func:`flash_attention_infer` (no vjp, packed
    ``sequence_ids`` masking, interpret-mode on CPU) with the score
    matmul quantized: q and k are dynamically quantized to int8 with one
    symmetric scale PER HEAD (per [batch*head] row — ops/quant.py
    ``quantize_symmetric``), the tile dot runs int8 x int8 -> int32,
    and a single fp32 rescale recovers the scores. Softmax and the PV
    contraction stay at the higher precision of the base kernel, so the
    only new error source is score rounding: |Δscore| <=
    (|q|·scale_k + |k|·scale_q + scale_q·scale_k·D/4) / sqrt(D) per
    element — model-level bounds are documented (docs/serving.md) and
    asserted by tests/test_kernels_fastpath.py on all four serve heads.
    """
    from bert_pytorch_tpu.ops import quant as quant_ops

    batch, seq, heads, depth = q.shape
    scale = 1.0 / float(depth) ** 0.5

    def to3(t):
        return t.transpose(0, 2, 1, 3).reshape(batch * heads, seq, depth)

    bias3, seg3, segmented = _infer_bias_seg(
        bias, sequence_ids, batch, seq, heads, "flash_attention_infer_int8")
    q3, k3, v3 = to3(q), to3(k), to3(v)
    bh = batch * heads
    # Per-head symmetric dynamic quantization, computed by XLA outside
    # the kernel (two cheap reductions fused into the surrounding
    # program); the kernel consumes the int8 tensors + [BH, 1, 1] scales.
    q8, q_scale = quant_ops.quantize_symmetric(q3, axes=(1, 2))
    k8, k_scale = quant_ops.quantize_symmetric(k3, axes=(1, 2))
    block_q, block_k, g = _infer_geometry("infer_int8", seq, bh, geometry)
    with trace_parts.kernel_build("flash_infer_fwd_int8"):
        out3 = pl.pallas_call(
            partial(_infer_fwd_kernel_int8, block_k=block_k, scale=scale,
                    bh_block=g, segmented=segmented),
            grid=(bh // g, seq // block_q),
            in_specs=[
                pl.BlockSpec((g, block_q, depth), lambda b, i: (b, i, 0)),
                pl.BlockSpec((g, seq, depth), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((g, seq, depth), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((g, 1, 1), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((g, 1, 1), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((g, 1, seq), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((g, 1, seq), lambda b, i: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((g, block_q, depth), lambda b, i: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, seq, depth), q3.dtype),
            name="flash_infer_fwd_int8",
            interpret=interpret_mode(),
        )(q8, k8, v3, q_scale, k_scale, bias3, seg3)
    return out3.reshape(batch, heads, seq, depth).transpose(0, 2, 1, 3)


def flash_attention(q, k, v, bias=None, dropout_rate=0.0, dropout_rng=None,
                    sequence_ids=None, causal=False, window=None, label=""):
    """Fused attention over [B, S, H, D] tensors. The values may be wider or
    narrower than the queries and keys ([B, S, H, Dv], static, from shapes):
    the output is then [B, S, H, Dv]; scores are scaled by the keys' width.
    ``label`` (static) goes into the kernels' names (``_name``) and nowhere
    else.

    ``bias`` is the [B, 1, 1, S] additive mask from
    :func:`bert_pytorch_tpu.ops.attention.make_attention_bias` (key-only
    bias; a full [B, H, Sq, Sk] bias is not supported by this kernel).

    ``sequence_ids`` ([B, S] int, 0 = pad) enables PACKED-batch attention
    (data/packing.py): each [block_q, block_k] tile regenerates its
    block-diagonal mask from the per-token id vectors inside the kernel —
    the [B, 1, S, S] mask the XLA path materializes never exists in HBM,
    the same property the dropout mask already has. Padding is excluded by
    id 0, so ``bias`` is redundant (and must be None) on this path.

    ``dropout_rate > 0`` applies attention-probability dropout *inside* the
    kernel using the TPU hardware PRNG, seeded from ``dropout_rng`` — the
    [B, H, S, S] mask never exists in HBM and the backward regenerates it
    from the same seed. Requires a real TPU (no interpret-mode lowering).

    ``causal`` (static) masks position q from the positions after it and
    skips the tiles above the diagonal (module docstring). ``window``
    (static, with ``causal``) also masks it from the positions ``window`` or
    more before it and skips the tiles below the band; a window that reaches
    the row's start everywhere is the causal kernel itself.
    """
    batch, seq, heads, depth = q.shape
    scale = 1.0 / float(depth) ** 0.5
    if window is not None:
        if not causal or window < 1 or sequence_ids is not None:
            raise ValueError(
                "flash_attention: a window is a positive width on the causal "
                "mask of unpacked rows")
        window = int(window) if window < seq else None

    def to3(t):
        return t.transpose(0, 2, 1, 3).reshape(batch * heads, seq,
                                               t.shape[-1])

    segmented = sequence_ids is not None
    if segmented and bias is not None:
        raise ValueError(
            "flash_attention: pass either bias (padded batches) or "
            "sequence_ids (packed batches), not both — packed padding is "
            "already encoded as sequence id 0")
    if segmented:
        seg3 = jnp.repeat(
            sequence_ids.astype(jnp.float32), heads, axis=0)[:, None, :]
    else:
        seg3 = jnp.zeros((batch * heads, 1, seq), jnp.float32)
    if bias is None:
        bias3 = jnp.zeros((batch * heads, 1, seq), jnp.float32)
    else:
        key_bias = bias.reshape(batch, -1)[:, -seq:]  # [B, S]
        bias3 = jnp.repeat(key_bias.astype(jnp.float32), heads, axis=0)[:, None, :]
    if dropout_rate > 0.0:
        if dropout_rng is None:
            raise ValueError("dropout_rate > 0 requires dropout_rng")
        # Derive a scalar seed from the key's raw data — no PRNG computation,
        # just bits; tile indices decorrelate the per-tile streams. A
        # position-dependent multiply-xor hash, NOT a plain xor-fold:
        # threefry keys are [0, n] (first word constant) and rbg keys are two
        # duplicated halves [t0, t1, t0, t1] (xor-fold would cancel to 0 for
        # EVERY rbg key — the training default).
        data = jax.random.key_data(dropout_rng).ravel().astype(jnp.uint32)
        seed = jnp.uint32(0)
        for idx in range(data.shape[0]):  # static length, unrolls in trace
            seed = (seed * jnp.uint32(0x9E3779B1)
                    + jnp.uint32(2 * idx + 1)) ^ data[idx]
        seed = seed.astype(jnp.int32)[None]
    else:
        seed = jnp.zeros((1,), jnp.int32)
    out3 = _flash(to3(q), to3(k), to3(v), bias3, seg3, seed, scale,
                  float(dropout_rate), segmented, bool(causal), window, label)
    return out3.reshape(batch, heads, seq, v.shape[-1]).transpose(0, 2, 1, 3)
