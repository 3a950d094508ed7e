"""The Mamba-1 selective scan as Pallas kernels (forward AND backward).

The recurrence (Gu & Dao 2023, arXiv:2312.00752), per channel d of D and
state n of N, everything float32:

    h_t[n, d] = exp(dt_t[d] A[n, d]) h_{t-1}[n, d] + dt_t[d] u_t[d] B_t[n]
    y_t[d]    = sum_n h_t[n, d] C_t[n]

``A`` holds a decay for every (state, channel) pair, so a chunk is no masked
matmul (``ops/ssm.py``'s Mamba-2 form has one scalar a head): it is
elementwise work with a dependence along t, and its state over a row, [S, N,
D] float32, is never held: 2.7 GB at 8192 x 16 x 5120. Both kernels keep the
state in VMEM and run the time loop inside:

* grid (batch, S / chunk, D / block); the channel blocks are the innermost
  axis, the chunks run in order, and a scratch [D / block, N, block] carries
  each channel block's state from chunk to chunk. The state is laid out [N,
  block]: states on sublanes, channels on lanes.
* ``B_t`` and ``C_t`` are N numbers a position that every channel shares. The
  kernels take them already broadcast over one tile of 128 lanes, [S, N, 128]
  (``selective_scan`` makes that in XLA, 67 MB a tensor at 8192 x 16 in
  float32), so a position's [N, 128] tile multiplies every 128-lane tile of
  the state with no move across lanes; and the backward kernel returns the
  cotangent in the same form, summed over the channel tiles it saw (the
  output block is revisited over the innermost axis), so that the sum over
  the 128 lanes is the transpose of XLA's broadcast.
* the forward writes y and the state AT THE START of every chunk ([B, S /
  chunk, N, D] float32: 21 MB at chunk 128). The backward walks the chunks
  from the last to the first: it makes a chunk's states again from its start
  (held in VMEM, [chunk, N, block]), then runs the recurrence of the
  cotangent backwards through them, ``dh_{t-1} = exp(dt_t A) dh_t``, carrying
  ``dh`` from chunk to chunk in scratch as the forward carries ``h``. It
  writes du, ddt, the broadcast forms of dB and dC, and one [N, block] piece
  of dA a chunk (summed over chunks outside: 21 MB).

Positions are taken 8 at a time (one sublane tile of the [chunk, block]
operands), so loads and stores are whole tiles. A length that is no multiple
of the chunk is padded by the caller with dt = 0: decay 1 and no input, which
leaves the state and the positions before it untouched.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bert_pytorch_tpu.ops.pallas.common import interpret_mode
from bert_pytorch_tpu.utils import trace_parts

LANES = 128
ROWS = 8  # positions a trip of the time loop takes: one float32 sublane tile
# the backward kernel's VMEM at chunk 128 x block 512: the chunk's states 4 MB,
# the operand and result blocks twice over ~10 MB (the default limit is 16 MB)
VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def pick_block(channels: int) -> int:
    """Channels a program holds: the widest of 512, 256, 128 that divides."""
    for block in (512, 256, 128):
        if channels % block == 0:
            return block
    raise ValueError(
        f"the selective scan's kernels take channels in tiles of {LANES}: "
        f"{channels}")


def _over_lanes(tile, block):
    """[N, 128] -> [N, block]: the tile beside itself."""
    return tile if block == LANES else jnp.concatenate(
        [tile] * (block // LANES), axis=1)


def _lane_tiles_sum(x):
    """[N, block] -> [N, 128]: the 128-lane tiles added up."""
    total = x[:, :LANES]
    for k in range(1, x.shape[1] // LANES):
        total = total + x[:, k * LANES:(k + 1) * LANES]
    return total


def _fwd_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, start_ref, h_scr,
                *, chunk):
    # u_ref, dt_ref, y_ref [1, chunk, block]; b_ref, c_ref [1, chunk, N, 128];
    # a_ref [N, block]; start_ref [1, 1, N, block]; h_scr [D / block, N, block]
    j = pl.program_id(2)
    block = u_ref.shape[2]

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_scr[j] = jnp.zeros(h_scr.shape[1:], jnp.float32)

    h0 = h_scr[j]
    start_ref[0, 0] = h0
    a = a_ref[...]

    def trip(g, h):
        t0 = pl.multiple_of(g * ROWS, ROWS)
        u8 = u_ref[0, pl.ds(t0, ROWS), :]
        dt8 = dt_ref[0, pl.ds(t0, ROWS), :]
        rows = []
        for i in range(ROWS):
            dt_t, u_t = dt8[i:i + 1, :], u8[i:i + 1, :]
            b_t = _over_lanes(b_ref[0, t0 + i], block)
            c_t = _over_lanes(c_ref[0, t0 + i], block)
            h = jnp.exp(dt_t * a) * h + (dt_t * u_t) * b_t
            rows.append(jnp.sum(h * c_t, axis=0, keepdims=True))
        y_ref[0, pl.ds(t0, ROWS), :] = jnp.concatenate(rows, axis=0)
        return h

    h_scr[j] = jax.lax.fori_loop(0, chunk // ROWS, trip, h0)


def _bwd_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, start_ref, dy_ref,
                du_ref, ddt_ref, db_ref, dc_ref, da_ref, dh_scr, before_scr,
                *, chunk):
    # as _fwd_kernel; dy_ref, du_ref, ddt_ref [1, chunk, block]; db_ref, dc_ref
    # [1, chunk, N, 128], revisited over j; da_ref [1, 1, N, block]; dh_scr
    # [D / block, N, block]; before_scr [chunk, N, block]: h_{t-1} for every t
    j = pl.program_id(2)
    block = u_ref.shape[2]
    trips = chunk // ROWS

    @pl.when(pl.program_id(1) == 0)  # the LAST chunk: the walk starts there
    def _():
        dh_scr[j] = jnp.zeros(dh_scr.shape[1:], jnp.float32)

    @pl.when(j == 0)
    def _():
        db_ref[...] = jnp.zeros(db_ref.shape, jnp.float32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, jnp.float32)

    a = a_ref[...]

    def again(g, h):
        t0 = pl.multiple_of(g * ROWS, ROWS)
        u8 = u_ref[0, pl.ds(t0, ROWS), :]
        dt8 = dt_ref[0, pl.ds(t0, ROWS), :]
        for i in range(ROWS):
            dt_t, u_t = dt8[i:i + 1, :], u8[i:i + 1, :]
            before_scr[t0 + i] = h
            h = jnp.exp(dt_t * a) * h + (dt_t * u_t) * _over_lanes(
                b_ref[0, t0 + i], block)
        return h

    jax.lax.fori_loop(0, trips, again, start_ref[0, 0])

    def back(r, carry):
        dh, da = carry
        t0 = pl.multiple_of((trips - 1 - r) * ROWS, ROWS)
        u8 = u_ref[0, pl.ds(t0, ROWS), :]
        dt8 = dt_ref[0, pl.ds(t0, ROWS), :]
        dy8 = dy_ref[0, pl.ds(t0, ROWS), :]
        du_rows, ddt_rows = [None] * ROWS, [None] * ROWS
        for i in reversed(range(ROWS)):
            dt_t, u_t, dy_t = dt8[i:i + 1, :], u8[i:i + 1, :], dy8[i:i + 1, :]
            b_t = _over_lanes(b_ref[0, t0 + i], block)
            c_t = _over_lanes(c_ref[0, t0 + i], block)
            before = before_scr[t0 + i]
            decay = jnp.exp(dt_t * a)
            dtu = dt_t * u_t
            h = decay * before + dtu * b_t
            dh = dh + c_t * dy_t
            dc_ref[0, t0 + i] += _lane_tiles_sum(h * dy_t)
            db_ref[0, t0 + i] += _lane_tiles_sum(dh * dtu)
            d_dtu = jnp.sum(dh * b_t, axis=0, keepdims=True)
            d_log = dh * before * decay          # cotangent of dt_t A
            ddt_rows[i] = u_t * d_dtu + jnp.sum(d_log * a, axis=0,
                                                keepdims=True)
            du_rows[i] = dt_t * d_dtu
            da = da + d_log * dt_t
            dh = decay * dh
        du_ref[0, pl.ds(t0, ROWS), :] = jnp.concatenate(du_rows, axis=0)
        ddt_ref[0, pl.ds(t0, ROWS), :] = jnp.concatenate(ddt_rows, axis=0)
        return dh, da

    dh, da = jax.lax.fori_loop(
        0, trips, back, (dh_scr[j], jnp.zeros(a.shape, jnp.float32)))
    dh_scr[j] = dh
    da_ref[0, 0] = da


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _specs(chunk, block, states, chunk_of):
    """BlockSpecs of (a [., chunk, block] operand, a [., chunk, N, 128] one, A,
    a [., 1, N, block] piece a chunk); ``chunk_of`` maps the grid's second
    index to the chunk (the backward walks them from the last)."""
    return (
        pl.BlockSpec((1, chunk, block), lambda b, c, j: (b, chunk_of(c), j)),
        pl.BlockSpec((1, chunk, states, LANES),
                     lambda b, c, j: (b, chunk_of(c), 0, 0)),
        pl.BlockSpec((states, block), lambda b, c, j: (0, j)),
        pl.BlockSpec((1, 1, states, block),
                     lambda b, c, j: (b, chunk_of(c), 0, j)))


def scan_forward(u, dt, b_wide, c_wide, a_t, chunk: int):
    """u, dt [B, S, D] float32 (S a multiple of ``chunk``, D of 128); b_wide,
    c_wide [B, S, N, 128]; a_t [N, D] -> (y [B, S, D], the state at the start
    of every chunk [B, S / chunk, N, D])."""
    batch, seq, channels = u.shape
    states, block = a_t.shape[0], pick_block(channels)
    chunks, blocks = seq // chunk, channels // block
    row, wide, a_spec, piece = _specs(chunk, block, states, lambda c: c)
    with trace_parts.kernel_build("selective_scan_fwd"):
        return pl.pallas_call(
            partial(_fwd_kernel, chunk=chunk),
            grid=(batch, chunks, blocks),
            in_specs=[row, row, wide, wide, a_spec],
            out_specs=[row, piece],
            out_shape=[
                jax.ShapeDtypeStruct((batch, seq, channels), jnp.float32),
                jax.ShapeDtypeStruct((batch, chunks, states, channels),
                                     jnp.float32)],
            scratch_shapes=[pltpu.VMEM((blocks, states, block), jnp.float32)],
            compiler_params=_params(),
            name="selective_scan_fwd",
            interpret=interpret_mode(),
        )(u, dt, b_wide, c_wide, a_t)


def scan_backward(u, dt, b_wide, c_wide, a_t, starts, dy, chunk: int):
    """The cotangents (du, ddt [B, S, D]; db_wide, dc_wide [B, S, N, 128],
    each lane a partial sum over the channels; da_t [B, S / chunk, N, D], a
    piece a chunk) of :func:`scan_forward`'s y under ``dy``."""
    batch, seq, channels = u.shape
    states, block = a_t.shape[0], pick_block(channels)
    chunks, blocks = seq // chunk, channels // block
    row, wide, a_spec, piece = _specs(
        chunk, block, states, lambda c: chunks - 1 - c)
    with trace_parts.kernel_build("selective_scan_bwd"):
        return pl.pallas_call(
            partial(_bwd_kernel, chunk=chunk),
            grid=(batch, chunks, blocks),
            in_specs=[row, row, wide, wide, a_spec, piece, row],
            out_specs=[row, row, wide, wide, piece],
            out_shape=[
                jax.ShapeDtypeStruct((batch, seq, channels), jnp.float32),
                jax.ShapeDtypeStruct((batch, seq, channels), jnp.float32),
                jax.ShapeDtypeStruct((batch, seq, states, LANES), jnp.float32),
                jax.ShapeDtypeStruct((batch, seq, states, LANES), jnp.float32),
                jax.ShapeDtypeStruct((batch, chunks, states, channels),
                                     jnp.float32)],
            scratch_shapes=[pltpu.VMEM((blocks, states, block), jnp.float32),
                            pltpu.VMEM((chunk, states, block), jnp.float32)],
            compiler_params=_params(),
            name="selective_scan_bwd",
            interpret=interpret_mode(),
        )(u, dt, b_wide, c_wide, a_t, starts, dy)
