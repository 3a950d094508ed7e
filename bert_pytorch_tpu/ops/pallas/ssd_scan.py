"""The Mamba-2 chunked scan (the SSD form, ``ops/ssm.py``) as two Pallas
kernels, ``ssd_scan_fwd`` and ``ssd_scan_bwd``.

Per head with state [P, N], over chunks of Q positions (``cum`` the log
decays ``dt A`` summed inside the chunk, ``xdt = dt x``):

    y_i   = sum_{j <= i} (C_i . B_j) exp(cum_i - cum_j) xdt_j
            + exp(cum_i) C_i state^T + D x_i
    state <- exp(cum_Q) state + sum_j exp(cum_Q - cum_j) xdt_j (x) B_j

In XLA every factor of that is a tensor in HBM: the [Q, Q] decay matrix of
every head and chunk (67 M elements a layer at 8192 x 64 heads, three times
over), the state added and the state before every chunk, and the transposes
that put heads before positions. Here a chunk's decay matrix and weights live
in VMEM between the two products that make and use them, and the running
state is a float32 scratch that never leaves the chip but once a chunk, for
the backward.

* **Layouts are the neighbours' own**: x and y [B, S, H * P] and B, C [B, S,
  G * N] as the projection and the convolution write them and the gated norm
  reads them, positions on sublanes; no transpose on either side. The
  per-head numbers (dt and ``cum``, float32, [B, S, H]: 2 MB where x is 67)
  come with the heads on 128 lanes, and ``cum`` a second time with the
  heads on sublanes ([B, H, S]), which is the decay matrix's column index;
  ``ops/ssm.py`` makes them (and the cumulative sum) in XLA.
* **grid (batch, chunks)**, the chunks in order (the backward from the last
  to the first); a step holds every head's block of the chunk. Inside, a
  ``lax.fori_loop`` over the groups (``cb = C B^T`` once a group) and one over
  the group's lane tiles, unrolled (four tiles a group at the published
  widths; the jaxpr stays one body): the tiles share nothing but ``cb``, and
  side by side they fill the issue slots that one tile's chain of dependent
  steps leaves empty (0.54 against 1.09 ms a forward call and 1.39 against
  1.98 a backward, on the chip: PERF.md 6, PR 37).
* **A tile** is 128 lanes of x, one head of 128 or two of 64, cut from the
  block by a dynamic lane offset. A per-head number is spread over its head's
  lanes by a lane gather (``_per_lane``); the heads of a tile share the
  products with C and B (the state's read and its update, full tiles on the
  MXU) and take their own decay matrix for the product inside the chunk (the
  other head's lanes zeroed). The backward puts a head's lanes back on the
  head's lane of [Q, 128] with an exact (``HIGHEST``) product by a 0/1 matrix.
* **State** [N, H * P] float32 in scratch (states on sublanes): the forward
  writes it at each chunk's start ([B, S / Q, N, H * P]: what ``before`` is in
  the XLA form); the backward reads it there and carries the state's
  cotangent in scratch the same way.
* **Precision** is the XLA form's: ``cum``, the exponentials and the state
  float32; matmul operands in the activations' dtype (weights, ``xdt``,
  ``xdt`` times the decay to the chunk's end, the state as read) with
  float32 accumulation; the cotangents of B and C are summed in float32 over
  a group's heads and both of their uses before they are cast.

``fits`` says which shapes the kernels take; ``ops/ssm.py`` runs the others
in XLA.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bert_pytorch_tpu.ops.pallas import common
from bert_pytorch_tpu.utils import trace_parts

LANES = 128
SUBLANES = 8
# the backward at 128 x 4096 bfloat16 with states of 128: blocks twice over
# ~12 MB, scratch 2 MB, a tile's temporaries (the default limit is 16 MB)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
MAX_WIDTH = 8192  # H * P: a step's x block [chunk, H * P] stays ~MBs

_ROWS, _LANES_OF_BOTH = (((0,), (0,)), ((), ())), (((1,), (1,)), ((), ()))


def fits(x_shape: tuple, b_shape: tuple, chunk: int) -> bool:
    """Whether the kernels take x [B, S, H, P] with b, c [B, S, G, N] in
    chunks of ``chunk`` (S a multiple of it): chunks and states of whole lane
    tiles, a head half a lane tile or a whole one, a group of whole tiles."""
    heads, hdim = x_shape[2:]
    groups, states = b_shape[2:]
    return (chunk in (LANES, 2 * LANES) and states in (LANES, 2 * LANES)
            and hdim in (LANES // 2, LANES) and heads <= LANES
            and heads * hdim <= MAX_WIDTH and heads % groups == 0
            and (heads // groups) % (LANES // hdim) == 0)


def _per_lane(numbers, first, hdim):
    """numbers [rows, 128], a number a head on the lanes -> [rows, 128]: on
    lane l the number of head ``first + l // hdim`` (a tile's heads, each
    over its own lanes)."""
    head = first + jax.lax.broadcasted_iota(
        jnp.int32, numbers.shape, 1) // hdim
    return jnp.take_along_axis(numbers, head, axis=1,
                               mode="promise_in_bounds")


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _tile(refs, g, t, *, chunk, per, hdim):
    """What both passes make of lane tile ``t`` of group ``g`` again: its
    lanes, first head, x, and the per-head numbers over the lanes."""
    x_ref, dt_ref, cum_ref = refs
    tiles = per * hdim // LANES
    index = g * tiles + t
    first = index * (LANES // hdim)
    lanes = pl.ds(pl.multiple_of(index * LANES, LANES), LANES)
    x32 = x_ref[0, :, lanes].astype(jnp.float32)
    dtw = _per_lane(dt_ref[0], first, hdim)
    cumw = _per_lane(cum_ref[0], first, hdim)
    return lanes, first, x32, dtw, cumw, cumw[chunk - 1:chunk, :]


def _decay(cumw, cumrow_ref, first, k, hdim, lower):
    """Head k of the tile: exp(cum_i - cum_j) for j <= i, else 0. [Q, Q]"""
    cum_i = cumw[:, k * hdim:k * hdim + 1]
    cum_j = cumrow_ref[0, pl.ds(first + k, 1), :]
    return jnp.exp(jnp.where(lower, cum_i - cum_j, -jnp.inf))


def _lower(chunk):
    return (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))


def _head_lanes(t, k, hdim):
    """The tile with the lanes of its other heads zeroed."""
    if hdim == LANES:
        return t
    head = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1) // hdim
    return jnp.where(head == k, t, jnp.zeros_like(t))


def _fwd_kernel(x_ref, dt_ref, cum_ref, cumrow_ref, b_ref, c_ref, d_ref,
                y_ref, start_ref, state_scr, *, chunk, groups, per, hdim,
                states):
    # x_ref, y_ref [1, Q, H * P]; dt_ref, cum_ref [1, Q, 128] float32 (heads
    # on lanes); cumrow_ref [1, H8, Q]; b_ref, c_ref [1, Q, G * N]; d_ref [8,
    # 128] (D on lanes, every row); start_ref [1, 1, N, H * P]; state_scr [N,
    # H * P] float32
    @pl.when(pl.program_id(1) == 0)
    def _():
        state_scr[...] = jnp.zeros(state_scr.shape, jnp.float32)

    dtype = x_ref.dtype
    lower = _lower(chunk)

    def group(g, _):
        of_group = pl.ds(pl.multiple_of(g * states, LANES), states)
        bg, cg = b_ref[0, :, of_group], c_ref[0, :, of_group]
        cb = _dot(cg, bg, _LANES_OF_BOTH)                       # [Q, Q]

        def tile(t, _):
            lanes, first, x32, dtw, cumw, last = _tile(
                (x_ref, dt_ref, cum_ref), g, t, chunk=chunk, per=per,
                hdim=hdim)
            xdt = x32 * dtw
            xdt_c = xdt.astype(dtype)
            state = state_scr[:, lanes]                          # [N, 128]
            start_ref[0, 0, :, lanes] = state
            y = (jnp.exp(cumw) * _dot(cg, state.astype(dtype))
                 + _per_lane(d_ref[...], first, hdim)[:1] * x32)
            for k in range(LANES // hdim):
                weights = cb * _decay(cumw, cumrow_ref, first, k, hdim, lower)
                y = y + _dot(weights.astype(dtype),
                             _head_lanes(xdt_c, k, hdim))
            y_ref[0, :, lanes] = y.astype(dtype)
            to_end = (xdt * jnp.exp(last - cumw)).astype(dtype)
            state_scr[:, lanes] = (state * jnp.exp(last)
                                   + _dot(bg, to_end, _ROWS))
            return 0

        jax.lax.fori_loop(0, per * hdim // LANES, tile, 0, unroll=True)
        return 0

    jax.lax.fori_loop(0, groups, group, 0)


def _bwd_kernel(x_ref, dt_ref, cum_ref, cumrow_ref, b_ref, c_ref, d_ref,
                start_ref, dy_ref, dx_ref, ddt_ref, dcum_ref, dcumrow_ref,
                db_ref, dc_ref, dd_ref, dstate_scr, *, chunk, groups, per,
                hdim, states):
    # as _fwd_kernel; dy_ref, dx_ref [1, Q, H * P]; ddt_ref, dcum_ref [1, Q,
    # 128] and dcumrow_ref [1, H8, Q] float32 (the cotangent of ``cum`` in the
    # two forms it came in: the caller adds them); db_ref, dc_ref [1, Q, G *
    # N]; dd_ref [1, 1, H * P] float32, revisited over the chunks (D's
    # cotangent lane by lane); dstate_scr [N, H * P]: the cotangent of the
    # state AFTER this chunk, carried from the chunk after it
    @pl.when(pl.program_id(1) == 0)  # the LAST chunk: the walk starts there
    def _():
        dstate_scr[...] = jnp.zeros(dstate_scr.shape, jnp.float32)
        dd_ref[...] = jnp.zeros(dd_ref.shape, jnp.float32)

    ddt_ref[...] = jnp.zeros(ddt_ref.shape, jnp.float32)
    dcum_ref[...] = jnp.zeros(dcum_ref.shape, jnp.float32)
    dcumrow_ref[...] = jnp.zeros(dcumrow_ref.shape, jnp.float32)
    dtype = x_ref.dtype
    lower = _lower(chunk)
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, LANES), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, LANES), 0)
    of_lane = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    head = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    exact = jax.lax.Precision.HIGHEST

    def group(g, _):
        of_group = pl.ds(pl.multiple_of(g * states, LANES), states)
        bg, cg = b_ref[0, :, of_group], c_ref[0, :, of_group]
        cb = _dot(cg, bg, _LANES_OF_BOTH)

        def tile(t, carry):
            dcb, db, dc = carry
            lanes, first, x32, dtw, cumw, last = _tile(
                (x_ref, dt_ref, cum_ref), g, t, chunk=chunk, per=per,
                hdim=hdim)
            xdt = x32 * dtw
            xdt_c = xdt.astype(dtype)
            dy = dy_ref[0, :, lanes]
            dy32 = dy.astype(jnp.float32)
            decay_in, decay_out = jnp.exp(cumw), jnp.exp(last - cumw)
            whole = jnp.exp(last)
            start = start_ref[0, 0, :, lanes]
            start_c = start.astype(dtype)
            dstate = dstate_scr[:, lanes]
            dstate_c = dstate.astype(dtype)
            to_end = xdt * decay_out
            to_end_c = to_end.astype(dtype)
            # the carried state's part of y, and the state handed back
            from_state = decay_in * _dot(cg, start_c)            # [Q, 128]
            dread = (decay_in * dy32).astype(dtype)
            dc = dc + _dot(dread, start_c, _LANES_OF_BOTH)
            dstate_scr[:, lanes] = whole * dstate + _dot(cg, dread, _ROWS)
            # what the chunk adds to the state
            dto_end = _dot(bg, dstate_c)                         # [Q, 128]
            db = db + _dot(to_end_c, dstate_c, _LANES_OF_BOTH)
            dlog_out = dto_end * to_end      # cotangent of (cum_Q - cum_i)
            dxdt = dto_end * decay_out
            dcum = dy32 * from_state - dlog_out
            dlast = (jnp.sum(dlog_out, axis=0, keepdims=True) + whole
                     * jnp.sum(dstate * start, axis=0, keepdims=True))
            # inside the chunk, a head at a time
            for k in range(LANES // hdim):
                decay = _decay(cumw, cumrow_ref, first, k, hdim, lower)
                weights = cb * decay
                dy_k = _head_lanes(dy, k, hdim)
                dxdt = dxdt + _dot(weights.astype(dtype), dy_k, _ROWS)
                dweights = _dot(dy_k, xdt_c, _LANES_OF_BOTH)      # [Q, Q]
                dcb = dcb + dweights * decay
                dgap = dweights * weights
                dcumrow_ref[0, pl.ds(first + k, 1), :] = -jnp.sum(
                    dgap, axis=0, keepdims=True)
                dcum = dcum + jnp.where(
                    lane == k * hdim, jnp.sum(dgap, axis=1, keepdims=True),
                    0.0)
            dcum = dcum + jnp.where(row == chunk - 1, dlast, 0.0)
            d_skip = _per_lane(d_ref[...], first, hdim)[:1]
            dx_ref[0, :, lanes] = (dxdt * dtw + d_skip * dy32).astype(dtype)
            dd_ref[0, :, lanes] += jnp.sum(dy32 * x32, axis=0, keepdims=True)
            # a head's lanes added up and put on the head's lane of 128
            to_head = (first + of_lane // hdim == head).astype(jnp.float32)
            ddt_ref[0] += jnp.dot(dxdt * x32, to_head, precision=exact,
                                  preferred_element_type=jnp.float32)
            dcum_ref[0] += jnp.dot(dcum, to_head, precision=exact,
                                   preferred_element_type=jnp.float32)
            return dcb, db, dc

        dcb, db, dc = jax.lax.fori_loop(
            0, per * hdim // LANES, tile,
            (jnp.zeros((chunk, chunk), jnp.float32),
             jnp.zeros((chunk, states), jnp.float32),
             jnp.zeros((chunk, states), jnp.float32)), unroll=True)
        dcb = dcb.astype(dtype)
        db_ref[0, :, of_group] = (db + _dot(dcb, cg, _ROWS)).astype(dtype)
        dc_ref[0, :, of_group] = (dc + _dot(dcb, bg)).astype(dtype)
        return 0

    jax.lax.fori_loop(0, groups, group, 0)


def _call(kernel, name, operands, results, args, heads, groups, chunk,
          backwards):
    """One pass as a ``pallas_call``: ``operands`` names the kind of each of
    ``args`` and ``results`` the (kind, dtype) of each result; a kind is an
    array's shape, its block a grid step and where that block lies (the
    backward walks the chunks from the last)."""
    batch, seq, width = args[0].shape
    states = args[4].shape[2] // groups
    chunks, rows = seq // chunk, -(-heads // SUBLANES) * SUBLANES
    at = (lambda c: chunks - 1 - c) if backwards else (lambda c: c)
    kinds = {
        "wide": ((batch, seq, width), (1, chunk, width),
                 lambda i, c: (i, at(c), 0)),
        "bc": ((batch, seq, groups * states), (1, chunk, groups * states),
               lambda i, c: (i, at(c), 0)),
        "heads": ((batch, seq, LANES), (1, chunk, LANES),
                  lambda i, c: (i, at(c), 0)),
        "rows": ((batch, rows, seq), (1, rows, chunk),
                 lambda i, c: (i, 0, at(c))),
        "d": ((SUBLANES, LANES), (SUBLANES, LANES), lambda i, c: (0, 0)),
        "start": ((batch, chunks, states, width), (1, 1, states, width),
                  lambda i, c: (i, at(c), 0, 0)),
        "dd": ((batch, 1, width), (1, 1, width), lambda i, c: (i, 0, 0)),
    }
    spec = lambda kind: pl.BlockSpec(*kinds[kind][1:])
    with trace_parts.kernel_build(name):
        return pl.pallas_call(
            partial(kernel, chunk=chunk, groups=groups, per=heads // groups,
                    hdim=width // heads, states=states),
            grid=(batch, chunks),
            in_specs=[spec(kind) for kind in operands],
            out_specs=[spec(kind) for kind, _ in results],
            out_shape=[jax.ShapeDtypeStruct(kinds[kind][0], dtype)
                       for kind, dtype in results],
            scratch_shapes=[pltpu.VMEM((states, width), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            name=name, interpret=common.interpret_mode(),
        )(*args)


_OPERANDS = ("wide", "heads", "heads", "rows", "bc", "bc", "d")


def ssd_forward(x, dt, cum, cum_rows, b, c, d, heads: int, groups: int,
                chunk: int):
    """x [B, S, H * P]; dt, cum [B, S, 128] float32 (head h on lane h); cum_rows
    [B, H8, S] (cum again, head h on row h, H8 = H rounded up to 8); b, c [B,
    S, G * N] in x's dtype; d [8, 128] float32 (D on the lanes of every row); S
    a multiple of ``chunk`` -> (y [B, S, H * P] in x's dtype, the state at
    every chunk's start [B, S / chunk, N, H * P] float32)."""
    return _call(_fwd_kernel, "ssd_scan_fwd", _OPERANDS,
                 (("wide", x.dtype), ("start", jnp.float32)),
                 (x, dt, cum, cum_rows, b, c, d), heads, groups, chunk,
                 backwards=False)


def ssd_backward(x, dt, cum, cum_rows, b, c, d, starts, dy, heads: int,
                 groups: int, chunk: int):
    """The cotangents of :func:`ssd_forward`'s y under ``dy``: (dx; ddt and
    dcum [B, S, 128]; dcum_rows [B, H8, S], the part of ``cum``'s cotangent
    that belongs to ``cum_rows``; db, dc; dd [B, 1, H * P] float32, lane by
    lane), dt's being the part through ``dt x`` alone."""
    f32 = jnp.float32
    return _call(
        _bwd_kernel, "ssd_scan_bwd", _OPERANDS + ("start", "wide"),
        (("wide", x.dtype), ("heads", f32), ("heads", f32), ("rows", f32),
         ("bc", b.dtype), ("bc", c.dtype), ("dd", f32)),
        (x, dt, cum, cum_rows, b, c, d, starts, dy), heads, groups, chunk,
        backwards=True)
