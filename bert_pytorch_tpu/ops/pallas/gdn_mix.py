"""The delta-rule mixer's element-wise stages as one pass over HBM each: two
Pallas kernel pairs, ``gdn_mix_fwd`` / ``gdn_mix_bwd`` before the rule and
``gated_norm_fwd`` / ``gated_norm_bwd`` after it (``ops/gdn_mix.py`` is their
plain-JAX face and holds the XLA form of both).

In XLA each stage is several passes: the convolution's shifted products, the
silu, the float32 copies the unit length is taken of, the scale and the cast
each read and write [B, S, 8192] again, and the neighbours on both sides are
kernels or matmuls, whose operands have to lie in HBM, so nothing of it fuses
into them (PERF.md 6, PR 42). Here a block of positions is read once and
written once, in the layouts the neighbours already use: the in-projection's
column blocks q, k [B, S, Hk * 128] and v, z [B, S, Hv * 128] as the matmuls
write them on one side, ``delta_rule_fwd``'s flat operands on the other;
positions on sublanes, a head one lane tile. No transpose, no lane slice.

* **Before the rule** (``gdn_mix_forward`` / ``gdn_mix_backward``): q, k and
  v are three operands with their own column blocks of the taps [K, width]
  (K <= 4). Grid (rows of the batch, blocks of positions); a step walks the
  heads of its block, 128 lanes at a time: the causal K-tap sum and silu in
  float32; for q and k the head's sum of squares, ``rsqrt`` and (q) the
  ``1 / sqrt(d)`` scale; ONE cast at the store. The K - 1 rows before a block
  are the last rows of the sixteen before it, read through a second index map
  of the same operand (zeros at a row's first block). The delayed copies of a
  block are sublane rotations of [those rows; the block].
  The backward keeps nothing but the operands: it makes the pre-activation
  again, walks a row's blocks from the LAST (the cotangent of a position's
  pre-activation reaches the K - 1 positions before it, so a block needs the
  first rows of the block after: carried in scratch, zeros at a row's last
  block) and sums the taps' gradient in a float32 block that stays in VMEM
  over the whole grid.
* **After the rule** (``gated_norm_forward`` / ``gated_norm_backward``):
  ``rmsnorm(o) * scale * silu(z)`` a head of 128 lanes in float32, one write;
  the backward reads o, z and the cotangent once, writes do and dz and sums
  the scale's gradient in a float32 block likewise. The same skeleton
  without the rows before.

``fits`` says which shapes the kernels take; ``ops/gdn_mix.py`` runs the
others in XLA.
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
ROW_TILE = 16   # a bfloat16 sublane tile: the block the rows before come in
HALO = 8        # a float32 sublane tile: the rows kept of it (>= taps - 1)
MAX_TAPS = 4
# positions a grid step: the backward holds six such blocks of the widest
# operand twice over (the pipeline's two buffers), 24 MB at 256 x 4096
BLOCK_ROWS = (256, 128, 64, 32, ROW_TILE)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def fits(shape: tuple, taps: int = 1) -> bool:
    """Whether the kernels take an operand [B, S, heads, head_dim] (under
    ``taps`` taps, before the rule): heads of one lane tile, positions in
    whole blocks, no more taps than a float32 tile has rows to lend."""
    _, seq, _, head_dim = shape
    return (head_dim == LANES and seq % ROW_TILE == 0
            and 1 <= taps <= MAX_TAPS)


def block_rows(seq: int) -> int:
    """Positions a grid step: the most of ``BLOCK_ROWS`` that divide seq."""
    return common.pick_block(seq, BLOCK_ROWS)


def _each_head(heads: int, body) -> None:
    """``body(lanes)`` for every head's lane tile of a block: a head's whole
    [rows, 128] at a time, the rows a STATIC range (a loop over chunks of
    rows inside the loop over heads, both offsets dynamic, ran at a third of
    the speed on the chip: PERF.md 6, PR 43)."""
    def step(head, carry):
        body(pl.ds(pl.multiple_of(head * LANES, LANES), LANES))
        return carry

    jax.lax.fori_loop(0, heads, step, None)


def _rows_before(before_ref, lanes, first):
    """The HALO rows before a block, float32; zeros at a row's first."""
    before = before_ref[0, :, lanes].astype(jnp.float32)[ROW_TILE - HALO:]
    return jnp.where(first, 0.0, before)


def _delayed(x, before, taps: int):
    """x [R, 128] float32 and the HALO rows before it -> [x_t, x_{t-1}, ...,
    x_{t-taps+1}], each [R, 128]."""
    rows_and_block = jnp.concatenate([before, x], axis=0)
    return [x] + [pltpu.roll(rows_and_block, delay, 0)[HALO:]
                  for delay in range(1, taps)]


def _tap_sum(shifted, w):
    """``sum_d shifted[d] * w[taps - 1 - d]``: w [taps, 128], its last row
    the tap of the position itself (``ops/ssm.py causal_depthwise_conv``)."""
    taps = len(shifted)
    total = shifted[0] * w[taps - 1:taps]
    for d in range(1, taps):
        total = total + shifted[d] * w[taps - 1 - d:taps - d]
    return total


def _lane_sum(t):
    return jnp.sum(t, axis=1, keepdims=True)


def _tiles_sum(t):
    """t [R, 128] -> [8, 128]: its float32 tiles added up (the rows' sum is
    the sum of this one's rows)."""
    return sum(t[HALO * i:HALO * (i + 1)] for i in range(t.shape[0] // HALO))


def _mix_fwd_kernel(*refs, unit_scales, epsilon):
    """refs: the raw q, k, v blocks; the sixteen rows before each; each one's
    taps; the three results. ``unit_scales``: per operand, None (convolution
    and silu alone) or the factor its unit-length heads are multiplied by."""
    first = pl.program_id(1) == 0
    for x_ref, before_ref, w_ref, out_ref, scale in zip(
            refs[0:3], refs[3:6], refs[6:9], refs[9:12], unit_scales):
        taps = w_ref.shape[0]

        def head(lanes, x_ref=x_ref, before_ref=before_ref, w_ref=w_ref,
                 out_ref=out_ref, scale=scale, taps=taps):
            x = x_ref[0, :, lanes].astype(jnp.float32)
            pre = _tap_sum(_delayed(
                x, _rows_before(before_ref, lanes, first), taps),
                w_ref[:, lanes])
            a = pre * jax.nn.sigmoid(pre)
            if scale is not None:
                a = a * (scale * jax.lax.rsqrt(_lane_sum(a * a) + epsilon))
            out_ref[0, :, lanes] = a.astype(out_ref.dtype)

        _each_head(x_ref.shape[2] // LANES, head)


def _mix_bwd_kernel(*refs, unit_scales, epsilon):
    """refs: the raw q, k, v blocks; the sixteen rows before each; each one's
    taps; the three cotangents; then the results: the raw operands'
    cotangents and the taps' ([taps, 8, width] float32: summed over the grid,
    the eight rows still to add up); then scratch: per operand the cotangent
    of the pre-activation on the first HALO rows of the block after. The grid
    walks a row's blocks from the last."""
    row, step = pl.program_id(0), pl.program_id(1)
    first = step == pl.num_programs(1) - 1   # the row's first block
    last = step == 0
    for (x_ref, before_ref, w_ref, dy_ref, dx_ref, dw_ref, after_ref,
         scale) in zip(refs[0:3], refs[3:6], refs[6:9], refs[9:12],
                       refs[12:15], refs[15:18], refs[18:21], unit_scales):
        taps, rows = w_ref.shape[0], x_ref.shape[1]

        @pl.when((row == 0) & last)
        def _(dw_ref=dw_ref):
            dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)

        def head(lanes, x_ref=x_ref, before_ref=before_ref, w_ref=w_ref,
                 dy_ref=dy_ref, dx_ref=dx_ref, dw_ref=dw_ref,
                 after_ref=after_ref, scale=scale, taps=taps, rows=rows):
            x = x_ref[0, :, lanes].astype(jnp.float32)
            dy = dy_ref[0, :, lanes].astype(jnp.float32)
            w = w_ref[:, lanes]
            shifted = _delayed(x, _rows_before(before_ref, lanes, first), taps)
            pre = _tap_sum(shifted, w)
            gate = jax.nn.sigmoid(pre)
            a = pre * gate
            if scale is None:
                da = dy
            else:  # y = scale a r, r = rsqrt(sum a^2 + epsilon)
                r = jax.lax.rsqrt(_lane_sum(a * a) + epsilon)
                da = (scale * r) * (dy - a * (r * r * _lane_sum(dy * a)))
            dpre = da * (gate * (1.0 + pre * (1.0 - gate)))
            for d in range(taps):
                dw_ref[taps - 1 - d, :, lanes] += _tiles_sum(dpre * shifted[d])
            # position t's operand reached the pre-activations of
            # t .. t + taps - 1
            block_and_rows = jnp.concatenate(
                [dpre, jnp.where(last, 0.0, after_ref[:, lanes])], axis=0)
            after_ref[:, lanes] = dpre[:HALO]
            ahead = [dpre] + [pltpu.roll(
                block_and_rows, rows + HALO - d, 0)[:rows]
                for d in range(1, taps)]
            dx_ref[0, :, lanes] = _tap_sum(ahead, w).astype(dx_ref.dtype)

        _each_head(x_ref.shape[2] // LANES, head)


def _mix_specs(arrays, taps, backwards: bool):
    """(grid, the BlockSpecs of a block, of the rows before it and of the
    taps) for operands [B, S, width], one triple an operand."""
    batch, seq, _ = arrays[0].shape
    rows = block_rows(seq)
    blocks, tiles = seq // rows, rows // ROW_TILE
    at = (lambda i: blocks - 1 - i) if backwards else (lambda i: i)
    block = [pl.BlockSpec((1, rows, t.shape[2]), lambda b, i: (b, at(i), 0))
             for t in arrays]
    before = [pl.BlockSpec(
        (1, ROW_TILE, t.shape[2]),
        lambda b, i: (b, jnp.maximum(at(i) * tiles - 1, 0), 0))
        for t in arrays]
    whole = [pl.BlockSpec(w.shape, lambda b, i: (0, 0)) for w in taps]
    return (batch, blocks), block, before, whole


@partial(jax.jit, static_argnames=("unit_scales", "epsilon"))
def gdn_mix_forward(q, k, v, taps_q, taps_k, taps_v, unit_scales, epsilon):
    """q, k [B, S, Hk * 128], v [B, S, Hv * 128] in one dtype (the
    in-projection's column blocks), each one's taps [K, width] float32 ->
    ``silu(conv(.))`` of each in that dtype, every head of 128 lanes of an
    operand whose ``unit_scales`` entry is a number brought to unit length
    (``epsilon`` under the root) and multiplied by it. S a multiple of 16.
    (jitted, as the backward: a model's layers then trace and lower each
    kernel once.)"""
    arrays, taps = (q, k, v), (taps_q, taps_k, taps_v)
    grid, block, before, whole = _mix_specs(arrays, taps, backwards=False)
    with trace_parts.kernel_build("gdn_mix_fwd"):
        return pl.pallas_call(
            partial(_mix_fwd_kernel, unit_scales=unit_scales, epsilon=epsilon),
            grid=grid, in_specs=block + before + whole, out_specs=block,
            out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in arrays],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            name="gdn_mix_fwd", interpret=common.interpret_mode(),
        )(*arrays, *arrays, *taps)


@partial(jax.jit, static_argnames=("unit_scales", "epsilon"))
def gdn_mix_backward(q, k, v, taps_q, taps_k, taps_v, dq, dk, dv,
                     unit_scales, epsilon):
    """The cotangents of :func:`gdn_mix_forward`'s operands under (dq, dk,
    dv): the raw q, k, v's in their dtype, the taps' float32."""
    arrays, taps = (q, k, v), (taps_q, taps_k, taps_v)
    grid, block, before, whole = _mix_specs(arrays, taps, backwards=True)
    sums = [(w.shape[0], HALO, w.shape[1]) for w in taps]
    with trace_parts.kernel_build("gdn_mix_bwd"):
        *raw, dtaps_q, dtaps_k, dtaps_v = pl.pallas_call(
            partial(_mix_bwd_kernel, unit_scales=unit_scales, epsilon=epsilon),
            grid=grid, in_specs=block + before + whole + block,
            out_specs=block + [pl.BlockSpec(shape, lambda b, i: (0, 0, 0))
                               for shape in sums],
            out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in arrays]
            + [jax.ShapeDtypeStruct(shape, jnp.float32) for shape in sums],
            scratch_shapes=[pltpu.VMEM((HALO, t.shape[2]), jnp.float32)
                            for t in arrays],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            name="gdn_mix_bwd", interpret=common.interpret_mode(),
        )(*arrays, *arrays, *taps, dq, dk, dv)
    return (*raw, *(jnp.sum(d, axis=1) for d in (dtaps_q, dtaps_k, dtaps_v)))


def _norm_fwd_kernel(o_ref, z_ref, scale_ref, out_ref, *, epsilon):
    scale = scale_ref[...]

    def head(lanes):
        o = o_ref[0, :, lanes].astype(jnp.float32)
        z = z_ref[0, :, lanes].astype(jnp.float32)
        r = jax.lax.rsqrt(_lane_sum(o * o) * (1.0 / LANES) + epsilon)
        out_ref[0, :, lanes] = (
            o * r * scale * (z * jax.nn.sigmoid(z))).astype(out_ref.dtype)

    _each_head(o_ref.shape[2] // LANES, head)


def _norm_bwd_kernel(o_ref, z_ref, scale_ref, dy_ref, do_ref, dz_ref,
                     dscale_ref, *, epsilon):
    """(dscale [8, 128]: summed over the grid, its rows still to add up)"""
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dscale_ref[...] = jnp.zeros(dscale_ref.shape, jnp.float32)

    scale = scale_ref[...]

    def head(lanes):
        o = o_ref[0, :, lanes].astype(jnp.float32)
        z = z_ref[0, :, lanes].astype(jnp.float32)
        dy = dy_ref[0, :, lanes].astype(jnp.float32)
        r = jax.lax.rsqrt(_lane_sum(o * o) * (1.0 / LANES) + epsilon)
        normed, gate = o * r, jax.nn.sigmoid(z)
        by_gate = dy * (z * gate)          # the cotangent of normed * scale
        dscale_ref[...] += _tiles_sum(by_gate * normed)
        dz_ref[0, :, lanes] = (dy * normed * scale * (
            gate * (1.0 + z * (1.0 - gate)))).astype(dz_ref.dtype)
        dnormed = by_gate * scale
        do_ref[0, :, lanes] = (r * (dnormed - normed * (_lane_sum(
            dnormed * normed) * (1.0 / LANES)))).astype(do_ref.dtype)

    _each_head(o_ref.shape[2] // LANES, head)


def _norm_call(kernel, name, arrays, scale, epsilon, backward: bool):
    """One pass over (o, z[, dy]) as a ``pallas_call``: one result like o, or
    (``backward``) two and the scale's [8, 128] sums."""
    batch, seq, width = arrays[0].shape
    rows = block_rows(seq)
    block = pl.BlockSpec((1, rows, width), lambda b, i: (b, i, 0))
    whole = pl.BlockSpec(scale.shape, lambda b, i: (0, 0))
    like_o = jax.ShapeDtypeStruct(arrays[0].shape, arrays[0].dtype)
    order = "arbitrary" if backward else "parallel"
    with trace_parts.kernel_build(name):
        return pl.pallas_call(
            partial(kernel, epsilon=epsilon),
            grid=(batch, seq // rows),
            in_specs=[block, block, whole] + [block] * (len(arrays) - 2),
            out_specs=[block, block, pl.BlockSpec(
                (HALO, LANES), lambda b, i: (0, 0))] if backward else [block],
            out_shape=[like_o, like_o, jax.ShapeDtypeStruct(
                (HALO, LANES), jnp.float32)] if backward else [like_o],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(order, order),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            name=name, interpret=common.interpret_mode(),
        )(arrays[0], arrays[1], scale, *arrays[2:])


@partial(jax.jit, static_argnames=("epsilon",))
def gated_norm_forward(o, z, scale, epsilon):
    """o, z [B, S, Hv * 128] in one dtype, scale [1, 128] float32 ->
    ``rmsnorm(o) * scale * silu(z)`` a head of 128 lanes, in o's dtype."""
    return _norm_call(_norm_fwd_kernel, "gated_norm_fwd", (o, z), scale,
                      epsilon, backward=False)[0]


@partial(jax.jit, static_argnames=("epsilon",))
def gated_norm_backward(o, z, scale, dy, epsilon):
    """(do, dz, dscale [1, 128] float32) of :func:`gated_norm_forward`."""
    do, dz, dscale = _norm_call(_norm_bwd_kernel, "gated_norm_bwd",
                                (o, z, dy), scale, epsilon, backward=True)
    return do, dz, jnp.sum(dscale, axis=0, keepdims=True)
