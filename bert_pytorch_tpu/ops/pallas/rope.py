"""The rotary turn as one Pallas kernel: a head read once and written once.

``rotary_turn(x, cos, sin, sign)`` over x [B, S, heads, head_dim] is
``ops/rope.py``'s rotation (pairs ``(i, i + rotary_dim / 2)`` of a head's
first ``rotary_dim = cos.shape[-1]`` dimensions, the rest unchanged) by the
tables' angle (``sign`` 1) or by its negative (``sign`` -1: the transpose,
which is the backward). XLA cannot do this in one pass on a TPU: a slice or a
concatenate along the lane dimension is never fused into its consumer, so
every formulation in jnp (``rotate_half``'s split and concatenate,
``jnp.roll``, a reshape and a reverse) materializes float32 pieces of the head
or changes its layout (the compiled HLO and a chip run, PR 35: ``jnp.roll``
with a custom backward takes what the split and the concatenate took).

* **No copy on either side.** The forward reads x as the projection wrote
  it, rows of [B * S, heads * head_dim], and writes [B, heads, S, head_dim],
  the layout the flash kernels take; it returns that array's transpose, [B, S,
  heads, head_dim] again, and the attention core's own transpose undoes it. The
  backward reads its cotangent as the flash kernels' backward wrote it and
  writes rows for the projection's backward. (With rows on both sides XLA put
  two layout copies of the head, each as long as the kernel, after every
  forward call and before every backward one: a chip run, PR 35.)
* grid over blocks of rows; a block holds every head of its rows, and the
  tables' block [rows, rotary_dim] float32 is read once for all of them;
* the tables are widened to the head in VMEM and the sine is signed there
  (``-sin`` on the first half of the pairs), once a block;
* each head is one [rows, head_dim] tile: bfloat16 in, float32 inside, the
  pair's partner fetched by a lane rotation (``pltpu.roll``: by ``+half``
  and, where the rotary dimensions are not the whole head, by ``-half`` with
  a select between the two), ``x * cos + partner * sin``, a select that
  keeps the dimensions beyond ``rotary_dim`` as they came, cast and stored.

``fits`` says which shapes the kernel takes on a TPU: a head of whole lane
tiles and rows in whole sublane tiles; ``ops/rope.py`` turns the others in
plain jnp.
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
ROW_TILE = 16  # a bfloat16 sublane tile
# x's block and the result's, each twice over (the pipeline's two buffers)
BLOCK_BYTES = 4 * 1024 * 1024
VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def fits(x_shape: tuple) -> bool:
    """Whether the kernel takes x [B, S, heads, head_dim]: heads of whole lane
    tiles, rows in whole sublane tiles."""
    _, seq, _, head_dim = x_shape
    return head_dim % LANES == 0 and seq % ROW_TILE == 0


def pick_rows(seq: int, row_bytes: int) -> int:
    """Rows a block: the most that divide ``seq`` and keep a block of x
    within ``BLOCK_BYTES`` (at least one sublane tile)."""
    sizes = [rows for rows in (1024, 512, 256, 128, 64, 32)
             if rows * row_bytes <= BLOCK_BYTES]
    return common.pick_block(seq, sizes + [ROW_TILE])


def _turn_kernel(x_ref, cos_ref, sin_ref, out_ref, *, heads, head_dim, sign):
    """Forward (``sign`` 1): rows [rows, heads * head_dim] in, [heads, rows,
    head_dim] out; the backward reads what the forward writes and writes what
    it reads."""
    cos, sin = cos_ref[...], sin_ref[...]
    rows, rotary_dim = cos.shape
    half = rotary_dim // 2
    partial_turn = rotary_dim < head_dim
    if partial_turn:  # widened to the head; a select keeps the rest
        rest = jnp.zeros((rows, head_dim - rotary_dim), cos.dtype)
        cos = jnp.concatenate([cos, rest], axis=1)
        sin = jnp.concatenate([sin, rest], axis=1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, head_dim), 1)
    first = lane < half  # the pairs' first members: x_i cos - x_{i+half} sin
    sin = sign * jnp.where(first, -sin, sin)
    def turn(head, carry):
        lanes = pl.ds(pl.multiple_of(head * head_dim, head_dim), head_dim)
        x = (x_ref[:, lanes] if sign > 0 else x_ref[head]).astype(jnp.float32)
        partner = pltpu.roll(x, half, 1)
        if partial_turn:
            partner = jnp.where(first, pltpu.roll(x, head_dim - half, 1),
                                partner)
        turned = x * cos + partner * sin
        if partial_turn:
            turned = jnp.where(lane < rotary_dim, turned, x)
        if sign > 0:
            out_ref[head] = turned.astype(out_ref.dtype)
        else:
            out_ref[:, lanes] = turned.astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, heads, turn, None)


def rotary_turn(x, cos, sin, sign: int = 1):
    """x [B, S, heads, head_dim] turned by the angle of cos, sin [S,
    rotary_dim] float32 (``sign`` -1: by its negative); ``fits`` must hold.
    The forward's result is the transpose of the [B, heads, S, head_dim] it
    wrote; the backward takes its cotangent the same way round."""
    batch, seq, heads, head_dim = x.shape
    width = heads * head_dim
    rows = pick_rows(seq, width * x.dtype.itemsize)
    blocks = seq // rows
    by_rows = pl.BlockSpec((rows, width), lambda i: (i, 0))
    by_heads = pl.BlockSpec((None, heads, rows, head_dim),
                            lambda i: (i // blocks, 0, i % blocks, 0))
    table = pl.BlockSpec((rows, cos.shape[-1]), lambda i: (i % blocks, 0))
    rows_shape, heads_shape = (batch * seq, width), (batch, heads, seq, head_dim)
    forward = sign > 0
    with trace_parts.kernel_build("rotary_turn"):
        out = pl.pallas_call(
            partial(_turn_kernel, heads=heads, head_dim=head_dim, sign=sign),
            grid=(batch * blocks,),
            in_specs=[by_rows if forward else by_heads, table, table],
            out_specs=by_heads if forward else by_rows,
            out_shape=jax.ShapeDtypeStruct(
                heads_shape if forward else rows_shape, x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=common.interpret_mode(),
            name="rotary_turn",
        )(x.reshape(rows_shape) if forward else x.transpose(0, 2, 1, 3), cos, sin)
    return out.transpose(0, 2, 1, 3) if forward else out.reshape(x.shape)
