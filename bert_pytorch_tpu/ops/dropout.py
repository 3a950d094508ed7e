"""Dropout keep masks, drawn by the chip that uses them.

XLA's ``RngBitGenerator`` is not split by the SPMD partitioner: a mask asked
for at the GLOBAL batch size under a data-parallel mesh is generated whole on
every chip, which then keeps its own rows (with ``dp=4`` each chip draws four
times its share of random words). :func:`keep_mask` is the one place the
training path draws a mask, and it decides from what it can observe while it
is traced, with no flag:

  - the ambient mesh (``with mesh:``) has batch axes ('data', 'fsdp') of
    product n > 1, the trace is in no manual region yet, and the leading
    (batch) dimension divides by n: the mask is drawn under a ``shard_map``
    manual over the batch axes only, each shard's ``[B/n, ...]`` rows from
    ``fold_in(rng, shard index)``;
  - otherwise (one device, no mesh, inside a ``shard_map`` such as the
    pipeline's stages, a batch that does not divide): plain
    ``jax.random.bernoulli`` on the key as given, so on one device the
    masks and the program are what they were.

Either way the bits are independent Bernoulli(keep_prob); under a mesh the
streams differ from the one-device streams (as ``rbg``'s already did).
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from bert_pytorch_tpu.parallel.mesh import AXIS_DATA, AXIS_FSDP, current_mesh
from bert_pytorch_tpu.parallel.pipeline import shard_map

BATCH_AXES = (AXIS_DATA, AXIS_FSDP)

# Shard counts of the draws traced since the last forget_draws().
_traced_shards: set = set()


def draw_shards() -> int:
    """In how many shards the masks traced so far are drawn: the SMALLEST
    count over the draw sites (1 if any site took the plain branch; 0 if no
    mask was traced at all, e.g. a model without dropout)."""
    return min(_traced_shards, default=0)


def forget_draws() -> None:
    """A runner calls this before it builds its step, so that
    :func:`draw_shards` speaks of that step alone."""
    _traced_shards.clear()


def _batch_split(rows: int):
    """(mesh, its batch axes, their product) where the draw can be split over
    ``rows``; (None, (), 1) where it stays plain."""
    mesh = current_mesh()
    if mesh is None or jax.sharding.get_abstract_mesh().manual_axes:
        return None, (), 1
    axes = tuple(a for a in BATCH_AXES if a in mesh.shape)
    n = math.prod(mesh.shape[a] for a in axes)
    if n <= 1 or rows % n:
        return None, (), 1
    return mesh, axes, n


def keep_mask(rng, keep_prob: float, shape) -> jax.Array:
    """Boolean keep mask of ``shape``, whose leading dimension is the batch
    (module docstring: per batch shard under a mesh, plain otherwise)."""
    shape = tuple(shape)
    mesh, axes, n = _batch_split(shape[0])
    _traced_shards.add(n)
    if mesh is None:
        return jax.random.bernoulli(rng, keep_prob, shape)

    def draw(key):
        key = jax.random.fold_in(key, jax.lax.axis_index(axes))
        return jax.random.bernoulli(
            key, keep_prob, (shape[0] // n,) + shape[1:])

    return shard_map(draw, mesh=mesh, axis_names=frozenset(axes),
                     in_specs=P(), out_specs=P(axes))(rng)


class Dropout(nn.Module):
    """``flax.linen.Dropout`` with its mask from :func:`keep_mask`. Named
    alike, so it takes the same place in the module tree (``Dropout_0``) and
    the same key from ``make_rng``."""

    rate: float

    def __call__(self, inputs, deterministic: bool):
        if self.rate == 0.0 or deterministic:
            return inputs
        if self.rate == 1.0:  # no NaN gradients from the division below
            return jnp.zeros_like(inputs)
        keep_prob = 1.0 - self.rate
        mask = keep_mask(self.make_rng("dropout"), keep_prob, inputs.shape)
        return jax.lax.select(mask, inputs / keep_prob, jnp.zeros_like(inputs))
