"""The element-wise stages on either side of the gated delta rule
(``ops/delta_rule.py``) in the Qwen3-Next delta-rule mixer:

* ``conv_silu_unit`` BEFORE it: the in-projection's q, k and v blocks pass a
  causal depthwise convolution (their own columns of one [taps, channels]
  kernel) and a silu; every head of q and k is brought to unit length in
  float32 (``u rsqrt(sum u^2 + 1e-6)``), q over ``sqrt(d)``;
* ``gated_head_norm`` AFTER it: ``rmsnorm(o) * scale * silu(z)`` a value
  head, in float32 inside and in o's dtype outside.

One algorithm each, two realisations, chosen by the shapes and dtypes the
call sees (``kernel_fit``), as ``ops/delta_rule.py`` chooses for the rule:

* where heads are one lane tile (128) wide, positions come in whole blocks
  of 16, the taps are at most 4 and the operands share one dtype, a
  ``jax.custom_vjp`` over the Pallas kernels of ``ops/pallas/gdn_mix.py``
  (``gdn_mix_fwd`` / ``gdn_mix_bwd``, ``gated_norm_fwd`` /
  ``gated_norm_bwd``): each stage ONE pass over HBM in the flat layouts the
  projections write and the rule's kernels read, float32 from the load to
  the one cast at the store. Between forward and backward each keeps its
  operands as they came and nothing else;
* every other call (the CPU tests' tiny widths, float32 runs with mixed
  dtypes, ragged lengths) in plain XLA: ``ops/ssm.py causal_depthwise_conv``
  in the operands' dtype, then the silu, then the unit length; the gated norm
  rematerialized (its backward keeps o and z as they came, not their float32
  copies). It is also the tests' second opinion on the kernels.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from bert_pytorch_tpu.ops import ssm

L2_EPSILON = 1e-6


def kernel_fit(q, k, v, taps: int) -> bool:
    """Whether BOTH stages of a mixer whose convolved blocks are q, k [B, S,
    Hk, D] and v [B, S, Hv, Dv] (z and o have v's shape and dtype) run in the
    Pallas kernels; shapes and dtypes are all it looks at."""
    from bert_pytorch_tpu.ops.pallas.gdn_mix import fits

    return (q.dtype == k.dtype == v.dtype and q.shape == k.shape
            and fits(k.shape, taps) and fits(v.shape, taps))


def unit_length(t):
    """Every head of t [..., d] to unit length, in float32."""
    t = t.astype(jnp.float32)
    return t * jax.lax.rsqrt(
        jnp.sum(jnp.square(t), axis=-1, keepdims=True) + L2_EPSILON)


def conv_silu_unit(q, k, v, taps_q, taps_k, taps_v, key_heads: int,
                   value_heads: int):
    """q, k [B, S, Hk * D], v [B, S, Hv * Dv] (the in-projection's column
    blocks), each one's [taps, width] float32 columns of the convolution's
    kernel -> (q, k [B, S, Hk, D], v [B, S, Hv, Dv]) in the operands' dtype:
    the rule's operands."""
    heads = lambda t, count: t.reshape(t.shape[:2] + (count, -1))
    root = math.sqrt(q.shape[-1] // key_heads)
    taps = (taps_q, taps_k, taps_v)
    if (taps_q.shape[0] == taps_k.shape[0] == taps_v.shape[0] and kernel_fit(
            heads(q, key_heads), heads(k, key_heads), heads(v, value_heads),
            taps_q.shape[0])):
        q, k, v = _mix_kernels(q, k, v, *taps, 1.0 / root)
    else:
        dtype = q.dtype
        q, k, v = (jax.nn.silu(ssm.causal_depthwise_conv(
            t, w, jnp.zeros((t.shape[-1],), jnp.float32)))
            for t, w in zip((q, k, v), taps))
        q = (unit_length(heads(q, key_heads)) / root).astype(dtype)
        k = unit_length(heads(k, key_heads)).astype(dtype)
    return heads(q, key_heads), heads(k, key_heads), heads(v, value_heads)


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def _mix_kernels(q, k, v, taps_q, taps_k, taps_v, q_scale):
    from bert_pytorch_tpu.ops.pallas.gdn_mix import gdn_mix_forward

    return tuple(gdn_mix_forward(
        q, k, v, taps_q, taps_k, taps_v,
        unit_scales=(q_scale, 1.0, None), epsilon=L2_EPSILON))


def _mix_kernels_fwd(q, k, v, taps_q, taps_k, taps_v, q_scale):
    operands = (q, k, v, taps_q, taps_k, taps_v)
    return _mix_kernels(*operands, q_scale), operands


def _mix_kernels_bwd(q_scale, operands, cotangents):
    from bert_pytorch_tpu.ops.pallas.gdn_mix import gdn_mix_backward

    cotangents = (d.astype(t.dtype) for d, t in zip(cotangents, operands))
    *raw, dtaps_q, dtaps_k, dtaps_v = gdn_mix_backward(
        *operands, *cotangents, unit_scales=(q_scale, 1.0, None),
        epsilon=L2_EPSILON)
    return (*raw, *(d.astype(w.dtype) for d, w in zip(
        (dtaps_q, dtaps_k, dtaps_v), operands[3:])))


_mix_kernels.defvjp(_mix_kernels_fwd, _mix_kernels_bwd)


def gated_head_norm(o, z, scale, epsilon: float):
    """``rmsnorm(o) * scale * silu(z)`` over the last axis of o, z [B, S, Hv,
    Dv] (scale [Dv] float32), in float32 inside and in o's dtype outside."""
    from bert_pytorch_tpu.ops.pallas.gdn_mix import fits

    if o.ndim == 4 and o.dtype == z.dtype and o.shape == z.shape and fits(
            o.shape):
        flat = o.shape[:2] + (-1,)
        return _norm_kernels(
            o.reshape(flat), z.reshape(flat),
            scale.astype(jnp.float32).reshape(1, -1), epsilon).reshape(o.shape)
    return _gated_head_norm_xla(o, z, scale, epsilon)


@partial(jax.checkpoint, static_argnums=(3,))
def _gated_head_norm_xla(o, z, scale, epsilon: float):
    """(rematerialized: the backward keeps o and z as they came, not their
    float32 copies)"""
    o32, z32 = o.astype(jnp.float32), z.astype(jnp.float32)
    normed = o32 * jax.lax.rsqrt(
        jnp.mean(jnp.square(o32), axis=-1, keepdims=True) + epsilon)
    return (normed * scale * jax.nn.silu(z32)).astype(o.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _norm_kernels(o, z, scale, epsilon):
    from bert_pytorch_tpu.ops.pallas.gdn_mix import gated_norm_forward

    return gated_norm_forward(o, z, scale, epsilon=epsilon)


def _norm_kernels_fwd(o, z, scale, epsilon):
    return _norm_kernels(o, z, scale, epsilon), (o, z, scale)


def _norm_kernels_bwd(epsilon, operands, dy):
    from bert_pytorch_tpu.ops.pallas.gdn_mix import gated_norm_backward

    o, z, scale = operands
    do, dz, dscale = gated_norm_backward(
        o, z, scale, dy.astype(o.dtype), epsilon=epsilon)
    return do, dz, dscale.astype(scale.dtype)


_norm_kernels.defvjp(_norm_kernels_fwd, _norm_kernels_bwd)
