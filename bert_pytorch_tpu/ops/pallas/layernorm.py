"""Fused LayerNorm Pallas kernel.

TPU-native equivalent of Apex ``FusedLayerNormAffineFunction``
(reference src/modeling.py:299-336): one pass over each row computes the
moments in fp32 and applies the affine transform, tiled over rows so the
hidden dimension stays resident in VMEM.

Forward is a Pallas kernel; the backward is a custom VJP computed with plain
XLA ops from saved (x, mean, rstd) — the backward is bandwidth-bound
elementwise math that XLA fuses well, so a hand kernel buys nothing there.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bert_pytorch_tpu.ops.pallas.common import interpret_mode, pick_block
from bert_pytorch_tpu.utils import trace_parts


def _ln_fwd_kernel(x_ref, scale_ref, bias_ref, out_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    centered = x - mean
    var = jnp.mean(centered * centered, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    normed = centered * rstd
    out = normed * scale_ref[:].astype(jnp.float32) + bias_ref[:].astype(jnp.float32)
    out_ref[:] = out.astype(out_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _ln_forward(x2d, scale, bias, eps):
    rows, hidden = x2d.shape
    block_rows = pick_block(rows, (256, 128, 64, 32, 16, 8, 4, 2, 1))
    grid = (rows // block_rows,)
    with trace_parts.kernel_build("layernorm_fwd"):
        out, mean, rstd = pl.pallas_call(
            partial(_ln_fwd_kernel, eps=eps),
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_rows, hidden), lambda i: (i, 0)),
                pl.BlockSpec((hidden,), lambda i: (0,)),
                pl.BlockSpec((hidden,), lambda i: (0,)),
            ],
            out_specs=[
                pl.BlockSpec((block_rows, hidden), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((rows, hidden), x2d.dtype),
                jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            ],
            name="layernorm_fwd",
            interpret=interpret_mode(),
        )(x2d, scale, bias)
    return out, mean, rstd


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _layer_norm_p(x2d, scale, bias, eps):
    out, _, _ = _ln_forward(x2d, scale, bias, eps)
    return out


def _layer_norm_p_fwd(x2d, scale, bias, eps):
    out, mean, rstd = _ln_forward(x2d, scale, bias, eps)
    return out, (x2d, scale, mean, rstd)


def _layer_norm_p_bwd(eps, residuals, g):
    x2d, scale, mean, rstd = residuals  # mean/rstd: [rows, 1]
    x = x2d.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    normed = (x - mean) * rstd
    dscale = jnp.sum(g32 * normed, axis=0)
    dbias = jnp.sum(g32, axis=0)
    # dx for y = normed*scale + bias, normed = (x-mean)*rstd:
    gs = g32 * scale.astype(jnp.float32)
    dx = rstd * (
        gs
        - jnp.mean(gs, axis=-1, keepdims=True)
        - normed * jnp.mean(gs * normed, axis=-1, keepdims=True)
    )
    return dx.astype(x2d.dtype), dscale.astype(scale.dtype), dbias.astype(scale.dtype)


_layer_norm_p.defvjp(_layer_norm_p_fwd, _layer_norm_p_bwd)


def layer_norm_pallas(x, scale, bias, eps: float = 1e-12):
    """LayerNorm over the last axis of arbitrary-rank ``x``."""
    hidden = x.shape[-1]
    x2d = x.reshape(-1, hidden)
    out = _layer_norm_p(x2d, scale, bias, eps)
    return out.reshape(x.shape)
