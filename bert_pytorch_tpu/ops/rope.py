"""Rotary position embedding: tables of two kinds, applied to part of a head.

A head's first ``rotary_dim`` dimensions are taken in pairs ``(i, i +
rotary_dim / 2)`` (the ``rotate_half`` convention) and each pair is turned by
the angle ``position * inv_freq[i]``; the head's other dimensions pass
unchanged (``partial_rotary_factor`` under 1).

``inverse_frequencies`` gives the two tables a model config can ask for
(``rope_type``):

* ``default``: ``inv_freq[i] = theta ** (-2 i / rotary_dim)``.
* ``yarn`` (Peng et al. 2023, arXiv:2309.00071, as the ``transformers``
  library computes it): each frequency is a blend of that (``extrapolation``)
  and that over ``factor`` (``interpolation``). The dimensions that turn more
  than ``beta_fast`` times over the ``original_max_position_embeddings``
  keep their frequency, those that turn fewer than ``beta_slow`` times are
  slowed by ``factor``, and between the two correction dimensions (floor of
  the one, ceiling of the other) a linear ramp blends them. Cosine and sine
  are multiplied by ``attention_factor`` (given, or ``0.1 ln(factor) + 1``).

The inverse frequencies are worked out in float64 on the host and rounded
once to float32; the angles, cosines and sines are float32 on the device
whatever the compute dtype (a bfloat16 angle at position 8191 would be off by
whole turns), and the rotated head is cast back to its own dtype.

**The turn** (``apply_rotary``) reads a head once and writes it once: where
the shapes allow (``ops/pallas/rope.py fits``: heads of whole lane tiles) it
is one Pallas kernel over the head's full width, with no slice, split or
concatenate in XLA; other shapes are turned by ``rotate_half`` in plain jnp.
**Its backward is the turn itself by the negative angle** (``jax.custom_vjp``:
the transpose of a rotation scaled by the attention factor is the rotation
the other way, scaled alike), in float32 inside and cast to the cotangent's
dtype; nothing is kept for it but the tables, which depend on no parameter
and get no cotangent. **The tables are the caller's to make once**
(``rotary_tables``: ``models/laguna.py`` makes one pair for each kind of layer
ahead of the layers and hands them to every block).

**Interleaved pairs** (``apply_rotary_interleaved``; a config's
``rope_interleave``: models/joyai.py): the WHOLE of x's last axis is taken in
pairs ``(2 i, 2 i + 1)``, each turned by ``position * inv_freq[i]``, from the
same tables (their first half holds each pair's angle once). Plain jnp, the
same backward by the negative angle; ``ops/pallas/rope.py`` knows the
``rotate_half`` pairs only.

Scope: the caller's (``attn_rope`` in models/laguna.py); the backward's ops
inherit it through the transpose's name.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bert_pytorch_tpu.ops.pallas import rope as kernel


def yarn_correction_range(rotary_dim: int, theta: float, original_max: int,
                          beta_fast: float, beta_slow: float) -> tuple:
    """(low, high): the pair indices between which YaRN's ramp runs: the
    dimension that turns ``beta_fast`` times over ``original_max`` positions,
    floored, and the one that turns ``beta_slow`` times, ceiled."""
    def dimension(turns):
        return (rotary_dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    return (max(math.floor(dimension(beta_fast)), 0),
            min(math.ceil(dimension(beta_slow)), rotary_dim - 1))


def inverse_frequencies(rotary_dim: int, rope: dict) -> tuple:
    """(inv_freq [rotary_dim / 2] float32, attention factor) for one entry of
    a config's ``rope_parameters``."""
    theta = float(rope["rope_theta"])
    kind = rope.get("rope_type", "default")
    pairs = np.arange(0, rotary_dim, 2, dtype=np.float64) / rotary_dim
    plain = theta ** -pairs
    if kind == "default":
        return plain.astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: this program builds 'default' "
                         "and 'yarn'")
    factor = float(rope["factor"])
    low, high = yarn_correction_range(
        rotary_dim, theta, int(rope["original_max_position_embeddings"]),
        float(rope.get("beta_fast", 32)), float(rope.get("beta_slow", 1)))
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rotary_dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    blended = plain / factor * ramp + plain * (1.0 - ramp)
    attention_factor = rope.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return blended.astype(np.float32), float(attention_factor)


def rotary_tables(seq: int, rotary_dim: int, rope: dict) -> tuple:
    """(cos, sin), each [seq, rotary_dim] float32, for positions 0 .. seq - 1:
    the pair's angle repeated over both halves, times the attention factor."""
    inv_freq, attention_factor = inverse_frequencies(rotary_dim, rope)
    angles = (jnp.arange(seq, dtype=jnp.float32)[:, None]
              * jnp.asarray(np.concatenate([inv_freq, inv_freq]))[None, :])
    return jnp.cos(angles) * attention_factor, jnp.sin(angles) * attention_factor


def _turn(x, cos, sin, sign: int):
    """The rotation by the tables' angle (``sign`` 1) or by its negative."""
    rotary_dim = cos.shape[-1]
    if kernel.fits(x.shape):
        return kernel.rotary_turn(x, cos, sin, sign)
    turned = x[..., :rotary_dim].astype(jnp.float32)
    first, second = jnp.split(turned, 2, axis=-1)
    turned = (turned * cos[:, None, :] + sign
              * jnp.concatenate([-second, first], axis=-1) * sin[:, None, :])
    return jnp.concatenate(
        [turned.astype(x.dtype), x[..., rotary_dim:]], axis=-1)


@jax.custom_vjp
def apply_rotary(x, cos, sin):
    """x [B, S, heads, head_dim] with its first ``cos.shape[-1]`` dimensions
    turned; the rest, if any, unchanged. In float32, back in x's dtype."""
    return _turn(x, cos, sin, 1)


def _apply_rotary_fwd(x, cos, sin):
    return _turn(x, cos, sin, 1), (cos, sin)


def _apply_rotary_bwd(tables, cotangent):
    return _turn(cotangent, *tables, -1), None, None


apply_rotary.defvjp(_apply_rotary_fwd, _apply_rotary_bwd)


def _turn_pairs(x, cos, sin, sign: int):
    """The rotation of the pairs (2 i, 2 i + 1) of x's last axis by the
    tables' angle (``sign`` 1) or by its negative."""
    half = x.shape[-1] // 2
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    cos, sin = cos[:, None, :half], sign * sin[:, None, :half]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


@jax.custom_vjp
def apply_rotary_interleaved(x, cos, sin):
    """x [B, S, heads, rotary_dim] with its pairs (2 i, 2 i + 1) turned, by
    the tables of ``rotary_tables(seq, rotary_dim, ...)``. In float32, back
    in x's dtype."""
    return _turn_pairs(x, cos, sin, 1)


def _apply_interleaved_fwd(x, cos, sin):
    return _turn_pairs(x, cos, sin, 1), (cos, sin)


def _apply_interleaved_bwd(tables, cotangent):
    return _turn_pairs(cotangent, *tables, -1), None, None


apply_rotary_interleaved.defvjp(_apply_interleaved_fwd, _apply_interleaved_bwd)
