"""``ops/rope.py`` against closed forms: the default table, YaRN's two ends and
its ramp, partial rotation, and the reference's own tables
(``benchmarks/reference/laguna_f32.py``, written apart)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import laguna_f32 as ref
from bert_pytorch_tpu.ops import rope

YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5}
DEFAULT = {"rope_type": "default", "rope_theta": 10000,
           "partial_rotary_factor": 1}


def test_the_default_table_is_theta_to_the_minus_2i_over_d():
    inv_freq, factor = rope.inverse_frequencies(128, DEFAULT)
    assert factor == 1.0 and inv_freq.shape == (64,) and inv_freq.dtype == np.float32
    np.testing.assert_allclose(
        inv_freq, [10000.0 ** (-2 * i / 128) for i in range(64)], rtol=1e-6)


def test_yarn_keeps_the_fast_end_slows_the_slow_end_and_ramps_between():
    """At theta 500000 over 64 rotary dimensions and 8192 original positions
    the pair that turns 32 times is 9.0 (floored: 9) and the one that turns
    once is 17.5 (ceiled: 18): pairs up to 9 keep ``theta^(-2i/64)``, pairs
    from 18 on are that over 128, and between them the blend is linear."""
    low, high = rope.yarn_correction_range(64, 500000, 8192, 32, 1)
    turns = lambda d: 8192 / (2 * math.pi * 500000 ** (2 * d / 64))
    assert (low, high) == (9, 18)
    assert turns(low) >= 32 > turns(low + 1) and turns(high - 1) > 1 >= turns(high)
    inv_freq, factor = rope.inverse_frequencies(64, YARN)
    assert factor == pytest.approx(0.1 * math.log(128) + 1.0)
    plain = np.array([500000.0 ** (-2 * i / 64) for i in range(32)])
    np.testing.assert_allclose(inv_freq[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[18:], plain[18:] / 128, rtol=1e-6)
    for i in range(10, 18):
        ramp = (i - 9) / 9
        assert inv_freq[i] == pytest.approx(
            plain[i] * (1 - ramp) + plain[i] / 128 * ramp, rel=1e-6)
    # without a given attention factor it is 0.1 ln(factor) + 1
    no_factor = {k: v for k, v in YARN.items() if k != "attention_factor"}
    assert rope.inverse_frequencies(64, no_factor)[1] == pytest.approx(factor)
    with pytest.raises(ValueError, match="rope_type"):
        rope.inverse_frequencies(64, dict(YARN, rope_type="linear"))


@pytest.mark.parametrize("rotary_dim,params", [(64, YARN), (128, DEFAULT)])
def test_rotation_is_the_closed_form_and_leaves_the_rest_alone(rotary_dim, params):
    seq, heads, hd = 37, 3, 128
    x = jax.random.normal(jax.random.PRNGKey(rotary_dim), (2, seq, heads, hd))
    cos, sin = rope.rotary_tables(seq, rotary_dim, params)
    assert cos.shape == sin.shape == (seq, rotary_dim) and cos.dtype == jnp.float32
    out = np.asarray(rope.apply_rotary(x, cos, sin))
    inv_freq, factor = rope.inverse_frequencies(rotary_dim, params)
    half = rotary_dim // 2
    x64 = np.asarray(x, np.float64)
    for pos in (0, 1, 17, seq - 1):
        angle = pos * inv_freq.astype(np.float64)
        a, b = x64[:, pos, :, :half], x64[:, pos, :, half:rotary_dim]
        np.testing.assert_allclose(
            out[:, pos, :, :half],
            factor * (a * np.cos(angle) - b * np.sin(angle)), atol=2e-5)
        np.testing.assert_allclose(
            out[:, pos, :, half:rotary_dim],
            factor * (b * np.cos(angle) + a * np.sin(angle)), atol=2e-5)
    np.testing.assert_array_equal(out[..., rotary_dim:],
                                  np.asarray(x)[..., rotary_dim:])
    # position 0 turns nothing; the factor alone is left
    np.testing.assert_allclose(out[:, 0, :, :rotary_dim],
                               factor * np.asarray(x)[:, 0, :, :rotary_dim],
                               rtol=1e-6)


def test_scores_depend_on_the_distance_alone():
    """q_i . k_j after rotation is a function of i - j: the same pair of
    vectors at (40, 30) and at (25, 15) scores alike."""
    q = jax.random.normal(jax.random.PRNGKey(0), (128,))
    k = jax.random.normal(jax.random.PRNGKey(1), (128,))
    cos, sin = rope.rotary_tables(64, 128, DEFAULT)
    at = lambda v, pos: rope.apply_rotary(
        jnp.zeros((1, 64, 1, 128)).at[0, pos, 0].set(v), cos, sin)[0, pos, 0]
    near = float(at(q, 40) @ at(k, 30))
    assert near == pytest.approx(float(at(q, 25) @ at(k, 15)), abs=1e-4)
    assert abs(near - float(at(q, 40) @ at(k, 31))) > 1e-3


def test_a_bfloat16_head_is_turned_in_float32_and_comes_back_bfloat16():
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 2, 128), jnp.bfloat16)
    cos, sin = rope.rotary_tables(16, 64, YARN)
    out = rope.apply_rotary(x, cos, sin)
    assert out.dtype == jnp.bfloat16
    want = rope.apply_rotary(x.astype(jnp.float32), cos, sin)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want),
                               rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("rotary_dim,params", [(64, YARN), (128, DEFAULT)])
def test_the_reference_written_apart_turns_alike(rotary_dim, params):
    """Float32 angles at position 8191 resolve 5e-4 of a turn: the two sides
    agree there because both round the SAME inverse frequencies once."""
    mine, factor = rope.inverse_frequencies(rotary_dim, params)
    theirs, their_factor = ref.inverse_frequencies(rotary_dim, params)
    np.testing.assert_array_equal(mine, theirs)
    assert factor == their_factor
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 8192, 1, 128))
    cos, sin = rope.rotary_tables(8192, rotary_dim, params)
    np.testing.assert_allclose(
        np.asarray(rope.apply_rotary(x, cos, sin)),
        np.asarray(ref.rotate(x, rotary_dim, params)), atol=1e-6)


# -- the turn's backward: the turn by the negative angle -------------------------

def _plain_turn(x, cos, sin):
    """rotate_half written apart, in the dtype it is given: slice, the halves
    swapped with a sign, concatenate; the rest handed on."""
    rotary_dim = cos.shape[-1]
    turned, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    first, second = turned[..., :rotary_dim // 2], turned[..., rotary_dim // 2:]
    swapped = jnp.concatenate([-second, first], axis=-1)
    return jnp.concatenate(
        [turned * cos[:, None, :] + swapped * sin[:, None, :], rest], axis=-1)


def _pulled_back(turn, x, cotangent, cos, sin):
    return jax.vjp(lambda x_: turn(x_, cos, sin), x)[1](cotangent)[0]


def _case(seq, rotary_dim, dtype, batch=2, heads=3):
    """x, a cotangent and YaRN's tables (attention factor 1.485, not 1)."""
    kx, kg = jax.random.split(jax.random.PRNGKey(seq + rotary_dim))
    x = jax.random.normal(kx, (batch, seq, heads, 128), dtype)
    cotangent = jax.random.normal(kg, x.shape, dtype)
    return x, cotangent, rope.rotary_tables(seq, rotary_dim, YARN)


# seq 32: the kernel (interpreted here); seq 37: rows in no whole tile, plain jnp
@pytest.mark.parametrize("seq", [32, 37])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rotary_dim", [128, 64])
def test_the_gradient_is_autodiffs_of_the_plain_formulation(rotary_dim, dtype, seq):
    assert rope.kernel.fits((2, seq, 3, 128)) == (seq == 32)
    x, cotangent, (cos, sin) = _case(seq, rotary_dim, dtype)
    got = _pulled_back(rope.apply_rotary, x, cotangent, cos, sin)
    want = _pulled_back(_plain_turn, x.astype(jnp.float32),
                        cotangent.astype(jnp.float32), cos, sin)
    assert got.dtype == dtype and got.shape == x.shape
    tolerance = 1e-5 if dtype == jnp.float32 else 2.0 ** -8
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=tolerance, atol=tolerance)


@pytest.mark.parametrize("seq", [32, 37])
@pytest.mark.parametrize("rotary_dim", [128, 64])
def test_a_bfloat16_cotangent_is_turned_back_in_float32(rotary_dim, seq):
    """One rounding, at the end: the result is within half a bfloat16 step
    (at most 2^-8 of the value) of the float32 transpose of the float32
    cotangent; products and sums rounded to bfloat16 on the way would be up
    to three times as far."""
    x, cotangent, (cos, sin) = _case(seq, rotary_dim, jnp.bfloat16)
    got = _pulled_back(rope.apply_rotary, x, cotangent, cos, sin)
    assert got.dtype == jnp.bfloat16
    want = np.asarray(_pulled_back(
        _plain_turn, x.astype(jnp.float32), cotangent.astype(jnp.float32),
        cos, sin))
    gap = np.abs(np.asarray(got, np.float32) - want)
    assert np.all(gap <= 2.0 ** -8 * np.abs(want) + 1e-6)


@pytest.mark.parametrize("seq", [32, 37])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_untouched_halfs_gradient_is_the_cotangent_bit_for_bit(dtype, seq):
    x, cotangent, (cos, sin) = _case(seq, 64, dtype)
    got = _pulled_back(rope.apply_rotary, x, cotangent, cos, sin)
    bits = jnp.uint32 if dtype == jnp.float32 else jnp.uint16
    np.testing.assert_array_equal(
        np.asarray(jax.lax.bitcast_convert_type(got[..., 64:], bits)),
        np.asarray(jax.lax.bitcast_convert_type(cotangent[..., 64:], bits)))
    assert np.any(np.asarray(got[..., :64] != cotangent[..., :64]))


def test_the_turn_keeps_nothing_for_its_backward_but_the_tables(capsys):
    x, _, (cos, sin) = _case(32, 64, jnp.bfloat16)
    jax.ad_checkpoint.print_saved_residuals(
        lambda x_: jnp.sum(rope.apply_rotary(x_, cos, sin).astype(jnp.float32)), x)
    kept = capsys.readouterr().out.splitlines()
    assert [line.split()[0] for line in kept] == ["f32[32,64]"] * 2, kept


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rotary_dim", [128, 64])
def test_the_kernel_and_the_plain_path_turn_alike_both_ways(rotary_dim, dtype,
                                                           monkeypatch):
    """Two rows of a batch read the same block of the tables; the negative
    angle undoes the turn up to the attention factor's square."""
    x, _, (cos, sin) = _case(64, rotary_dim, dtype)
    by_kernel = {sign: rope._turn(x, cos, sin, sign) for sign in (1, -1)}
    monkeypatch.setattr(rope.kernel, "fits", lambda *_: False)
    tolerance = 1e-6 if dtype == jnp.float32 else 2.0 ** -8
    for sign, turned in by_kernel.items():
        assert turned.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(turned, np.float32),
            np.asarray(rope._turn(x, cos, sin, sign), np.float32),
            rtol=tolerance, atol=tolerance)
    if dtype == jnp.float32:
        factor = rope.inverse_frequencies(rotary_dim, YARN)[1]
        back = np.asarray(rope._turn(by_kernel[1], cos, sin, -1))
        np.testing.assert_allclose(back[..., :rotary_dim],
                                   factor ** 2 * np.asarray(x)[..., :rotary_dim],
                                   rtol=1e-5, atol=1e-5)
