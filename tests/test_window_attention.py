"""The window on causal attention: the Pallas kernels (interpret mode on the
CPU) and the XLA path's band against a dense masked softmax, forward and the
three gradients, for a window under a tile, of a tile, over a tile and as long
as the row; and what the kernels visit, from shapes.

Tolerance: float32 at ``highest`` on both sides (conftest); the kernels add
a row's keys tile by tile (online softmax) where the dense form adds them at
once, so a few 1e-6 of the largest element is the order of the sums; 2e-5
would not pass a band off by one position (percents).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bert_pytorch_tpu.ops.attention import dot_product_attention
from bert_pytorch_tpu.ops.pallas import attention as flash

TOL = 2e-5


def close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    # (inputs are of order 1: a gradient that is exactly zero, as the scores'
    # under a window of one position, is held to that scale)
    assert np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 0.1), (
        np.max(np.abs(a - b)), np.max(np.abs(b)))


def dense_band(q, k, v, window):
    """Position i sees j with i - window < j <= i; plain softmax."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i = jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(q.shape[1])[None, :]
    s = jnp.where((j <= i) & (i - j < window), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def qkv(seq, seed=0, heads=4, kv=2, depth=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (2, seq, heads, depth)),
            jax.random.normal(k[1], (2, seq, kv, depth)),
            jax.random.normal(k[2], (2, seq, kv, depth)))


# 256 positions are four 64-wide tiles a side on the kernel path: a window of
# 24 lies inside one tile, 64 is a tile, 100 and 160 cross one and two, 256
# and 1000 reach every row's start (the causal kernel itself).
@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("window", [1, 24, 64, 100, 160, 256, 1000])
def test_the_band_matches_a_dense_masked_softmax(backend, window):
    q, k, v = qkv(256, seed=window)
    mine = lambda *a: dot_product_attention(
        *a, backend=backend, causal=True, window=window)
    theirs = lambda *a: dense_band(*a, window)
    close(mine(q, k, v), theirs(q, k, v))
    loss = lambda fn: (lambda *a: jnp.sum(jnp.sin(fn(*a))))
    got = jax.grad(loss(mine), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(theirs), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        close(g, w)


def test_a_ragged_single_tile_and_unlike_blocks():
    """40 positions are one ragged tile; 192 = 3 x 64."""
    for seq, window in ((40, 7), (192, 65)):
        q, k, v = qkv(seq, seed=seq)
        close(dot_product_attention(q, k, v, backend="pallas", causal=True,
                                    window=window),
              dense_band(q, k, v, window))


def test_a_window_is_not_the_triangle():
    """The comparison is not blind: the causal result is far from the band's."""
    q, k, v = qkv(256, seed=3)
    full = dot_product_attention(q, k, v, backend="pallas", causal=True)
    band = dot_product_attention(q, k, v, backend="pallas", causal=True,
                                 window=64)
    assert np.max(np.abs(np.asarray(full - band))) > 0.05
    # ... and up to the window's length the two are one
    close(full[:, :64], band[:, :64])


def test_a_window_needs_the_causal_mask_of_unpacked_rows():
    q, k, v = qkv(64)
    for backend in ("xla", "pallas"):
        with pytest.raises(ValueError, match="window"):
            dot_product_attention(q, k, v, backend=backend, window=8)
        with pytest.raises(ValueError, match="window"):
            dot_product_attention(q, k, v, backend=backend, causal=True,
                                  window=0)
        with pytest.raises(ValueError, match="window"):
            dot_product_attention(
                q, k, v, backend=backend, causal=True, window=8,
                sequence_ids=jnp.ones((2, 64), jnp.int32))
    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, k, v, backend="ring", causal=True, window=8)


def test_the_kernels_follow_the_band_not_the_triangle():
    """From shapes: at 8192 positions (sixteen 512-wide tiles a side) the
    causal kernels visit 136 tiles a head and the windowed ones 31, about
    (512 + tile) / 4096 of them; a window as long as the row visits what the
    causal kernel visits, and one of a position still visits the diagonal."""
    assert flash.tiles_visited(8192) == 256
    assert flash.tiles_visited(8192, causal=True) == 136
    assert flash.tiles_visited(8192, causal=True, window=512) == 31
    assert flash.tiles_visited(8192, causal=True, window=8192) == 136
    assert flash.tiles_visited(8192, causal=True, window=1) == 16
    assert flash.tiles_visited(8192, causal=True, window=514) == 16 + 15 + 14


def test_the_windowed_calls_carry_their_own_names():
    """A trace tells the windowed kernels from the full ones; a caller that
    sets no window traces the names it traced before."""
    q, k, v = qkv(128)

    def names(**flags):
        text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
            dot_product_attention(*a, backend="pallas", **flags)),
            argnums=(0, 1, 2)))(q, k, v))
        return {n for n in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                            "flash_window_fwd", "flash_window_bwd_dq",
                            "flash_window_bwd_dkv") if n in text}

    full = {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    assert names() == full and names(causal=True) == full
    assert names(causal=True, window=128) == full  # reaches every row's start
    assert names(causal=True, window=32) == {
        "flash_window_fwd", "flash_window_bwd_dq", "flash_window_bwd_dkv"}
