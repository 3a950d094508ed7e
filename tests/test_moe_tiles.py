"""The tiles of the grouped expert products (``ops/moe.py gmm_tiles``), the
``jax.custom_vjp`` that hands each of the three products its own
(``_tiled_grouped_dot``, megablox's kernels in the Pallas interpreter), and
the counter ``tile_fill``. On the CPU ``grouped_dot`` itself stays
``jax.lax.ragged_dot``: the families' own tests cover that path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bert_pytorch_tpu.ops import moe

SCOPED_VMEM = 16 * 2 ** 20  # what Mosaic gives a kernel on a v5e by default

# (rows of a piece, expected rows a group, hidden, the up product's width,
# the expert's width): the three cells that run the layer (PERF.md 4)
CELLS = {
    "zaya": (15872, 963, 2048, 4096, 2048),
    "laguna": (5120, 320, 3072, 2048, 1024),
    "hybrid": (6144, 384, 2688, 1856, 1856),
}


def _products():
    """Every grouped product the three cells run: (id, m, k, n, group rows,
    weights_out): up and down, forward, the rows' cotangent (k and n change
    places) and the weights' (``tgmm``)."""
    for cell, (m, group, hidden, up, width) in CELLS.items():
        for name, k, n in (("up", hidden, up), ("down", width, hidden)):
            yield f"{cell}-{name}-fwd", m, k, n, group, False
            yield f"{cell}-{name}-d_rows", m, n, k, group, False
            yield f"{cell}-{name}-d_w", m, k, n, group, True


def _check(m, k, n, group, weights_out, most_padding):
    tm, tk, tn = moe.gmm_tiles(k, n, group, 2, weights_out)
    assert tm in (128, 256) and moe.GMM_TILE_ROWS % tm == 0 and m % tm == 0
    assert tk % 128 == 0 and tn % 128 == 0
    for size, tile in ((k, tk), (n, tn)):
        padded = -(-size // tile) * tile
        assert padded < size + tile
        if size % 128 == 0:
            assert padded == size, (size, tile)
        assert padded <= size * (1 + most_padding), (size, tile)
    assert moe.gmm_vmem_bytes(tm, tk, tn, 2, weights_out) <= \
        moe.GMM_VMEM_BYTES < SCOPED_VMEM
    # a smaller expected group never gets a taller tile
    tms = [moe.gmm_tiles(k, n, g, 2, weights_out)[0]
           for g in (4096, 1024, 964, 512, 384, 320, 256, 100, 1)]
    assert tms == sorted(tms, reverse=True)
    assert tms[0] == 256 and tms[-1] == 128


@pytest.mark.parametrize(
    "m,k,n,group,weights_out",
    [pytest.param(*p[1:], id=p[0]) for p in _products()])
def test_tiles_of_the_cells_products(m, k, n, group, weights_out):
    """tk and tn whole lane tiles that divide k and n where those are
    multiples of 128 and waste at most 4% where not (1856), tm a divisor of
    512 (pieces are multiples of 512 rows), everything a grid step holds
    under the scoped VMEM."""
    _check(m, k, n, group, weights_out, most_padding=0.04)


@pytest.mark.parametrize("seed", range(8))
def test_tiles_of_random_lane_multiples(seed):
    """The same over random widths that are multiples of 128 (no padding at
    all), random piece sizes and groups, both kinds of product."""
    rng = np.random.default_rng(seed)
    for _ in range(40):
        k, n = (int(v) * 128 for v in rng.integers(1, 65, size=2))
        m = int(rng.integers(1, 64)) * 512
        group = int(rng.integers(1, 3000))
        _check(m, k, n, group, bool(rng.integers(2)), most_padding=0.0)


def test_widths_waste_the_least_there_is():
    assert moe._widths(1856) == [1920, 640, 384, 128]
    assert moe._widths(2688) == [2688, 896, 384, 128]
    assert moe._widths(1024) == [1024, 512, 256, 128]
    assert moe._widths(100) == [128]


# -- the custom_vjp over megablox's kernels, interpreted -----------------------

def _operands(m, k, n, groups, seed=0):
    key = jax.random.split(jax.random.PRNGKey(seed), 3)
    rows = jax.random.normal(key[0], (m, k), jnp.bfloat16)
    weights = jax.random.normal(key[1], (groups, k, n), jnp.bfloat16) * 0.1
    pull = jax.random.normal(key[2], (m, n), jnp.float32)
    return rows, weights, pull


@pytest.mark.parametrize("m,k,n,sizes,group_rows", [
    # groups that end inside a tile, an empty one, dead rows past the last
    pytest.param(512, 256, 384, [100, 0, 200, 57], 100, id="ragged"),
    # no dead row, tiles of 256 rows, k and n change places in the backward
    pytest.param(512, 384, 128, [300, 212], 600, id="full"),
    # k no multiple of 128: one padded k tile, masked in the kernel
    pytest.param(256, 320, 256, [0, 130, 90], 128, id="padded_k"),
    # every row dead
    pytest.param(256, 128, 128, [0, 0], 64, id="empty"),
])
def test_tiled_grouped_dot_matches_ragged_dot(m, k, n, sizes, group_rows):
    """Value, the rows' cotangent and the weights' against
    ``jax.lax.ragged_dot`` on the live rows (the rows past the groups are
    undefined in both and pass a select in ``_held_sum``)."""
    rows, weights, pull = _operands(m, k, n, len(sizes))
    sizes = jnp.asarray(sizes, jnp.int32)
    live = (jnp.arange(m) < jnp.sum(sizes))[:, None]

    def loss(dot):
        def fn(rows, weights):
            out = jnp.where(live, dot(jnp.where(live, rows, 0), weights), 0)
            return jnp.sum(out.astype(jnp.float32) * pull), out
        return jax.value_and_grad(fn, argnums=(0, 1), has_aux=True)

    mine = loss(lambda r, w: moe._tiled_grouped_dot(
        r, w, sizes, group_rows))(rows, weights)
    theirs = loss(lambda r, w: jax.lax.ragged_dot(
        r, w, sizes, preferred_element_type=r.dtype))(rows, weights)
    (_, out), (d_rows, d_weights) = mine
    (_, want), (want_rows, want_weights) = theirs
    assert out.dtype == rows.dtype and d_rows.dtype == rows.dtype
    assert d_weights.dtype == weights.dtype
    for got, ref in ((out, want), (d_rows, want_rows),
                     (d_weights, want_weights)):
        got, ref = (np.asarray(a, np.float32) for a in (got, ref))
        # both round a float32 sum to bfloat16 once: the last bit may differ
        np.testing.assert_allclose(got, ref, rtol=2 ** -7,
                                   atol=2 ** -7 * np.abs(ref).max() + 1e-30)


def test_each_product_is_given_its_own_tiles(monkeypatch):
    """Off the CPU ``grouped_dot`` runs three Pallas calls for a value and
    its two cotangents, and their blocks are ``gmm_tiles`` of the forward
    (m, k, n), of (m, n, k) and of the weights' product; on the CPU none."""
    m, k, n, group = 1024, 256, 1024, 600
    shapes = (jax.ShapeDtypeStruct((m, k), jnp.bfloat16),
              jax.ShapeDtypeStruct((4, k, n), jnp.bfloat16),
              jax.ShapeDtypeStruct((4,), jnp.int32))
    traced = lambda: jax.make_jaxpr(jax.grad(  # (a new function: no cache)
        lambda r, w, s: jnp.sum(moe.grouped_dot(r, w, s, group).astype(
            jnp.float32)), argnums=(0, 1)))(*shapes)
    assert "pallas_call" not in str(traced())
    monkeypatch.setattr(moe, "interpret_mode", lambda: False)

    blocks = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                blocks.append([
                    tuple(d.block_size for d in b.block_shape
                          if hasattr(d, "block_size"))
                    for b in eqn.params["grid_mapping"].block_mappings])
            for value in eqn.params.values():
                for inner in value if isinstance(value, (list, tuple)) \
                        else [value]:
                    if hasattr(inner, "eqns"):
                        walk(inner)
                    elif hasattr(inner, "jaxpr"):
                        walk(inner.jaxpr)

    walk(traced().jaxpr)
    tm, tk, tn = moe.gmm_tiles(k, n, group, 2)
    bm, bk, bn = moe.gmm_tiles(n, k, group, 2)       # contracts n
    wm, wk, wn = moe.gmm_tiles(k, n, group, 2, weights_out=True)
    assert blocks == [
        [(tm, tk), (tk, tn), (tm, tn)],
        [(bm, bk), (bn, bk), (bm, bn)],                 # the weights, turned
        [(wm, wk), (wm, wn), (wk, wn)]]
    assert (tk, tn) != (bk, bn)  # one triple for the three would not do


# -- the counter ---------------------------------------------------------------

def _counters(chosen, w_up, w_down, first, experts):
    """``held_experts``'s counters alone (jitted, so the products they do not
    depend on are never run)."""
    tokens, hidden = chosen.shape[0], w_up.shape[1]
    return jax.jit(lambda chosen: moe.held_experts(
        jnp.ones((tokens, hidden), jnp.bfloat16), chosen,
        jnp.ones(chosen.shape, jnp.float32), w_up, w_down, first, experts,
        jax.nn.relu)[1])(jnp.asarray(chosen))


def test_tile_fill_against_a_count_by_hand():
    """Two pieces of 512 sorted slots, four held experts of 16, expected
    group 64 (tiles of 128 rows). Groups of 200, 0, 450 and 0 local slots:
    rows 0-199 visit tiles 0-1; rows 200-511 visit tiles 1-3 of the first
    piece and rows 0-137 of the second its tiles 0-1: 7 visits of 128 rows
    for 650 live ones, k and n whole lane tiles."""
    tokens, top_k, experts, held, hidden, width = 1024, 1, 16, 4, 256, 128
    chosen = np.full((tokens, top_k), 15, np.int32)
    chosen[:200] = 2
    chosen[200:650] = 4
    w_up = jnp.zeros((held, hidden, width), jnp.bfloat16)
    w_down = jnp.zeros((held, width, hidden), jnp.bfloat16)
    assert moe.chunk_rows(tokens, top_k, experts, held) == 512
    counters = _counters(chosen, w_up, w_down, 2, experts)
    assert float(counters["local_slots"]) == 650
    assert float(counters["pieces_run"]) == 2
    assert moe.gmm_tiles(hidden, width, 64, 2)[0] == 128
    np.testing.assert_allclose(float(counters["tile_fill"]), 650 / (7 * 128),
                               rtol=1e-6)
    # without a local slot nothing is visited and the counter reads 0
    none = _counters(np.full((tokens, top_k), 15, np.int32), w_up, w_down, 2,
                     experts)
    assert float(none["tile_fill"]) == 0.0


@pytest.mark.parametrize("hidden,width,fill", [
    pytest.param(2688, 1856, 1856 / 1920, id="hybrid"),
    pytest.param(2048, 4096, 1.0, id="wide"),  # a tile's work passes 2**31
])
def test_tile_fill_counts_the_padding_of_k_and_n(hidden, width, fill):
    """The hybrid decoder's widths: 1856 is 14.5 lane tiles and is computed
    as 1920, in both products; whole lane tiles waste nothing. Every group
    is whole tiles of rows."""
    tokens = 2048
    chosen = np.repeat(np.arange(4, dtype=np.int32), tokens // 4)[:, None]
    counters = _counters(chosen, jnp.zeros((4, hidden, width), jnp.bfloat16),
                         jnp.zeros((4, width, hidden), jnp.bfloat16), 0, 4)
    np.testing.assert_allclose(float(counters["tile_fill"]), fill, rtol=1e-6)
