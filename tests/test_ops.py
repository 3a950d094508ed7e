"""Op-library tests: Pallas kernels vs the XLA reference paths.

Kernels run in interpret mode on CPU (ops/pallas/common.py), so numerical
agreement here carries to the compiled TPU path.
"""

import jax
import jax.numpy as jnp
import numpy as np

from bert_pytorch_tpu import ops


def test_layer_norm_pallas_matches_xla():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(6, 16, 128)), jnp.float32)
    scale = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    ref = ops.layer_norm(x, scale, bias, eps=1e-12, backend="xla")
    out = ops.layer_norm(x, scale, bias, eps=1e-12, backend="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_layer_norm_pallas_grads_match():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 128)), jnp.float32)
    scale = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(128,)), jnp.float32)

    def loss(backend):
        def f(x, s, b):
            return jnp.sum(jnp.sin(ops.layer_norm(x, s, b, backend=backend)))

        return jax.grad(f, argnums=(0, 1, 2))(x, scale, bias)

    gx_ref, gs_ref, gb_ref = loss("xla")
    gx, gs, gb = loss("pallas")
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gs_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(gb_ref), atol=1e-4)


def _qkv(batch=2, seq=64, heads=2, depth=32, seed=0):
    rng = np.random.default_rng(seed)
    shp = (batch, seq, heads, depth)
    q = jnp.asarray(rng.normal(size=shp), jnp.float32)
    k = jnp.asarray(rng.normal(size=shp), jnp.float32)
    v = jnp.asarray(rng.normal(size=shp), jnp.float32)
    mask = np.ones((batch, seq), np.int32)
    mask[:, seq - 5 :] = 0
    bias = ops.attention.make_attention_bias(jnp.asarray(mask))
    return q, k, v, bias


def test_flash_attention_matches_xla():
    q, k, v, bias = _qkv()
    ref = ops.dot_product_attention(q, k, v, bias=bias, backend="xla")
    out = ops.dot_product_attention(q, k, v, bias=bias, backend="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_grads_match():
    q, k, v, bias = _qkv(batch=1, seq=32, heads=2, depth=16)

    def make_loss(backend):
        def f(q, k, v):
            out = ops.dot_product_attention(q, k, v, bias=bias, backend=backend)
            return jnp.sum(jnp.tanh(out))

        return jax.grad(f, argnums=(0, 1, 2))

    ref = make_loss("xla")(q, k, v)
    got = make_loss("pallas")(q, k, v)
    for r, g in zip(ref, got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-4)


def test_flash_attention_bf16_matches_xla():
    """Exercise the mixed-precision path: bf16 operands with fp32 softmax and
    accumulation (the training dtype). The fp32 tests above collapse the
    kernel's .astype(v.dtype) operand casts to no-ops; this one doesn't."""
    q, k, v, bias = _qkv(batch=1, seq=64, heads=2, depth=32)
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    ref = ops.dot_product_attention(q, k, v, bias=bias, backend="xla")
    out = ops.dot_product_attention(q, k, v, bias=bias, backend="pallas")
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )

    def make_loss(backend):
        def f(q, k, v):
            o = ops.dot_product_attention(q, k, v, bias=bias, backend=backend)
            return jnp.sum(jnp.tanh(o.astype(jnp.float32)))

        return jax.grad(f, argnums=(0, 1, 2))

    ref_g = make_loss("xla")(q, k, v)
    got_g = make_loss("pallas")(q, k, v)
    for r, g in zip(ref_g, got_g):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(r, np.float32), atol=5e-2
        )


def test_global_norm_and_clip():
    tree = {"a": jnp.asarray([3.0, 4.0]), "b": jnp.zeros((2, 2))}
    assert np.isclose(float(ops.global_norm(tree)), 5.0)
    clipped, norm = ops.clip_by_global_norm(tree, 1.0)
    assert np.isclose(float(norm), 5.0)
    assert np.isclose(float(ops.global_norm(clipped)), 1.0, atol=1e-4)
    # already within bounds -> unchanged
    same, _ = ops.clip_by_global_norm(tree, 10.0)
    np.testing.assert_allclose(np.asarray(same["a"]), np.asarray(tree["a"]))


def test_act2fn_bias_variants():
    x = jnp.asarray([[0.5, -0.3]], jnp.float32)
    b = jnp.asarray([0.1, 0.2], jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.bias_gelu(b, x)), np.asarray(ops.gelu(x + b)), atol=1e-6
    )


def test_flash_attention_bias_grad_matches():
    """dbias comes out of the fused dkv kernel — check it against autodiff."""
    q, k, v, bias = _qkv(batch=1, seq=32, heads=2, depth=16)

    def make_loss(backend):
        def f(bias):
            out = ops.dot_product_attention(q, k, v, bias=bias, backend=backend)
            return jnp.sum(jnp.tanh(out))

        return jax.grad(f)

    ref = make_loss("xla")(bias)
    got = make_loss("pallas")(bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4)


def test_pallas_dropout_falls_back_on_cpu():
    """Interpret mode has no TPU PRNG; active dropout must route to XLA and
    still produce a stochastic, correctly-scaled result."""
    q, k, v, bias = _qkv(batch=1, seq=32, heads=2, depth=16)
    out = ops.dot_product_attention(
        q, k, v, bias=bias, backend="pallas",
        dropout_rng=jax.random.PRNGKey(0), dropout_rate=0.5,
        deterministic=False)
    ref = ops.dot_product_attention(q, k, v, bias=bias, backend="xla")
    assert out.shape == ref.shape
    assert not np.allclose(np.asarray(out), np.asarray(ref))


def test_pallas_dropout_on_tpu():
    """In-kernel dropout statistics + determinism (real chip only)."""
    import pytest

    if jax.default_backend() != "tpu":
        pytest.skip("TPU hardware PRNG has no interpret-mode lowering")
    from bert_pytorch_tpu.ops.pallas.attention import flash_attention

    q, k, v, bias = _qkv(batch=2, seq=128, heads=4, depth=64)
    base = flash_attention(q, k, v, bias=bias)
    # Exercise BOTH PRNG impls: rbg key data duplicates its halves
    # ([t0,t1,t0,t1]), which once collapsed a naive xor-fold seed to 0.
    for impl in ("threefry2x32", "rbg"):
        with jax.default_prng_impl(impl):
            key = jax.random.PRNGKey(7)
            d1 = flash_attention(q, k, v, bias=bias, dropout_rate=0.1,
                                 dropout_rng=key)
            d2 = flash_attention(q, k, v, bias=bias, dropout_rate=0.1,
                                 dropout_rng=key)
            d3 = flash_attention(q, k, v, bias=bias, dropout_rate=0.1,
                                 dropout_rng=jax.random.PRNGKey(8))
            s1, s2 = jax.random.split(key)
            e1 = flash_attention(q, k, v, bias=bias, dropout_rate=0.1,
                                 dropout_rng=s1)
            e2 = flash_attention(q, k, v, bias=bias, dropout_rate=0.1,
                                 dropout_rng=s2)
            assert bool(jnp.all(d1 == d2)), impl  # same key -> same masks
            assert bool(jnp.any(d1 != d3)), impl  # fresh keys differ
            assert bool(jnp.any(e1 != e2)), impl  # split keys differ
            assert bool(jnp.any(d1 != base)), impl
    # E[dropout(out)] -> out: mean over seeds approaches the dense result
    acc = sum(
        flash_attention(q, k, v, bias=bias, dropout_rate=0.1,
                        dropout_rng=jax.random.PRNGKey(i))
        for i in range(32)
    )
    rel = float(jnp.abs(acc / 32 - base).mean() / jnp.abs(base).mean())
    assert rel < 0.1


class TestPallasBhBlock:
    def test_cap_and_divisibility_walk(self):
        from bert_pytorch_tpu.ops.pallas.attention import _pick_bh_block

        # the heuristic caps at 16 (the 4096 VMEM budget)
        assert _pick_bh_block(128, 896) == 16
        # and the divisibility walk rules: bh % g == 0
        assert _pick_bh_block(128, 48) == 16
