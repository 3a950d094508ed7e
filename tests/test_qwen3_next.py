"""The ``qwen3_next`` family against its plain float32 reference
(``benchmarks/reference/qwen3next_f32.py``) at a small size on the CPU: the
chunked delta rule against the literal recurrence over the whole published
range of ``A_log``, each part of the two mixers alone (the convolution, the
gated norm, the ``1 + w`` norm, the quarter turn, the output gate), the expert
layer with its gated shared expert, the sixteen expert shares adding up to the
uncut layer, the whole model's loss and gradients at two periods, two whole
updates through ``pretrain.make_train_step``, the pinned counts, and the
normal path (``run_pretraining.main``) from a config file.

Tolerances: everything here is float32 at ``highest`` on both sides
(conftest), so program and reference differ only in the ORDER of float32 sums
(the rule's chunks against its tokens, the kernel's tiles, the experts' sorted
slots). A few 1e-6 relative to the largest element is that; 2e-5 leaves a
decade of room (5e-5 where the chunked rule's inverse stands between) and
would not pass a dropped convolution, gate, norm or correction (each moves the
result by percents: the "is seen" tests below).
"""

import json
import math

import flax
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import qwen3next_f32 as ref
from benchmarks.reference import qwen3next_map
from bert_pytorch_tpu import optim, pretrain
from bert_pytorch_tpu.config import Qwen3NextConfig, load_model_config
from bert_pytorch_tpu.models import build_pretraining_model, decoder, qwen3_next
from bert_pytorch_tpu.models.losses import next_token_loss
from bert_pytorch_tpu.ops import delta_rule, gdn_mix, rope
from bert_pytorch_tpu.utils import flops

# the published layer at a small size: 2 key / 4 value heads of 16 behind 4
# taps, 4 / 2 attention heads of 16 with a quarter turned, 4 of 8 experts held
# top-3 with a gated shared expert; one period of four layers
TINY = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4, delta_chunk=8,
    num_experts=4, ep_size=2, ep_rank=1, num_experts_per_tok=3,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    partial_rotary_factor=0.25, rope_theta=10000000, rms_norm_eps=1e-6,
    full_attention_interval=4, moe_piece_multiple=8)
TOL = 2e-5


def close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-30), (
        np.max(np.abs(a - b)), np.max(np.abs(b)))


def far(a, b, share=0.02):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) > share * np.max(np.abs(b))


def keys(n, seed=0):
    return jax.random.split(jax.random.PRNGKey(seed), n)


def _seeded(seed=3, loud=False, **changes):
    """Sizes and seeded weights; ``loud``: norms, ``dt_bias`` and the gated
    norm's scale away from their 0 and 1 and every matrix five times larger,
    so that every parameter shows in the output."""
    c = ref.sizes(dict(TINY, **changes))
    p = ref.seeded_params(ref.key_from_seed(seed), c)
    if loud:
        table = ref.param_table(c)
        for index, name in enumerate(sorted(p)):
            draw = jax.random.fold_in(jax.random.PRNGKey(seed + 100), index)
            if table[name][1] in ("ones", "zeros"):
                p[name] = p[name] + 0.3 * jax.random.normal(draw, p[name].shape)
            elif table[name][1] in ("normal", "out", "vector"):
                p[name] = 5.0 * p[name]
    return c, p


def _model(backend="xla", remat="full", **changes):
    return build_pretraining_model(
        Qwen3NextConfig(**dict(TINY, **changes)), jnp.float32, remat=remat,
        attention_backend=backend)


def _mixer(c, p, layer, x, backend="xla", **changes):
    cfg = Qwen3NextConfig(**dict(TINY, **changes))
    tree = qwen3next_map.to_program(p, c)[f"layers_{layer}"]["mixer"]
    if cfg.layer_types[layer] == "linear_attention":
        return qwen3_next.GatedDeltaNet(cfg, jnp.float32).apply(
            {"params": tree}, x)[0]
    return qwen3_next.GatedSoftmaxAttention(cfg, jnp.float32, backend).apply(
        {"params": tree}, x)


# -- the rule --------------------------------------------------------------------

def _recurrence(q, k, v, g, beta):
    """The literal rule (the reference's scan over tokens), the key heads
    repeated as the chunked form reads them."""
    ratio = v.shape[2] // k.shape[2]
    return ref.recurrence(jnp.repeat(q, ratio, axis=2),
                          jnp.repeat(k, ratio, axis=2), v, g, beta)


def _rule_inputs(seq, a_values, seed=0, batch=2, key_heads=2, dim=16):
    value_heads = len(a_values)
    k = keys(6, seed)
    q = gdn_mix.unit_length(
        jax.random.normal(k[0], (batch, seq, key_heads, dim))) / math.sqrt(dim)
    key = gdn_mix.unit_length(
        jax.random.normal(k[1], (batch, seq, key_heads, dim)))
    v = jax.random.normal(k[2], (batch, seq, value_heads, dim))
    g = -jnp.asarray(a_values, jnp.float32) * jax.nn.softplus(
        jax.random.normal(k[3], (batch, seq, value_heads)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (batch, seq, value_heads)))
    return q, key, v, g, beta


@pytest.mark.parametrize("seq,chunk", [(192, 64), (200, 64), (40, 8), (5, 64)])
def test_chunked_rule_matches_the_recurrence(seq, chunk):
    """Forward and all five cotangents, at the published chunk of 64 (a whole
    number of chunks, a ragged end, a row shorter than a chunk) and at a
    small one; decays from almost none to e^-20 a token (``A`` over the whole
    published range of (0, 16))."""
    args = _rule_inputs(seq, [1e-6, 0.3, 4.0, 16.0], seed=seq)
    mine = delta_rule.gated_delta_rule(*args, chunk)
    theirs = _recurrence(*args)
    assert bool(jnp.all(jnp.isfinite(mine)))
    close(mine, theirs, 5e-5)
    weight = jax.random.normal(keys(1, 7)[0], theirs.shape)
    grads = lambda fn: jax.grad(
        lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2, 3, 4))(*args)
    for got, want in zip(grads(lambda *a: delta_rule.gated_delta_rule(*a, chunk)),
                         grads(_recurrence)):
        assert bool(jnp.all(jnp.isfinite(got)))
        close(got, want, 5e-5)


def test_the_rule_is_finite_in_bfloat16_over_the_published_decays():
    """The chip's dtype: bfloat16 operands, float32 state; nothing overflows
    where a head forgets everything within a token, forward or backward."""
    q, k, v, g, beta = _rule_inputs(256, [1e-6, 16.0, 16.0, 8.0])
    low = lambda t: t.astype(jnp.bfloat16)
    fn = lambda q_, k_, v_, g_, b_: jnp.sum(delta_rule.gated_delta_rule(
        q_, k_, v_, g_ * 4.0, b_).astype(jnp.float32) ** 2)
    out = delta_rule.gated_delta_rule(low(q), low(k), low(v), g, beta)
    assert out.dtype == jnp.bfloat16 and bool(jnp.all(jnp.isfinite(out)))
    close(out.astype(jnp.float32),
          _recurrence(q, k, v, g, beta), 0.03)
    for grad in jax.grad(fn, argnums=(0, 1, 2, 3, 4))(low(q), low(k), low(v),
                                                      g, beta):
        assert bool(jnp.all(jnp.isfinite(grad.astype(jnp.float32))))


def test_the_reference_recurrence_in_blocks_and_with_both_faults():
    """The reference's scan gives the same whatever its blocks (each
    checkpointed), and equals three tokens worked by hand; a correction read
    from the undecayed state, or ``beta`` left out, moves the result."""
    q, k, v, g, beta = _rule_inputs(70, [0.5, 0.5, 2.0, 2.0])
    q4, k4 = jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2)
    want = _recurrence(q, k, v, g, beta)
    close(ref.recurrence(q4, k4, v, g, beta, block=16), want)
    state = np.zeros((16, 16))
    for t in range(3):  # batch 0, value head 3 (key head 1)
        state = state * np.exp(float(g[0, t, 3]))
        read = state.T @ np.asarray(k[0, t, 1], np.float64)
        state = state + np.outer(k[0, t, 1], float(beta[0, t, 3]) * (
            np.asarray(v[0, t, 3], np.float64) - read))
        close(want[0, t, 3], state.T @ np.asarray(q[0, t, 1], np.float64))
    for fault in ("undecayed_read", "no_beta"):
        far(ref.recurrence(q4, k4, v, g, beta, faults=(fault,)), want)


def test_key_head_j_serves_value_heads_2j_and_2j_plus_1():
    q, k, v, g, beta = _rule_inputs(24, [1.0, 1.0, 1.0, 1.0])
    whole = delta_rule.gated_delta_rule(q, k, v, g, beta, 8)
    for head in range(4):
        alone = delta_rule.gated_delta_rule(
            q[:, :, head // 2:head // 2 + 1], k[:, :, head // 2:head // 2 + 1],
            v[:, :, head:head + 1], g[..., head:head + 1],
            beta[..., head:head + 1], 8)
        close(whole[:, :, head:head + 1], alone)


def test_the_unit_lower_inverse_and_its_backward():
    a = jnp.tril(jax.random.normal(keys(1, 2)[0], (3, 64, 64)), -1) * 0.3
    inverse = delta_rule.unit_lower_inverse(a)
    close(inverse, np.linalg.inv(np.eye(64) + np.asarray(a, np.float64)), 1e-4)
    weight = jax.random.normal(keys(1, 3)[0], a.shape)
    mine = jax.grad(lambda a_: jnp.sum(delta_rule.unit_lower_inverse(a_) * weight))(a)
    theirs = jax.grad(lambda a_: jnp.sum(
        jnp.linalg.inv(jnp.eye(64) + jnp.tril(a_, -1)) * weight))(a)
    close(mine, theirs, 1e-4)
    assert delta_rule.delta_chunks(2, 8192) == 256
    assert delta_rule.delta_chunks(3, 65, 64) == 6


# -- the delta-rule mixer -----------------------------------------------------------

def test_delta_mixer_matches_the_reference():
    c, p = _seeded(loud=True)
    x = jax.random.normal(keys(1, 1)[0], (2, 21, c["H"]))
    mine = lambda x_: _mixer(c, p, 1, x_)
    theirs = lambda x_: ref.delta_mixer(p, "l1.", c, x_, "f32")
    close(mine(x), theirs(x), 5e-5)
    loss = lambda fn: (lambda x_: jnp.sum(jnp.sin(fn(x_))))
    close(jax.grad(loss(mine))(x), jax.grad(loss(theirs))(x), 5e-5)


def test_no_part_of_either_mixer_reads_a_later_position():
    c, p = _seeded(loud=True)
    x = jax.random.normal(keys(1, 2)[0], (1, 24, c["H"]))
    moved = x.at[:, 12].add(1.0)
    for layer in (0, 3):
        a, b = (np.asarray(_mixer(c, p, layer, t)) for t in (x, moved))
        np.testing.assert_allclose(a[:, :12], b[:, :12], rtol=0, atol=1e-6)
        assert np.abs(a[:, 12:] - b[:, 12:]).max() > 1e-3


def test_the_convolution_is_four_taps_over_q_k_and_v_with_zeros_before():
    """An impulse at t = 5 through ``ops/ssm.py``'s convolution as the mixer
    calls it (no bias) and through the reference's: tap 3 lands at 5, tap 0
    at 8, nothing before 5 or after 8; z does not pass it."""
    c, p = _seeded()
    taps = p["l0.conv"]
    channels = taps.shape[1]
    assert channels == 2 * 2 * 16 + 4 * 16  # q, k and v; not z
    x = jnp.zeros((1, 12, channels)).at[0, 5].set(
        jax.random.normal(keys(1, 3)[0], (channels,)))
    from bert_pytorch_tpu.ops import ssm
    mine = ssm.causal_depthwise_conv(x, taps, jnp.zeros((channels,)))
    close(mine, ref.causal_conv(x, taps))
    for t in range(12):
        want = taps[3 - (t - 5)] * x[0, 5] if 5 <= t <= 8 else 0 * x[0, 5]
        close(mine[0, t], want) if 5 <= t <= 8 else np.testing.assert_array_equal(
            np.asarray(mine[0, t]), 0.0)


def test_the_gated_norm_has_no_offset_and_gates_by_silu_z():
    o = jax.random.normal(keys(1, 4)[0], (2, 5, 4, 16))
    z = jax.random.normal(keys(1, 5)[0], (2, 5, 4, 16))
    scale = 1.0 + 0.3 * jax.random.normal(keys(1, 6)[0], (16,))
    want = (np.asarray(o) / np.sqrt(np.mean(np.square(o), -1, keepdims=True) + 1e-6)
            * np.asarray(scale) * np.asarray(z) / (1 + np.exp(-np.asarray(z))))
    close(gdn_mix.gated_head_norm(o, z, scale, 1e-6), want)
    # the mixer's scale starts at ONE (the other norms' w starts at zero)
    shapes = _model().init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    tree = nn.unbox(shapes)["params"]["layers_0"]
    np.testing.assert_array_equal(tree["mixer"]["norm_scale"], 1.0)
    np.testing.assert_array_equal(tree["mixer"]["dt_bias"], 1.0)
    np.testing.assert_array_equal(tree["mixer_norm"]["scale"], 0.0)
    a = np.exp(np.asarray(tree["mixer"]["A_log"]))
    assert a.shape == (4,) and (a > 0).all() and (a < 16).all()


@pytest.mark.parametrize("dropped", ["conv", "silu", "beta", "decay", "gate",
                                     "l2"])
def test_a_dropped_part_of_the_delta_mixer_is_seen(dropped):
    c, p = _seeded(loud=True)
    x = jax.random.normal(keys(1, 6)[0], (2, 16, c["H"]))
    want = ref.delta_mixer(p, "l0.", c, x, "f32")
    with pytest.MonkeyPatch.context() as patch:
        if dropped == "conv":
            patch.setattr(gdn_mix.ssm, "causal_depthwise_conv",
                          lambda t, w, b: t * w[-1])
        elif dropped == "silu":
            patch.setattr(qwen3_next.jax.nn, "silu", lambda t: t)
        elif dropped == "l2":
            patch.setattr(gdn_mix, "unit_length", lambda t: t)
        elif dropped == "gate":
            patch.setattr(gdn_mix, "gated_head_norm",
                          lambda o, z, s, e: o * s)
        else:
            rule = delta_rule.gated_delta_rule
            patch.setattr(
                qwen3_next.delta_rule, "gated_delta_rule",
                lambda q, k, v, g, b, n: rule(q, k, v, g, 0 * b + 1, n)
                if dropped == "beta" else rule(q, k, v, 0 * g, b, n))
        far(_mixer(c, p, 0, x), want)


# -- the attention mixer -------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_attention_matches_the_reference(backend):
    c, p = _seeded(loud=True)
    x = jax.random.normal(keys(1, 1)[0], (2, 40, c["H"]))
    mine = lambda x_: _mixer(c, p, 3, x_, backend)
    theirs = lambda x_: ref.attention(p, "l3.", c, x_, "f32", block_rows=16)
    close(mine(x), theirs(x))
    loss = lambda fn: (lambda x_: jnp.sum(jnp.sin(fn(x_))))
    close(jax.grad(loss(mine))(x), jax.grad(loss(theirs))(x))


def test_the_norm_multiplies_by_one_plus_w():
    x = jax.random.normal(keys(1, 7)[0], (3, 5, 64))
    w = 0.3 * jax.random.normal(keys(1, 8)[0], (64,))
    norm = decoder.RMSNorm(1e-6, jnp.float32, offset=1)
    plain = np.asarray(x) / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-6)
    close(norm.apply({"params": {"scale": w}}, x), plain * (1 + np.asarray(w)))
    close(ref.norm(x, w, 1e-6), plain * (1 + np.asarray(w)))
    start = norm.init(jax.random.PRNGKey(0), x)["params"]["scale"]
    np.testing.assert_array_equal(start, 0.0)
    # the other families' norm is as it was: scale from one, no offset
    old = decoder.RMSNorm(1e-5, jnp.float32)
    np.testing.assert_array_equal(
        old.init(jax.random.PRNGKey(0), x)["params"]["scale"], 1.0)
    close(old.apply({"params": {"scale": 1 + w}}, x),
          np.asarray(x) / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-5)
          * (1 + np.asarray(w)))


def test_a_quarter_of_each_head_is_turned():
    """What reaches the core: the first 4 of a head's 16 dimensions turned by
    the default table at theta 1e7, the other 12 bit for bit the normed q and
    k; the label and the causal flag."""
    c, p = _seeded(loud=True)
    x = jax.random.normal(keys(1, 9)[0], (2, 12, c["H"]))
    caught = []

    def core(q, k, v, **kwargs):
        caught.append(dict(q=q, k=k, v=v, **kwargs))
        return jnp.zeros(q.shape, q.dtype)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(qwen3_next, "dot_product_attention", core)
        _mixer(c, p, 3, x)
        patch.setattr(qwen3_next.rope, "apply_rotary", lambda t, cos, sin: t)
        _mixer(c, p, 3, x)
    turned, plain = caught
    assert turned["causal"] is True and turned["label"] == "gated"
    assert turned["q"].shape[2:] == (4, 16) and turned["k"].shape[2:] == (2, 16)
    for name in ("q", "k"):
        np.testing.assert_array_equal(np.asarray(turned[name])[..., 4:],
                                      np.asarray(plain[name])[..., 4:])
        far(np.asarray(turned[name])[:, 1:, :, :4],
            np.asarray(plain[name])[:, 1:, :, :4])
        close(turned[name], ref.rotate(plain[name], 4, c["rope"]))
    assert Qwen3NextConfig(**TINY).rope == (4, c["rope"])
    cos, _ = rope.rotary_tables(12, *Qwen3NextConfig().rope)
    assert cos.shape == (12, 64)  # 64 of the published head's 256


@pytest.mark.parametrize("dropped", ["gate", "qk_norm", "rotary"])
def test_a_dropped_part_of_the_attention_is_seen(dropped):
    c, p = _seeded(loud=True)
    x = jax.random.normal(keys(1, 6)[0], (2, 16, c["H"]))
    want = ref.attention(p, "l3.", c, x, "f32")
    with pytest.MonkeyPatch.context() as patch:
        if dropped == "gate":
            patch.setattr(qwen3_next.jax.nn, "sigmoid", lambda t: 0 * t + 1)
        elif dropped == "rotary":
            patch.setattr(qwen3_next.rope, "apply_rotary", lambda t, c_, s: t)
        else:
            p = dict(p, **{"l3.q_norm": 0 * p["l3.q_norm"] + 2.0})
        far(_mixer(c, p, 3, x), want)


# -- the expert layer ------------------------------------------------------------------

def _expert_layer(c, p, layer, x, **changes):
    cfg = Qwen3NextConfig(**dict(TINY, **changes))
    tree = qwen3next_map.to_program(p, c)[f"layers_{layer}"]["mlp"]
    return qwen3_next.expert_layer(cfg, jnp.float32).apply({"params": tree}, x)


def test_expert_layer_matches_the_reference_and_the_shared_gate_is_seen():
    c, p = _seeded(4, loud=True)
    x = jax.random.normal(keys(1, 4)[0], (2, 24, c["H"]))
    names = ("l1.router", "l1.w_gu", "l1.w_down", "l1.shared_gu",
             "l1.shared_down", "l1.shared_gate")

    def mine(x_, *w):
        return _expert_layer(c, dict(p, **dict(zip(names, w))), 1, x_)[0]

    def theirs(x_, *w):
        return ref.expert_layer(dict(p, **dict(zip(names, w))), "l1.", c, x_,
                                "f32")[0]

    args = (x,) + tuple(p[n] for n in names)
    close(mine(*args), theirs(*args))
    loss = lambda fn: (lambda *a: jnp.sum(jnp.sin(fn(*a))))
    for got, want in zip(jax.grad(loss(mine), argnums=range(7))(*args),
                         jax.grad(loss(theirs), argnums=range(7))(*args)):
        close(got, want)
        assert float(jnp.max(jnp.abs(want))) > 0
    far(ref.expert_layer(p, "l1.", c, x, "f32", faults=("no_shared_gate",))[0],
        theirs(*args))
    far(ref.expert_layer(p, "l1.", c, x, "f32", faults=("no_renorm",))[0],
        theirs(*args))
    chosen, weights = ref.route(p, "l1.", c, x.reshape(-1, c["H"]))
    assert chosen.shape == (48, 3)
    np.testing.assert_allclose(np.sum(weights, -1), 1.0, rtol=1e-6)


def test_the_sixteen_expert_shares_add_up_to_the_uncut_layer():
    """Each of 16 ranks holds 2 of 32 experts and the shared expert whole:
    the ranks' outputs, with the shared expert's term counted ONCE, are the
    reference's uncut layer (every expert on one chip)."""
    changes = dict(num_experts=2, ep_size=16, ep_rank=0, num_experts_per_tok=5)
    c, p = _seeded(4, loud=True, **changes)
    held, every = c["held"], c["experts"]
    assert (held, every) == (2, 32)
    x = jax.random.normal(keys(1, 8)[0], (2, 32, c["H"]))
    k = keys(2, 9)
    q = dict(p)
    q["l1.w_gu"] = 5 * c["std"] * jax.random.normal(
        k[0], (every, c["H"], 2 * c["F"]))
    q["l1.w_down"] = 5 * c["std"] * jax.random.normal(
        k[1], (every, c["F"], c["H"]))
    whole = dict(c, held=every, first=0)
    uncut, _ = ref.expert_layer(q, "l1.", whole, x, "f32")
    shared = uncut - ref.expert_layer(q, "l1.", whole, x, "f32", shared=False)[0]
    total, slots = 0.0, 0.0
    for rank in range(every // held):
        mine = slice(rank * held, (rank + 1) * held)
        share = dict(q, **{"l1.w_gu": q["l1.w_gu"][mine],
                           "l1.w_down": q["l1.w_down"][mine]})
        out, counters = _expert_layer(c, share, 1, x, **dict(changes, ep_rank=rank))
        close(out, ref.expert_layer(share, "l1.", dict(c, first=rank * held),
                                    x, "f32")[0])
        total = total + out - shared  # the held experts' terms alone
        slots += float(counters["moe_local_slots"])
        assert float(counters["moe_dropped_slots"]) == 0.0
    assert slots == 64 * 5  # every slot is some share's
    close(total + shared, uncut)


# -- the whole model ---------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_loss_and_gradients_match_the_reference_at_two_periods(backend):
    changes = dict(num_hidden_layers=8)
    c, rp = _seeded(5, loud=True, **changes)
    assert c["kinds"] == (["linear_attention"] * 3 + ["full_attention"]) * 2
    pp = qwen3next_map.to_program(rp, c)
    model = _model(backend, **changes)
    ids = jax.random.randint(keys(1, 1)[0], (2, 21), 0, c["V"])
    want = jax.tree_util.tree_structure(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids))["params"])
    assert want == jax.tree_util.tree_structure(pp)

    def mine(p):
        logits, counters = model.apply({"params": p}, ids)
        return next_token_loss(logits, ids)[0], (logits, counters)

    (loss, (logits, counters)), grads = jax.value_and_grad(mine, has_aux=True)(pp)
    (ref_loss, routed), ref_grads = jax.value_and_grad(
        lambda p: ref.next_token_loss(p, c, ids), has_aux=True)(rp)
    close(logits, ref.forward(rp, c, ids)[0], 5e-5)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    grads = qwen3next_map.from_program(grads, c)
    for name in ref_grads:
        close(grads[name], ref_grads[name], 1e-4)
        assert float(jnp.max(jnp.abs(ref_grads[name]))) > 0, name
    lo, hi = c["first"], c["first"] + c["held"]
    local = sum(int(np.sum((np.asarray(r) >= lo) & (np.asarray(r) < hi)))
                for r in routed)
    assert float(counters["moe_local_slots"]) == local
    assert float(counters["moe_dropped_slots"]) == 0.0
    # 6 delta-rule layers x 2 rows x ceil(21 / 8) chunks
    assert float(counters["delta_chunks_run"]) == 6 * 2 * 3


def test_two_updates_through_make_train_step_match_the_reference():
    """Through the program's own step (micro-batch scan, clipping, AdamW with
    the no-decay mask) against the reference's AdamW: losses, and the
    parameters' change after two updates."""
    c = ref.sizes(TINY)
    recipe = ref.Recipe(learning_rate=1e-3, warmup_proportion=0.01,
                        max_steps=1000)
    seed = 11
    model = _model()
    schedule = optim.make_schedule("constant", recipe.learning_rate,
                                   recipe.warmup_proportion, recipe.max_steps)
    tx = optim.adamw(schedule, b1=recipe.b1, b2=recipe.b2, eps=recipe.eps,
                     weight_decay=recipe.weight_decay,
                     weight_decay_mask=optim.no_decay_mask,
                     max_grad_norm=recipe.max_grad_norm)
    params = qwen3next_map.to_program(
        ref.seeded_params(ref.key_from_seed(seed), c), c)
    state = pretrain.TrainState(params=params, opt_state=tx.init(params),
                                rng=jax.random.PRNGKey(0))
    step = pretrain.make_train_step(model, tx, schedule=schedule,
                                    next_sentence=False)
    rng = np.random.default_rng(0)
    updates = [rng.integers(0, c["V"], (2, 2, 24)).astype(np.int32)
               for _ in range(2)]
    losses = []
    for upd in updates:
        state, metrics = step(state, {"input_ids": jnp.asarray(upd)})
        losses.append(float(metrics["loss"]))
        assert float(metrics["moe_dropped_slots"]) == 0.0
        assert float(metrics["finite"]) == 1.0
        assert 0 < float(metrics["moe_local_slots"]) < 4 * 96 * 3
        # 3 delta-rule layers x 2 rows x 3 chunks, over 2 micro-batches
        assert float(metrics["delta_chunks_run"]) == 36.0
    followed = ref.follow(seed, TINY, recipe, updates)
    np.testing.assert_allclose(losses, followed["loss"], atol=2e-5)
    assert [r.shape for r in followed["chosen"]] == [(48, 3)] * 4
    start = ref.seeded_params(ref.key_from_seed(seed), c)
    mine = qwen3next_map.from_program(state.params, c)
    change = ref.leaf_norms({k: mine[k] - start[k] for k in mine})
    for name, want in followed["delta_norms"].items():
        # Adam divides by sqrt(v): where a gradient is all but zero its sign
        # is rounding, so the change is compared as a norm, at 2%.
        np.testing.assert_allclose(np.asarray(change[name]), want,
                                   rtol=0.02, atol=1e-7, err_msg=name)


# -- configuration, counts, FLOPs, optimizer mask ------------------------------------

def test_model_type_chooses_the_family_and_the_config_says_what_it_cannot_be(
        tmp_path):
    path = tmp_path / "c.json"
    path.write_text(json.dumps(dict(TINY, model_type="qwen3_next")))
    config = load_model_config(str(path))
    assert isinstance(config, Qwen3NextConfig)
    assert (config.router_experts, config.first_expert) == (8, 4)
    assert config.layer_types == ["linear_attention"] * 3 + ["full_attention"]
    assert config.to_dict()["model_type"] == "qwen3_next"
    whole = Qwen3NextConfig()
    assert whole.layer_types.count("full_attention") == 12
    assert [i for i, k in enumerate(whole.layer_types)
            if k == "full_attention"][:3] == [3, 7, 11]
    assert whole.rope == (64, {"rope_theta": 10000000, "rope_type": "default"})
    for wrong, match in (
            (dict(layer_types=["linear_attention"] * 3 + ["sliding"]),
             "layer_types"),
            (dict(tie_word_embeddings=True), "untied"),
            (dict(mlp_only_layers=[0]), "expert layer in every layer"),
            (dict(num_key_value_heads=3), "key-value heads"),
            (dict(linear_num_value_heads=3), "whole groups"),
            (dict(ep_rank=2), "ep_rank")):
        with pytest.raises(ValueError, match=match):
            Qwen3NextConfig(**dict(TINY, **wrong))


def _count(tree):
    return sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(tree))


def _shapes(config):
    model = build_pretraining_model(config, jnp.bfloat16)
    return jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))["params"]


def test_published_configuration_counts_626_million():
    """The benchmark's configuration file, built abstractly: the cut's
    arithmetic (ISSUE 41) against the tree's own count, part by part."""
    config = load_model_config("benchmarks/configs/qwen3-next-80b-a3b.json")
    shapes = _shapes(config)
    delta, attn = shapes["layers_0"], shapes["layers_3"]
    assert _count(delta["mixer"]) == pytest.approx(33.72e6, rel=1e-3)
    assert _count(attn["mixer"]) == pytest.approx(27.26e6, rel=1e-3)
    beside = lambda layer: sum(
        _count(v) for k, v in layer["mlp"].items()
        if not k.startswith("experts_")) + 2 * 2048
    assert beside(delta) == beside(attn) == pytest.approx(4.20e6, rel=2e-3)
    assert _count(delta["mlp"]["experts_up"]) + _count(
        delta["mlp"]["experts_down"]) == 32 * 3 * 2048 * 512
    assert delta["mixer"]["in_proj_qkvz"].shape == (2048, 12288)
    assert delta["mixer"]["conv_kernel"].shape == (4, 8192)
    assert attn["mixer"]["q_proj"].shape == (2048, 8192)
    assert attn["mixer"]["k_proj"]["kernel"].shape == (2048, 512)
    assert attn["mlp"]["router_kernel"].shape == (2048, 512)
    assert attn["mlp"]["shared_gate"].shape == (2048,)
    assert shapes["embedding"].shape == (19072, 2048)
    assert shapes["lm_head"]["kernel"].shape == (2048, 19072)
    assert _count(shapes) == 625_994_816
    assert 16 * _count(shapes) == pytest.approx(10.02e9, rel=1e-3)
    with open("benchmarks/configs/qwen3-next-80b-a3b.json") as f:
        written = json.load(f)
    for key, value in dict(
            hidden_size=2048, head_dim=256, num_attention_heads=16,
            num_key_value_heads=2, linear_num_key_heads=16,
            linear_num_value_heads=32, linear_key_head_dim=128,
            linear_value_head_dim=128, linear_conv_kernel_dim=4,
            moe_intermediate_size=512, shared_expert_intermediate_size=512,
            num_experts_per_tok=10, partial_rotary_factor=0.25,
            intermediate_size=5120, full_attention_interval=4).items():
        assert written[key] == value, key
    assert (config.router_experts, config.ep_size, config.ep_rank) == (512, 16, 0)
    for key in ("source", "reduced", "published", "assumed", "precision",
                "deployment"):
        assert written[key], key
    assert written["reduced"] == ["num_hidden_layers", "num_experts",
                                  "vocab_size"]
    assert 19072 == 149 * 128 >= 151936 / 8


def test_the_whole_model_counts_79_67_billion():
    shapes = _shapes(Qwen3NextConfig())
    assert _count(shapes) == pytest.approx(79.67e9, rel=2e-4)
    embeddings = 2 * 151936 * 2048
    experts = 48 * 512 * 3 * 2048 * 512
    active = _count(shapes) - embeddings - experts + 48 * 10 * 3 * 2048 * 512
    assert active == pytest.approx(3.25e9, rel=0.02)  # the published A3B


def test_flops_are_the_issues_arithmetic():
    config = load_model_config("benchmarks/configs/qwen3-next-80b-a3b.json")
    parts = {k: v / 1e6 for k, v in
             flops.qwen3_next_forward_flops_per_token(config, 8192).items()}
    assert parts["gdn_proj"] == pytest.approx(202.1, abs=0.1)
    assert parts["delta_rule"] == pytest.approx(15.7, abs=0.1)
    assert parts["attention_proj"] == pytest.approx(54.5, abs=0.1)
    assert parts["attention_core"] == pytest.approx(67.1, abs=0.1)
    assert parts["experts"] == pytest.approx(49.3, abs=0.1)
    assert parts["head"] == pytest.approx(78.1, abs=0.1)
    assert sum(parts.values()) == pytest.approx(466.9, abs=0.2)
    # 65,536 tokens an update
    assert flops.causal_lm_train_flops_per_seq(config, 8192) * 8 == (
        pytest.approx(91.8e12, rel=2e-3))


def test_no_decay_mask_leaves_out_the_new_vectors():
    model = _model(remat="none")
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    mask = optim.no_decay_mask(params)
    flat = {"/".join(k): v for k, v in
            flax.traverse_util.flatten_dict(mask).items()}
    assert {k.split("/")[-1] for k, v in flat.items() if not v} == {
        "scale", "A_log", "dt_bias", "norm_scale", "shared_gate"}
    assert flat["layers_0/mixer/conv_kernel"] and flat["embedding"]
    c = ref.sizes(TINY)
    for name, path in qwen3next_map.table(c).items():
        assert flat[path] == ref.decays(name, c), name


# -- the family's scopes reach the compiled step ------------------------------------

def _compiled_step_names(widths, seq):
    """Every ``op_name`` of the family's compiled train step (bfloat16,
    ``--remat full``, 2 micro-batches of one row of ``seq`` tokens)."""
    import re

    model = build_pretraining_model(Qwen3NextConfig(**widths), jnp.bfloat16,
                                    remat="full")
    tx = optim.adamw(1e-3, max_grad_norm=1.0,
                     weight_decay_mask=optim.no_decay_mask)
    state = pretrain.make_init_fn(
        model, tx, (jnp.zeros((1, 8), jnp.int32),), None)(jax.random.PRNGKey(0))
    step = pretrain.make_train_step(model, tx, next_sentence=False)
    batch = {"input_ids": np.zeros((2, 1, seq), np.int32)}
    text = step.lower(state, batch).compile().as_text()
    return set(re.findall(r'op_name="([^"]+)"', text))


@pytest.fixture(scope="module")
def step_names():
    return _compiled_step_names(TINY, 24)


@pytest.mark.parametrize("scope",
                         pretrain.QWEN3_NEXT_SCOPES + ("attention_core",))
def test_every_scope_of_the_family_reaches_the_compiled_step(step_names, scope):
    assert any(f"/{scope}/" in name or f"({scope})" in name
               for name in step_names), scope


def test_the_mixers_parts_lie_under_gdn_and_the_gate_under_moe_shared(
        step_names):
    for inner in ("gdn_in_proj", "gdn_conv", "gdn_gates", "delta_rule",
                  "gdn_gate_norm", "gdn_out_proj"):
        assert any(f"/gdn/{inner}/" in name for name in step_names), inner
    assert any("/moe/moe_shared/moe_shared_gate/" in name
               for name in step_names)


def test_at_fitting_shapes_the_rules_scope_holds_the_kernels_and_no_loop():
    """With keys and values of 128 in chunks of 64 (one delta-rule layer, 3
    chunks a row) the compiled step's ``delta_rule`` scope holds the calls of
    ``delta_rule_fwd`` (forward and recompute) and ``delta_rule_bwd``, and no
    loop but theirs (here the interpreter's walk over each kernel's grid): no
    ``lax.map`` over rows, no scan over chunks. The tiny widths' step keeps
    both loops of the XLA form."""
    fitting = dict(TINY, num_hidden_layers=1, linear_num_key_heads=1,
                   linear_num_value_heads=2, linear_key_head_dim=128,
                   linear_value_head_dim=128, delta_chunk=64)
    under = lambda names: {n.split("/delta_rule/", 1)[1] for n in names
                           if "/delta_rule/" in n}
    rule = under(_compiled_step_names(fitting, 192))
    for kernel in ("delta_rule_fwd/", "delta_rule_bwd/"):
        assert any(kernel in n for n in rule), kernel
    loops = {n for n in rule if "while" in n}
    assert loops and all(
        "while" not in n.split("delta_rule_fwd/")[0].split("delta_rule_bwd/")[0]
        for n in loops)
    assert any(n.startswith("while") for n in under(
        _compiled_step_names(dict(TINY, num_hidden_layers=1), 24)))


# -- the normal path ------------------------------------------------------------------

def test_run_pretraining_trains_the_family_from_its_config_file(tmp_path):
    """``run_pretraining.main`` builds the family from ``model_type``, feeds
    it rows of token ids and logs its counters with the train record."""
    import h5py

    import run_pretraining

    (tmp_path / "data").mkdir()
    rows = np.random.default_rng(0).integers(0, 256, (64, 32)).astype(np.int32)
    with h5py.File(tmp_path / "data" / "shard_000.hdf5", "w") as f:
        f.create_dataset("input_ids", data=rows)
    (tmp_path / "model.json").write_text(
        json.dumps(dict(TINY, model_type="qwen3_next")))
    args = run_pretraining.parse_arguments([
        "--input_dir", str(tmp_path / "data"),
        "--output_dir", str(tmp_path / "out"),
        "--model_config_file", str(tmp_path / "model.json"),
        "--local_batch_size", "2", "--global_batch_size", "16",
        "--optimizer", "adamw", "--adamw_clip", "--max_steps", "2",
        "--learning_rate", "1e-3", "--warmup_proportion", "0.5",
        "--lr_decay", "constant", "--dtype", "float32", "--remat", "full",
        "--seed", "3", "--skip_final_checkpoint", "--disable_tensorboard"])
    result = run_pretraining.main(args)
    assert result["global_step"] == 2 and np.isfinite(result["loss"])
    assert abs(result["loss"] - np.log(256)) < 0.5
    assert result["moe_dropped_slots"] == 0.0 and result["moe_local_slots"] > 0
    # 3 delta-rule layers x 16 rows x 4 chunks of 8
    assert result["delta_chunks_run"] == 3 * 16 * 4
    log = (tmp_path / "out" / "pretraining.txt").read_text()
    assert "delta_chunks_run" in log and "moe_tile_fill" in log
