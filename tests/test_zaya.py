"""The ``zaya`` family against its plain float32 reference
(``benchmarks/reference/zaya_f32.py``) at a small size on the CPU: each part
of the attention inside the latent against a hand-written case, the router's
state handed from layer to layer, top-1 routing with the skip, the two expert
shares adding up to the uncut layer, the whole model's loss and gradients, two
whole updates through ``pretrain.make_train_step``, and the normal path
(``run_pretraining.main``) from a config file.

Tolerances: everything here is float32 at ``highest`` on both sides
(conftest), so program and reference differ only in the ORDER of float32 sums
(the kernel's tiles, the experts' sorted slots, the second convolution's
products shifted after or before). A few 1e-6 relative to the largest element
is that; 2e-5 leaves a decade of room and would not pass a dropped
convolution, mean, shift, norm or router state (each moves the result by
percents: the "is seen" tests below).
"""

import json

import flax
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import zaya_f32 as ref
from benchmarks.reference import zaya_map
from bert_pytorch_tpu import optim, pretrain
from bert_pytorch_tpu.config import ZayaConfig, load_model_config
from bert_pytorch_tpu.models import build_pretraining_model, zaya
from bert_pytorch_tpu.models.losses import next_token_loss
from bert_pytorch_tpu.ops import moe
from bert_pytorch_tpu.utils import flops

# the published layer at a small size: 4 / 2 heads of 16 in a latent of 64 + 32
# beside a stream of 64, two taps twice, half of each head turned, 4 of 8
# experts held, the router 16 wide with 9 outputs
TINY = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    cca_time0=2, cca_time1=2, layer_types=["hybrid"] * 3,
    rope_parameters=ZayaConfig().rope_parameters, num_experts=4, ep_size=2,
    ep_rank=1, moe_intermediate_size=32, router_hidden_size=16,
    rms_norm_eps=1e-5, moe_piece_multiple=8)
TOL = 2e-5


def close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-30), (
        np.max(np.abs(a - b)), np.max(np.abs(b)))


def far(a, b, share=0.05):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) > share * np.max(np.abs(b))


def keys(n, seed=0):
    return jax.random.split(jax.random.PRNGKey(seed), n)


def _seeded(seed=3, loud=False, **changes):
    """Sizes and seeded weights; ``loud``: biases, temperatures and merges
    away from their 0 and 1, so that every parameter shows in the output."""
    c = ref.sizes(dict(TINY, **changes))
    p = ref.seeded_params(ref.key_from_seed(seed), c)
    if loud:
        table = ref.param_table(c)
        for index, name in enumerate(sorted(p)):
            draw = jax.random.fold_in(jax.random.PRNGKey(seed + 100), index)
            if table[name][1] in ("ones", "zeros") and "beta" not in name:
                # (a bias that drowns what differs from token to token would
                # send every token the same way)
                size = (0.3 if table[name][1] == "ones" else
                        0.005 if name.endswith(("_bx", "_by")) else 0.03)
                p[name] = p[name] + size * jax.random.normal(draw, p[name].shape)
    return c, p


def _attention(c, p, layer, x, backend="xla"):
    tree = zaya_map.to_program(p, c)[f"layers_{layer}"]["attn"]
    return zaya.CompressedConvAttention(
        ZayaConfig(**TINY), jnp.float32, backend).apply({"params": tree}, x)


# -- the attention inside the latent ----------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_attention_matches_the_reference(backend):
    c, p = _seeded(loud=True)
    x = jax.random.normal(keys(1, 1)[0], (2, 40, c["H"]))
    mine = lambda x_: _attention(c, p, 1, x_, backend)
    theirs = lambda x_: ref.cca(p, "l1.", c, x_, "f32", block_rows=16)
    close(mine(x), theirs(x))
    loss = lambda fn: (lambda x_: jnp.sum(jnp.sin(fn(x_))))
    close(jax.grad(loss(mine))(x), jax.grad(loss(theirs))(x))


def test_reference_attention_in_blocks_matches_whole():
    c, p = _seeded(loud=True)
    x = jax.random.normal(keys(1)[0], (2, 21, c["H"]))
    close(ref.cca(p, "l0.", c, x, "f32", block_rows=8),
          ref.cca(p, "l0.", c, x, "f32", block_rows=64))


def test_no_part_of_the_attention_reads_a_later_position():
    """Position t's output depends on positions <= t alone: a change at
    position 12 leaves the outputs before it bit for bit, in the program and
    in the reference, through both convolutions, the shifted values and the
    core."""
    c, p = _seeded(loud=True)
    x = jax.random.normal(keys(1, 2)[0], (1, 24, c["H"]))
    moved = x.at[:, 12].add(1.0)
    for fn in (lambda x_: _attention(c, p, 0, x_),
               lambda x_: ref.cca(p, "l0.", c, x_, "f32", block_rows=8)):
        a, b = np.asarray(fn(x)), np.asarray(fn(moved))
        np.testing.assert_array_equal(a[:, :12], b[:, :12])
        assert np.abs(a[:, 12:] - b[:, 12:]).max() > 1e-3


def test_the_convolutions_are_the_hand_written_sums():
    """A row whose only non-zero position is t = 5: convolution 0 puts ``a1
    z`` at 5 and ``a0 z`` at 6, convolution 1 mixes each head's channels and
    reaches position 7, nothing lands before 5, and the biases are what a
    zero input gives."""
    c, p = _seeded(loud=True)
    pre, hd, groups = "l0.", c["hd"], c["heads"] + c["KV"]
    h = jnp.zeros((1, 10, c["H"])).at[0, 5].set(
        jax.random.normal(keys(1, 3)[0], (c["H"],)))
    z = np.concatenate([np.asarray(h[0, 5] @ p[pre + "wq"]),
                        np.asarray(h[0, 5] @ p[pre + "wk"])])
    a, bias0 = np.asarray(p[pre + "conv0"]), np.asarray(p[pre + "conv0_b"])
    z1 = {t: bias0 for t in range(10)}
    z1[5], z1[6] = bias0 + a[1] * z, bias0 + a[0] * z
    A, bias1 = np.asarray(p[pre + "conv1"]), np.asarray(p[pre + "conv1_b"])
    mix = lambda tap, v: np.einsum("gi,gio->go", v.reshape(groups, hd), A[tap])
    z2 = lambda t: bias1 + mix(0, z1[t - 1]) + mix(1, z1[t])
    # the reference's q and k before mean and norm, read back through them:
    # with Wq's and Wk's mean term zero elsewhere, positions other than 5 are
    # the convolutions' output alone, normed
    q, k = ref.latent_qk(p, pre, c, h, "f32")
    for t in (4, 6, 7, 8):
        want = z2(t)
        norm = lambda v: v * np.sqrt(hd) / np.linalg.norm(v, axis=-1, keepdims=True)
        close(q[0, t], norm(want[:c["heads"]]))
        close(k[0, t], norm(want[c["heads"]:]) * np.asarray(
            p[pre + "tau"])[:, None])
    close(z2(4), z2(8))  # before the impulse and past its reach: biases alone


def test_queries_and_keys_have_the_length_sqrt_d():
    c, p = _seeded(loud=True)
    x = jax.random.normal(keys(1, 4)[0], (2, 16, c["H"]))
    q, k = ref.latent_qk(p, "l1.", c, x, "f32")
    np.testing.assert_allclose(np.linalg.norm(q, axis=-1), np.sqrt(c["hd"]),
                               rtol=1e-5)
    np.testing.assert_allclose(
        np.linalg.norm(k, axis=-1),
        np.sqrt(c["hd"]) * np.abs(np.asarray(p["l1.tau"]))[None, None, :]
        * np.ones(k.shape[:3]), rtol=1e-5)
    # the program's, caught on its way into the core
    caught = {}

    def core(q, k, v, **kwargs):
        caught.update(q=q, k=k, v=v, **kwargs)
        return jnp.zeros(q.shape[:3] + (v.shape[-1],), q.dtype)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(zaya, "dot_product_attention", core)
        patch.setattr(zaya.rope, "apply_rotary", lambda t, cos, sin: t)
        _attention(c, p, 1, x)
    close(caught["q"], q)
    close(caught["k"], k)
    assert caught["causal"] is True and caught["label"] == "cca"
    assert caught["q"].shape[2:] == (4, 16) and caught["k"].shape[2:] == (2, 16)


def test_the_second_half_of_the_value_heads_reads_the_token_before():
    c, p = _seeded()
    x = jax.random.normal(keys(1, 5)[0], (2, 12, c["H"]))
    v = np.asarray(ref.values(p, "l0.", c, x, "f32"))
    plain = np.asarray(x @ p["l0.wv"]).reshape(2, 12, c["KV"], c["hd"])
    close(v[:, :, 0], plain[:, :, 0])                 # head 0: this token
    close(v[:, 1:, 1], plain[:, :-1, 1])              # head 1: the one before
    np.testing.assert_array_equal(v[:, 0, 1], 0.0)    # nothing before the row
    caught = {}

    def core(q, k, v, **kwargs):
        caught["v"] = v
        return jnp.zeros(q.shape, q.dtype)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(zaya, "dot_product_attention", core)
        _attention(c, p, 0, x)
    close(caught["v"], v)
    np.testing.assert_array_equal(np.asarray(caught["v"])[:, 0, 1], 0.0)


def test_the_qk_mean_is_added_after_the_convolutions_per_group():
    """With both convolutions silent ``q`` and ``k`` are the normed means:
    query head i of group j gets ``(q0[i] + k0[j]) / 2``, key head j the mean
    of its two queries' ``q0`` and its own ``k0``, halved."""
    c, p = _seeded(loud=True)
    quiet = dict(p, **{name: 0 * p[name] for name in (
        "l1.conv0", "l1.conv0_b", "l1.conv1", "l1.conv1_b")})
    x = jax.random.normal(keys(1, 6)[0], (2, 9, c["H"]))
    q0 = np.asarray(x @ p["l1.wq"]).reshape(2, 9, 4, 16)
    k0 = np.asarray(x @ p["l1.wk"]).reshape(2, 9, 2, 16)
    norm = lambda v: v * 4.0 / np.linalg.norm(v, axis=-1, keepdims=True)
    q, k = ref.latent_qk(quiet, "l1.", c, x, "f32")
    for i in range(4):
        close(q[:, :, i], norm((q0[:, :, i] + k0[:, :, i // 2]) / 2))
    for j in range(2):
        close(k[:, :, j], norm((q0[:, :, 2 * j:2 * j + 2].mean(2) + k0[:, :, j])
                               / 2) * float(p["l1.tau"][j]))
    far(ref.latent_qk(p, "l1.", c, x, "f32")[0], q)  # the convolutions count


@pytest.mark.parametrize("dropped", ["conv0", "conv1", "shift", "rotary", "tau"])
def test_a_dropped_part_of_the_attention_is_seen(dropped):
    """Not blind: the reference with one part taken out is far from the
    program's layer."""
    c, p = _seeded(loud=True)
    # (queries and keys are normed, so what sharpens the softmax is tau)
    p = dict(p, **{"l1.tau": p["l1.tau"] * 3})
    x = jax.random.normal(keys(1, 9)[0], (2, 40, c["H"]))
    mine = _attention(c, p, 1, x)
    q = dict(p)
    if dropped in ("conv0", "conv1"):  # the tap on the position before
        q["l1." + dropped] = p["l1." + dropped].at[0].set(0.0)
    elif dropped == "tau":
        q["l1.tau"] = jnp.ones_like(p["l1.tau"])
    with pytest.MonkeyPatch.context() as patch:
        if dropped == "shift":
            patch.setattr(ref, "values", lambda p_, prefix, c_, h, precision: (
                h @ p_[prefix + "wv"]).reshape(h.shape[0], h.shape[1],
                                               c_["KV"], c_["hd"]))
        elif dropped == "rotary":
            patch.setattr(ref, "rotate", lambda t, rotary, rope: t)
        wrong = ref.cca(q, "l1.", c, x, "f32")
    far(wrong, mine, share=0.01)


# -- the router: its state, top-1, the skip -----------------------------------------

def _router(c, p, layer, h, before):
    tree = zaya_map.to_program(p, c)[f"layers_{layer}"]["router"]
    return zaya.ZayaRouter(ZayaConfig(**TINY)).apply({"params": tree}, h, before)


def test_router_matches_the_reference_and_the_weight_is_the_chosen_probability():
    c, p = _seeded(loud=True)
    h = jax.random.normal(keys(1, 6)[0], (48, c["H"]))
    before = jax.random.normal(keys(1, 7)[0], (48, c["R"]))
    state, chosen, weights = _router(c, p, 1, h, before)
    ref_state, ref_chosen, ref_weight, probs = ref.router(p, "l1.", c, h, before)
    close(state, ref_state)
    np.testing.assert_array_equal(chosen[:, 0], ref_chosen)
    close(weights[:, 0], ref_weight)
    # top-1, never renormalised: the weight is p_e itself and sums to no 1
    np.testing.assert_array_equal(chosen[:, 0], np.argmax(probs, axis=-1))
    close(weights[:, 0], np.max(probs, axis=-1))
    assert probs.shape[-1] == c["experts"] + 1 == 9
    assert float(jnp.max(weights)) < 1.0 and len(set(np.asarray(chosen[:, 0]))) > 3
    # the first layer has no state before it and no gamma
    assert "eda_scale" not in zaya_map.to_program(p, c)["layers_0"]["router"]
    first = _router(c, p, 0, h, None)[0]
    close(first, h @ p["l0.wd"] + p["l0.bd"])


def test_the_correction_bias_chooses_and_gets_no_gradient():
    c, p = _seeded(loud=True)
    h = jax.random.normal(keys(1, 8)[0], (32, c["H"]))
    pushed = dict(p, **{"l0.beta": p["l0.beta"].at[2].set(10.0)})
    _, chosen, weights = _router(c, pushed, 0, h, None)
    np.testing.assert_array_equal(chosen, 2)
    probs = ref.router(p, "l0.", c, h, None)[3]
    close(weights[:, 0], probs[:, 2])  # the probability, not the biased score
    tree = zaya_map.to_program(pushed, c)["layers_0"]["router"]
    grads = jax.grad(lambda t: jnp.sum(zaya.ZayaRouter(ZayaConfig(**TINY)).apply(
        {"params": t}, h, None)[2]))(tree)
    np.testing.assert_array_equal(grads["router_correction_bias"], 0.0)
    assert float(jnp.max(jnp.abs(grads["out_proj"]["kernel"]))) > 0


def _model(backend="xla", remat="full", **changes):
    return build_pretraining_model(ZayaConfig(**dict(TINY, **changes)),
                                   jnp.float32, remat=remat,
                                   attention_backend=backend)


def test_a_layers_routing_reads_the_state_of_the_layer_before():
    """Layer 1's probabilities move when layer 0's ``r`` is perturbed, the
    later layers' choices change when the hand-on is cut (gamma at zero), the
    program's choices are the reference's either way, and gamma's gradient is
    not zero."""
    c, p = _seeded(loud=True)
    h = jax.random.normal(keys(1, 6)[0], (32, c["H"]))
    r0 = ref.router(p, "l0.", c, h, None)[0]
    far(ref.router(p, "l1.", c, h, r0 + 0.5)[3], ref.router(p, "l1.", c, h, r0)[3])
    far(ref.router(p, "l1.", c, h, None)[3], ref.router(p, "l1.", c, h, r0)[3])
    ids = jax.random.randint(keys(1, 1)[0], (2, 16), 0, c["V"])
    silent = dict(p, **{"l1.gamma": 0 * p["l1.gamma"],
                        "l2.gamma": 0 * p["l2.gamma"]})
    model = _model()

    def mine(q):
        (out, _), kept = model.apply(
            {"params": zaya_map.to_program(q, c)}, ids,
            mutable=["intermediates"])
        return out, [np.asarray(kept["intermediates"][f"layers_{i}"]["mlp"][
            "chosen"][0]).reshape(2, 16) for i in range(3)]

    for q in (p, silent):
        logits, routed = ref.forward(q, c, ids)
        close(mine(q)[0], logits)
        for got, want in zip(mine(q)[1], routed):
            np.testing.assert_array_equal(got, want)
    heard, cut = ref.forward(p, c, ids)[1], ref.forward(silent, c, ids)[1]
    np.testing.assert_array_equal(heard[0], cut[0])  # nothing before layer 0
    assert int(np.sum(np.asarray(heard[2]) != np.asarray(cut[2]))) > 8
    grads = jax.grad(lambda q: jnp.sum(jnp.sin(model.apply(
        {"params": zaya_map.to_program(q, c)}, ids)[0])))(p)
    for name in ("l1.gamma", "l2.gamma", "l0.bd"):
        assert float(jnp.max(jnp.abs(grads[name]))) > 1e-6, name


def test_a_token_that_draws_the_skip_adds_exactly_zero_and_is_counted():
    c, p = _seeded(loud=True, ep_rank=0)
    skip = c["experts"]
    # every token of layer 1 is pushed to the skip; layers 0 and 2 route freely
    pushed = dict(p, **{"l1.beta": p["l1.beta"].at[skip].set(10.0)})
    ids = jax.random.randint(keys(1, 2)[0], (2, 16), 0, c["V"])
    model = _model(ep_rank=0)
    tree = zaya_map.to_program(pushed, c)
    (_, counters), kept = model.apply({"params": tree}, ids,
                                      mutable=["intermediates"])
    chosen = kept["intermediates"]["layers_1"]["mlp"]["chosen"][0]
    np.testing.assert_array_equal(chosen, skip)
    free = model.apply({"params": zaya_map.to_program(p, c)}, ids)[1]
    assert float(counters["moe_skip_slots"]) - float(free["moe_skip_slots"]) == (
        32 - float(jnp.sum(ref.forward(p, c, ids)[1][1] == skip)))
    assert float(counters["moe_dropped_slots"]) == 0.0
    assert float(counters["router_carried_layers"]) == 2.0
    # the layer alone: the experts' term is exactly zero on every token
    h = jax.random.normal(keys(1, 3)[0], (2, 16, c["H"]))
    out, _, _ = ref.expert_layer(pushed, "l1.", c, h, None, "f32")
    np.testing.assert_array_equal(out, 0.0)
    routing = (jnp.full((32, 1), skip, jnp.int32), jnp.ones((32, 1)))
    mine, counted = zaya.expert_layer(ZayaConfig(**dict(TINY, ep_rank=0)),
                                      jnp.float32).apply(
        {"params": tree["layers_1"]["mlp"]}, h, routing)
    np.testing.assert_array_equal(mine, 0.0)
    assert float(counted["moe_local_slots"]) == 0.0
    assert float(counted["moe_pieces_run"]) == 0.0


def _program_routed(c, p, x, first, held_weights, before=None):
    state, chosen, weights = _router(c, p, 1, x, before)
    w_gu, w_down = held_weights
    out, counters = moe.held_experts(
        x, chosen, weights, w_gu, w_down, first, c["experts"] + 1,
        jax.nn.silu, multiple=8, gated=True)
    return out, counters, chosen


def test_expert_layer_matches_the_reference():
    """Value and the gradients with respect to x, the router's tensors and
    both expert tensors (the gradient reaches the router through ``p_e``
    alone)."""
    c, p = _seeded(4, loud=True)
    x = jax.random.normal(keys(1, 4)[0], (48, c["H"]))
    before = jax.random.normal(keys(1, 5)[0], (48, c["R"]))
    names = ("l1.wd", "l1.w3", "l1.gamma", "l1.w_gu", "l1.w_down")

    def mine(x_, *w):
        q = dict(p, **dict(zip(names, w)))
        return _program_routed(c, q, x_, c["first"],
                               (q["l1.w_gu"], q["l1.w_down"]), before)[0]

    def theirs(x_, *w):
        q = dict(p, **dict(zip(names, w)))
        return ref.expert_layer(q, "l1.", c, x_, before, "f32")[0]

    args = (x,) + tuple(p[n] for n in names)
    close(mine(*args), theirs(*args))
    loss = lambda fn: (lambda *a: jnp.sum(jnp.sin(fn(*a))))
    got = jax.grad(loss(mine), argnums=range(6))(*args)
    want = jax.grad(loss(theirs), argnums=range(6))(*args)
    for name, g, w in zip(("x",) + names, got, want):
        close(g, w)
        assert float(jnp.max(jnp.abs(w))) > 0, name


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """The terms that ``ep_rank`` 0's and 1's experts give add up to the uncut
    reference's whole layer: no shared expert to count once, and the skip's
    slots are nobody's."""
    c, p = _seeded(4, loud=True)
    x = jax.random.normal(keys(1, 8)[0], (64, c["H"]))
    held, every = c["held"], c["experts"]
    k = keys(2, 9)
    q = dict(p)
    q["l1.w_gu"] = c["std"] * jax.random.normal(k[0], (every, c["H"], 2 * c["F"]))
    q["l1.w_down"] = c["std"] * jax.random.normal(k[1], (every, c["F"], c["H"]))
    whole = dict(c, held=every, first=0)  # every expert, one chip
    uncut, _, chosen = ref.expert_layer(q, "l1.", whole, x, None, "f32")
    total, slots = 0.0, 0.0
    for rank in range(every // held):
        mine = slice(rank * held, (rank + 1) * held)
        out, counters, _ = _program_routed(
            c, q, x, rank * held, (q["l1.w_gu"][mine], q["l1.w_down"][mine]))
        total = total + out
        slots += float(counters["local_slots"])
        assert float(counters["dropped_slots"]) == 0.0
        # the reference's own share, one rank at a time, is the same term
        close(out, ref.expert_layer(
            dict(q, **{"l1.w_gu": q["l1.w_gu"][mine],
                       "l1.w_down": q["l1.w_down"][mine]}),
            "l1.", dict(c, first=rank * held), x, None, "f32")[0])
    skipped = int(np.sum(np.asarray(chosen) == every))
    assert 0 < skipped < x.shape[0]
    assert slots == x.shape[0] - skipped  # every other slot is some share's
    close(total, uncut)


def test_the_other_families_route_as_before():
    """``route`` is ``choose`` of the matrix's logits: the sigmoid rule with
    its bias, the softmax rule without, and an unknown rule refused."""
    x = jax.random.normal(keys(1, 1)[0], (16, 8))
    w = jax.random.normal(keys(1, 2)[0], (8, 6))
    bias = jnp.arange(6.0)
    for score, correction in (("sigmoid", bias), ("softmax", None)):
        chosen, weights = moe.route(x, w, correction, 2, 2.5, True, score)
        again = moe.choose(jnp.matmul(x, w, precision="highest"), correction, 2,
                           2.5, True, score)
        np.testing.assert_array_equal(chosen, again[0])
        np.testing.assert_array_equal(weights, again[1])
    np.testing.assert_array_equal(
        moe.route(x, w, bias, 2, 1.0, True, "sigmoid")[0][:, 0], 5)
    with pytest.raises(ValueError, match="score"):
        moe.choose(x @ w, None, 1, 1.0, False, "tanh")


# -- the whole model -------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_loss_and_gradients_match_the_reference(backend):
    c, rp = _seeded(5, loud=True)
    pp = zaya_map.to_program(rp, c)
    model = _model(backend)
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
    close(logits, ref.forward(rp, c, ids)[0])
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    grads = zaya_map.from_program(grads, c)
    for name in ref_grads:
        close(grads[name], ref_grads[name])
        if not name.endswith(".beta"):  # every other tensor is trained
            assert float(jnp.max(jnp.abs(ref_grads[name]))) > 0, name
    lo, hi = c["first"], c["first"] + c["held"]
    local = sum(int(np.sum((np.asarray(r) >= lo) & (np.asarray(r) < hi)))
                for r in routed)
    assert float(counters["moe_local_slots"]) == local
    assert float(counters["moe_skip_slots"]) == sum(
        int(np.sum(np.asarray(r) == c["experts"])) for r in routed)
    assert float(counters["moe_dropped_slots"]) == 0.0
    assert float(counters["router_carried_layers"]) == 2.0


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
    params = zaya_map.to_program(
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
        # two micro-batches of two rows of 24: every token of every layer is
        # local, another chip's or the skip's; the carried layers are a mean
        assert 0 < float(metrics["moe_local_slots"]) < 3 * 96
        assert 0 < float(metrics["moe_skip_slots"]) < 3 * 96
        assert float(metrics["router_carried_layers"]) == 2.0
    followed = ref.follow(seed, TINY, recipe, updates)
    np.testing.assert_allclose(losses, followed["loss"], atol=2e-5)
    assert [r.shape for r in followed["chosen"]] == [(48, 1)] * 3
    start = ref.seeded_params(ref.key_from_seed(seed), c)
    mine = zaya_map.from_program(state.params, c)
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
    path.write_text(json.dumps(dict(TINY, model_type="zaya")))
    config = load_model_config(str(path))
    assert isinstance(config, ZayaConfig)
    assert (config.router_experts, config.router_outputs,
            config.first_expert) == (8, 9, 4)
    assert config.rope[0] == 8 and config.rope[1]["rope_theta"] == 5000000
    assert config.to_dict()["model_type"] == "zaya"
    whole = ZayaConfig()
    assert whole.layer_types == ["hybrid"] * 40 and whole.router_outputs == 17
    for wrong, match in (
            (dict(sliding_window=4096), "sliding_window"),
            (dict(layer_types=["hybrid", "hybrid", "hybrid_sliding"]),
             "layer_types"),
            (dict(num_experts_per_tok=2), "one expert a token"),
            (dict(tie_word_embeddings=False), "tied"),
            (dict(zaya_use_eda=False), "handed from layer to layer"),
            (dict(num_key_value_heads=1), "even number"),
            (dict(cca_time1=0), "at least one tap"),
            (dict(ep_rank=2), "ep_rank")):
        with pytest.raises(ValueError, match=match):
            ZayaConfig(**dict(TINY, **wrong))


def _count(tree):
    return sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(tree))


def test_published_configuration_counts_602_million():
    """The benchmark's configuration file, built abstractly: the cut's
    arithmetic (ISSUE 39) against the tree's own count, part by part."""
    config = load_model_config("benchmarks/configs/zaya1-8b.json")
    model = build_pretraining_model(config, jnp.bfloat16)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))["params"]
    assert _count(shapes["layers_1"]["attn"]) == pytest.approx(5.58e6, rel=2e-3)
    assert _count(shapes["layers_1"]["router"]) == pytest.approx(0.66e6, rel=5e-3)
    assert _count(shapes["layers_0"]["router"]) == _count(
        shapes["layers_1"]["router"]) - 256  # no gamma in the first layer
    outside = sum(_count(v) for k, v in shapes["layers_1"].items() if k != "mlp")
    assert outside == pytest.approx(6.26e6, rel=2e-3)
    assert _count(shapes["layers_1"]["mlp"]) == 8 * 3 * 2048 * 2048
    assert set(shapes["layers_1"]["mlp"]) == {"experts_up", "experts_down"}
    assert shapes["layers_1"]["router"]["out_proj"]["kernel"].shape == (256, 17)
    assert shapes["layers_1"]["attn"]["o_proj"]["kernel"].shape == (1024, 2048)
    assert shapes["embedding"].shape == (32896, 2048) and "lm_head" not in shapes
    assert _count(shapes) == 601_975_135
    assert 16 * _count(shapes) == pytest.approx(9.63e9, rel=1e-3)
    # every width is the published one
    with open("benchmarks/configs/zaya1-8b.json") as f:
        written = json.load(f)
    for key, value in dict(
            hidden_size=2048, head_dim=128, num_attention_heads=8,
            num_key_value_heads=2, moe_intermediate_size=2048,
            router_hidden_size=256, num_experts_per_tok=1, cca_time0=2,
            cca_time1=2, partial_rotary_factor=0.5).items():
        assert written[key] == value, key
    assert (config.router_outputs, config.ep_size, config.ep_rank) == (17, 2, 0)
    for key in ("reduced", "published", "assumed", "precision", "deployment"):
        assert written[key], key
    assert written["reduced"] == ["num_hidden_layers", "num_experts",
                                  "vocab_size", "layer_types"]
    assert 32896 == 257 * 128 >= 262272 / 8


def test_the_whole_model_counts_8_3_billion_and_the_embedding():
    model = build_pretraining_model(ZayaConfig(), jnp.bfloat16)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))["params"]
    embedding = 262272 * 2048
    assert _count(shapes) == 8_840_485_368
    assert _count(shapes) - embedding == pytest.approx(8.30e9, rel=1e-3)
    active = _count(shapes) - embedding - 40 * 15 * 3 * 2048 * 2048
    assert active == pytest.approx(754e6, rel=2e-3)  # the published A0.76B


def test_flops_are_the_issues_arithmetic():
    config = load_model_config("benchmarks/configs/zaya1-8b.json")
    parts = {k: v / 1e6 for k, v in
             flops.zaya_forward_flops_per_token(config, 8192).items()}
    assert parts["cca_core"] / 5 == pytest.approx(16.8, abs=0.05)
    assert parts["cca_proj"] / 5 == pytest.approx(11.1, abs=0.05)
    assert parts["router"] / 5 == pytest.approx(1.3, abs=0.05)
    assert parts["experts"] / 5 == pytest.approx(11.8, abs=0.05)
    assert parts["head"] == pytest.approx(134.7, abs=0.05)
    assert sum(parts.values()) == pytest.approx(340.2, abs=0.2)
    assert parts["head"] / sum(parts.values()) == pytest.approx(0.40, abs=0.005)
    # 65,536 tokens an update
    assert flops.causal_lm_train_flops_per_seq(config, 8192) * 8 == (
        pytest.approx(66.9e12, rel=2e-3))


def test_no_decay_mask_covers_the_family():
    model = _model(remat="none")
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    mask = optim.no_decay_mask(params)
    flat = {"/".join(k): v for k, v in
            flax.traverse_util.flatten_dict(mask).items()}
    assert {k.split("/")[-1] for k, v in flat.items() if not v} == {
        "scale", "bias", "x_scale", "x_bias", "y_scale", "y_bias", "k_scale",
        "eda_scale", "conv0_bias", "conv1_bias", "router_correction_bias"}
    c = ref.sizes(TINY)
    for name, path in zaya_map.table(c).items():
        assert flat[path] == ref.decays(name, c), name


# -- the family's scopes reach the compiled step ------------------------------------

@pytest.fixture(scope="module")
def zaya_step_names():
    import re

    model = build_pretraining_model(ZayaConfig(**TINY), jnp.bfloat16,
                                    remat="full")
    tx = optim.adamw(1e-3, max_grad_norm=1.0,
                     weight_decay_mask=optim.no_decay_mask)
    state = pretrain.make_init_fn(
        model, tx, (jnp.zeros((1, 8), jnp.int32),), None)(jax.random.PRNGKey(0))
    step = pretrain.make_train_step(model, tx, next_sentence=False)
    batch = {"input_ids": np.zeros((2, 1, 24), np.int32)}
    text = step.lower(state, batch).compile().as_text()
    return set(re.findall(r'op_name="([^"]+)"', text))


@pytest.mark.parametrize("scope", pretrain.ZAYA_SCOPES + ("attention_core",))
def test_every_scope_of_the_family_reaches_the_compiled_step(
        zaya_step_names, scope):
    assert any(f"/{scope}/" in name or f"({scope})" in name
               for name in zaya_step_names), scope


def test_the_routers_parts_lie_under_moe_route_and_the_mixing_under_cca(
        zaya_step_names):
    for inner in ("router_down", "router_eda", "router_mlp"):
        assert any(f"/moe/moe_route/router/{inner}/" in name
                   for name in zaya_step_names), inner
    for inner in ("attn_qkv", "cca_conv", "cca_qk_mean", "cca_value_shift",
                  "cca_norm", "attn_rope", "attention_core", "attn_out"):
        assert any(f"/cca/{inner}/" in name for name in zaya_step_names), inner


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
        json.dumps(dict(TINY, model_type="zaya")))
    args = run_pretraining.parse_arguments([
        "--input_dir", str(tmp_path / "data"),
        "--output_dir", str(tmp_path / "out"),
        "--model_config_file", str(tmp_path / "model.json"),
        "--local_batch_size", "1", "--global_batch_size", "16",
        "--optimizer", "adamw", "--adamw_clip", "--max_steps", "2",
        "--learning_rate", "1e-3", "--warmup_proportion", "0.5",
        "--lr_decay", "constant", "--dtype", "float32", "--remat", "full",
        "--seed", "3", "--skip_final_checkpoint", "--disable_tensorboard"])
    result = run_pretraining.main(args)
    assert result["global_step"] == 2 and np.isfinite(result["loss"])
    assert abs(result["loss"] - np.log(256)) < 0.5
    assert result["moe_dropped_slots"] == 0.0 and result["moe_local_slots"] > 0
    # 16 rows of 32 tokens through 3 layers: local, absent or skipped
    assert 0 < result["moe_skip_slots"] < 3 * 16 * 32
    assert result["router_carried_layers"] == 2.0
    log = (tmp_path / "out" / "pretraining.txt").read_text()
    assert "moe_skip_slots" in log and "router_carried_layers" in log
