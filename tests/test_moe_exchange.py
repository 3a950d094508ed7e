"""The expert axis and the exchange between chips (``ops/moe.py
exchanged_experts``, ``pretrain.make_train_step`` under ``--mesh ep=4``) at a
small size on the CPU's virtual devices, against the same program on one
device and against the plain reference (``benchmarks/reference/
mellum_f32.py``, which knows nothing of chips).

Tolerances: float32 at ``highest`` everywhere (conftest). One device, four
devices and the reference differ only in the ORDER of float32 sums (which
round of the exchange a slot rides in, the head's columns summed over chips,
the whole tensors' gradients summed over chips): 2e-5 of a tensor's largest
element, as ``tests/test_mellum.py``. After AdamW a gradient that is all but
zero has a sign that is rounding (the update is m / sqrt(v): a tiny gradient's
rounding is divided by itself), so updated parameters are compared at 1e-2
of the tensor's largest change, and bit for bit where the claim is about
bits (the whole tensors on the four devices).
"""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmarks.reference import mellum_f32 as ref
from benchmarks.reference import mellum_map
from bert_pytorch_tpu import optim, pretrain
from bert_pytorch_tpu.config import MellumConfig
from bert_pytorch_tpu.models import build_pretraining_model
from bert_pytorch_tpu.models.laguna import expert_layer
from bert_pytorch_tpu.ops import moe
from bert_pytorch_tpu.parallel import MeshSpec, create_mesh, logical_axis_rules
from bert_pytorch_tpu.parallel.mesh import AXIS_EXPERT
from tests.test_mellum import TINY, close, seeded, tiny_model

RECIPE = ref.Recipe(learning_rate=1e-3, warmup_proportion=0.01, max_steps=1000)


def mesh_of(text, devices):
    spec = MeshSpec.parse(text)
    return spec, create_mesh(spec.mesh_config(),
                             devices=jax.devices()[:devices])


def skewed(rp, c, scale=40.0):
    """A seeded router that sends every token's first choice to chip 0: its
    two experts' columns are one direction and its negative, far larger than
    the rest, so one of the two wins whatever the token."""
    out = dict(rp)
    for i in range(c["L"]):
        w = np.array(rp[f"l{i}.router"])
        w[:, 0] = scale * w[:, 2]
        w[:, 1] = -w[:, 0]
        out[f"l{i}.router"] = jnp.asarray(w)
    return out


def step_under(text, devices, rp, c, updates, dtype=jnp.float32):
    """Two updates through ``make_train_step`` under the mesh ``text``:
    (first gradients under the reference's names, losses, metrics, final
    state)."""
    spec, mesh = mesh_of(text, devices)
    model = tiny_model(dtype)
    schedule = optim.make_schedule("constant", RECIPE.learning_rate,
                                   RECIPE.warmup_proportion, RECIPE.max_steps)
    tx = optim.adamw(schedule, b1=RECIPE.b1, b2=RECIPE.b2, eps=RECIPE.eps,
                     weight_decay=RECIPE.weight_decay,
                     weight_decay_mask=optim.no_decay_mask,
                     max_grad_norm=RECIPE.max_grad_norm)
    sample = (jnp.zeros((1, 16), jnp.int32),)
    with mesh:
        shardings = pretrain.state_shardings(
            mesh, model, logical_axis_rules(spec), sample)
        b_shardings = pretrain.batch_shardings(mesh, {"input_ids": 3})
        # (from host copies: the step donates its state)
        params = jax.device_put(
            mellum_map.to_program(jax.device_get(rp), c), shardings.params)
        state = pretrain.TrainState(
            params=params, opt_state=jax.jit(
                tx.init, out_shardings=shardings.opt_state)(params),
            rng=jax.device_put(jax.random.PRNGKey(0), shardings.rng))
        step = pretrain.make_train_step(
            model, tx, schedule=schedule, next_sentence=False, mesh=mesh,
            shardings=shardings, batch_shardings_=b_shardings)
        losses, metrics, first = [], [], None
        for upd in updates:
            state, m = step(state, jax.device_put(
                {"input_ids": jnp.asarray(upd)}, b_shardings))
            losses.append(float(m["loss"]))
            metrics.append({k: float(v) for k, v in m.items()})
            if first is None:  # the first moment holds the clipped gradient
                clip = min(1.0, RECIPE.max_grad_norm
                           / (metrics[0]["grad_norm"] + 1e-6))
                first = {k: np.asarray(v) / ((1 - RECIPE.b1) * clip)
                         for k, v in mellum_map.from_program(
                             jax.device_get(state.opt_state.mu), c).items()}
    return first, losses, metrics, state


@pytest.fixture(scope="module")
def updates():
    rng = np.random.default_rng(0)
    return [rng.integers(0, TINY["vocab_size"], (2, 4, 24)).astype(np.int32)
            for _ in range(2)]


@pytest.fixture(scope="module", params=["seeded", "skewed"])
def runs(request, updates):
    """(sizes, one device's run, four devices' run, the reference's) of two
    updates from one seed: under the seeded router, and under one skewed so
    that chip 0's experts take every token's first choice."""
    c, rp = seeded(11)
    if request.param == "skewed":
        rp = skewed(rp, c)
    one = step_under("dp=1", 1, rp, c, updates)
    four = step_under("ep=4", 4, rp, c, updates)

    def reference():
        (loss, _), g = jax.value_and_grad(
            lambda p_: ref.next_token_loss(
                p_, c, jnp.asarray(updates[0].reshape(-1, 24))),
            has_aux=True)(rp)
        return g, float(loss)

    return request.param, c, one, four, reference()


# -- (b), (c): the step under ep=4 ---------------------------------------------

def test_losses_and_gradients_equal_one_device_and_the_reference(runs):
    kind, c, one, four, (ref_grads, ref_loss) = runs
    # (under the skewed router a token's second choice is among near-ties: on
    # the SECOND update one may fall the other way on one device and on four,
    # a term's worth of difference and no rounding; the first update's
    # numbers are from the same weights)
    np.testing.assert_allclose(four[1][:1], one[1][:1], atol=2e-5)
    np.testing.assert_allclose(four[1], one[1],
                               atol=2e-3 if kind == "skewed" else 2e-5)
    # (the update's loss is the mean over micro-batches of equal size: the
    # reference's over all the update's rows at once)
    assert four[1][0] == pytest.approx(ref_loss, abs=2e-5)
    # (gradients read back from the first moment: one more rounding than
    # TOL's; under the skewed router, whose columns are 40 times larger, a
    # rounding of the router's input moves 40 times more)
    tol = 1e-3 if kind == "skewed" else 1e-4
    for name, want in ref_grads.items():
        close(four[0][name], one[0][name], tol=tol)
        close(four[0][name], want, tol=tol)


def test_updated_parameters_equal_one_device(runs):
    kind, c, one, four, _ = runs
    if kind == "skewed":
        pytest.skip("a flipped near-tie on the second update is no rounding: "
                    "the skewed run's gradients are held equal above")
    mine = mellum_map.from_program(jax.device_get(four[3].params), c)
    theirs = mellum_map.from_program(jax.device_get(one[3].params), c)
    start = seeded(11)[1]
    for name in mine:
        moved = float(np.max(np.abs(theirs[name] - np.asarray(start[name]))))
        assert float(np.max(np.abs(mine[name] - theirs[name]))) <= (
            1e-2 * moved + 1e-9), name


def test_whole_tensors_are_bit_equal_on_the_four_devices(runs):
    """What every chip holds whole (attention, norms, routers) is updated
    from ONE summed gradient: after two updates its copies hold the same
    bits; the divided tensors have one copy of each shard."""
    kind, c, one, four, _ = runs
    flat = mellum_map.from_program(four[3].params, c)
    for name in mellum_map.replicated_names(c):
        copies = [np.asarray(s.data) for s in flat[name].addressable_shards]
        assert len(copies) == 4
        for copy in copies[1:]:
            assert copy.tobytes() == copies[0].tobytes(), name
    for name in ("emb", "l0.w_gu", "l3.w_down"):
        shards = flat[name].addressable_shards
        assert len({s.index for s in shards}) == 4
        assert shards[0].data.shape[0] * 4 == flat[name].shape[0]
    assert flat["head"].addressable_shards[0].data.shape == (
        c["H"], c["V"] // 4)


def test_counters_of_the_exchange(runs):
    kind, c, one, four, _ = runs
    slots = 2 * 4 * 24 * TINY["num_experts_per_tok"] * c["L"]
    for m_one, m in zip(one[2], four[2]):
        assert m["moe_dropped_slots"] == 0.0
        assert m["moe_local_slots"] == m_one["moe_local_slots"] == slots
        assert m["moe_exchange_slots_out"] == m["moe_exchange_slots_in"] > 0
        assert m["moe_exchange_bytes_out"] == (
            m["moe_exchange_slots_out"] * c["H"] * 4)
        assert m["moe_load_max_over_mean"] == pytest.approx(
            m_one["moe_load_max_over_mean"])
        assert m["attn_window_tiles_run"] == m_one["attn_window_tiles_run"]
        assert m_one["moe_exchange_slots_out"] == 0.0
        if kind == "skewed":
            # every first choice on chip 0: half the slots at least (where the
            # softmax underflows the rest tie at zero and the second choice
            # is chip 0's other expert)
            assert m["moe_chip_load_max_over_mean"] >= 2.0
            # rows a round are half a pair's expected load: the full pair
            # takes four rounds where an even routing takes two or three
            # (a piece a round on each of the four chips)
            assert m["moe_pieces_run"] >= 4 * 4 * 2 * c["L"]
        else:
            assert 1.0 <= m["moe_chip_load_max_over_mean"] < 2.0


def test_data_axis_beside_the_expert_axis(updates):
    """dp=2 x ep=2 over four devices: the rows over both axes, the experts
    over one, every gradient summed over the other."""
    c, rp = seeded(11)
    one = step_under("dp=1", 1, rp, c, updates)
    both = step_under("dp=2,ep=2", 4, rp, c, updates)
    np.testing.assert_allclose(both[1], one[1], atol=2e-5)
    for name in one[0]:
        close(both[0][name], one[0][name], tol=1e-4)


def test_a_family_without_axis_names_is_refused():
    from bert_pytorch_tpu.config import LagunaConfig
    from tests.test_laguna import TINY as LAGUNA

    spec, mesh = mesh_of("ep=4", 4)
    model = build_pretraining_model(LagunaConfig(**LAGUNA), jnp.float32)
    tx = optim.adamw(1e-3, weight_decay_mask=optim.no_decay_mask)
    sample = (jnp.zeros((1, 16), jnp.int32),)
    with mesh:
        shardings = pretrain.state_shardings(
            mesh, model, logical_axis_rules(spec), sample)
        step = pretrain.make_train_step(
            model, tx, next_sentence=False, mesh=mesh, shardings=shardings,
            batch_shardings_=pretrain.batch_shardings(mesh, {"input_ids": 3}))
        state = jax.eval_shape(pretrain.make_init_fn(
            model, tx, sample, shardings), jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="expert axis"):
            step.lower(state, {"input_ids": jax.ShapeDtypeStruct(
                (1, 4, 24), np.int32)})


def test_under_the_axis_no_chip_is_asked_for_logits():
    """A chip holds a quarter of the head's columns: the model gives its
    hidden states and the loss crosses the axis (``models/losses.py``); the
    whole rows of logits are refused, not gathered."""
    _, mesh = mesh_of("ep=4", 4)
    local = tiny_model().on_expert_axis(AXIS_EXPERT, 4)
    whole = pretrain.on_expert_axis(
        lambda ids: local.init(jax.random.PRNGKey(0), ids), mesh,
        (P(AXIS_EXPERT),), P())
    with pytest.raises(ValueError, match="no chip holds a row's logits"):
        jax.eval_shape(whole, jnp.zeros((4, 16), jnp.int32))


# -- the exchange alone ----------------------------------------------------------

def _layer_inputs(tokens=48):
    c, rp = seeded(5)
    return c, rp, jax.random.normal(jax.random.PRNGKey(0), (tokens, c["H"]))


def _dense_layer(c, rp, x, chosen, weights):
    """Every slot's term by a loop over experts (the reference's form)."""
    out = jnp.zeros_like(x)
    for e in range(c["held"]):
        mine = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        out = out + mine[:, None] * ref.glu(
            x, rp["l0.w_gu"][e], rp["l0.w_down"][e], "f32")
    return out


def _exchanged(c, rp, x, chosen, weights, chips=4):
    """``exchanged_experts`` over ``chips`` devices: tokens and experts
    divided ``chips`` ways."""
    _, mesh = mesh_of(f"ep={chips}", chips)

    def body(x_, chosen_, weights_, up, down):
        out, counters = moe.exchanged_experts(
            x_, chosen_, weights_, up, down, c["held"], jax.nn.silu,
            AXIS_EXPERT, multiple=8, gated=True)
        return out, {k: v[None] for k, v in counters.items()}

    rows = P(AXIS_EXPERT)
    fn = pretrain.on_expert_axis(
        body, mesh, (rows, rows, rows, rows, rows), (rows, rows))
    return jax.jit(fn)(x, chosen, weights, rp["l0.w_gu"], rp["l0.w_down"])


@pytest.mark.parametrize("routing", ["routed", "one_chip", "one_expert",
                                     "by_row"])
def test_exchange_gives_the_whole_layer_under_any_routing(routing):
    """Forward and backward of the exchange alone against the loop over
    experts: under the router's own choice, with EVERY slot on chip 0's two
    experts, with every token's two slots... on one expert each of chip 3
    (the fullest pair then needs eight rounds), and with each chip's own
    tokens all on the NEXT chip's two experts (a row's preference: the chips
    draw even loads, and after the deal so does every pair): nothing is
    dropped."""
    c, rp, x = _layer_inputs()
    chosen, weights = ref.route(rp, "l0.", c, x)
    if routing == "one_chip":
        chosen = jnp.broadcast_to(jnp.array([0, 1]), chosen.shape)
    elif routing == "one_expert":
        chosen = jnp.broadcast_to(jnp.array([7, 6]), chosen.shape)
    elif routing == "by_row":
        chip = (jnp.arange(48) // 12 + 1) % 4
        chosen = 2 * chip[:, None] + jnp.array([0, 1])
    want = _dense_layer(c, rp, x, chosen, weights)
    out, counters = _exchanged(c, rp, x, chosen, weights)
    close(out, want)
    assert float(jnp.sum(counters["dropped_slots"])) == 0.0
    assert float(jnp.sum(counters["exchange_slots_out"])) == float(
        jnp.sum(counters["exchange_slots_in"]))
    assert float(jnp.sum(counters["local_slots"])) == 48 * 2
    if routing in ("one_chip", "one_expert"):
        # three chips send all they have to one: 36 tokens' slots leave
        assert float(jnp.sum(counters["exchange_slots_out"])) == 36 * 2
        assert float(counters["chip_load_max_over_mean"][0]) == 4.0
        # rows a round: half of 12 x 2 / 4, rounded up to 8; 24 slots a pair
        assert float(counters["pieces_run"][0]) == 3.0
    if routing == "by_row":
        # undealt, chip r would send chip r + 1 all its 24 slots in three
        # rounds; dealt, every pair carries 6 and one round does
        assert float(counters["chip_load_max_over_mean"][0]) == 1.0
        assert float(counters["pieces_run"][0]) == 1.0
        assert float(jnp.sum(counters["exchange_slots_out"])) == 36 * 2

    def loss(fn, x_, w_, up, down):
        return jnp.sum(jnp.sin(fn(x_, w_, up, down)))

    mine = jax.grad(functools.partial(loss, lambda x_, w_, up, down: _exchanged(
        c, dict(rp, **{"l0.w_gu": up, "l0.w_down": down}), x_, chosen, w_)[0]),
        argnums=(0, 1, 2, 3))(x, weights, rp["l0.w_gu"], rp["l0.w_down"])
    theirs = jax.grad(functools.partial(loss, lambda x_, w_, up, down: (
        _dense_layer(c, dict(rp, **{"l0.w_gu": up, "l0.w_down": down}), x_,
                     chosen, w_))), argnums=(0, 1, 2, 3))(
        x, weights, rp["l0.w_gu"], rp["l0.w_down"])
    for got, want_ in zip(mine, theirs):
        close(got, want_)


def test_tokens_that_do_not_divide_by_the_chips_go_undealt():
    """Ten tokens a chip over four chips: no deal (``moe._dealt`` hands them
    back as they are), the same layer."""
    c, rp, x = _layer_inputs(tokens=40)
    chosen, weights = ref.route(rp, "l0.", c, x)
    out, counters = _exchanged(c, rp, x, chosen, weights)
    close(out, _dense_layer(c, rp, x, chosen, weights))
    assert float(jnp.sum(counters["local_slots"])) == 40 * 2
    assert float(jnp.sum(counters["dropped_slots"])) == 0.0


def test_rows_of_a_round():
    """Half the expected load of a pair of chips, in whole tiles."""
    assert moe.exchange_rows(8192, 8, 4) == 8192
    assert moe.exchange_rows(8192, 8, 4, 512) % 512 == 0
    assert moe.exchange_rows(12, 2, 4, 8) == 8
    assert moe.exchange_rows(1, 1, 4, 8) == 8


# -- (d) the shares add up ---------------------------------------------------------

def test_four_stated_shares_add_up_to_the_exchange_and_the_reference():
    """The guide's sum: the layer told a share (``ep_size`` 4, ``ep_rank``
    0..3, one device each, no mesh) computes its own experts' terms; the four
    parts add up to what the exchange gives over four devices and to the
    reference's whole layer."""
    c, rp, x = _layer_inputs()
    x = x.reshape(2, 24, -1)
    want, _ = ref.expert_layer(rp, "l0.", c, x, "f32")
    total = jnp.zeros_like(x)
    for rank in range(4):
        cfg = MellumConfig(**dict(TINY, num_experts=2, ep_size=4, ep_rank=rank))
        part, counters = expert_layer(cfg, jnp.float32).apply({"params": {
            "router_kernel": rp["l0.router"],
            "experts_up": rp["l0.w_gu"][2 * rank:2 * rank + 2],
            "experts_down": rp["l0.w_down"][2 * rank:2 * rank + 2]}}, x)
        assert float(counters["moe_dropped_slots"]) == 0.0
        total = total + part
    close(total, want)
    chosen, weights = ref.route(rp, "l0.", c, x.reshape(48, -1))
    close(_exchanged(c, rp, x.reshape(48, -1), chosen, weights)[0],
          want.reshape(48, -1))
    whole = expert_layer(MellumConfig(**TINY), jnp.float32).apply({"params": {
        "router_kernel": rp["l0.router"], "experts_up": rp["l0.w_gu"],
        "experts_down": rp["l0.w_down"]}}, x)[0]
    close(whole, want)


# -- (e) without the axis nothing moved ----------------------------------------

def test_without_an_expert_axis_the_layer_lowers_as_it_did():
    """The layer takes the axis from its fields alone: with none it lowers to
    the held experts' program, with no collective and none of the exchange's
    scopes, whatever mesh is in context and whether or not its tensors carry
    axis names."""
    cfg = MellumConfig(**dict(TINY, num_experts=2, ep_size=4, ep_rank=1))
    x = jnp.zeros((2, 24, TINY["hidden_size"]))

    def lowered(**fields):
        layer = expert_layer(cfg, jnp.float32, **fields)
        params = nn.unbox(jax.eval_shape(
            lambda: layer.init(jax.random.PRNGKey(0), x)))
        return jax.jit(lambda p, x_: jax.grad(lambda p_: jnp.sum(
            layer.apply(p_, x_)[0]))(p)).lower(params, x)

    plain = lowered().as_text()
    named = lowered().as_text(debug_info=True)  # the scopes ride in the locs
    for absent in ("all_to_all", "all_reduce", "collective", "moe_exchange_out",
                   "moe_exchange_back"):
        assert absent not in named, absent
    assert "moe_dispatch" in named and "moe_experts" in named
    assert lowered(axis_names=True).as_text() == plain
    with mesh_of("ep=4", 4)[1]:
        assert lowered().as_text() == plain
