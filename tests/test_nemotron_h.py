"""The ``nemotron_h`` family against its plain float32 reference
(``benchmarks/reference/nemotron_h_f32.py``) at a small size on the CPU:
each new mixer alone (forward and gradients), the expert share, the no-drop
property, and two whole updates through ``pretrain.make_train_step``.

Tolerances: everything here is float32 at ``highest`` on both sides
(conftest), so program and reference differ only in the ORDER of float32
sums: the chunked scan adds a chunk's terms as matrix products where the
reference adds them token by token, the experts add a token's slots in sorted
order where the reference adds them expert by expert. A few 1e-6 relative to
the largest element is that; 2e-5 leaves a decade of room and would not pass
a wrong mask, a dropped decay or a missing expert (each moves the result by
percents).
"""

import json

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import nemotron_h_f32 as ref
from benchmarks.reference import nemotron_h_map
from bert_pytorch_tpu import optim, pretrain
from bert_pytorch_tpu.config import (BertConfig, NemotronHConfig,
                                     load_model_config)
from bert_pytorch_tpu.models import build_pretraining_model
from bert_pytorch_tpu.models.losses import (chunked_next_token_loss,
                                            next_token_loss)
from bert_pytorch_tpu.ops import moe, ssm
from bert_pytorch_tpu.ops.attention import dot_product_attention

TINY = dict(vocab_size=256, hidden_size=64, num_hidden_layers=3,
            hybrid_override_pattern="ME*", num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
            mamba_head_dim=16, n_groups=2, ssm_state_size=16, chunk_size=8,
            conv_kernel=4, n_routed_experts=4, ep_size=4, ep_rank=1,
            num_experts_per_tok=2, moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=64,
            routed_scaling_factor=2.5, layer_norm_epsilon=1e-5,
            time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
            moe_piece_multiple=8)
TOL = 2e-5


def close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-30), (
        np.max(np.abs(a - b)), np.max(np.abs(b)))


def keys(n, seed=0):
    return jax.random.split(jax.random.PRNGKey(seed), n)


# -- the chunked scan against the per-token recurrence -----------------------

@pytest.mark.parametrize("seq", [32, 29, 5])  # whole chunks, a ragged end, under one
def test_chunked_scan_matches_the_recurrence(seq):
    k = keys(6)
    batch, heads, hdim, groups, state, chunk = 2, 4, 8, 2, 8, 8
    x = jax.random.normal(k[0], (batch, seq, heads, hdim))
    dt = jax.nn.softplus(jax.random.normal(k[1], (batch, seq, heads)))
    a = -jnp.exp(jax.random.normal(k[2], (heads,)))
    b = jax.random.normal(k[3], (batch, seq, groups, state))
    c = jax.random.normal(k[4], (batch, seq, groups, state))
    d = jax.random.normal(k[5], (heads,))

    def loss(fn):
        return lambda *args: jnp.sum(jnp.sin(fn(*args)))

    mine = lambda *args: ssm.ssd_chunked_scan(*args, chunk)
    theirs = lambda *args: ref.recurrence(*args, block=8)
    close(mine(x, dt, a, b, c, d), theirs(x, dt, a, b, c, d))
    got = jax.grad(loss(mine), argnums=range(6))(x, dt, a, b, c, d)
    want = jax.grad(loss(theirs), argnums=range(6))(x, dt, a, b, c, d)
    for g, w in zip(got, want):
        close(g, w)


def test_a_dropped_decay_is_seen():
    """The comparison above is not blind: the scan without its decay (a = 0)
    is far from the recurrence."""
    k = keys(6, 1)
    x = jax.random.normal(k[0], (1, 16, 2, 4))
    dt = jax.nn.softplus(jax.random.normal(k[1], (1, 16, 2)))
    a = -jnp.exp(jax.random.normal(k[2], (2,)))
    b = jax.random.normal(k[3], (1, 16, 1, 4))
    c = jax.random.normal(k[4], (1, 16, 1, 4))
    d = jnp.ones((2,))
    want = ref.recurrence(x, dt, a, b, c, d, block=8)
    wrong = ssm.ssd_chunked_scan(x, dt, jnp.zeros_like(a), b, c, d, 8)
    assert np.max(np.abs(np.asarray(wrong - want))) > 0.05 * np.max(np.abs(want))


def test_gated_norm_and_conv_match_the_reference():
    k = keys(4, 2)
    c = ref.sizes(TINY)
    x = jax.random.normal(k[0], (2, 13, c["H"]))
    p = ref.seeded_params(ref.key_from_seed(3), c)
    cfg = NemotronHConfig(**TINY)
    model = build_pretraining_model(cfg, jnp.float32)
    tree = nemotron_h_map.to_program(p, c)
    from bert_pytorch_tpu.models.nemotron_h import Mamba2Mixer

    mine, counters = Mamba2Mixer(cfg, jnp.float32).apply(
        {"params": tree["layers_0"]["mixer"]}, x)
    close(mine, ref.mamba_mixer(p, "l0.", c, x, "f32"))
    assert float(counters["ssd_chunks_run"]) == 0  # heads of 16: the XLA form
    assert model.objective == "causal_lm"


# -- causal grouped-query attention --------------------------------------------

def _plain_causal(q, k, v):
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    seq = q.shape[1]
    s = jnp.where(jnp.arange(seq)[None, :] <= jnp.arange(seq)[:, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


# 192 = three 64-wide tiles a side on the kernel path: tiles below, on and
# above the diagonal; 40 is a single ragged tile.
@pytest.mark.parametrize("backend,seq", [("xla", 40), ("pallas", 40),
                                         ("pallas", 192)])
def test_causal_grouped_query_attention(backend, seq):
    k = keys(3, seq)
    q = jax.random.normal(k[0], (2, seq, 4, 16))
    kk = jax.random.normal(k[1], (2, seq, 2, 16))
    v = jax.random.normal(k[2], (2, seq, 2, 16))
    mine = lambda *a: dot_product_attention(*a, backend=backend, causal=True)
    close(mine(q, kk, v), _plain_causal(q, kk, v))
    loss = lambda fn: (lambda *a: jnp.sum(jnp.sin(fn(*a))))
    got = jax.grad(loss(mine), argnums=(0, 1, 2))(q, kk, v)
    want = jax.grad(loss(_plain_causal), argnums=(0, 1, 2))(q, kk, v)
    for g, w in zip(got, want):
        close(g, w)


def test_causal_is_not_bidirectional():
    k = keys(3, 7)
    q, kk, v = (jax.random.normal(key, (1, 64, 2, 16)) for key in k)
    both = dot_product_attention(q, kk, v, backend="pallas")
    causal = dot_product_attention(q, kk, v, backend="pallas", causal=True)
    assert np.max(np.abs(np.asarray(both - causal))) > 0.05
    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, kk, v, backend="ring", causal=True)


def test_reference_attention_in_blocks_matches_plain():
    c = dict(ref.sizes(TINY))
    p = ref.seeded_params(ref.key_from_seed(1), c)
    x = jax.random.normal(keys(1)[0], (2, 21, c["H"]))
    whole = ref.causal_attention(p, "l2.", c, x, "f32", block_rows=64)
    close(ref.causal_attention(p, "l2.", c, x, "f32", block_rows=8), whole)


# -- the expert layer: against the reference, the share, no drop ---------------

def _expert_inputs(seed=0, tokens=48):
    c = ref.sizes(TINY)
    p = ref.seeded_params(ref.key_from_seed(seed), c)
    x = jax.random.normal(keys(1, seed)[0], (tokens, c["H"]))
    return c, p, x


def _program_routed(c, p, x, first, multiple=8):
    chosen, weights = moe.route(
        x, p["l1.router"], p["l1.router_bias"], c["top_k"], c["route_scale"])
    return moe.held_experts(
        x, chosen, weights, p["l1.w_up"], p["l1.w_down"], first, c["experts"],
        lambda t: jnp.square(jax.nn.relu(t)), multiple=multiple)


def _routing_case(taken):
    """(sizes, parameters, x, pieces run) for an expert layer whose held
    experts draw no slot, one piece of the sorted slots, or several: the
    correction bias moves the CHOICE (not the weights) away from or towards
    them, and ``several`` holds 2 experts of 16, so 4 pieces of 24 rows hold
    every slot there is."""
    c, p, x = _expert_inputs()
    lo = c["first"]
    if taken == "several":
        c = dict(c, held=2)
        p = dict(p, **{"l1.w_up": p["l1.w_up"][:2],
                       "l1.w_down": p["l1.w_down"][:2]})
    push = {"none": -10.0, "one": 0.0, "several": 0.06}[taken]
    bias = p["l1.router_bias"].at[lo:lo + c["held"]].add(push)
    return c, dict(p, **{"l1.router_bias": bias}), x, {
        "none": 0, "one": 1, "several": 3}[taken]


@pytest.mark.parametrize("taken", ["none", "one", "several"])
def test_expert_layer_matches_the_reference(taken):
    """Value and the gradients with respect to x, the router and both expert
    weights, whatever number of pieces holds a local slot; without one the
    layer's output and all four gradients are exactly zero."""
    c, p, x, pieces_run = _routing_case(taken)
    names = ("l1.router", "l1.w_up", "l1.w_down")

    def mine(x, *w):
        q = dict(p, **dict(zip(names, w)))
        return _program_routed(c, q, x, c["first"])[0]

    def theirs(x, *w):
        q = dict(p, **dict(zip(names, w)))
        return ref.expert_layer(q, "l1.", c, x, "f32", shared=False)[0]

    args = (x,) + tuple(p[n] for n in names)
    counters = _program_routed(c, p, x, c["first"])[1]
    assert float(counters["pieces_run"]) == pieces_run
    assert float(counters["dropped_slots"]) == 0.0
    rows = moe.chunk_rows(x.shape[0], c["top_k"], c["experts"], c["held"], 8)
    assert -(-float(counters["local_slots"]) // rows) == pieces_run
    close(mine(*args), theirs(*args))
    loss = lambda fn: (lambda *a: jnp.sum(jnp.sin(fn(*a))))
    for g, w in zip(jax.grad(loss(mine), argnums=range(4))(*args),
                    jax.grad(loss(theirs), argnums=range(4))(*args)):
        close(g, w)
        assert pieces_run or not np.any(np.asarray(g))
    assert pieces_run or not np.any(np.asarray(mine(*args)))


def _census(jaxpr, shapes):
    """(``cond`` equations, zero-fills of one of ``shapes``) in a jaxpr and
    in every jaxpr inside it (loop bodies, branches, calls)."""
    from jax.extend import core

    conds = fills = 0
    for eqn in jaxpr.eqns:
        conds += eqn.primitive.name == "cond"
        fills += (eqn.primitive.name == "broadcast_in_dim"
                  and eqn.outvars[0].aval.shape in shapes
                  and isinstance(eqn.invars[0], core.Literal)
                  and eqn.invars[0].val == 0)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, core.Jaxpr):
                    inner = _census(sub, shapes)
                    conds, fills = conds + inner[0], fills + inner[1]
    return conds, fills


def test_a_piece_without_a_local_slot_costs_no_branch_and_no_zero_fill():
    """What a skipped ``lax.cond`` cost (PERF.md 6, PR 30): its false branch
    wrote zeros for the piece's term and, in the backward pass, for the
    cotangents of x and of BOTH weight tensors, once a piece. The gradient
    program now holds no ``cond``, and its zero-fills of those shapes do not
    grow with the number of pieces (2 against 8)."""
    tokens, hidden, width, held, top_k = 64, 24, 8, 4, 3
    shapes = {(tokens, hidden), (held, hidden, width), (held, width, hidden)}

    def census(n_experts):
        def loss(x, router, w_up, w_down):
            chosen, weights = moe.route(x, router, jnp.zeros((n_experts,)),
                                        top_k, 2.5)
            return jnp.sum(moe.held_experts(
                x, chosen, weights, w_up, w_down, 0, n_experts,
                lambda t: jnp.square(jax.nn.relu(t)), multiple=8)[0])

        rows = moe.chunk_rows(tokens, top_k, n_experts, held, 8)
        assert rows != tokens  # a piece's own rows are not counted
        grad = jax.grad(loss, argnums=range(4))
        args = (jnp.ones((tokens, hidden)), jnp.ones((hidden, n_experts)),
                jnp.ones((held, hidden, width)), jnp.ones((held, width, hidden)))
        assert "stablehlo.case" not in jax.jit(grad).lower(*args).as_text()
        return -(-tokens * top_k // rows), _census(
            jax.make_jaxpr(grad)(*args).jaxpr, shapes)

    (few, (conds_few, fills_few)), (many, (conds_many, fills_many)) = (
        census(16), census(64))
    assert (few, many) == (2, 8)
    assert conds_few == conds_many == 0
    # the forward sum's and the x cotangent's initial zeros, whatever the pieces
    assert fills_many <= fills_few <= 2


def test_the_shares_add_up_to_the_uncut_layer():
    """The share test: the routed parts that all ``ep_size`` shares give,
    plus the shared expert counted once, are the reference's uncut layer."""
    c, p, x = _expert_inputs(4)
    whole = dict(c, held=c["experts"], first=0)  # every expert, one chip
    k = keys(2, 9)
    q = dict(p)
    q["l1.w_up"] = c["std"] * jax.random.normal(
        k[0], (c["experts"], c["H"], c["F"]))
    q["l1.w_down"] = c["std"] * jax.random.normal(
        k[1], (c["experts"], c["F"], c["H"]))
    uncut, _ = ref.expert_layer(q, "l1.", whole, x, "f32")
    total = ref.expert_layer(q, "l1.", dict(whole, held=0), x, "f32")[0]  # shared alone
    held = c["held"]
    slots = 0.0
    for rank in range(c["experts"] // held):
        share = dict(p, **{"l1.w_up": q["l1.w_up"][rank * held:(rank + 1) * held],
                           "l1.w_down": q["l1.w_down"][rank * held:(rank + 1) * held]})
        out, counters = _program_routed(c, share, x, rank * held)
        total = total + out
        slots += float(counters["local_slots"])
        assert float(counters["dropped_slots"]) == 0.0
    assert slots == x.shape[0] * c["top_k"]  # every slot is some share's
    close(total, uncut)


@pytest.mark.parametrize("skew", ["all_here", "none_here"])
def test_no_slot_is_dropped_under_any_routing(skew):
    """A router skewed so that every token chooses held experts fills EVERY
    piece of the rows (every slot there is); one skewed away leaves the first
    empty. Neither loses a slot; both agree with the reference."""
    c, p, x = _expert_inputs(5, tokens=64)
    lo, hi = c["first"], c["first"] + c["held"]
    bias = jnp.full((c["experts"],), -10.0).at[lo:hi].set(10.0)
    if skew == "none_here":
        bias = -bias
    router = p["l1.router"] * 0.01 + 0.0  # near-flat scores: the bias decides
    q = dict(p, **{"l1.router": router, "l1.router_bias": bias})
    rows = moe.chunk_rows(64, c["top_k"], c["experts"], c["held"], 8)
    assert 64 * c["top_k"] > rows  # several pieces hold every slot there is
    out, counters = _program_routed(c, q, x, c["first"])
    want = 64 * c["top_k"] if skew == "all_here" else 0
    assert float(counters["local_slots"]) == want
    assert float(counters["dropped_slots"]) == 0.0
    assert float(counters["pieces_run"]) == want // rows  # all of them, or none
    close(out, ref.expert_layer(q, "l1.", c, x, "f32", shared=False)[0],
          tol=TOL if want else 0.0)


def test_experts_left_out_are_seen():
    """Not blind: the layer without one held expert's terms is far off."""
    c, p, x = _expert_inputs(6)
    full = _program_routed(c, p, x, c["first"])[0]
    less = dict(p, **{"l1.w_down": p["l1.w_down"].at[0].set(0.0)})
    assert np.max(np.abs(np.asarray(
        _program_routed(c, less, x, c["first"])[0] - full))) > 0.05 * np.max(
            np.abs(np.asarray(full)))


# -- the whole model -------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_loss_and_gradients_match_the_reference(backend):
    c = ref.sizes(TINY)
    rp = ref.seeded_params(ref.key_from_seed(5), c)
    pp = nemotron_h_map.to_program(rp, c)
    model = build_pretraining_model(NemotronHConfig(**TINY), jnp.float32,
                                    remat="full", attention_backend=backend)
    ids = jax.random.randint(keys(1, 1)[0], (2, 21), 0, c["V"])
    want = jax.tree_util.tree_structure(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids))["params"])
    assert want == jax.tree_util.tree_structure(pp)

    def mine(p):
        logits, counters = model.apply({"params": p}, ids)
        return next_token_loss(logits, ids)[0]

    loss, grads = jax.value_and_grad(mine)(pp)
    (ref_loss, _), ref_grads = jax.value_and_grad(
        lambda p: ref.next_token_loss(p, c, ids), has_aux=True)(rp)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    grads = nemotron_h_map.from_program(grads, c)
    for name in ref_grads:
        close(grads[name], ref_grads[name])


def test_two_updates_through_make_train_step_match_the_reference():
    """``M E *`` through the program's own step (micro-batch scan, clipping,
    AdamW with the extended no-decay mask) against the reference's AdamW:
    losses, the first gradient's norms, the parameters after two updates."""
    c = ref.sizes(TINY)
    recipe = ref.Recipe(learning_rate=1e-3, warmup_proportion=0.01,
                        max_steps=1000)
    seed = 11
    model = build_pretraining_model(NemotronHConfig(**TINY), jnp.float32,
                                    remat="full")
    schedule = optim.make_schedule("constant", recipe.learning_rate,
                                   recipe.warmup_proportion, recipe.max_steps)
    tx = optim.adamw(schedule, b1=recipe.b1, b2=recipe.b2, eps=recipe.eps,
                     weight_decay=recipe.weight_decay,
                     weight_decay_mask=optim.no_decay_mask,
                     max_grad_norm=recipe.max_grad_norm)
    params = nemotron_h_map.to_program(
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
        # one expert layer, two micro-batches: a piece each (or two)
        assert 2.0 <= float(metrics["moe_pieces_run"]) <= 4.0
        assert float(metrics["finite"]) == 1.0
    followed = ref.follow(seed, TINY, recipe, updates)
    np.testing.assert_allclose(losses, followed["loss"], atol=2e-5)
    start = ref.seeded_params(ref.key_from_seed(seed), c)
    mine = nemotron_h_map.from_program(state.params, c)
    change = ref.leaf_norms({k: mine[k] - start[k] for k in mine})
    for name, want in followed["delta_norms"].items():
        # Adam divides by sqrt(v): where a gradient is all but zero its sign
        # is rounding, so the change is compared as a norm, at 2%.
        np.testing.assert_allclose(np.asarray(change[name]), want,
                                   rtol=0.02, atol=1e-7, err_msg=name)


def test_chunked_head_loss_is_the_whole_loss():
    k = keys(3, 3)
    hidden = jax.random.normal(k[0], (2, 32, 16))
    kernel = jax.random.normal(k[1], (16, 40))
    ids = jax.random.randint(k[2], (2, 32), 0, 40)
    whole = lambda h, w: next_token_loss(h @ w, ids)
    pieces = lambda h, w: chunked_next_token_loss(h, w, ids, 4)
    for a, b in zip(whole(hidden, kernel), pieces(hidden, kernel)):
        close(a, b)
    for g, w in zip(jax.grad(lambda *a: pieces(*a)[0], (0, 1))(hidden, kernel),
                    jax.grad(lambda *a: whole(*a)[0], (0, 1))(hidden, kernel)):
        close(g, w)


# -- configuration, data, optimizer mask ----------------------------------------

def test_model_type_chooses_the_family(tmp_path):
    path = tmp_path / "c.json"
    path.write_text(json.dumps(dict(TINY, model_type="nemotron_h")))
    config = load_model_config(str(path))
    assert isinstance(config, NemotronHConfig)
    assert (config.router_experts, config.first_expert) == (16, 4)
    path.write_text(json.dumps({"hidden_size": 32}))
    assert isinstance(load_model_config(str(path)), BertConfig)
    path.write_text(json.dumps({"model_type": "other"}))
    with pytest.raises(ValueError, match="unknown model_type"):
        load_model_config(str(path))
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        NemotronHConfig(**dict(TINY, hybrid_override_pattern="MX*"))


def test_published_configuration_counts_667_million():
    """The benchmark's configuration file, built abstractly: the cut's
    arithmetic (ISSUE 27) against the tree's own count."""
    config = load_model_config(
        "benchmarks/configs/nemotron-3-nano-30b-a3b.json")
    model = build_pretraining_model(config, jnp.bfloat16)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(leaf.shape))
                             for leaf in jax.tree_util.tree_leaves(tree))
    assert count(shapes["layers_0"]) == pytest.approx(38.74e6, rel=5e-4)  # M
    assert count(shapes["layers_5"]) == pytest.approx(23.40e6, rel=5e-4)  # *
    assert count(shapes["layers_1"]) == pytest.approx(100.13e6, rel=5e-4)  # E
    assert abs(count(shapes) - 667.0e6) < 0.05e6
    assert 16 * count(shapes) == pytest.approx(10.67e9, rel=1e-3)


def test_token_rows_through_the_loader(tmp_path):
    import h5py

    from bert_pytorch_tpu.data import (DataLoader, DistributedSampler,
                                       TokenRowsDataset)

    rows = np.arange(12 * 16, dtype=np.int32).reshape(12, 16)
    for s in range(2):
        with h5py.File(tmp_path / f"shard_{s}.hdf5", "w") as f:
            f.create_dataset("input_ids", data=rows[6 * s:6 * s + 6])
    dataset = TokenRowsDataset(sorted(str(p) for p in tmp_path.iterdir()))
    assert len(dataset) == 12
    loader = DataLoader(dataset, DistributedSampler(dataset, 1, 0),
                        batch_size=4, drop_last=True)
    batches = list(loader)
    assert [sorted(b) for b in batches] == [["input_ids"]] * 3
    np.testing.assert_array_equal(
        np.concatenate([b["input_ids"] for b in batches]), rows)
    stacked = pretrain.stack_microbatches(batches[0], 2)
    assert stacked["input_ids"].shape == (2, 2, 16)


def test_no_decay_mask_covers_the_family():
    model = build_pretraining_model(NemotronHConfig(**TINY), jnp.float32)
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    mask = optim.no_decay_mask(params)
    flat = {"/".join(k): v for k, v in
            __import__("flax").traverse_util.flatten_dict(mask).items()}
    exempt = sorted(k.split("/")[-1] for k, v in flat.items() if not v)
    assert set(exempt) == {"A_log", "D", "conv_bias", "dt_bias", "norm_scale",
                           "router_correction_bias", "scale"}
    c = ref.sizes(TINY)
    for name, path in nemotron_h_map.table(c).items():
        assert flat[path] == ref.decays(name, c), name


# -- the family's scopes reach the compiled step ------------------------------------

@pytest.fixture(scope="module")
def causal_step_names():
    import re

    model = build_pretraining_model(NemotronHConfig(**TINY), jnp.bfloat16,
                                    remat="full")
    tx = optim.adamw(1e-3, max_grad_norm=1.0,
                     weight_decay_mask=optim.no_decay_mask)
    state = pretrain.make_init_fn(
        model, tx, (jnp.zeros((1, 8), jnp.int32),), None)(jax.random.PRNGKey(0))
    step = pretrain.make_train_step(model, tx, next_sentence=False)
    batch = {"input_ids": np.zeros((2, 1, 24), np.int32)}
    text = step.lower(state, batch).compile().as_text()
    return set(re.findall(r'op_name="([^"]+)"', text))


@pytest.mark.parametrize("scope", pretrain.CAUSAL_LM_SCOPES)
def test_every_scope_of_the_family_reaches_the_compiled_step(
        causal_step_names, scope):
    """As tests/test_spans.py asks of ``pretrain.SCOPES`` in BERT's step: a
    profiler trace can tell the family's parts only by names that are there."""
    assert any(f"/{scope}/" in name or f"({scope})" in name
               for name in causal_step_names), scope


def test_the_shared_scopes_are_in_the_causal_step_too(causal_step_names):
    for scope in ("micro_batches", "grad_accumulate", "optimizer", "clip",
                  "step_metrics", "attention_core"):
        assert any(f"/{scope}/" in name for name in causal_step_names), scope
