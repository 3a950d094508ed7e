"""The ``laguna`` family against its plain float32 reference
(``benchmarks/reference/laguna_f32.py``) at a small size on the CPU: the
attention of both kinds (window, two rotary tables, per-head gate), the gated
expert layer under softmax routing, the shares (experts, heads) adding up to
the uncut layer, the whole model's loss and gradients, two whole updates
through ``pretrain.make_train_step``, and the normal path
(``run_pretraining.main``) from a config file.

Tolerances: everything here is float32 at ``highest`` on both sides
(conftest), so program and reference differ only in the ORDER of float32
sums (the kernel's tiles, the experts' sorted slots). A few 1e-6 relative to
the largest element is that; 2e-5 leaves a decade of room and would not pass
a dropped window, rotary table, gate or expert (each moves the result by
percents: the "is seen" tests below).
"""

import json

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import laguna_f32 as ref
from benchmarks.reference import laguna_map
from bert_pytorch_tpu import optim, pretrain
from bert_pytorch_tpu.config import LagunaConfig, load_model_config
from bert_pytorch_tpu.models import build_pretraining_model
from bert_pytorch_tpu.models.laguna import GatedAttention
from bert_pytorch_tpu.models.losses import next_token_loss
from bert_pytorch_tpu.ops import moe
from bert_pytorch_tpu.utils import flops

ROPE = LagunaConfig().rope_parameters  # the published two tables
# the published period at a small size: dense then sparse, full then three
# sliding then full, unlike head counts, a window shorter than the rows
TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_attention_heads_per_layer=[4, 6, 6, 6, 4],
    layer_types=["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 4, sliding_window=8,
    rope_parameters=ROPE, num_experts=4, ep_size=4, ep_rank=1,
    num_experts_per_tok=3, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, moe_routed_scaling_factor=2.5,
    rms_norm_eps=1e-6, moe_piece_multiple=8)
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


def _seeded(seed=3, sharp=False, **changes):
    """Sizes and seeded weights; ``sharp``: queries and keys ten times
    larger, so that the softmax is far from uniform and what turns or masks
    the scores shows in the output."""
    c = ref.sizes(dict(TINY, **changes))
    p = ref.seeded_params(ref.key_from_seed(seed), c)
    if sharp:
        p = {name: v * 10 if name.endswith((".wq", ".wk")) else v
             for name, v in p.items()}
    return c, p


# -- attention: window, rotary, gate ------------------------------------------

def _program_attention(c, p, layer, x, backend="xla", **changes):
    tree = laguna_map.to_program(p, c)[f"layers_{layer}"]["attn"]
    cfg = LagunaConfig(**dict(TINY, **changes))
    return GatedAttention(cfg, layer, jnp.float32, backend).apply(
        {"params": tree}, x)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("layer", [0, 1])  # full with YaRN / sliding
def test_attention_matches_the_reference(layer, backend):
    c, p = _seeded(sharp=True)
    x = jax.random.normal(keys(1, layer)[0], (2, 40, c["H"]))
    mine = lambda x_: _program_attention(c, p, layer, x_, backend)[0]
    theirs = lambda x_: ref.attention(p, f"l{layer}.", c, layer, x_, "f32",
                                      block_rows=16)
    close(mine(x), theirs(x))
    loss = lambda fn: (lambda x_: jnp.sum(jnp.sin(fn(x_))))
    close(jax.grad(loss(mine))(x), jax.grad(loss(theirs))(x))


@pytest.mark.parametrize("dropped", ["window", "rotary", "gate"])
def test_a_dropped_part_of_the_attention_is_seen(dropped):
    """Not blind: the reference without its window, its rotary table or its
    gate is far from the program's sliding layer."""
    c, p = _seeded(sharp=True)
    x = jax.random.normal(keys(1, 9)[0], (2, 40, c["H"]))
    mine = _program_attention(c, p, 1, x)[0]
    wrong = ref.attention(p, "l1.", c, 1, x, "f32", **{
        "window": {"window": None}, "rotary": {"rotary": False},
        "gate": {"gate": False}}[dropped])
    far(wrong, mine)


def test_reference_attention_in_blocks_matches_whole():
    c, p = _seeded()
    x = jax.random.normal(keys(1)[0], (2, 21, c["H"]))
    for layer in (0, 1):
        whole = ref.attention(p, f"l{layer}.", c, layer, x, "f32", block_rows=64)
        close(ref.attention(p, f"l{layer}.", c, layer, x, "f32", block_rows=8),
              whole)


def test_the_head_shares_add_up_to_the_uncut_layer():
    """The output projection's partial sums over both ``tp_size`` shares of
    the heads (key-value heads, their query heads, their columns of Wq, Wk,
    Wv and Wg, their rows of Wo) are the uncut reference's layer."""
    shares, hd = 2, TINY["head_dim"]
    whole = dict(TINY, num_key_value_heads=2 * shares,
                 num_attention_heads_per_layer=[
                     h * shares for h in TINY["num_attention_heads_per_layer"]])
    c, p = _seeded(7, sharp=True, **whole)
    x = jax.random.normal(keys(1, 7)[0], (2, 24, c["H"]))
    held = ref.sizes(TINY)
    for layer in (0, 1):
        pre = f"l{layer}."
        uncut = ref.attention(p, pre, c, layer, x, "f32")
        heads, kv = held["heads"][layer], held["KV"]
        total = 0.0
        for rank in range(shares):
            cols = lambda n: slice(rank * n, (rank + 1) * n)
            part = {pre + "wq": p[pre + "wq"][:, cols(heads * hd)],
                    pre + "wk": p[pre + "wk"][:, cols(kv * hd)],
                    pre + "wv": p[pre + "wv"][:, cols(kv * hd)],
                    pre + "wg": p[pre + "wg"][:, cols(heads)],
                    pre + "wo": p[pre + "wo"][cols(heads * hd)]}
            tree = {name: {"kernel": part[pre + short]} for name, short in (
                ("q_proj", "wq"), ("k_proj", "wk"), ("v_proj", "wv"),
                ("g_proj", "wg"), ("o_proj", "wo"))}
            out, _ = GatedAttention(LagunaConfig(**TINY), layer, jnp.float32,
                                    "pallas").apply({"params": tree}, x)
            total = total + out
        close(total, uncut)


# -- the expert layer: softmax routing, gated experts ---------------------------

def _program_routed(c, p, x, first, held_weights=None):
    chosen, weights = moe.route(x, p["l1.router"], None, c["top_k"],
                                c["route_scale"], c["norm_topk"], "softmax")
    w_gu, w_down = held_weights or (p["l1.w_gu"], p["l1.w_down"])
    return moe.held_experts(x, chosen, weights, w_gu, w_down, first,
                            c["experts"], jax.nn.silu, multiple=8, gated=True)


def _routing_case(taken):
    """An expert layer whose held experts draw no slot, one piece of the
    sorted slots, or several: the router's columns of the held experts are
    pushed away from or towards every token (all tokens share a positive
    component), and ``several`` holds 2 experts of 16."""
    c, p = _seeded(4)
    x = jax.random.normal(keys(1, 4)[0], (48, c["H"])) + 1.0
    lo = c["first"]
    if taken == "several":
        c = dict(c, held=2)
        p = dict(p, **{"l1.w_gu": p["l1.w_gu"][:2],
                       "l1.w_down": p["l1.w_down"][:2]})
    push = {"none": -1.0, "one": 0.0, "several": 0.03}[taken]
    router = p["l1.router"].at[:, lo:lo + c["held"]].add(push)
    return c, dict(p, **{"l1.router": router}), x


@pytest.mark.parametrize("taken", ["none", "one", "several"])
def test_expert_layer_matches_the_reference(taken):
    """Value and the gradients with respect to x, the router and both expert
    tensors, whatever number of pieces holds a local slot."""
    c, p, x = _routing_case(taken)
    names = ("l1.router", "l1.w_gu", "l1.w_down")

    def mine(x_, *w):
        q = dict(p, **dict(zip(names, w)))
        return _program_routed(c, q, x_, c["first"])[0]

    def theirs(x_, *w):
        q = dict(p, **dict(zip(names, w)))
        return ref.expert_layer(q, "l1.", c, x_, "f32", shared=False)[0]

    args = (x,) + tuple(p[n] for n in names)
    out, counters = _program_routed(c, p, x, c["first"])
    pieces = float(counters["pieces_run"])
    assert {"none": pieces == 0, "one": pieces == 1,
            "several": pieces > 1}[taken], pieces
    assert float(counters["dropped_slots"]) == 0.0
    close(out, theirs(*args), tol=TOL if pieces else 0.0)
    loss = lambda fn: (lambda *a: jnp.sum(jnp.sin(fn(*a))))
    got = jax.grad(loss(mine), argnums=range(4))(*args)
    want = jax.grad(loss(theirs), argnums=range(4))(*args)
    for g, w in zip(got, want):
        close(g, w, tol=TOL if pieces else 0.0)


def test_the_weights_are_the_softmax_over_the_chosen_times_the_scale():
    c, p = _seeded(5)
    x = jax.random.normal(keys(1, 5)[0], (32, c["H"]))
    chosen, weights = moe.route(x, p["l1.router"], None, 3, 2.5, True, "softmax")
    probs = jax.nn.softmax(x @ p["l1.router"], axis=-1)
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(
        np.argsort(-np.asarray(probs), -1)[:, :3], -1))
    np.testing.assert_allclose(np.sum(weights, -1), 2.5, rtol=1e-6)
    picked = np.take_along_axis(np.asarray(probs), np.asarray(chosen), -1)
    np.testing.assert_allclose(weights, 2.5 * picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    ref_chosen, ref_weights = ref.route(p, "l1.", dict(c, top_k=3), x)
    np.testing.assert_array_equal(chosen, ref_chosen)
    close(weights, ref_weights)
    with pytest.raises(ValueError, match="score"):
        moe.route(x, p["l1.router"], None, 3, 2.5, True, "tanh")


def test_the_gated_product_without_its_up_half_is_seen():
    """Not blind: ``silu(gate)`` alone is far from ``silu(gate) * up``."""
    c, p = _seeded(6)
    x = jax.random.normal(keys(1, 6)[0], (48, c["H"]))
    full = _program_routed(c, p, x, c["first"])[0]
    ones_up = p["l1.w_gu"].at[..., c["F"]:].set(0.0)
    far(_program_routed(c, p, x, c["first"], (ones_up, p["l1.w_down"]))[0], full)
    far(ref.expert_layer(dict(p, **{"l1.w_gu": ones_up}), "l1.", c, x, "f32",
                         shared=False)[0], full)


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """The routed parts that all ``ep_size`` shares give, plus the shared
    expert counted once, are the reference's uncut layer."""
    c, p = _seeded(4)
    x = jax.random.normal(keys(1, 8)[0], (48, c["H"]))
    whole = dict(c, held=c["experts"], first=0)  # every expert, one chip
    k = keys(2, 9)
    q = dict(p)
    q["l1.w_gu"] = c["std"] * jax.random.normal(
        k[0], (c["experts"], c["H"], 2 * c["F"]))
    q["l1.w_down"] = c["std"] * jax.random.normal(
        k[1], (c["experts"], c["F"], c["H"]))
    uncut, _ = ref.expert_layer(q, "l1.", whole, x, "f32")
    total = ref.expert_layer(q, "l1.", dict(whole, held=0), x, "f32")[0]  # shared
    held, slots = c["held"], 0.0
    for rank in range(c["experts"] // held):
        mine = slice(rank * held, (rank + 1) * held)
        out, counters = _program_routed(
            c, q, x, rank * held, (q["l1.w_gu"][mine], q["l1.w_down"][mine]))
        total = total + out
        slots += float(counters["local_slots"])
        assert float(counters["dropped_slots"]) == 0.0
    assert slots == x.shape[0] * c["top_k"]  # every slot is some share's
    close(total, uncut)


# -- the whole model -------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_loss_and_gradients_match_the_reference(backend):
    c, rp = _seeded(5)
    pp = laguna_map.to_program(rp, c)
    model = build_pretraining_model(LagunaConfig(**TINY), jnp.float32,
                                    remat="full", attention_backend=backend)
    ids = jax.random.randint(keys(1, 1)[0], (2, 21), 0, c["V"])
    want = jax.tree_util.tree_structure(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids))["params"])
    assert want == jax.tree_util.tree_structure(pp)

    def mine(p):
        logits, counters = model.apply({"params": p}, ids)
        return next_token_loss(logits, ids)[0], counters

    (loss, counters), grads = jax.value_and_grad(mine, has_aux=True)(pp)
    (ref_loss, _), ref_grads = jax.value_and_grad(
        lambda p: ref.next_token_loss(p, c, ids), has_aux=True)(rp)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    grads = laguna_map.from_program(grads, c)
    for name in ref_grads:
        close(grads[name], ref_grads[name])
    # score tiles from shapes: 21 positions are one tile a side on either
    # path, so both counters are batch x heads x layers of the kind
    assert float(counters["attn_full_tiles_run"]) == 2 * (4 + 4)
    assert float(counters["attn_window_tiles_run"]) == 2 * (6 + 6 + 6)
    assert float(counters["moe_dropped_slots"]) == 0.0


def test_the_tile_counters_tell_a_skipped_window_from_a_masked_one(monkeypatch):
    """At 64 positions in 8-wide tiles (eight a side; the kernels' own choice
    there is one of 64) the kernel path visits the band of the sliding layers
    and the triangle of the full ones; the XLA path computes every square
    whole."""
    from bert_pytorch_tpu.ops.pallas import attention as flash

    monkeypatch.setattr(flash, "_pick_blocks", lambda seq: (8, 8))
    model = lambda backend: build_pretraining_model(
        LagunaConfig(**TINY), jnp.float32, attention_backend=backend)
    ids = jnp.zeros((1, 64), jnp.int32)
    params = model("xla").init(jax.random.PRNGKey(0), ids)
    run = lambda backend: {k: float(v) for k, v in model(backend).apply(
        params, ids)[1].items()}
    masked, skipped = run("xla"), run("pallas")
    assert masked["attn_window_tiles_run"] == 18 * 64
    assert masked["attn_full_tiles_run"] == 8 * 64
    assert skipped["attn_window_tiles_run"] == 18 * 15   # two a row, one first
    assert skipped["attn_full_tiles_run"] == 8 * 36      # the triangle


def test_two_updates_through_make_train_step_match_the_reference():
    """Through the program's own step (micro-batch scan, clipping, AdamW with
    the no-decay mask) against the reference's AdamW: losses, and the
    parameters' change after two updates."""
    c = ref.sizes(TINY)
    recipe = ref.Recipe(learning_rate=1e-3, warmup_proportion=0.01,
                        max_steps=1000)
    seed = 11
    model = build_pretraining_model(LagunaConfig(**TINY), jnp.float32,
                                    remat="full")
    schedule = optim.make_schedule("constant", recipe.learning_rate,
                                   recipe.warmup_proportion, recipe.max_steps)
    tx = optim.adamw(schedule, b1=recipe.b1, b2=recipe.b2, eps=recipe.eps,
                     weight_decay=recipe.weight_decay,
                     weight_decay_mask=optim.no_decay_mask,
                     max_grad_norm=recipe.max_grad_norm)
    params = laguna_map.to_program(
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
        # two micro-batches of two rows: summed over them and the layers
        # (24 positions: nine 8-wide tiles a head, all computed on this path)
        assert float(metrics["attn_window_tiles_run"]) == 2 * 2 * 18 * 9
        assert float(metrics["attn_full_tiles_run"]) == 2 * 2 * 8 * 9
    followed = ref.follow(seed, TINY, recipe, updates)
    np.testing.assert_allclose(losses, followed["loss"], atol=2e-5)
    start = ref.seeded_params(ref.key_from_seed(seed), c)
    mine = laguna_map.from_program(state.params, c)
    change = ref.leaf_norms({k: mine[k] - start[k] for k in mine})
    for name, want in followed["delta_norms"].items():
        # Adam divides by sqrt(v): where a gradient is all but zero its sign
        # is rounding, so the change is compared as a norm, at 2%.
        np.testing.assert_allclose(np.asarray(change[name]), want,
                                   rtol=0.02, atol=1e-7, err_msg=name)


# -- configuration, FLOPs, optimizer mask ----------------------------------------

def test_model_type_chooses_the_family(tmp_path):
    path = tmp_path / "c.json"
    path.write_text(json.dumps(dict(TINY, model_type="laguna")))
    config = load_model_config(str(path))
    assert isinstance(config, LagunaConfig)
    assert (config.router_experts, config.first_expert) == (16, 4)
    assert config.window_of(0) is None and config.window_of(1) == 8
    assert config.rope_of(0)[0] == 8 and config.rope_of(1)[0] == 16
    assert config.to_dict()["model_type"] == "laguna"
    # the published period when the lists are left out
    whole = LagunaConfig()
    assert whole.layer_types[:5] == TINY["layer_types"]
    assert whole.mlp_layer_types[:2] == ["dense", "sparse"]
    for wrong, match in (
            (dict(layer_types=["full_attention"] * 4), "layer_types"),
            (dict(mlp_layer_types=["dense"] * 4 + ["moe"]), "mlp_layer_types"),
            (dict(num_attention_heads_per_layer=[4, 6, 6, 6, 5]), "multiples"),
            (dict(ep_rank=4), "ep_rank"), (dict(tp_rank=1), "tp_rank"),
            (dict(gating="none"), "per-head"),
            (dict(moe_router_logit_softcapping=30), "soft cap")):
        with pytest.raises(ValueError, match=match):
            LagunaConfig(**dict(TINY, **wrong))


def test_published_configuration_counts_672_million():
    """The benchmark's configuration file, built abstractly: the cut's
    arithmetic (ISSUE 31) against the tree's own count, part by part."""
    config = load_model_config("benchmarks/configs/laguna-s-2.1.json")
    model = build_pretraining_model(config, jnp.bfloat16)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(leaf.shape))
                             for leaf in jax.tree_util.tree_leaves(tree))
    assert count(shapes["layers_0"]["attn"]) == pytest.approx(22.09e6, rel=5e-4)
    assert count(shapes["layers_4"]["attn"]) == count(shapes["layers_0"]["attn"])
    for layer in (1, 2, 3):
        assert count(shapes[f"layers_{layer}"]["attn"]) == pytest.approx(
            31.57e6, rel=5e-4)
    assert count(shapes["layers_0"]["mlp"]) == pytest.approx(113.25e6, rel=5e-4)
    assert count(shapes["layers_1"]["mlp"]) == pytest.approx(85.72e6, rel=5e-4)
    assert shapes["layers_1"]["mlp"]["router_kernel"].shape == (3072, 256)
    assert shapes["layers_1"]["mlp"]["experts_up"].shape == (8, 3072, 2048)
    assert count(shapes) == 672_125_952
    assert 16 * count(shapes) == pytest.approx(10.75e9, rel=1e-3)
    # every width is the published one
    with open("benchmarks/configs/laguna-s-2.1.json") as f:
        written = json.load(f)
    for key, value in dict(
            hidden_size=3072, head_dim=128, intermediate_size=12288,
            moe_intermediate_size=1024, shared_expert_intermediate_size=1024,
            num_experts_per_tok=10, sliding_window=512).items():
        assert written[key] == value, key
    assert config.router_experts == 256 and config.tp_size == 2
    for key in ("reduced", "published", "assumed", "precision", "deployment"):
        assert written[key], key
    assert set(written["reduced"]) >= {
        "num_hidden_layers", "num_experts", "vocab_size", "num_attention_heads",
        "num_key_value_heads", "num_attention_heads_per_layer"}


def test_flops_are_the_issues_arithmetic():
    config = load_model_config("benchmarks/configs/laguna-s-2.1.json")
    parts = {k: v / 1e6 for k, v in
             flops.laguna_forward_flops_per_token(config, 8192).items()}
    assert parts["attention_proj"] == pytest.approx(278, abs=0.5)
    assert parts["attention_full"] == pytest.approx(101, abs=0.5)
    # the band: 28 by 4 x 512 x 36 x 128 a layer, less the rows' short starts
    assert parts["attention_window"] == pytest.approx(28.3, rel=0.04)
    assert parts["dense_mlp"] == pytest.approx(226.5, abs=0.5)
    assert parts["experts"] == pytest.approx(105, abs=0.5)
    assert parts["head"] == pytest.approx(77, abs=0.5)
    assert sum(parts.values()) == pytest.approx(816, rel=2e-3)
    assert flops.causal_lm_train_flops_per_seq(config, 8192) * 4 == (
        pytest.approx(80.2e12, rel=2e-3))
    # unwindowed, the three sliding layers would do the triangle: 226
    no_window = LagunaConfig(**dict(config.to_dict(), sliding_window=8192))
    assert flops.laguna_forward_flops_per_token(no_window, 8192)[
        "attention_window"] / 1e6 == pytest.approx(226, abs=1)


def test_no_decay_mask_covers_the_family():
    model = build_pretraining_model(LagunaConfig(**TINY), jnp.float32)
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    mask = optim.no_decay_mask(params)
    flat = {"/".join(k): v for k, v in
            __import__("flax").traverse_util.flatten_dict(mask).items()}
    assert {k.split("/")[-1] for k, v in flat.items() if not v} == {"scale"}
    c = ref.sizes(TINY)
    for name, path in laguna_map.table(c).items():
        assert flat[path] == ref.decays(name, c), name


# -- the family's scopes reach the compiled step ------------------------------------

@pytest.fixture(scope="module")
def laguna_step_names():
    import re

    model = build_pretraining_model(LagunaConfig(**TINY), jnp.bfloat16,
                                    remat="full")
    tx = optim.adamw(1e-3, max_grad_norm=1.0,
                     weight_decay_mask=optim.no_decay_mask)
    state = pretrain.make_init_fn(
        model, tx, (jnp.zeros((1, 8), jnp.int32),), None)(jax.random.PRNGKey(0))
    step = pretrain.make_train_step(model, tx, next_sentence=False)
    batch = {"input_ids": np.zeros((2, 1, 24), np.int32)}
    text = step.lower(state, batch).compile().as_text()
    return set(re.findall(r'op_name="([^"]+)"', text))


@pytest.mark.parametrize("scope", pretrain.LAGUNA_SCOPES + ("attention_core",))
def test_every_scope_of_the_family_reaches_the_compiled_step(
        laguna_step_names, scope):
    assert any(f"/{scope}/" in name or f"({scope})" in name
               for name in laguna_step_names), scope


# -- the rotary turn in the lowered step ---------------------------------------------

@pytest.fixture(scope="module")
def lowered_rotary_ops():
    """[(op, name)] of the StableHLO ops of a small step (heads of 128, so the
    turn's kernel takes them; ``--remat full``) lowered for the TPU, with the
    names JAX gave them (scopes, ``transpose(``, ``rematted_computation``)."""
    import re

    from bert_pytorch_tpu.ops.pallas import common

    model = build_pretraining_model(
        LagunaConfig(**dict(TINY, head_dim=128)), jnp.bfloat16, remat="full")
    tx = optim.adamw(1e-3, max_grad_norm=1.0,
                     weight_decay_mask=optim.no_decay_mask)
    state = jax.eval_shape(pretrain.make_init_fn(
        model, tx, (jnp.zeros((1, 16), jnp.int32),), None), jax.random.PRNGKey(0))
    step = pretrain.make_train_step(model, tx, next_sentence=False)
    batch = {"input_ids": jax.ShapeDtypeStruct((2, 1, 32), np.int32)}
    with pytest.MonkeyPatch.context() as patch:
        # the kernel compiled, as on the chip, not unrolled by the interpreter
        patch.setattr(common, "interpret_mode", lambda: False)
        text = step.trace(state, batch).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    locations = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, flags=re.M))

    def name(reference, depth=0):
        body = locations.get(reference, reference)
        return body if depth > 8 else re.sub(
            r"#loc\d+", lambda m: name(m.group(0), depth + 1), body)

    return [(m.group(1), name(m.group(2))) for m in re.finditer(
        r"(stablehlo\.\w+).* loc\((#loc\d+)\)$", text, flags=re.M)]


def test_the_turn_is_one_kernel_a_call_with_no_concatenate_and_no_pad(
        lowered_rotary_ops):
    """Five layers x (q, k) x (forward, recompute, backward): thirty calls of
    the kernel under ``attn_rope`` and, under that scope, no piece of a head
    sliced off, padded or joined in XLA; the backward's and the recompute's
    calls carry the markers the benchmark's trace rules class them by."""
    mine = [(op, name) for op, name in lowered_rotary_ops if "attn_rope" in name]
    assert not [op for op, _ in mine if op in (
        "stablehlo.concatenate", "stablehlo.pad", "stablehlo.slice")]
    kernels = [name for op, name in mine
               if op == "stablehlo.custom_call" and "rotary_turn" in name]
    assert len(kernels) == 30
    assert sum("rematted_computation" in name for name in kernels) == 10
    assert sum("transpose(" in name and "rematted_computation" not in name
               for name in kernels) == 10


def test_each_rotary_table_is_made_once_a_step(lowered_rotary_ops):
    """Two kinds of layer, two tables: one cosine and one sine each in the
    whole step, not one in every layer of every pass."""
    ops = [op for op, _ in lowered_rotary_ops]
    assert ops.count("stablehlo.cosine") == ops.count("stablehlo.sine") == 2


def test_the_turn_is_looked_up_on_the_module_when_the_model_is_called(
        monkeypatch):
    """The seam the benchmark's planted fault ``rotary_dropped`` uses
    (``benchmarks/tests/test_train_laguna.py``): the identity in
    ``rope.apply_rotary``'s place moves the logits (by half a percent at
    fresh weights, whose scores are nearly flat: thousands of times the
    float32 noise)."""
    from bert_pytorch_tpu.models import laguna

    model = build_pretraining_model(LagunaConfig(**TINY), jnp.float32)
    ids = jax.random.randint(keys(1, 2)[0], (2, 24), 0, TINY["vocab_size"])
    params = model.init(jax.random.PRNGKey(0), ids)
    turned = model.apply(params, ids)[0]
    monkeypatch.setattr(laguna.rope, "apply_rotary", lambda x, cos, sin: x)
    far(model.apply(params, ids)[0], turned, share=1e-3)


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
        json.dumps(dict(TINY, model_type="laguna")))
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
    devices = jax.device_count()
    micro = 16 // devices
    assert result["attn_full_tiles_run"] == micro * devices * 8
    assert result["attn_window_tiles_run"] == micro * devices * 18
    assert result["moe_dropped_slots"] == 0.0 and result["moe_local_slots"] > 0
    log = (tmp_path / "out" / "pretraining.txt").read_text()
    assert "attn_window_tiles_run" in log and "moe_pieces_run" in log
