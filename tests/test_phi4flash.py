"""The ``phi4flash`` family against its plain float32 reference
(``benchmarks/reference/phi4flash_f32.py``) at a small size on the CPU: the
selective scan's kernels against a position-by-position loop, every kind of
layer, the whole model's loss and every leaf's gradient, two whole updates
through ``pretrain.make_train_step``, planted faults that must NOT compare
equal, the published layer rule, the pinned parameter count, the head shares
under ``tp_size`` 2, and the normal path (``run_pretraining.main``) from a
config file.

Tolerances: everything here is float32 at ``highest`` on both sides
(conftest), so program and reference differ only in the ORDER of float32
sums (the kernels' tiles, the scan's chunks). A few 1e-6 relative to the
largest element is that; 2e-5 leaves a decade of room and would not pass a
dropped window, lambda term, norm, gate or skip (each moves the result by
percents: the planted faults below).
"""

import json

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import phi4flash_f32 as ref
from benchmarks.reference import phi4flash_map
from bert_pytorch_tpu import optim, pretrain
from bert_pytorch_tpu.config import (PhiFlashConfig, load_model_config,
                                     phi_flash_layer_types)
from bert_pytorch_tpu.models import build_pretraining_model
from bert_pytorch_tpu.models.phi4flash import (DifferentialAttention,
                                               GatedMemoryUnit, Mamba1Mixer)
from bert_pytorch_tpu.ops import ssm
from bert_pytorch_tpu.utils import flops

CONFIG_FILE = "benchmarks/configs/phi-4-mini-flash-reasoning.json"
# the benchmark's cut at a small size: published layers 0, 1, 16, 17, 18, 19
# of 32, a window shorter than the rows, a scan chunk shorter than the rows
TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96, num_hidden_layers=6,
    num_attention_heads=8, num_key_value_heads=4, sliding_window=8,
    layer_indices=[0, 1, 16, 17, 18, 19], published_num_hidden_layers=32,
    mamba_dt_rank=4, scan_chunk=16, layer_norm_eps=1e-5,
    # 40 times narrower than the published widths: weights six times larger
    # keep the activations' sizes, so the scan's term is heard beside D * u
    initializer_range=0.12)
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
    """Sizes and seeded weights; ``sharp``: query and key columns five
    times larger, the memory unit's output ten times and every bias drawn, so
    that the softmax is far from uniform and what masks the scores or gates
    the memory shows in the output."""
    c = ref.sizes(dict(TINY, **changes))
    p = ref.seeded_params(ref.key_from_seed(seed), c)
    if sharp:
        wide = c["heads"] * c["hd"]
        for name in list(p):
            if name.endswith(".wqkv"):
                p[name] = p[name].at[:, :wide + c["KV"] * c["hd"]].multiply(5)
            elif name.endswith(".wq"):
                p[name] = p[name] * 5
            elif name.endswith(".gmu_out"):  # the memory's layer is heard
                p[name] = p[name] * 10
            elif name.endswith((".bq", ".bk", ".bv", ".bo", ".conv_b")):
                p[name] = 0.1 * jax.random.normal(
                    keys(1, len(name))[0], p[name].shape)
    return c, p


def _tree(c, p, layer):
    return phi4flash_map.to_program(p, c)[f"layers_{layer}"]["mixer"]


# -- the selective scan against a position-by-position loop --------------------

def _scan_operands(seq, channels=128, states=16, batch=2):
    k = keys(6, seq)
    u = jax.random.normal(k[0], (batch, seq, channels))
    dt = jax.nn.softplus(jax.random.normal(k[1], (batch, seq, channels)) - 2)
    a = -jnp.exp(0.5 * jax.random.normal(k[2], (channels, states)))
    b = jax.random.normal(k[3], (batch, seq, states))
    c = jax.random.normal(k[4], (batch, seq, states))
    return (u, dt, a, b, c), jax.random.normal(k[5], (batch, seq, channels))


def _loop(u, dt, a, b, c):
    """One position at a time, nothing else."""
    h = jnp.zeros((u.shape[0], u.shape[2], a.shape[1]))
    ys = []
    for t in range(u.shape[1]):
        h = (jnp.exp(dt[:, t, :, None] * a) * h
             + (dt[:, t] * u[:, t])[..., None] * b[:, t, None, :])
        ys.append(jnp.sum(h * c[:, t, None, :], axis=-1))
    return jnp.stack(ys, axis=1)


@pytest.mark.parametrize("seq", [32, 16, 40, 7])  # chunk 16: whole and ragged
def test_selective_scan_matches_a_loop_over_positions(seq):
    operands, w = _scan_operands(seq)
    value = lambda fn: (lambda *t: jnp.sum(fn(*t) * w))
    mine = lambda *t: ssm.selective_scan(*t, chunk=16)
    close(mine(*operands), _loop(*operands))
    got = jax.grad(value(mine), argnums=(0, 1, 2, 3, 4))(*operands)
    want = jax.grad(value(_loop), argnums=(0, 1, 2, 3, 4))(*operands)
    for g, w_ in zip(got, want):
        close(g, w_)


def test_selective_scan_keeps_its_state_across_chunks_and_channel_blocks():
    """Several chunks and more channels than one program holds (640 = 5
    blocks of 128): the carried state of every block is its own."""
    operands, _ = _scan_operands(48, channels=640, batch=1)
    close(ssm.selective_scan(*operands, chunk=16), _loop(*operands))
    close(ref.selective_scan(*operands, block=16), _loop(*operands))
    assert ssm.scan_chunks(48, 16) == 3 and ssm.scan_chunks(40, 16) == 3


def test_selective_scan_refuses_channels_off_the_lane_tile():
    operands, _ = _scan_operands(16, channels=96)
    with pytest.raises(ValueError, match="tiles of 128"):
        ssm.selective_scan(*operands, chunk=16)


# -- every kind of layer ---------------------------------------------------------

@pytest.mark.parametrize("layer", [0, 2])  # plain, and the one that writes m
def test_mamba_mixer_matches_the_reference(layer):
    c, p = _seeded(sharp=True)
    x = jax.random.normal(keys(1, layer)[0], (2, 40, c["H"]))
    cfg = PhiFlashConfig(**TINY)
    mine = lambda x_: Mamba1Mixer(cfg, jnp.float32).apply(
        {"params": _tree(c, p, layer)}, x_)
    theirs = lambda x_: ref.mamba_mixer(p, f"l{layer}.", c, x_, "f32")
    for mine_part, their_part in zip(mine(x), theirs(x)):
        close(mine_part, their_part)
    loss = lambda fn: (lambda x_: sum(jnp.sum(jnp.sin(t)) for t in fn(x_)))
    close(jax.grad(loss(mine))(x), jax.grad(loss(theirs))(x))


def test_gated_memory_unit_matches_the_reference():
    c, p = _seeded()
    x, m = (jax.random.normal(k, shape) for k, shape in zip(
        keys(2, 4), ((2, 24, c["H"]), (2, 24, c["inner"]))))
    mine = GatedMemoryUnit(PhiFlashConfig(**TINY), jnp.float32).apply(
        {"params": _tree(c, p, 4)}, x, m)
    close(mine, ref.gated_memory_unit(p, "l4.", x, m, "f32"))


def _program_attention(c, p, layer, x, backend="xla", kept=None, **changes):
    cfg = PhiFlashConfig(**dict(TINY, **changes))
    return DifferentialAttention(cfg, layer, jnp.float32, backend).apply(
        {"params": _tree(c, p, layer)}, x, kept)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("layer", [1, 3, 5])  # window, full, cross
def test_differential_attention_matches_the_reference(layer, backend):
    c, p = _seeded(sharp=True)
    x, y = (jax.random.normal(k, (2, 40, c["H"])) for k in keys(2, layer))
    kept = None
    if layer == 5:  # keys and values of ANOTHER input, through layer 3
        kept = ref.differential_attention(p, "l3.", c, 3, y, "f32")[1]
    mine = lambda x_: _program_attention(c, p, layer, x_, backend, kept)[0]
    theirs = lambda x_: ref.differential_attention(
        p, f"l{layer}.", c, layer, x_, "f32", kept)[0]
    close(mine(x), theirs(x))
    loss = lambda fn: (lambda x_: jnp.sum(jnp.sin(fn(x_))))
    close(jax.grad(loss(mine))(x), jax.grad(loss(theirs))(x))
    if kept is None:  # what the layer hands on is what the reference hands on
        for mine_kv, their_kv in zip(
                _program_attention(c, p, layer, x, backend)[1],
                ref.differential_attention(p, f"l{layer}.", c, layer, x, "f32")[1]):
            close(mine_kv, their_kv)


def test_reference_attention_in_blocks_matches_whole():
    c, p = _seeded(sharp=True)
    x = jax.random.normal(keys(1)[0], (2, 21, c["H"]))
    q, k, v = (jax.random.normal(key, (2, 21, 4, d)) for key, d in zip(
        keys(3, 1), (16, 16, 32)))
    for window in (None, 8):
        close(ref.softmax_values(q, k, v, window, "f32", block_rows=8),
              ref.softmax_values(q, k, v, window, "f32", block_rows=64))


def test_the_head_shares_add_up_to_the_whole_layer():
    """Under ``tp_size`` 2 the two ranks' attention outputs (their heads'
    columns of Wqkv and its bias, their rows of out_proj), the bias of
    out_proj ONCE, add up to the whole layer's."""
    c, p = _seeded(7, sharp=True)
    x = jax.random.normal(keys(1, 7)[0], (2, 24, c["H"]))
    heads, kv, hd = c["heads"], c["KV"], c["hd"]
    whole = ref.differential_attention(p, "l3.", c, 3, x, "f32")[0]
    total, biases = 0.0, 0
    for rank in range(2):
        held = dict(TINY, num_attention_heads=heads // 2,
                    num_key_value_heads=kv // 2, tp_size=2, tp_rank=rank)
        hc = ref.sizes(held)
        assert hc["hd"] == hd
        cols = lambda n, base: np.arange(
            base + rank * n // 2 * hd, base + (rank + 1) * n // 2 * hd)
        take = np.concatenate([cols(heads, 0), cols(kv, heads * hd),
                               cols(kv, (heads + kv) * hd)])
        mine = {"l3.wqkv": p["l3.wqkv"][:, take],
                "l3.bq": p["l3.bq"][cols(heads, 0)],
                "l3.bk": p["l3.bk"][cols(kv, 0)], "l3.bv": p["l3.bv"][cols(kv, 0)],
                "l3.wo": p["l3.wo"][cols(heads, 0)], "l3.subln": p["l3.subln"],
                **{f"l3.{n}": p[f"l3.{n}"] for n in ("lq1", "lk1", "lq2", "lk2")}}
        if rank == 0:
            mine["l3.bo"] = p["l3.bo"]
        tree = phi4flash_map.to_program(
            {**{k: v for k, v in p.items() if not k.startswith("l3.")
                or k.split(".")[1] in ("ln1_w", "ln1_b", "ln2_w", "ln2_b",
                                       "fc1", "fc2")}, **mine}, hc)
        out = DifferentialAttention(
            PhiFlashConfig(**held), 3, jnp.float32).apply(
                {"params": tree["layers_3"]["mixer"]}, x)[0]
        biases += "bias" in tree["layers_3"]["mixer"]["out_proj"]
        close(out, ref.differential_attention(mine, "l3.", hc, 3, x, "f32")[0])
        total = total + out
    assert biases == 1
    close(total, whole)


# -- the whole model ----------------------------------------------------------------

def _model_loss(c, backend="xla", remat="full"):
    model = build_pretraining_model(PhiFlashConfig(**TINY), jnp.float32,
                                    remat=remat, attention_backend=backend)

    def loss(tree, ids):
        from bert_pytorch_tpu.models.losses import next_token_loss

        logits, _ = model.apply({"params": tree}, ids)
        return next_token_loss(logits, ids)[0]

    return loss


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_loss_and_every_gradient_match_the_reference(backend):
    c, p = _seeded(5, sharp=True)
    ids = jax.random.randint(keys(1, 5)[0], (2, 40), 0, c["V"])
    loss, grads = jax.value_and_grad(_model_loss(c, backend))(
        phi4flash_map.to_program(p, c), ids)
    want, want_grads = jax.value_and_grad(
        lambda p_: ref.next_token_loss(p_, c, ids))(p)
    assert abs(float(loss) - float(want)) < 1e-5
    mine = phi4flash_map.from_program(grads, c)
    assert set(mine) == set(want_grads)
    for name, g in want_grads.items():
        if name.endswith(".bk"):
            # a bias on the keys moves every score of a row alike: its
            # gradient is zero but for rounding, on both sides
            scale = float(jnp.max(jnp.abs(want_grads[name[:-1] + "q"])))
            assert float(jnp.max(jnp.abs(g))) < 1e-4 * scale, name
            assert float(jnp.max(jnp.abs(mine[name]))) < 1e-4 * scale, name
            continue
        assert float(jnp.max(jnp.abs(g))) > 0, name  # no leaf without a gradient
        # (through six layers and softmaxes made sharp on purpose the order
        # of float32 sums shows at some 1e-4 of a tensor's largest entry)
        close(mine[name], g, tol=2e-3)


@pytest.mark.parametrize("fault", ["window", "lambda", "subln", "memory_gate",
                                   "cross_kv", "skip", "tied"])
def test_a_planted_fault_is_not_equal(fault):
    """Not blind: the reference with the window dropped, the ``lam A2`` term
    dropped, the 128-wide norm dropped, the memory gate dropped, the cross
    layer reading layer 1's K/V, ``D * u`` dropped or the head untied is far
    from the program, in its logits and so in its loss."""
    c, p = _seeded(5, sharp=True)
    ids = jax.random.randint(keys(1, 6)[0], (2, 40), 0, c["V"])
    model = build_pretraining_model(PhiFlashConfig(**TINY), jnp.float32)
    mine = model.apply({"params": phi4flash_map.to_program(p, c)}, ids)[0]
    close(mine, ref.forward(p, c, ids), tol=2e-4)
    far(ref.forward(p, c, ids, faults=(fault,)), mine, share=0.02)


def test_two_updates_through_make_train_step_match_the_reference():
    """Through the program's own step (micro-batch scan, clipping, AdamW with
    the no-decay mask, the tied head in the loss) against the reference's
    AdamW: losses, the counters, and the parameters' change after two
    updates."""
    c = ref.sizes(TINY)
    recipe = ref.Recipe(learning_rate=1e-3, warmup_proportion=0.01,
                        max_steps=1000)
    seed = 11
    model = build_pretraining_model(PhiFlashConfig(**TINY), jnp.float32,
                                    remat="full")
    schedule = optim.make_schedule("constant", recipe.learning_rate,
                                   recipe.warmup_proportion, recipe.max_steps)
    tx = optim.adamw(schedule, b1=recipe.b1, b2=recipe.b2, eps=recipe.eps,
                     weight_decay=recipe.weight_decay,
                     weight_decay_mask=optim.no_decay_mask,
                     max_grad_norm=recipe.max_grad_norm)
    params = phi4flash_map.to_program(
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
        assert float(metrics["finite"]) == 1.0
        # two micro-batches of two rows; 24 positions are two chunks of 16
        # and nine 8-wide tiles a map, all computed on the XLA path
        assert float(metrics["scan_chunks_run"]) == 2 * 2 * 2 * 2
        assert float(metrics["attn_window_tiles_run"]) == 2 * 2 * 8 * 9
        assert float(metrics["attn_full_tiles_run"]) == 2 * 2 * 2 * 8 * 9
        assert float(metrics["memory_readers"]) == 1.0
        assert float(metrics["shared_kv_readers"]) == 1.0
    followed = ref.follow(seed, TINY, recipe, updates)
    np.testing.assert_allclose(losses, followed["loss"], atol=2e-5)
    start = ref.seeded_params(ref.key_from_seed(seed), c)
    mine = phi4flash_map.from_program(state.params, c)
    change = ref.leaf_norms({k: mine[k] - start[k] for k in mine})
    for name, want in followed["delta_norms"].items():
        # Adam divides by sqrt(v): where a gradient is all but zero its sign
        # is rounding, so the change is compared as a norm, at 2%; the keys'
        # bias has NO gradient but rounding, and its change is that noise
        # divided by its own size (the cell's comparison leaves it out too).
        if name.endswith(".bk"):
            continue
        np.testing.assert_allclose(np.asarray(change[name]), want,
                                   rtol=0.02, atol=1e-7, err_msg=name)


def test_the_kernel_path_counts_the_band_and_the_triangle(monkeypatch):
    from bert_pytorch_tpu.ops.pallas import attention as flash

    monkeypatch.setattr(flash, "_pick_blocks", lambda seq: (8, 8))
    model = lambda backend: build_pretraining_model(
        PhiFlashConfig(**TINY), jnp.float32, attention_backend=backend)
    ids = jnp.zeros((1, 64), jnp.int32)
    params = model("xla").init(jax.random.PRNGKey(0), ids)
    run = lambda backend: {k: float(v) for k, v in model(backend).apply(
        params, ids)[1].items()}
    masked, skipped = run("xla"), run("pallas")
    assert masked["attn_window_tiles_run"] == 8 * 64
    assert masked["attn_full_tiles_run"] == 2 * 8 * 64
    assert skipped["attn_window_tiles_run"] == 8 * 15   # two a row, one first
    assert skipped["attn_full_tiles_run"] == 2 * 8 * 36  # layer 17 and the cross
    assert skipped["scan_chunks_run"] == 2 * 4


# -- configuration, layer rule, parameter count, FLOPs, optimizer mask -------------

@pytest.mark.parametrize("n", [8, 32])
def test_the_published_layer_rule(n):
    rule = phi_flash_layer_types(n)
    assert rule == ref.layer_rule(n) and len(rule) == n
    half = n // 2
    assert rule[:half] == ["mamba", "sliding_attention"] * (half // 2)
    assert rule[half:half + 2] == ["mamba_memory", "full_attention"]
    assert rule[half + 2:] == ["gmu", "cross_attention"] * (half // 2 - 1)
    if n == 32:  # the benchmark's cut is rows 0, 1, 16, 17, 18, 19 of it
        config = load_model_config(CONFIG_FILE)
        assert config.layer_indices == [0, 1, 16, 17, 18, 19]
        assert config.layer_types == [rule[l] for l in config.layer_indices]
        assert (rule.count("mamba") + 1, rule.count("sliding_attention"),
                rule.count("gmu"), rule.count("cross_attention")) == (9, 8, 7, 7)
        assert config.lambda_init(3) == pytest.approx(
            0.8 - 0.6 * np.exp(-0.3 * 17))
    for wrong in (6, 2, 0):
        with pytest.raises(ValueError, match="n % 4"):
            phi_flash_layer_types(wrong)


def test_model_type_chooses_the_family(tmp_path):
    path = tmp_path / "c.json"
    path.write_text(json.dumps(dict(TINY, model_type="phi4flash")))
    config = load_model_config(str(path))
    assert isinstance(config, PhiFlashConfig)
    assert config.window_of(0) is None and config.window_of(1) == 8
    assert config.head_dim == 8 and config.mamba_inner == 128
    assert config.to_dict()["model_type"] == "phi4flash"
    assert PhiFlashConfig().layer_types == phi_flash_layer_types(32)
    assert PhiFlashConfig().mamba_dt_rank == 160
    for wrong, match in (
            (dict(layer_types=["mamba"] * 5), "layer_types"),
            (dict(layer_types=["gmu"] + ["mamba"] * 5), "gmu layer reads"),
            (dict(layer_types=["mamba", "cross_attention"] + ["mamba"] * 4),
             "cross_attention layer reads"),
            (dict(num_attention_heads=6), "query heads"),
            (dict(tp_rank=1), "tp_rank"),
            (dict(tie_word_embeddings=False), "tied head"),
            (dict(mlp_bias=True), "tied head")):
        with pytest.raises(ValueError, match=match):
            PhiFlashConfig(**dict(TINY, **wrong))


def _count(tree):
    return sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(tree))


def _abstract_params(config):
    model = build_pretraining_model(config, jnp.bfloat16)
    return jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))["params"]


def test_published_configuration_counts_671_million():
    """The benchmark's configuration file, built abstractly: the cut's
    arithmetic (ISSUE 34: the stated fallback, heads two ways, which the
    chip's memory forced) against the tree's own count, part by part; and the
    697,299,072 of the heads whole."""
    config = load_model_config(CONFIG_FILE)
    shapes = _abstract_params(config)
    mixers = [_count(shapes[f"layers_{i}"]["mixer"]) for i in range(6)]
    assert mixers == [41_241_600, 9_835_904, 41_241_600, 9_835_904,
                      26_214_400, 6_557_824]
    for i in range(6):
        assert _count(shapes[f"layers_{i}"]["mlp"]) == 78_643_200
        assert _count(shapes[f"layers_{i}"]) == mixers[i] + 78_643_200 + 10_240
    assert shapes["embedding"].shape == (25_088, 2560) and "lm_head" not in shapes
    assert shapes["layers_0"]["mixer"]["A_log"].shape == (5120, 16)
    assert shapes["layers_1"]["mixer"]["Wqkv"]["kernel"].shape == (2560, 2560)
    assert shapes["layers_1"]["mixer"]["out_proj"]["kernel"].shape == (1280, 2560)
    assert shapes["layers_1"]["mixer"]["out_proj"]["bias"].shape == (2560,)
    assert shapes["layers_1"]["mixer"]["subln"]["scale"].shape == (128,)
    assert _count(shapes) == 671_078_272
    assert 16 * _count(shapes) == pytest.approx(10.74e9, rel=1e-3)
    # every width is the published one, and only depth and vocabulary are cut
    with open(CONFIG_FILE) as f:
        written = json.load(f)
    for key, value in dict(
            hidden_size=2560, intermediate_size=10240, sliding_window=512,
            layer_norm_eps=1e-5,
            mb_per_layer=2, max_position_embeddings=262144, embd_pdrop=0,
            resid_pdrop=0, hidden_act="silu", tie_word_embeddings=True,
            mlp_bias=False, lm_head_bias=False,
            model_type="phi4flash").items():
        assert written[key] == value, key
    assert written["reduced"] == ["num_hidden_layers", "vocab_size",
                                  "num_attention_heads", "num_key_value_heads"]
    assert (config.num_attention_heads, config.num_key_value_heads,
            config.tp_size, config.head_dim) == (20, 10, 2, 64)
    assert written["published"]["num_attention_heads"] == 40
    assert written["published"]["num_key_value_heads"] == 20
    for key in ("published", "assumed", "precision", "deployment"):
        assert written[key], key
    # the heads whole: the cut the chip's compiler refused by 312 MB
    whole = PhiFlashConfig(**dict(
        {k: v for k, v in config.to_dict().items() if k != "model_type"},
        num_attention_heads=40, num_key_value_heads=20, tp_size=1))
    assert whole.head_dim == 64
    shapes = _abstract_params(whole)
    assert [_count(shapes[f"layers_{i}"]["mixer"]) for i in (1, 5)] == [
        19_668_864, 13_112_704]
    assert _count(shapes) == 697_299_072


def test_flops_are_the_issues_arithmetic():
    config = load_model_config(CONFIG_FILE)
    parts = {k: v / 1e6 for k, v in
             flops.phi_flash_forward_flops_per_token(config, 8192).items()}
    assert parts["mlp"] == pytest.approx(944, abs=0.5)
    assert parts["s6_proj"] == pytest.approx(165, abs=0.6)
    assert parts["head"] == pytest.approx(128, abs=0.5)
    assert parts["gmu"] == pytest.approx(52, abs=0.5)
    # half the issue's heads (the fallback): half its 126, 7.9 and 105
    assert parts["attention_full"] == pytest.approx(126 / 2, abs=0.5)
    # the band: 6 x 64 x 20 x 512 a position, less the rows' short starts
    assert parts["attention_window"] == pytest.approx(7.9 / 2, rel=0.04)
    assert parts["attention_proj"] == pytest.approx(105 / 2, abs=0.5)
    assert sum(parts.values()) == pytest.approx(1408.3, rel=1e-3)
    assert flops.causal_lm_train_flops_per_seq(config, 8192) * 4 == (
        pytest.approx(138.4e12, rel=2e-3))
    whole = PhiFlashConfig(**dict(
        {k: v for k, v in config.to_dict().items() if k != "model_type"},
        num_attention_heads=40, num_key_value_heads=20, tp_size=1))
    assert sum(flops.phi_flash_forward_flops_per_token(whole, 8192).values()
               ) == pytest.approx(1528e6, rel=1e-3)


def test_no_decay_mask_covers_the_family():
    model = build_pretraining_model(PhiFlashConfig(**TINY), jnp.float32)
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))["params"]
    mask = optim.no_decay_mask(params)
    flat = {"/".join(k): v for k, v in
            __import__("flax").traverse_util.flatten_dict(mask).items()}
    assert {k.split("/")[-1] for k, v in flat.items() if not v} == {
        "scale", "bias", "conv_bias", "dt_bias", "A_log", "D", "lambda_q1",
        "lambda_k1", "lambda_q2", "lambda_k2"}
    c = ref.sizes(TINY)
    for name, path in phi4flash_map.table(c).items():
        assert flat[path] == ref.decays(name, c), name
    # the program's own init draws what the reference's seeded weights draw
    kinds = {name: kind for name, (_, kind) in ref.param_table(c).items()}
    mine = phi4flash_map.from_program(params, c)
    close(mine["l0.A_log"], ref.seeded_params(ref.key_from_seed(0), c)["l0.A_log"])
    assert float(jnp.max(jnp.abs(mine["l0.conv_w"]))) <= 0.5
    assert kinds["l0.conv_w"] == "conv" and kinds["l1.lq1"] == "lambda"


# -- the family's scopes reach the compiled step ------------------------------------

@pytest.fixture(scope="module")
def phi_step_names():
    import re

    model = build_pretraining_model(PhiFlashConfig(**TINY), jnp.bfloat16,
                                    remat="full")
    tx = optim.adamw(1e-3, max_grad_norm=1.0,
                     weight_decay_mask=optim.no_decay_mask)
    state = pretrain.make_init_fn(
        model, tx, (jnp.zeros((1, 16), jnp.int32),), None)(jax.random.PRNGKey(0))
    step = pretrain.make_train_step(model, tx, next_sentence=False)
    batch = {"input_ids": np.zeros((2, 1, 24), np.int32)}
    text = step.lower(state, batch).compile().as_text()
    return set(re.findall(r'op_name="([^"]+)"', text))


@pytest.mark.parametrize("scope",
                         pretrain.PHI_FLASH_SCOPES + ("attention_core",))
def test_every_scope_of_the_family_reaches_the_compiled_step(
        phi_step_names, scope):
    assert any(f"/{scope}/" in name or f"({scope})" in name
               for name in phi_step_names), scope


def test_the_kernels_names_tell_the_three_kinds_of_call_apart():
    """``flash_diff_window_*``, ``flash_diff_*`` and ``flash_diff_cross_*``,
    and ``selective_scan_fwd`` / ``_bwd``, in the lowered gradient."""
    model = build_pretraining_model(PhiFlashConfig(**TINY), jnp.float32,
                                    attention_backend="pallas")
    ids = jnp.zeros((1, 32), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: jnp.sum(model.apply(p, ids)[0])))(params))
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        for kind in ("flash_diff_window_", "flash_diff_", "flash_diff_cross_"):
            assert f"name={kind}{kernel}" in text.replace('"', ""), kind + kernel
    assert "selective_scan_fwd" in text and "selective_scan_bwd" in text


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
        json.dumps(dict(TINY, model_type="phi4flash")))
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
    assert result["scan_chunks_run"] == micro * devices * 2 * 2
    assert result["attn_full_tiles_run"] == micro * devices * 2 * 8
    assert result["attn_window_tiles_run"] == micro * devices * 8
    assert result["memory_readers"] == 1.0
    assert result["shared_kv_readers"] == 1.0
    log = (tmp_path / "out" / "pretraining.txt").read_text()
    assert "scan_chunks_run" in log and "shared_kv_readers" in log
