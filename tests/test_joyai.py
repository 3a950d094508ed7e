"""The ``joyai_llm_flash`` family against its plain float32 reference
(``benchmarks/reference/joyai_f32.py``) at a small size on the CPU: the
interleaved rotary turn against its pairs written out, the causal flash
kernels at a head of 128 + 64 over values of 128 against the XLA form, the
latent attention layer with every cotangent, the expert shares adding up to
the uncut layer, the second target's shift, the two gradient paths, the whole
model's two losses and every gradient, two whole updates through
``pretrain.make_train_step`` on the whole and on the chunked head path, the
pinned counts, and the normal path (``run_pretraining.main``) from a config
file.

Tolerances: everything here is float32 at ``highest`` on both sides
(conftest), so program and reference differ only in the ORDER of float32
sums: 2e-5 of the largest element leaves a decade of room and would not pass
a latent norm left out or a target one place off (each moves the result by
percents).
"""

import functools
import json

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import joyai_f32 as ref
from benchmarks.reference import joyai_map
from bert_pytorch_tpu import optim, pretrain
from bert_pytorch_tpu.config import JoyAIConfig, LagunaConfig, load_model_config
from bert_pytorch_tpu.models import build_pretraining_model, joyai, losses
from bert_pytorch_tpu.ops import rope
from bert_pytorch_tpu.ops.attention import dot_product_attention
from bert_pytorch_tpu.ops.pallas.attention import tiles_visited
from bert_pytorch_tpu.utils import flops

# the published layer at a small size: 4 heads of 16 + 8 turned over values
# of 16, latents of 48 and 32, one dense layer then one of 4 of 8 experts
# held top-3 with the shared expert, and the module
TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96, num_hidden_layers=2,
    first_k_dense_replace=1, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, n_routed_experts=4, n_shared_experts=1, ep_size=2, ep_rank=1,
    num_experts_per_tok=3, moe_intermediate_size=32, routed_scaling_factor=2.5,
    rope_theta=32000000,
    rms_norm_eps=1e-6, moe_piece_multiple=8)
TOL = 2e-5
CONFIG_FILE = "benchmarks/configs/joyai-llm-flash.json"


def close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-30), (
        np.max(np.abs(a - b)), np.max(np.abs(b)))


def keys(n, seed=0):
    return jax.random.split(jax.random.PRNGKey(seed), n)


def _seeded(seed=3, loud=False, **changes):
    """Sizes and seeded weights; ``loud``: the norms' scales away from one and
    every matrix ten times larger, so that every parameter shows in the
    output and the routers' scores are far from equal."""
    c = ref.sizes(dict(TINY, **changes))
    p = ref.seeded_params(ref.key_from_seed(seed), c)
    if loud:
        table = ref.param_table(c)
        for index, name in enumerate(sorted(p)):
            draw = jax.random.fold_in(jax.random.PRNGKey(seed + 100), index)
            if table[name][1] == "ones":
                p[name] = p[name] + 0.3 * jax.random.normal(draw, p[name].shape)
            elif table[name][1] != "zeros":
                p[name] = 10.0 * p[name]
    return c, p


def _model(backend="xla", remat="full", **changes):
    return build_pretraining_model(
        JoyAIConfig(**dict(TINY, **changes)), jnp.float32, remat=remat,
        attention_backend=backend)


def _objective(model, ids):
    return lambda params: pretrain._apply_causal_lm_loss(
        model, {"params": params}, {"input_ids": ids})


# -- the interleaved turn --------------------------------------------------------------

def test_the_interleaved_turn_is_the_pairs_written_out_and_its_backward_the_turn_back():
    seq, heads, width, theta = 12, 3, 8, 32000000.0
    x = np.asarray(jax.random.normal(keys(1)[0], (2, seq, heads, width)))
    cos, sin = rope.rotary_tables(seq, width, {"rope_theta": theta})
    got = rope.apply_rotary_interleaved(jnp.asarray(x), cos, sin)
    want = np.zeros_like(x)
    for t in range(seq):
        for i in range(width // 2):
            angle = t * theta ** (-2.0 * i / width)
            a, b = x[:, t, :, 2 * i], x[:, t, :, 2 * i + 1]
            want[:, t, :, 2 * i] = a * np.cos(angle) - b * np.sin(angle)
            want[:, t, :, 2 * i + 1] = b * np.cos(angle) + a * np.sin(angle)
    close(got, want)
    close(ref.turn_pairs(jnp.asarray(x), theta), want)
    assert not np.allclose(rope.apply_rotary(jnp.asarray(x), cos, sin), want)
    g = jax.random.normal(keys(1, 1)[0], x.shape)
    mine = jax.vjp(lambda t: rope.apply_rotary_interleaved(t, cos, sin),
                   jnp.asarray(x))[1](g)[0]
    plain = jax.vjp(lambda t: ref.turn_pairs(t, theta), jnp.asarray(x))[1](g)[0]
    close(mine, plain)


# -- the core's kernels ---------------------------------------------------------------

def test_the_flash_kernels_at_a_head_of_192_over_values_of_128_match_the_xla_form():
    """Interpreted, at the published lane proportions (a tile and a half of
    queries and keys, one tile of values), forward and every gradient."""
    k = keys(4, 5)
    shape = lambda width: (1, 512, 2, width)
    q, key = (jax.random.normal(k[i], shape(192)) for i in range(2))
    v = jax.random.normal(k[2], shape(128))
    g = jax.random.normal(k[3], shape(128))

    def run(backend):
        out, back = jax.vjp(lambda *t: dot_product_attention(
            *t, backend=backend, causal=True, label="mla"), q, key, v)
        return (out,) + back(g)

    for mine, theirs in zip(run("pallas"), run("xla")):
        assert mine.shape == theirs.shape
        close(mine, theirs, 1e-4)


# -- the attention layer ---------------------------------------------------------------

# the smallest shapes the causal kernels take at the published lane
# proportions: rows of 512, two heads of 128 + 64 over values of 128
KERNEL_SIZES = dict(num_attention_heads=2, num_key_value_heads=2,
                    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
# region -> (layers of the model, what the loss reads): a dense block alone
# (``hidden_states`` never runs the module), the module alone (no layer ahead
# of it, so the one core of the program is its block's)
REGIONS = {"block": (1, "hidden_states"), "module": (0, "streams")}


def _kernel_calls(jaxpr):
    """The name of every ``pallas_call`` a jaxpr holds, the nested jaxprs'
    too, in program order."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _kernel_calls(sub)
    return found


@functools.lru_cache(maxsize=None)
def _region_grad(region, remat):
    """(the gradient function of a loss through ONE rematerialized region of
    the model as it builds them, its parameters): the kernels, interpreted."""
    layers, method = REGIONS[region]
    model = _model("pallas", remat, num_hidden_layers=layers,
                   first_k_dense_replace=layers, **KERNEL_SIZES)
    ids = jax.random.randint(keys(1, 11)[0], (1, 512), 0, TINY["vocab_size"])
    params = flax.linen.unbox(model.init(keys(1, 12)[0], ids)["params"])

    def loss(params):
        out = model.apply({"params": params}, ids,
                          method=getattr(model, method))
        y = out[2][joyai.MTP] if region == "module" else out[0]
        return jnp.sum(y * jnp.cos(y))
    return jax.grad(loss), params


@pytest.fixture(scope="module")
def region_grads():
    """(region, remat) -> the gradients, each program run once a module."""
    made = {}

    def of(region, remat):
        if (region, remat) not in made:
            grad, params = _region_grad(region, remat)
            made[region, remat] = jax.jit(grad)(params)
        return made[region, remat]
    return of


@pytest.mark.parametrize("remat", ["full", "dots", "none"])
@pytest.mark.parametrize("region", sorted(REGIONS))
def test_the_core_runs_once_a_region_under_a_gradient_whatever_the_remat(
        region, remat, region_grads):
    """A block and the module each keep the flash forward kernel's output and
    log-sum-exps across remat by name (``joyai.KEPT_ACROSS_REMAT``), under
    ``--remat full`` too: the gradient program through a rematerialized
    region holds ONE ``flash_mla_fwd`` as it does without remat (two before:
    the recompute ran the forward rule of the kernel's ``custom_vjp`` again),
    the two backward kernels once each, and every gradient is
    ``remat='none'``'s bit for bit: those of the three projections that make
    q, k and v, which the core's gradients to q, k and v pass into, with the
    rest (float32: on the chip a kept bfloat16 output beside recomputed q, k
    and v moves them by rounding, PERF.md 6)."""
    grad, params = _region_grad(region, remat)
    made = _kernel_calls(jax.make_jaxpr(grad)(params).jaxpr)
    assert sorted(made) == ["flash_mla_bwd_dkv", "flash_mla_bwd_dq",
                            "flash_mla_fwd"], made
    if remat == "none":
        return
    got, want = region_grads(region, remat), region_grads(region, "none")
    block = want["mtp"]["block"] if region == "module" else want["layers_0"]
    for name in ("q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj"):
        assert np.asarray(block["attention"][name]["kernel"]).any(), name
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_every_region_of_the_model_keeps_and_no_other_policy_does():
    """Two layers and the module: three forward calls of the core in the
    whole objective's gradient program under ``--remat full`` (six with the
    plain policy, which is what ``rematerialized`` still builds for a family
    that asks for nothing: ``flash_out`` and ``flash_lse`` are no names of
    ``KEPT_UNDER_FULL``), each region's backward kernels straight after the
    forward pass's own, none of them behind a second forward call."""
    from bert_pytorch_tpu.models import decoder
    from bert_pytorch_tpu.ops import remat

    assert joyai.KEPT_ACROSS_REMAT == (remat.FLASH_OUT, remat.FLASH_LSE)
    assert not set(joyai.KEPT_ACROSS_REMAT) & set(remat.KEPT_UNDER_FULL)
    model = _model("pallas", "full", **KERNEL_SIZES)
    ids = jnp.zeros((1, 512), jnp.int32)
    params = flax.linen.unbox(jax.eval_shape(
        model.init, keys(1)[0], ids)["params"])
    objective = lambda p: _objective(model, ids)(p)[0]
    made = _kernel_calls(jax.make_jaxpr(jax.grad(objective))(params).jaxpr)
    backward = ["flash_mla_bwd_dq", "flash_mla_bwd_dkv"]
    assert made == ["flash_mla_fwd"] * 3 + backward * 3, made

    plain = decoder.rematerialized("full", joyai.JoyAIBlock)(
        model.config, True, jnp.float32, "pallas")
    x = jnp.zeros((1, 512, TINY["hidden_size"]), jnp.float32)
    block_params = jax.eval_shape(plain.init, keys(1)[0], x)["params"]
    through = lambda p: jnp.sum(plain.apply({"params": p}, x)[0])
    made = _kernel_calls(jax.make_jaxpr(jax.grad(through))(
        flax.linen.unbox(block_params)).jaxpr)
    assert made == ["flash_mla_fwd"] * 2 + backward, made


@pytest.mark.parametrize("config_file,micro_batch,asked,line", [
    (CONFIG_FILE, 1, dict(regions=8, heads=32, head_dim=128),
     "flash_out 67108864 B, flash_lse 1048576 B (0.55 GB over 8 regions)"),
    ("benchmarks/configs/zaya1-8b.json", 2,
     dict(regions=5, heads=8, head_dim=128),
     "flash_out 33554432 B, flash_lse 524288 B (0.17 GB over 5 regions)"),
    ("benchmarks/configs/qwen3-next-80b-a3b.json", 2,
     dict(regions=1, heads=16, head_dim=256),
     "flash_out 134217728 B, flash_lse 1048576 B (0.14 GB over 1 regions)"),
], ids=["joyai", "zaya", "qwen3_next"])
def test_the_start_up_line_says_what_the_family_keeps(
        config_file, micro_batch, asked, line):
    """``run_pretraining._kept_across_remat`` reads the family's own answer
    (``kept_across_remat``: the names, the regions and the OUTPUT's shape:
    joyai's values of 128 and not its keys of 192) through ``ops/remat.py
    kept_residual_bytes``, at each cell's micro-batch of rows of 8192 (a
    joyai region keeps 67,108,864 + 1,048,576 B, the issue's arithmetic); a
    family that asks for nothing says so and gets no line."""
    import run_pretraining
    from bert_pytorch_tpu.models.laguna import LagunaForCausalLM
    from bert_pytorch_tpu.ops import remat

    config = load_model_config(config_file)
    model = build_pretraining_model(config, jnp.bfloat16, remat="full",
                                    attention_backend="pallas")
    assert model.kept_across_remat() == dict(
        asked, keeping=(remat.FLASH_OUT, remat.FLASH_LSE))
    assert run_pretraining._kept_across_remat(
        model, config, micro_batch, 8192) == (
            "remat full, attention path pallas at seq 8192: kept across "
            "remat per region and micro-batch: " + line)
    for value, backend in (("none", "pallas"), ("full", "xla")):
        other = build_pretraining_model(config, jnp.bfloat16, remat=value,
                                        attention_backend=backend)
        assert run_pretraining._kept_across_remat(
            other, config, micro_batch, 8192).endswith(
                "no named residual kept across remat")
    assert LagunaForCausalLM(LagunaConfig()).kept_across_remat() == {}


def test_latent_attention_matches_the_reference_with_every_cotangent():
    c, p = _seeded(4, loud=True)
    u = jax.random.normal(keys(1, 6)[0], (2, 40, c["H"]))
    g = jax.random.normal(keys(1, 7)[0], u.shape)
    layer = joyai.LatentAttention(JoyAIConfig(**TINY), jnp.float32)

    def mine(tree, x):
        return layer.apply({"params": tree}, x)[0]

    def theirs(p_, x):
        return ref.attention(p_, "l1.", c, x, "f32", block_rows=16)

    def with_cotangents(f):
        def run(weights, x):
            out, back = jax.vjp(f, weights, x)
            return (out,) + back(g)
        return jax.jit(run)

    tree = joyai_map.to_program(p, c)["layers_1"]["attention"]
    out, grads, du = with_cotangents(mine)(tree, u)
    want, want_grads, want_du = with_cotangents(theirs)(p, u)
    close(out, want)
    close(du, want_du)
    table = {name: path.split("/", 2)[2] for name, path in
             joyai_map.table(c).items() if path.startswith("layers_1/attention/")}
    assert len(table) == 7
    flat = flax.traverse_util.flatten_dict(grads, sep="/")
    for name, path in table.items():
        close(flat[path], want_grads[name])


def test_no_part_of_the_model_reads_a_later_position():
    """Neither stream: the module's output at t reads tokens 0 .. t + 1."""
    c, p = _seeded(5, loud=True)
    model = _model(remat="none")
    ids = jax.random.randint(keys(1, 8)[0], (1, 24), 0, c["V"])
    run = jax.jit(lambda i: model.apply(
        {"params": joyai_map.to_program(p, c)}, i, method="streams"))
    hidden, _, further = run(ids)
    moved, _, moved_further = run(ids.at[0, 16].set((ids[0, 16] + 1) % c["V"]))
    np.testing.assert_array_equal(hidden[:, :16], moved[:, :16])
    np.testing.assert_array_equal(further["mtp"][:, :15],
                                  moved_further["mtp"][:, :15])
    assert np.abs(further["mtp"][:, 15] - moved_further["mtp"][:, 15]).max() > 0


# -- the expert layer ------------------------------------------------------------------

def test_the_expert_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once():
    """Each of 4 ranks holds 2 of 8 experts; every rank computes the shared
    expert alike: the ranks' routed parts and ONE shared expert add up to the
    reference's uncut layer (every expert on one chip)."""
    changes = dict(n_routed_experts=2, ep_size=4, ep_rank=0)
    c, p = _seeded(4, loud=True, **changes)
    held, every = c["held"], c["experts"]
    assert (held, every) == (2, 8)
    x = jax.random.normal(keys(1, 8)[0], (2, 32, c["H"]))
    k = keys(2, 9)
    q = dict(p)
    q["l1.w_gu"] = 5 * c["std"] * jax.random.normal(
        k[0], (every, c["H"], 2 * c["F"]))
    q["l1.w_down"] = 5 * c["std"] * jax.random.normal(
        k[1], (every, c["F"], c["H"]))
    uncut, _ = ref.expert_layer(q, "l1.", dict(c, held=every, first=0), x, "f32")
    shared = ref.glu(x, q["l1.shared_gu"], q["l1.shared_down"], "f32")
    total, slots = 0.0, 0.0
    for rank in range(every // held):
        mine = slice(rank * held, (rank + 1) * held)
        share = dict(q, **{"l1.w_gu": q["l1.w_gu"][mine],
                           "l1.w_down": q["l1.w_down"][mine]})
        cfg = JoyAIConfig(**dict(TINY, **dict(changes, ep_rank=rank)))
        out, counters = joyai.expert_layer(cfg, jnp.float32).apply(
            {"params": joyai_map.to_program(share, c)["layers_1"]["mlp"]}, x)
        close(out, ref.expert_layer(share, "l1.", dict(c, first=rank * held),
                                    x, "f32")[0])
        total = total + (out - shared)
        slots += float(counters["moe_local_slots"])
        assert float(counters["moe_dropped_slots"]) == 0.0
    assert slots == 64 * 3  # every slot is some share's
    close(total + shared, uncut)


# -- the second target -----------------------------------------------------------------

@pytest.mark.parametrize("pieces", [1, 4])
def test_a_row_whose_second_loss_is_known_by_hand(pieces):
    """Position t's logits hold ``c`` at token t + 2 and 0 elsewhere (its
    last two positions at a token that is not there): against token t + 2 the
    loss is ln(1 + (V - 1) e^-c) over the S - 2 counted positions and every
    arg-max is right; against token t + 1 it is that plus c wherever the two
    tokens differ."""
    vocab, seq, c = 16, 16, 9.0
    ids = np.random.default_rng(0).permutation(vocab)[None, :seq]
    hidden = np.zeros((1, seq, vocab), np.float32)
    for t in range(seq):
        hidden[0, t, ids[0, t + 2] if t + 2 < seq else ids[0, 0]] = c
    floor = np.log(1 + (vocab - 1) * np.exp(-c))
    run = lambda shift: losses.chunked_next_token_loss(
        jnp.asarray(hidden), jnp.eye(vocab), jnp.asarray(ids), pieces, shift,
        "mtp")
    loss, right = run(2)
    np.testing.assert_allclose(loss, floor, rtol=1e-3)
    assert float(right) == 1.0
    loss, right = run(1)
    np.testing.assert_allclose(loss, floor + c, rtol=1e-5)
    assert float(right) == 0.0
    whole = losses.next_token_loss(jnp.asarray(hidden), jnp.asarray(ids), 2)
    np.testing.assert_allclose(whole[0], floor, rtol=1e-3)


@pytest.fixture(scope="module")
def model_case():
    c, p = _seeded(10, loud=True)
    ids = jax.random.randint(keys(1, 10)[0], (2, 40), 0, c["V"])
    grad = lambda faults=(): jax.jit(jax.value_and_grad(
        lambda p_: ref.objective(p_, c, ids, faults=faults), has_aux=True))(p)
    return c, p, ids, grad


@pytest.mark.parametrize("remat", ["full", "none"])
def test_both_losses_and_every_gradient_match_the_reference(model_case, remat):
    c, p, ids, grad = model_case
    model = _model(remat=remat)
    (got, aux), grads = jax.jit(jax.value_and_grad(
        _objective(model, ids), has_aux=True))(joyai_map.to_program(p, c))
    (want, (second, routed)), want_grads = grad()
    close(got, want)
    close(aux["mtp_loss"], second)
    assert float(second) > 1.0
    grads = joyai_map.from_program(grads, c)
    for name in p:
        close(grads[name], want_grads[name])
    assert len(routed) == 2 and float(aux["moe_dropped_slots"]) == 0.0
    # blocks x rows x heads x the whole square's tiles (the XLA form)
    assert float(aux["mla_tiles_run"]) == 3 * 2 * 4 * tiles_visited(40)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_fault_planted_in_the_reference_is_seen(model_case, fault):
    """The module's input moved the wrong way, its target one place short,
    the module left out, the latent norms left out, the shared key's gradient
    from one head alone: each moves the loss or a gradient by far more than
    the comparison's tolerance."""
    c, p, ids, grad = model_case
    if fault == "shared_key_first_head_only":  # the forward pass is sound
        (sound, _), sound_grads = grad()
        (loss, _), grads = grad((fault,))
        assert float(loss) == float(sound)
        moved = [np.abs(grads[n] - sound_grads[n]).max()
                 / np.abs(sound_grads[n]).max() for n in ("l0.wkva", "l1.wkva")]
        assert min(moved) > 0.01
        return
    value = lambda faults=(): jax.jit(
        lambda p_: ref.objective(p_, c, ids, faults=faults))(p)
    (sound, (second, _)), (loss, (other, _)) = value(), value((fault,))
    assert abs(float(loss - sound)) > 1e-3
    if fault.startswith("mtp_"):
        assert abs(float(other - second)) > 1e-3


def _is_module(path) -> bool:
    return path[0] == "mtp"


@pytest.mark.parametrize("term", ["next_token", "next_next_token"])
def test_the_two_gradient_paths(term):
    """The first term reaches no leaf of the module (``W_eh`` among them);
    the second reaches every leaf of the module and, through ``h`` and the
    shared embedding and head, every leaf of the model but the main stack's
    final norm; the routers' bias has no gradient from either."""
    c, p = _seeded(12, loud=True)
    model = _model(remat="none",
                   mtp_loss_coef=0.0 if term == "next_token" else 1.0)
    ids = jax.random.randint(keys(1, 12)[0], (2, 24), 0, c["V"])
    params = joyai_map.to_program(p, c)

    def loss(tree):
        whole, aux = _objective(model, ids)(tree)
        return aux["mtp_loss"] if term == "next_next_token" else whole

    flat = flax.traverse_util.flatten_dict(jax.jit(jax.grad(loss))(params))
    for path, g in flat.items():
        norm = float(jnp.linalg.norm(g))
        if path[-1] == "router_correction_bias":
            assert norm == 0.0, path
        elif term == "next_token":
            assert (norm == 0.0) == _is_module(path), path
        else:
            assert (norm == 0.0) == (path == ("final_norm", "scale")), path


def test_the_model_names_its_stream_and_the_others_name_none():
    model = _model()
    assert model.prediction_streams() == {"mtp": (2, 0.3)}
    assert model.objective_terms() == {}
    assert _model(num_nextn_predict_layers=0).prediction_streams() == {}
    assert build_pretraining_model(
        LagunaConfig(), jnp.float32).prediction_streams() == {}


@pytest.mark.parametrize("head", ["whole", "chunked"])
def test_two_updates_through_make_train_step_match_the_reference(
        monkeypatch, head):
    """Through the program's own step (micro-batch scan, clipping, AdamW with
    the no-decay mask), both passes of the shared head whole and in pieces,
    against the reference's AdamW: both losses, and the parameters' change
    after two updates."""
    if head == "chunked":  # rows of 32 in two pieces of 16
        monkeypatch.setattr(pretrain, "LM_HEAD_PIECE", 16)
    config = dict(TINY, initializer_range=0.1)
    c = ref.sizes(config)
    recipe = ref.Recipe(learning_rate=1e-3, warmup_proportion=0.01,
                        max_steps=1000)
    seed = 11
    model = _model(initializer_range=0.1)
    schedule = optim.make_schedule("constant", recipe.learning_rate,
                                   recipe.warmup_proportion, recipe.max_steps)
    tx = optim.adamw(schedule, b1=recipe.b1, b2=recipe.b2, eps=recipe.eps,
                     weight_decay=recipe.weight_decay,
                     weight_decay_mask=optim.no_decay_mask,
                     max_grad_norm=recipe.max_grad_norm)
    params = joyai_map.to_program(
        ref.seeded_params(ref.key_from_seed(seed), c), c)
    state = pretrain.TrainState(params=params, opt_state=tx.init(params),
                                rng=jax.random.PRNGKey(0))
    step = pretrain.make_train_step(model, tx, schedule=schedule,
                                    next_sentence=False)
    rng = np.random.default_rng(0)
    updates = [rng.integers(0, c["V"], (2, 2, 32)).astype(np.int32)
               for _ in range(2)]
    got = []
    for upd in updates:
        state, metrics = step(state, {"input_ids": jnp.asarray(upd)})
        got.append((float(metrics["loss"]), float(metrics["mtp_loss"])))
        assert float(metrics["moe_dropped_slots"]) == 0.0
        assert float(metrics["finite"]) == 1.0
        assert float(metrics["mla_tiles_run"]) == 3 * 4 * 4
        assert 0.0 <= float(metrics["mtp_token_accuracy"]) < 0.2
    followed = ref.follow(seed, config, recipe, updates)
    for index, atol in ((0, 2e-5), (1, 1e-4)):
        np.testing.assert_allclose(
            got[index], (followed["loss"][index], followed["mtp_loss"][index]),
            atol=atol)
    assert [r.shape for r in followed["chosen"]] == [(64, 3)] * 2
    start = ref.seeded_params(ref.key_from_seed(seed), c)
    mine = joyai_map.from_program(state.params, c)
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
    path.write_text(json.dumps(dict(TINY, model_type="joyai_llm_flash")))
    config = load_model_config(str(path))
    assert isinstance(config, JoyAIConfig)
    assert (config.router_experts, config.first_expert) == (8, 4)
    assert (config.qk_head_dim, config.shared_width) == (24, 32)
    assert config.to_dict()["model_type"] == "joyai_llm_flash"
    whole = JoyAIConfig()
    assert (whole.qk_head_dim, whole.q_lora_rank, whole.kv_lora_rank,
            whole.n_routed_experts, whole.num_hidden_layers,
            whole.rope) == (192, 1536, 512, 256, 40, (
                64, {"rope_theta": 32000000, "rope_type": "default"}))
    for wrong in (dict(tie_word_embeddings=True), dict(attention_bias=True),
                  dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}),
                  dict(n_group=8, topk_group=4), dict(scoring_func="softmax"),
                  dict(num_nextn_predict_layers=2), dict(qk_head_dim=128),
                  dict(ep_size=2, ep_rank=2), dict(num_key_value_heads=8)):
        with pytest.raises(ValueError):
            JoyAIConfig(**wrong)


def _count(tree):
    return sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(tree))


def _shapes(config):
    model = build_pretraining_model(config, jnp.bfloat16)
    return jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 16), jnp.int32))["params"]


def test_published_configuration_counts_630_8_million():
    """The cell's configuration: every published width, layers 0-6 and the
    module, 8 of 256 experts, an eighth of the vocabulary in whole lanes."""
    with open(CONFIG_FILE) as f:
        raw = json.load(f)
    config = load_model_config(CONFIG_FILE)
    assert (config.hidden_size, config.q_lora_rank, config.kv_lora_rank,
            config.qk_nope_head_dim, config.qk_rope_head_dim,
            config.v_head_dim, config.intermediate_size,
            config.moe_intermediate_size, config.router_experts,
            config.num_experts_per_tok, config.routed_scaling_factor) == (
                2048, 1536, 512, 128, 64, 128, 7168, 768, 256, 8, 2.5)
    assert (config.num_hidden_layers, config.n_routed_experts, config.ep_size,
            config.vocab_size, config.first_k_dense_replace,
            config.num_nextn_predict_layers) == (7, 8, 32, 16256, 1, 1)
    assert config.vocab_size % 128 == 0 and config.vocab_size * 8 >= 129280
    assert raw["published"]["ep_size"] == 1
    assert set(raw["reduced"]) >= {"num_hidden_layers", "n_routed_experts",
                                   "vocab_size"}
    shapes = _shapes(config)
    attention = 2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 \
        + 4096 * 2048 + 1536 + 512
    assert _count(shapes["layers_0"]["attention"]) == attention == 26347520
    expert_layer = attention + 4096 + 2048 * 256 + 256 + 9 * 3 * 2048 * 768
    assert _count(shapes["layers_1"]) == expert_layer == 69343488
    assert _count(shapes["layers_0"]) == attention + 4096 + 3 * 2048 * 7168
    module = expert_layer + 2 * 2048 + 4096 * 2048 + 2048
    assert _count(shapes["mtp"]) == module == 77738240
    assert _count(shapes) == 630777600 == raw["parameters"]
    c = ref.sizes(raw)
    assert sum(int(np.prod(shape)) for shape, _ in
               ref.param_table(c).values()) == 630777600


def test_the_whole_model_counts_48_9_billion_and_its_module_1_25():
    shapes = _shapes(JoyAIConfig())
    module = _count(shapes["mtp"])
    assert module == 1247949056
    assert _count(shapes) - module == 48942542592


def test_flops_are_the_issues_arithmetic():
    config = load_model_config(CONFIG_FILE)
    parts = flops.joyai_forward_flops_per_token(config, 8192)
    total = sum(parts.values())
    assert round(total / 1e6) == 1421
    share = {name: round(100 * value / total) for name, value in parts.items()}
    assert share == {"mla_proj": 30, "mla_core": 47, "dense_mlp": 6,
                     "experts": 6, "mtp_merge": 1, "head": 5, "mtp_head": 5}
    assert flops.causal_lm_train_flops_per_seq(config, 8192) == 3 * 8192 * total


def test_no_decay_mask_leaves_out_the_norms_and_the_routers_bias():
    c, p = _seeded()
    mask = flax.traverse_util.flatten_dict(
        optim.no_decay_mask(joyai_map.to_program(p, c)), sep="/")
    for name, path in joyai_map.table(c).items():
        assert mask[path] == ref.decays(name, c), name
    left_out = {path.split("/")[-2] for path, decays in mask.items()
                if not decays and path.endswith("/scale")}
    assert {"q_a_norm", "kv_a_norm", "enorm", "hnorm"} <= left_out


# -- the family's scopes reach the compiled step ------------------------------------

@pytest.fixture(scope="module")
def step_names():
    """Every ``op_name`` of the family's compiled train step (bfloat16,
    ``--remat full``, 2 micro-batches of one row of 24 tokens)."""
    import re

    model = build_pretraining_model(JoyAIConfig(**TINY), jnp.bfloat16,
                                    remat="full", attention_backend="xla")
    tx = optim.adamw(1e-3, max_grad_norm=1.0,
                     weight_decay_mask=optim.no_decay_mask)
    state = pretrain.make_init_fn(
        model, tx, (jnp.zeros((1, 8), jnp.int32),), None)(jax.random.PRNGKey(0))
    step = pretrain.make_train_step(model, tx, next_sentence=False)
    batch = {"input_ids": np.zeros((2, 1, 24), np.int32)}
    text = step.lower(state, batch).compile().as_text()
    return set(re.findall(r'op_name="([^"]+)"', text))


@pytest.mark.parametrize("scope", pretrain.JOYAI_SCOPES)
def test_every_scope_of_the_family_reaches_the_compiled_step(step_names, scope):
    assert any(f"/{scope}/" in name or f"({scope})" in name
               for name in step_names), scope


def test_the_modules_block_lies_under_mtp_and_its_head_apart(step_names):
    for inner in ("mtp_merge", "mla", "mla_core", "moe", "moe_shared"):
        assert any("/mtp/" in name and f"/{inner}/" in name
                   for name in step_names), inner
    assert not any("/mtp/" in name and "/dense_mlp/" in name
                   for name in step_names)
    for head in ("mtp_head", "mtp_loss", "lm_head", "lm_loss"):
        assert not any("/mtp/" in name and f"/{head}/" in name
                       for name in step_names), head
    for inner in ("mla_q_proj", "mla_kv_proj", "mla_core", "attn_out"):
        assert not any(f"/{inner}/" in name and "/mla/" not in name
                       for name in step_names), inner


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
        json.dumps(dict(TINY, model_type="joyai_llm_flash")))
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
    # ln V for the first term and 0.3 ln V for the second
    assert abs(result["loss"] - 1.3 * np.log(256)) < 0.5
    assert abs(result["mtp_loss"] - np.log(256)) < 0.5
    assert result["moe_dropped_slots"] == 0.0 and result["moe_local_slots"] > 0
    assert result["mla_tiles_run"] == 3 * 16 * 4  # blocks x rows x heads
    log = (tmp_path / "out" / "pretraining.txt").read_text()
    assert "mla_tiles_run" in log and "mtp_loss" in log
    assert "mtp_token_accuracy" in log
