"""The ``mellum`` family against its plain float32 reference
(``benchmarks/reference/mellum_f32.py``) at a small size on the CPU, on ONE
device: logits, loss, every gradient, two whole updates through
``pretrain.make_train_step``, the configuration and the normal path
(``run_pretraining.main``, also under ``--mesh ep=4``). The same under an
expert axis, the exchange and the shares are ``tests/test_moe_exchange.py``'s.

Tolerances. In float32 both sides compute at ``highest`` (conftest), so they
differ only in the ORDER of float32 sums (the experts' sorted slots against a
loop over experts, the head in pieces): a few 1e-6 of the largest element;
2e-5 leaves a decade of room and would not pass a dropped window, rotary
table or expert (each moves the result by percents: the "is seen" test). In
bfloat16 the program rounds every product's operands to 8 bits of mantissa
(2^-9 relative a rounding, some tens of roundings deep along four layers) and
the reference does not: losses agree to 2e-2 absolute and the gradient as a
whole to 6% of its norm, twice the 3% the benchmark's cell reads at the
published widths (``all_grad_rel_diff``), where sums are longer and average
more; a part left out moves both by far more.
"""

import json

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import mellum_f32 as ref
from benchmarks.reference import mellum_map
from bert_pytorch_tpu import optim, pretrain
from bert_pytorch_tpu.config import (LagunaConfig, MellumConfig,
                                     load_model_config)
from bert_pytorch_tpu.models import MellumForCausalLM, build_pretraining_model
from bert_pytorch_tpu.models.losses import next_token_loss
from bert_pytorch_tpu.utils import flops

# the published period at a small size: three sliding layers then a full one,
# a window shorter than the rows, both rotary tables, 8 experts top 2
TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=48, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["sparse"] * 4,
    rope_parameters=MellumConfig().rope_parameters,  # the published two
    sliding_window=8, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=24, rms_norm_eps=1e-6, moe_piece_multiple=8,
    initializer_range=0.2)
TOL = 2e-5


def close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-30), (
        np.max(np.abs(a - b)), np.max(np.abs(b)))


def far(a, b, share=0.05):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) > share * np.max(np.abs(b))


def seeded(seed=3, **changes):
    c = ref.sizes(dict(TINY, **changes))
    return c, ref.seeded_params(ref.key_from_seed(seed), c)


def tiny_model(dtype=jnp.float32, remat="full", backend="xla", **changes):
    return build_pretraining_model(MellumConfig(**dict(TINY, **changes)),
                                   dtype, remat=remat,
                                   attention_backend=backend)


def tiny_ids(seed=1, shape=(2, 24)):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                              TINY["vocab_size"])


# -- (a) one device against the reference ------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_logits_loss_and_gradients_match_the_reference(backend):
    c, rp = seeded(5)
    pp = mellum_map.to_program(rp, c)
    model = tiny_model(backend=backend)
    ids = tiny_ids()
    want = jax.tree_util.tree_structure(nn.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids))["params"]))
    assert want == jax.tree_util.tree_structure(pp)
    close(model.apply({"params": pp}, ids)[0], ref.forward(rp, c, ids)[0])

    def mine(p):
        logits, counters = model.apply({"params": p}, ids)
        return next_token_loss(logits, ids)[0], counters

    (loss, counters), grads = jax.value_and_grad(mine, has_aux=True)(pp)
    (ref_loss, _), ref_grads = jax.value_and_grad(
        lambda p: ref.next_token_loss(p, c, ids), has_aux=True)(rp)
    close(loss, ref_loss)
    assert float(counters["moe_dropped_slots"]) == 0.0
    assert float(counters["moe_local_slots"]) == 4 * 2 * 24 * 2
    assert float(counters["moe_exchange_slots_out"]) == 0.0  # no axis here
    for name, leaf in mellum_map.from_program(grads, c).items():
        close(leaf, ref_grads[name])


def test_bfloat16_stays_within_its_rounding():
    """The program in bfloat16 against the float32 reference (the module's
    note gives the reason for each number)."""
    c, rp = seeded(5)
    pp = mellum_map.to_program(rp, c)
    model, ids = tiny_model(jnp.bfloat16), tiny_ids()

    def mine(p):
        return next_token_loss(model.apply({"params": p}, ids)[0], ids)[0]

    loss, grads = jax.value_and_grad(mine)(pp)
    (ref_loss, _), ref_grads = jax.value_and_grad(
        lambda p: ref.next_token_loss(p, c, ids), has_aux=True)(rp)
    assert abs(float(loss) - float(ref_loss)) < 2e-2
    mine_flat = mellum_map.from_program(grads, c)
    diff = sum(float(jnp.sum(jnp.square(mine_flat[k] - ref_grads[k])))
               for k in ref_grads)
    whole = sum(float(jnp.sum(jnp.square(v))) for v in ref_grads.values())
    assert 0 < diff ** 0.5 < 0.06 * whole ** 0.5


@pytest.mark.parametrize("dropped", ["window", "rotary", "experts"])
def test_a_dropped_part_is_seen(dropped):
    """Not blind: the program without its window, with both layers' kinds on
    one rotary table, or with two experts fewer is far from the reference."""
    c, rp = seeded(5)
    rp = {k: v * 6 if k.endswith((".wq", ".wk")) else v for k, v in rp.items()}
    ids = tiny_ids(shape=(2, 40))
    wrong = {"window": dict(sliding_window=64),
             "rotary": dict(rope_parameters=dict(
                 MellumConfig().rope_parameters,
                 full_attention={"rope_type": "default", "rope_theta": 100})),
             "experts": dict(num_experts_per_tok=1)}[dropped]
    logits = tiny_model(**wrong).apply(
        {"params": mellum_map.to_program(rp, c)}, ids)[0]
    far(logits, ref.forward(rp, c, ids)[0], share=0.02)


def test_reference_loss_in_blocks_matches_whole(monkeypatch):
    c, rp = seeded()
    ids = tiny_ids(shape=(2, 21))
    logits, _ = ref.forward(rp, c, ids)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    whole = -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1))
    monkeypatch.setattr(ref, "HEAD_BLOCK", 8)  # three blocks, the last padded
    close(ref.next_token_loss(rp, c, ids)[0], whole)


def test_two_updates_through_make_train_step_match_the_reference():
    """Through the program's own step (micro-batch scan, clipping, AdamW with
    the no-decay mask) against the reference's AdamW: losses, and the
    parameters' change after two updates."""
    c = ref.sizes(TINY)
    recipe = ref.Recipe(learning_rate=1e-3, warmup_proportion=0.01,
                        max_steps=1000)
    seed = 11
    model = tiny_model()
    schedule = optim.make_schedule("constant", recipe.learning_rate,
                                   recipe.warmup_proportion, recipe.max_steps)
    tx = optim.adamw(schedule, b1=recipe.b1, b2=recipe.b2, eps=recipe.eps,
                     weight_decay=recipe.weight_decay,
                     weight_decay_mask=optim.no_decay_mask,
                     max_grad_norm=recipe.max_grad_norm)
    params = mellum_map.to_program(
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
        # two micro-batches of two rows, four heads, nine 8-wide tiles a head
        assert float(metrics["attn_window_tiles_run"]) == 2 * 2 * 3 * 4 * 9
        assert float(metrics["attn_full_tiles_run"]) == 2 * 2 * 1 * 4 * 9
    followed = ref.follow(seed, TINY, recipe, updates)
    np.testing.assert_allclose(losses, followed["loss"], atol=2e-5)
    start = ref.seeded_params(ref.key_from_seed(seed), c)
    mine = mellum_map.from_program(state.params, c)
    change = ref.leaf_norms({k: mine[k] - start[k] for k in mine})
    for name, want in followed["delta_norms"].items():
        # Adam divides by sqrt(v): where a gradient is all but zero its sign
        # is rounding, so the change is compared as a norm, at 2%.
        np.testing.assert_allclose(np.asarray(change[name]), want,
                                   rtol=0.02, atol=1e-7, err_msg=name)


# -- configuration, FLOPs -----------------------------------------------------

def test_model_type_chooses_the_family(tmp_path):
    path = tmp_path / "c.json"
    path.write_text(json.dumps(dict(TINY, model_type="mellum", ep_size=4,
                                    ep_rank=1)))
    config = load_model_config(str(path))
    assert isinstance(config, MellumConfig)
    assert isinstance(config, LagunaConfig)  # the blocks are that family's
    assert isinstance(build_pretraining_model(config, jnp.float32),
                      MellumForCausalLM)
    assert (config.router_experts, config.first_expert) == (32, 8)
    assert config.window_of(0) == 8 and config.window_of(3) is None
    assert config.rope_of(0) == (16, config.rope_parameters["sliding_attention"])
    assert config.rope_of(3)[1]["rope_type"] == "yarn"
    assert config.to_dict() == dict(
        MellumConfig.from_dict(config.to_dict()).to_dict(), model_type="mellum")
    assert "shared_expert_intermediate_size" not in config.to_dict()
    whole = MellumConfig()  # the published period when the lists are left out
    assert whole.layer_types[:4] == ["sliding_attention"] * 3 + ["full_attention"]
    assert len(whole.layer_types) == 28 and set(whole.mlp_layer_types) == {"sparse"}
    for wrong, match in (
            (dict(layer_types=["full_attention"] * 3), "layer_types"),
            (dict(mlp_layer_types=["dense"] * 4), "mlp_layer_types"),
            (dict(num_attention_heads=5), "query heads"),
            (dict(ep_rank=1), "ep_rank"), (dict(hidden_act="gelu"), "silu"),
            (dict(tie_word_embeddings=True), "untied")):
        with pytest.raises(ValueError, match=match):
            MellumConfig(**dict(TINY, **wrong))


def test_published_configuration_counts_2124_million():
    """The benchmark's configuration file, built abstractly: ISSUE 53's
    arithmetic against the tree's own count, part by part, and the names the
    mesh's rules read."""
    config = load_model_config("benchmarks/configs/mellum2-12b-a2.5b.json")
    model = build_pretraining_model(config, jnp.bfloat16)
    boxed = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))["params"]
    shapes = nn.unbox(boxed)
    count = lambda tree: sum(int(np.prod(leaf.shape))
                             for leaf in jax.tree_util.tree_leaves(tree))
    for layer in range(4):
        block = shapes[f"layers_{layer}"]
        assert count(block["attn"]) == pytest.approx(21.23e6, rel=5e-4)
        assert "g_proj" not in block["attn"]
        assert count(block["mlp"]) == pytest.approx(396.36e6 + 0.15e6, rel=5e-4)
        assert block["mlp"]["experts_up"].shape == (64, 2304, 1792)
    assert count(shapes["embedding"]) + count(shapes["lm_head"]) == 452984832
    assert count(shapes) == pytest.approx(2124.0e6, rel=5e-4)
    names = nn.get_partition_spec(boxed)
    assert tuple(names["layers_0"]["mlp"]["experts_up"]) == (
        "experts", None, None)
    assert tuple(names["embedding"]) == ("vocab_rows", None)
    assert tuple(names["lm_head"]["kernel"]) == (None, "vocab_rows")
    assert tuple(names["layers_0"]["attn"]["q_proj"]["kernel"]) == ()
    # a chip of four: ISSUE 53's 595.1 M
    mine = count(shapes) - 0.75 * (
        sum(count(shapes[f"layers_{i}"]["mlp"]["experts_up"])
            + count(shapes[f"layers_{i}"]["mlp"]["experts_down"])
            for i in range(4))
        + count(shapes["embedding"]) + count(shapes["lm_head"]))
    assert mine == pytest.approx(595.1e6, rel=1e-3)


def test_flops_by_part():
    """The trainer's MFU count for this family: laguna's less the gate."""
    config = load_model_config("benchmarks/configs/mellum2-12b-a2.5b.json")
    parts = flops.mellum_forward_flops_per_token(config, 8192)
    assert parts["attention_proj"] == 4 * (
        4 * 2304 * 4096 + 4 * 2304 * 512)
    assert parts["experts"] == 4 * (2 * 2304 * 64 + 8 * 6 * 2304 * 896)
    assert parts["head"] == 2 * 2304 * 98304 and parts["dense_mlp"] == 0
    # ISSUE 53: the head is 40% of the forward FLOPs with four layers
    assert parts["head"] / sum(parts.values()) == pytest.approx(0.40, abs=0.02)
    assert flops.causal_lm_train_flops_per_seq(config, 8192) == (
        3.0 * 8192 * sum(parts.values()))


# -- the normal path ----------------------------------------------------------

@pytest.mark.parametrize("mesh", ["dp=-1", "dp=2,ep=4"])
def test_run_pretraining_trains_from_a_config_file(tmp_path, mesh):
    """``run_pretraining.main`` from a config file over the eight virtual
    devices: data-parallel alone, and with the experts and the vocabulary over
    an expert axis of four beside a data axis of two (the update is the same
    mathematics: tests/test_moe_exchange.py holds it equal tensor by
    tensor)."""
    import run_pretraining
    from benchmarks.traffic import generate_lm

    config = tmp_path / "tiny.json"
    config.write_text(json.dumps(dict(TINY, model_type="mellum")))
    mix = {"seq_len": 32, "sequences": 64, "shards": 1,
           "documents": {"median_tokens": 12, "sigma": 1.0, "min_tokens": 4,
                         "max_tokens": 32, "eod_id": 0}}
    generate_lm.write_shards(mix, TINY["vocab_size"], 7,
                             str(tmp_path / "shards"))
    run_pretraining.main(run_pretraining.parse_arguments([
        "--input_dir", str(tmp_path / "shards"),
        "--output_dir", str(tmp_path / "out"),
        "--model_config_file", str(config), "--mesh", mesh,
        "--global_batch_size", "16", "--local_batch_size", "1",
        "--max_steps", "3", "--optimizer", "adamw", "--adamw_clip",
        "--adam_beta2", "0.95", "--adam_eps", "1e-8", "--lr_decay", "constant",
        "--remat", "full", "--dtype", "float32", "--log_steps", "1",
        "--skip_final_checkpoint", "--disable_tensorboard"]))
    records = [json.loads(line) for line in open(
        tmp_path / "out" / "pretraining_telemetry.jsonl")]
    train = [r for r in records if "moe_dropped_slots" in r]
    assert len(train) >= 3, sorted({r.get("kind") for r in records})
    losses = [r["step_loss"] for r in train]
    assert all(np.isfinite(losses))
    assert losses[0] == pytest.approx(np.log(64), abs=0.6)
    assert train[-1]["moe_dropped_slots"] == 0
    # two micro-batches of eight rows, four layers, 32 tokens top 2
    assert train[-1]["moe_local_slots"] == 2 * 8 * 4 * 32 * 2
    out, back = (train[-1]["moe_exchange_slots_out"],
                 train[-1]["moe_exchange_slots_in"])
    assert out == back and (out > 0) == ("ep" in mesh)
