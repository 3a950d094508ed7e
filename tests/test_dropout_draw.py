"""Dropout masks are drawn per shard of the batch (ops/dropout.py, ISSUE 28).

On one device, or where the trace is already in a manual region, or where the
batch does not divide, ``keep_mask`` is ``jax.random.bernoulli`` on the key as
given: the same bits, no ``shard_map``. Under a mesh whose batch axes have
more than one device it draws each shard's rows on that shard, from
``fold_in(key, shard index)``: the program asks the generator for the LOCAL
mask only (what XLA's ``RngBitGenerator`` cannot be split into afterwards),
and the bits are still independent Bernoulli(keep_prob).
"""

import collections
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from bert_pytorch_tpu import optim, pretrain
from bert_pytorch_tpu.config import BertConfig
from bert_pytorch_tpu.models import BertForPreTraining
from bert_pytorch_tpu.ops import dropout
from bert_pytorch_tpu.ops.attention import dot_product_attention
from bert_pytorch_tpu.parallel import (MeshConfig, create_mesh,
                                       logical_axis_rules)
from bert_pytorch_tpu.parallel.mesh import AXIS_DATA, AXIS_FSDP, AXIS_PIPE
from bert_pytorch_tpu.parallel.pipeline import shard_map

KEEP = 0.9
# The draw sites' shapes (models/bert.py, ops/attention.py), at a small size:
# hidden states after the embeddings / attention's output / the FFN, the
# attention probabilities, a task head's pooled row.
SITE_SHAPES = {"hidden": (8, 16, 32), "probabilities": (8, 4, 16, 16),
               "pooled": (8, 32)}
MESHES = {"dp=4": MeshConfig(data=4), "dp=2,fsdp=2": MeshConfig(data=2, fsdp=2)}


def _mesh(name):
    return create_mesh(MESHES[name], devices=jax.devices()[:4])


def _primitives(jaxpr, acc=None):
    acc = collections.Counter() if acc is None else acc
    for eqn in jaxpr.eqns:
        acc[eqn.primitive.name] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, acc)
    return acc


@pytest.fixture(autouse=True)
def _forget():
    dropout.forget_draws()
    yield
    dropout.forget_draws()


# -- (a) one device or no mesh: the bits and the program of before -----------

@pytest.mark.parametrize("impl", ["rbg", "threefry2x32"])
@pytest.mark.parametrize("where", ["no_mesh", "one_device_mesh"])
@pytest.mark.parametrize("site", sorted(SITE_SHAPES))
def test_one_device_draws_what_bernoulli_draws(site, where, impl):
    shape = SITE_SHAPES[site]
    with jax.default_prng_impl(impl):
        key = jax.random.PRNGKey(11)
        want = jax.random.bernoulli(key, KEEP, shape)
        draw = lambda k: dropout.keep_mask(k, KEEP, shape)
        if where == "no_mesh":
            got, program = jax.jit(draw)(key), jax.make_jaxpr(draw)(key)
        else:
            with create_mesh(MeshConfig(data=1), devices=jax.devices()[:1]):
                got, program = jax.jit(draw)(key), jax.make_jaxpr(draw)(key)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert "shard_map" not in _primitives(program.jaxpr)
    assert dropout.draw_shards() == 1


def test_the_module_takes_flax_dropouts_place():
    """Same name in the module tree, so the same key from ``make_rng`` and,
    on one device, the same output as ``flax.linen.Dropout``."""

    class Block(nn.Module):
        kind: type

        @nn.compact
        def __call__(self, x):
            x = self.kind(rate=0.1)(x, deterministic=False)
            return self.kind(rate=0.1)(x + 1.0, deterministic=False)

    x = jax.random.normal(jax.random.PRNGKey(0), SITE_SHAPES["hidden"])
    rngs = {"dropout": jax.random.PRNGKey(5)}
    np.testing.assert_array_equal(
        np.asarray(Block(dropout.Dropout).apply({}, x, rngs=rngs)),
        np.asarray(Block(nn.Dropout).apply({}, x, rngs=rngs)))
    same = dropout.Dropout(rate=0.1).apply({}, x, deterministic=True)
    assert same is x


# -- (b) under a mesh: still Bernoulli(keep), one stream a shard -------------

@pytest.mark.parametrize("site", ["hidden", "probabilities"])
@pytest.mark.parametrize("mesh_name", sorted(MESHES))
def test_each_shard_draws_its_own_bernoulli_stream(mesh_name, site):
    shape = (64,) + SITE_SHAPES[site][1:]
    with jax.default_prng_impl("rbg"), _mesh(mesh_name) as mesh:
        key = jax.random.PRNGKey(3)
        draw = jax.jit(lambda k: dropout.keep_mask(k, KEEP, shape))
        mask = draw(key)
        again = draw(key)
        other = draw(jax.random.PRNGKey(4))
        program = jax.make_jaxpr(
            lambda k: dropout.keep_mask(k, KEEP, shape))(key)
    assert dropout.draw_shards() == 4
    assert _primitives(program.jaxpr)["shard_map"] == 1
    assert mask.shape == shape and mask.dtype == jnp.bool_
    assert mask.sharding.is_equivalent_to(
        NamedSharding(mesh, P((AXIS_DATA, AXIS_FSDP))), len(shape))
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(again))
    assert not np.array_equal(np.asarray(mask), np.asarray(other))

    shards = np.asarray(mask).reshape(4, -1).astype(np.float64)
    n = shards.shape[1]
    sigma = np.sqrt(KEEP * (1 - KEEP) / n)
    for share in shards.mean(axis=1):
        assert abs(share - KEEP) < 4 * sigma, share
    for i in range(4):
        for j in range(i + 1, 4):
            assert not np.array_equal(shards[i], shards[j])
            # independent streams: the correlation of n pairs of bits is
            # within 4 / sqrt(n) of zero
            assert abs(np.corrcoef(shards[i], shards[j])[0, 1]) \
                < 4 / np.sqrt(n), (i, j)


# -- (c) the program asks the generator for the local mask only --------------

def _tiny_step(mesh, accum=2, rows=8, seq=16, **config):
    """(step, state, batch) of ``make_train_step`` as the trainer builds it,
    for a two-layer model under ``mesh`` (None: no mesh, no shardings)."""
    cfg = BertConfig(**{**dict(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=seq), **config})
    model = BertForPreTraining(cfg, dtype=jnp.float32, remat="dots",
                               attention_backend="xla")
    tx = optim.lamb(1e-3)
    sample = (jnp.zeros((1, seq), jnp.int32),) * 3
    batch_spec = {"input_ids": 3, "segment_ids": 3, "input_mask": 3,
                  "masked_lm_labels": 3, "next_sentence_labels": 2}
    shardings = b_shardings = None
    if mesh is not None:
        shardings = pretrain.state_shardings(
            mesh, model, logical_axis_rules("dp"), sample)
        b_shardings = pretrain.batch_shardings(mesh, batch_spec)
    state = pretrain.make_init_fn(model, tx, sample, shardings)(
        jax.random.PRNGKey(0))
    step = pretrain.make_train_step(
        model, tx, next_sentence=True, shardings=shardings,
        batch_shardings_=b_shardings, max_pred_per_seq=4, mesh=mesh)
    rng = np.random.default_rng(0)
    batch = {k: rng.integers(1, 60, (accum, rows) + (seq,) * (nd - 2))
             .astype(np.int32) for k, nd in batch_spec.items()}
    batch["input_mask"][:] = 1
    batch["segment_ids"][:] = 0
    batch["masked_lm_labels"][:] = -1
    batch["masked_lm_labels"][:, :, 3] = 5
    batch["next_sentence_labels"][:] = 0
    return step, state, batch


def _generator_results(stablehlo: str):
    """Elements of every ``rng_bit_generator`` result in a lowered module."""
    sizes = []
    for dims in re.findall(
            r"stablehlo\.rng_bit_generator[^\n]*-> \(tensor<[^>]*>, "
            r"tensor<([0-9x]+)xui\d+>\)", stablehlo):
        sizes.append(int(np.prod([int(d) for d in dims.split("x")])))
    return sizes


@pytest.mark.parametrize("mesh_name", [None] + sorted(MESHES))
def test_the_step_asks_for_local_masks_only(mesh_name):
    """The lowered ``make_train_step`` with ``rbg``: every call of the
    generator is for one shard's rows (XLA never splits a call afterwards;
    tests/test_chip_compile.py reads the same off the TPU compiler's
    partitioned module). Without a mesh the calls are the whole batch's."""
    rows, seq, heads, hidden = 8, 16, 4, 32
    shards = 1 if mesh_name is None else 4
    with jax.default_prng_impl("rbg"):
        if mesh_name is None:
            step, state, batch = _tiny_step(None)
            text = step.lower(state, batch).as_text()
        else:
            with _mesh(mesh_name) as mesh:
                step, state, batch = _tiny_step(mesh)
                text = step.lower(state, batch).as_text()
    sizes = _generator_results(text)
    local = {rows // shards * heads * seq * seq,  # the probabilities' mask
             rows // shards * seq * hidden}       # a hidden-state mask
    assert sizes and set(sizes) == local, (sizes, local)
    assert ("sdy.manual_computation" in text) == (shards > 1)
    assert dropout.draw_shards() == shards


# -- (d) where it stays plain -------------------------------------------------

@pytest.mark.parametrize("case", ["manual_batch_axes", "manual_pipe_axis",
                                  "indivisible_batch"])
def test_plain_draw_where_the_rows_cannot_be_split(case):
    shape = SITE_SHAPES["hidden"]
    key = jax.random.PRNGKey(9)
    if case == "indivisible_batch":
        shape = (6,) + shape[1:]
        with _mesh("dp=4"):
            draw = lambda k: dropout.keep_mask(k, KEEP, shape)
            got, program = jax.jit(draw)(key), jax.make_jaxpr(draw)(key)
        nested = _primitives(program.jaxpr)["shard_map"]
    else:
        manual, config = {
            "manual_batch_axes": ({AXIS_DATA, AXIS_FSDP}, MeshConfig(data=4)),
            "manual_pipe_axis": ({AXIS_PIPE}, MeshConfig(data=2, pipe=2)),
        }[case]
        mesh = create_mesh(config, devices=jax.devices()[:4])
        region = shard_map(
            lambda k: dropout.keep_mask(k, KEEP, shape), mesh=mesh,
            axis_names=manual, in_specs=P(), out_specs=P())
        with mesh:
            # every shard draws the same (replicated) mask from the same key
            got = jax.jit(region)(key)
            program = jax.make_jaxpr(region)(key)
        nested = _primitives(program.jaxpr)["shard_map"] - 1
    assert nested == 0
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jax.random.bernoulli(key, KEEP, shape)))
    assert dropout.draw_shards() == 1


# -- (e) the gradient under dp=4 is the hand-masked one -----------------------

@pytest.mark.parametrize("site", ["attention", "hidden"])
def test_gradient_matches_a_hand_masked_reference(site):
    batch, seq, heads, depth = 8, 16, 4, 8
    key = jax.random.PRNGKey(21)
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i),
                                 (batch, seq, heads, depth)) for i in range(3))
    with _mesh("dp=4") as mesh:
        rows = NamedSharding(mesh, P((AXIS_DATA, AXIS_FSDP)))
        if site == "attention":
            def loss(q, k, v):
                out = dot_product_attention(
                    q, k, v, dropout_rng=key, dropout_rate=1 - KEEP,
                    deterministic=False, backend="xla")
                return jnp.sum(out * out)

            mask = jax.jit(lambda: dropout.keep_mask(
                key, KEEP, (batch, heads, seq, seq)))()

            def reference(q, k, v):
                scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(depth)
                probs = jax.nn.softmax(scores, axis=-1) * mask / KEEP
                out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
                return jnp.sum(out * out)
        else:
            layer = dropout.Dropout(rate=1 - KEEP)
            apply = lambda x: layer.apply(
                {}, x, deterministic=False, rngs={"dropout": key})

            def loss(q, k, v):
                return jnp.sum(apply(q * k) * v)

            mask = jax.jit(apply)(jnp.ones_like(q)) != 0

            def reference(q, k, v):
                return jnp.sum(q * k * mask / KEEP * v)

        args = [jax.device_put(a, rows) for a in (q, k, v)]
        got = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(*args)
    assert dropout.draw_shards() == 4
    assert 0.8 < float(jnp.mean(mask)) < 0.97
    want = jax.grad(reference, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


# -- (f) the benchmark's rules still place the draw's ops --------------------

def test_the_draws_ops_keep_names_the_trace_rules_place():
    """Every op of the per-shard draw in the compiled ``dp=4`` step (its
    ``op_name`` holds the ``shard_map``) falls under ``dropout`` or
    ``attention_dropout`` by benchmarks/trace/scopes.json, in all three
    passes; none is left for ``unattributed_device_pct.train``."""
    from benchmarks.trace import scopes

    limit = "jax_traceback_in_locations_limit"  # as the entry points run
    before = getattr(jax.config, limit)
    jax.config.update(limit, 1)
    try:
        with jax.default_prng_impl("rbg"), _mesh("dp=4") as mesh:
            step, state, batch = _tiny_step(mesh)
            text = step.lower(state, batch).compile().as_text()
    finally:
        jax.config.update(limit, before)
    table = scopes.rules()
    placed = collections.Counter()
    for instruction, op_name in re.findall(
            r'^\s*(?:ROOT )?(%[\w.-]+) = [^\n]*op_name="([^"]+)"', text,
            flags=re.M):
        if "shard_map" in op_name:
            placed[scopes.classify(op_name, instruction, table)] += 1
    parts = {part for _, part in placed}
    assert parts == {"dropout", "attention_dropout"}, placed
    # the hidden-state masks are drawn again in the backward pass under
    # remat='dots'; the probabilities' mask is kept (ops/remat.py)
    assert ("recompute", "dropout") in placed
    assert ("recompute", "attention_dropout") not in placed


# -- the trainer says in how many shards its step draws ----------------------

@pytest.mark.parametrize("chips", [1, 4])
def test_the_trainer_reports_dropout_draw_shards(chips, tmp_path, monkeypatch):
    """A two-update ``run_pretraining.main`` of a tiny model on ``chips`` of
    the virtual devices: ``dropout_draw_shards`` in the run summary (beside
    ``mesh_spec``) and in one start-up log line."""
    import glob
    import json

    import run_pretraining
    from bert_pytorch_tpu.tools.make_synthetic_data import make_shard
    from bert_pytorch_tpu.utils import logging as logger

    for i in range(2):
        make_shard(str(tmp_path / f"shard_{i}.hdf5"), 64, 32, 1000, seed=i)
    (tmp_path / "model.json").write_text(json.dumps({
        "vocab_size": 1000, "hidden_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 64,
        "max_position_embeddings": 32, "type_vocab_size": 2,
        "next_sentence": True, "mask_token_id": 4}))
    real_mesh, real_info, said = run_pretraining.create_mesh, logger.info, []
    monkeypatch.setattr(
        run_pretraining, "create_mesh",
        lambda config: real_mesh(config, devices=jax.devices()[:chips]))
    monkeypatch.setattr(
        logger, "info", lambda text, *a, **k: (said.append(str(text)),
                                               real_info(text, *a, **k))[1])
    run_pretraining.main(run_pretraining.parse_arguments([
        "--input_dir", str(tmp_path), "--output_dir", str(tmp_path / "out"),
        "--model_config_file", str(tmp_path / "model.json"),
        "--mesh", f"dp={chips}",
        "--global_batch_size", "16", "--local_batch_size", "2",
        "--max_steps", "2", "--steps", "2", "--learning_rate", "1e-3",
        "--dtype", "float32", "--seed", "7", "--skip_final_checkpoint",
        "--disable_tensorboard"]))
    summaries = [json.loads(line)
                 for path in glob.glob(str(tmp_path / "out" / "*.jsonl"))
                 for line in open(path, encoding="utf-8")
                 if '"run_summary"' in line]
    assert [s["dropout_draw_shards"] for s in summaries] == [chips]
    assert summaries[0]["mesh_spec"] == f"dp={chips}"
    lines = [text for text in said if "dropout masks drawn in" in text]
    assert lines == [
        f"dropout masks drawn in {chips} shard(s) of the batch "
        "(ops/dropout.py)"]
