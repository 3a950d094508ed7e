"""remat='dots' keeps attention's named residuals (ops/remat.py): the
probability-dropout mask on the XLA path, the flash kernel's output and
log-sum-exp on the Pallas path. Counted in the differentiated program, whose
remat regions JAX has already DCE'd: what is kept is not computed again."""

import collections

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bert_pytorch_tpu import pretrain
from bert_pytorch_tpu.config import BertConfig
from bert_pytorch_tpu.models import bert
from bert_pytorch_tpu.ops import remat
from bert_pytorch_tpu.ops.attention import make_attention_bias

LAYERS, BATCH, SEQ, HEADS, HIDDEN = 2, 2, 16, 2, 32

# (backend, deterministic, what remat must not make twice, how many of them
# the names add to the forward scan's stacked residuals). The interpreter
# has no PRNG, so the kernel half runs at rate 0; the XLA half runs with
# attention dropout alone, so every random-bits op is the mask's.
PATHS = {
    "xla": ("xla", False, "random_bits", 1),
    "pallas": ("pallas", True, "flash_fwd", 2),
}
PLAIN_DOTS = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims


def _ops(jaxpr, acc=None):
    """Primitive names (a pallas_call under its kernel's name) -> count,
    through every sub-jaxpr."""
    acc = collections.Counter() if acc is None else acc
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            name = eqn.params["name"]
        acc[name] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _ops(sub, acc)
    return acc


def _forward_residuals(jaxpr):
    """The stacked outputs of the layer scan's forward pass."""
    scan = next(e for e in jaxpr.eqns if e.primitive.name == "scan")
    return [v.aval for v in scan.outvars[scan.params["num_carry"]:]]


def _grad_program(remat_value, path, with_grads=True, batch=BATCH):
    backend, deterministic, _, _ = PATHS[path]
    cfg = BertConfig(
        vocab_size=64, hidden_size=HIDDEN, num_hidden_layers=LAYERS,
        num_attention_heads=HEADS, intermediate_size=64,
        max_position_embeddings=SEQ, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.1)
    encoder = bert.BertEncoder(cfg, dtype=jnp.float32, remat=remat_value,
                               attention_backend=backend)
    hidden = jax.random.normal(jax.random.PRNGKey(1), (batch, SEQ, HIDDEN))
    mask = jnp.ones((batch, SEQ), jnp.int32).at[:, SEQ - 3:].set(0)
    bias = make_attention_bias(mask)
    params = nn.unbox(encoder.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(2)},
        hidden, bias, deterministic))["params"]

    def loss(p):
        out = encoder.apply({"params": p}, hidden, bias, deterministic,
                            rngs={"dropout": jax.random.PRNGKey(3)})
        return jnp.sum(out * out)

    grad = jax.grad(loss)
    return (grad(params) if with_grads else None,
            jax.make_jaxpr(grad)(params).jaxpr)


def _assert_grads_close(got, want, rtol):
    # One scale for the whole tree: the key bias's gradient is zero by the
    # softmax's shift invariance, and what is computed for it is rounding.
    want = jax.tree_util.tree_leaves(want)
    atol = rtol * max(float(jnp.max(jnp.abs(b))) for b in want)
    for a, b in zip(jax.tree_util.tree_leaves(got), want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                                   atol=atol)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_dots_keeps_the_named_residuals(path, monkeypatch):
    """One costly op per layer under 'dots', two under a policy without the
    names; the same gradients either way, and as without remat."""
    _, _, costly, added = PATHS[path]
    grads, program = _grad_program("dots", path)
    assert _ops(program)[costly] == 1  # the forward scan's; none in the backward
    kept = _forward_residuals(program)

    monkeypatch.setattr(bert, "remat_policy", lambda _: PLAIN_DOTS)
    plain_grads, plain_program = _grad_program("dots", path)
    assert _ops(plain_program)[costly] == 2
    assert len(kept) == len(_forward_residuals(plain_program)) + added
    monkeypatch.undo()

    # Same arithmetic from the same draws; XLA fuses the three programs
    # differently, so the last bits may differ (they do between 'none' and
    # plain 'dots' at the parent commit too).
    _assert_grads_close(grads, plain_grads, rtol=1e-6)
    _assert_grads_close(grads, _grad_program("none", path)[0], rtol=1e-5)


def test_dots_keeps_the_mask_drawn_per_shard(monkeypatch):
    """Under ``dp=4`` the mask comes out of a ``shard_map`` (ops/dropout.py)
    and is named outside it: still drawn once, in the forward scan, and the
    gradients are those of ``remat='none'`` from the same draws."""
    from bert_pytorch_tpu.parallel import MeshConfig, create_mesh

    with create_mesh(MeshConfig(data=4), devices=jax.devices()[:4]):
        grads, program = _grad_program("dots", "xla", batch=8)
        ops = _ops(program)
        assert ops["random_bits"] == 1 and ops["shard_map"]
        masks = [a for a in _forward_residuals(program)
                 if a.dtype == jnp.bool_]
        assert [a.shape for a in masks] == [(LAYERS, 8, HEADS, SEQ, SEQ)]

        monkeypatch.setattr(bert, "remat_policy", lambda _: PLAIN_DOTS)
        plain_ops = _ops(_grad_program("dots", "xla", False, batch=8)[1])
        assert plain_ops["random_bits"] == 2
        monkeypatch.undo()
        _assert_grads_close(
            grads, _grad_program("none", "xla", batch=8)[0], rtol=1e-5)


def test_the_kept_mask_is_one_byte_an_element():
    _, program = _grad_program("dots", "xla", with_grads=False)
    masks = [a for a in _forward_residuals(program) if a.dtype == jnp.bool_]
    assert [a.shape for a in masks] == [(LAYERS, BATCH, HEADS, SEQ, SEQ)]
    assert remat.kept_residual_bytes(
        "dots", "xla", dropout=True, batch=BATCH, seq=SEQ, heads=HEADS,
        head_dim=HIDDEN // HEADS, dtype=jnp.float32
    ) == {remat.KEEP_MASK: masks[0].size // LAYERS}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_full_still_keeps_nothing(path):
    """Attention's three names add nothing to 'full' where the caller asks
    for none of them, as the encoder asks for none (its policy keeps four
    names, all the sparse attention's: asserted below, and no tensor of an
    encoder carries any of them): the costly op runs twice, and only the
    layer's inputs cross the scan."""
    _, _, costly, _ = PATHS[path]
    _, program = _grad_program("full", path, with_grads=False)
    assert _ops(program)[costly] == 2
    _, dots_program = _grad_program("dots", path, with_grads=False)
    assert (len(_forward_residuals(program))
            < len(_forward_residuals(dots_program)))
    assert not any(a.shape[1:] in {(BATCH, HEADS, SEQ, SEQ),
                                   (BATCH * HEADS, SEQ, HIDDEN // HEADS),
                                   (BATCH * HEADS, 1, SEQ)}
                   for a in _forward_residuals(program))


def test_one_function_builds_the_policy_for_both_sites():
    assert bert.remat_policy is remat.remat_policy
    assert pretrain.remat_policy is remat.remat_policy
    assert remat.remat_policy("none") is None
    # 'full' keeps four names and nothing else, all the sparse attention's:
    # the choice (one bit a pair), the gradients of the indexer's KL, and
    # the core's output and log-sum-exps (its backward kernels' residuals)
    from jax._src.ad_checkpoint import name_p

    full = remat.remat_policy("full")
    assert remat.KEPT_UNDER_FULL == (remat.DSA_CHOICE, remat.DSA_INDEX_GRADS,
                                     remat.DSA_CORE_OUT, remat.DSA_CORE_LSE)
    assert all(full(name_p, name=name) for name in remat.KEPT_UNDER_FULL)
    assert not any(full(name_p, name=name) for name in (
        remat.KEEP_MASK, remat.FLASH_OUT, remat.FLASH_LSE, "some_other_name"))
    dots = remat.remat_policy("dots")
    assert set(remat.KEPT_UNDER_FULL) < set(remat.KEPT_NAMES)
    assert all(dots(name_p, name=name) for name in remat.KEPT_NAMES)
    assert not dots(name_p, name="some_other_name")
    assert not full(jax.lax.dot_general_p) and not full(jax.lax.exp_p)
    # a model may leave names out of a block's policy, and only kept names
    core = (remat.DSA_CORE_OUT, remat.DSA_CORE_LSE)
    for value in ("full", "dots"):
        fewer = remat.remat_policy(value, without=core)
        assert not any(fewer(name_p, name=name) for name in core)
        assert all(fewer(name_p, name=name)
                   for name in (remat.DSA_CHOICE, remat.DSA_INDEX_GRADS))
    assert remat.remat_policy("none", without=core) is None
    # a family whose chip has the room asks 'full' for the flash kernel's two
    # residuals by name, and gets them beside the four, and nothing else
    flash = (remat.FLASH_OUT, remat.FLASH_LSE)
    family = remat.remat_policy("full", keeping=flash)
    assert all(family(name_p, name=name)
               for name in flash + remat.KEPT_UNDER_FULL)
    assert not family(name_p, name=remat.KEEP_MASK)
    assert not family(name_p, name="some_other_name")
    assert not family(jax.lax.dot_general_p) and not family(jax.lax.exp_p)
    assert remat.kept_names("full", keeping=flash) == (
        flash + remat.KEPT_UNDER_FULL)
    assert remat.kept_names("full") == remat.KEPT_UNDER_FULL
    assert remat.kept_names("dots", keeping=flash) == remat.KEPT_NAMES
    assert remat.kept_names("none", keeping=flash) == ()
    assert not any(remat.remat_policy("full", flash, flash)(name_p, name=name)
                   for name in flash)  # ``without`` has the last word
    assert remat.remat_policy("none", keeping=flash) is None
    with pytest.raises(ValueError, match="some_other_name"):
        remat.remat_policy("full", without=("some_other_name",))
    with pytest.raises(ValueError, match="some_other_name"):
        remat.remat_policy("full", keeping=("some_other_name",))
    with pytest.raises(ValueError, match="none|dots|full"):
        remat.remat_policy("some")


@pytest.mark.parametrize("path,batch,seq,want", [
    # the benchmark's two cells, BERT-large in bf16: 16 heads of 64
    ("xla", 64, 128, {remat.KEEP_MASK: 16_777_216}),
    ("pallas", 16, 512,
     {remat.FLASH_OUT: 16_777_216, remat.FLASH_LSE: 524_288}),
    ("ring", 16, 512, {}),
])
def test_kept_residual_bytes_from_shapes(path, batch, seq, want):
    shapes = dict(batch=batch, seq=seq, heads=16, head_dim=64,
                  dtype=jnp.bfloat16)
    flash = (remat.FLASH_OUT, remat.FLASH_LSE)
    assert remat.kept_residual_bytes("dots", path, True, **shapes) == want
    assert remat.kept_residual_bytes("full", path, True, **shapes) == {}
    assert remat.kept_residual_bytes("none", path, True, **shapes) == {}
    # a family that asks 'full' for the flash kernel's two gets those two
    assert remat.kept_residual_bytes(
        "full", path, True, keeping=flash, **shapes) == (
            want if path == "pallas" else {})
    assert remat.kept_residual_bytes(
        "none", path, True, keeping=flash, **shapes) == {}
    if path == "xla":
        assert remat.kept_residual_bytes("dots", path, False, **shapes) == {}


@pytest.mark.parametrize("remat_value,want", [
    ("full", {remat.FLASH_OUT: 67_108_864, remat.FLASH_LSE: 1_048_576}),
    ("dots", {remat.FLASH_OUT: 67_108_864, remat.FLASH_LSE: 1_048_576}),
    ("none", {}),
])
def test_kept_residual_bytes_of_a_latent_block(remat_value, want):
    """The joyai cell's block: one row of 8192, 32 heads over VALUES of 128
    (the output's width, not the keys' 192) in bfloat16, the family's own
    ``keeping``; without it 'full' keeps none."""
    from bert_pytorch_tpu.models import joyai

    shapes = dict(batch=1, seq=8192, heads=32, head_dim=128,
                  dtype=jnp.bfloat16)
    assert joyai.KEPT_ACROSS_REMAT == (remat.FLASH_OUT, remat.FLASH_LSE)
    assert remat.kept_residual_bytes(
        remat_value, "pallas", False, keeping=joyai.KEPT_ACROSS_REMAT,
        **shapes) == want
    assert remat.kept_residual_bytes("full", "pallas", False, **shapes) == {}
