"""The Mamba-2 scan's Pallas kernels (``ops/pallas/ssd_scan.py``, run by the
interpreter on the CPU) against the literal recurrence of
``benchmarks/reference/nemotron_h_f32.py`` and against the XLA form
(``ops/ssm.py _ssd``), values and all six cotangents; which shapes take the
kernels; and the counter that says they ran.

Tolerances. Float32 at ``highest`` on both sides (conftest), so kernels, XLA
form and recurrence differ in the ORDER of float32 sums only: ``TOL`` is
``tests/test_nemotron_h.py``'s 2e-5 of the largest element, but for A's
cotangent, one number a head summed over every position of terms that cancel
(``A_TOL``: the XLA form and the recurrence differ by as much there). With
bfloat16 operands the kernels round where the XLA form rounds (weights, dt x,
the state as read), so each stays within 2% of the float32 recurrence's
largest element, the tolerance that file gives quantities whose last bits are
rounding.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import nemotron_h_f32 as ref
from bert_pytorch_tpu import optim, pretrain
from bert_pytorch_tpu.config import NemotronHConfig
from bert_pytorch_tpu.models import build_pretraining_model
from bert_pytorch_tpu.ops import ssm
from bert_pytorch_tpu.ops.pallas import ssd_scan

CHUNK, STATES = 128, 128
TOL, A_TOL, BF16_TOL = 2e-5, 2e-4, 0.02
NAMES = ("x", "dt", "a", "b", "c", "d")
# (heads, head width, groups, length), chunk, states
SHAPES = {
    "pairs_of_64": ((4, 64, 2, 256), CHUNK, STATES),
    "heads_of_128": ((2, 128, 1, 256), CHUNK, STATES),
    # groups fewer than heads
    "a_group_of_two_tiles": ((4, 64, 1, 256), CHUNK, STATES),
    # padded to 256 with dt = 0
    "ragged_length": ((4, 64, 2, 200), CHUNK, STATES),
    "chunks_and_states_of_256": ((2, 128, 2, 512), 256, 256),
}


def operands(heads, hdim, groups, seq, states=STATES, dtype=jnp.float32,
             batch=2, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    scale = states ** -0.25  # C . B of order one
    return (jax.random.normal(k[0], (batch, seq, heads, hdim)).astype(dtype),
            jax.nn.softplus(jax.random.normal(k[1], (batch, seq, heads)) - 2),
            -jnp.exp(jax.random.normal(k[2], (heads,))),
            (scale * jax.random.normal(
                k[3], (batch, seq, groups, states))).astype(dtype),
            (scale * jax.random.normal(
                k[4], (batch, seq, groups, states))).astype(dtype),
            jax.random.normal(k[5], (heads,)))


def worst(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    return np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30)


def value_and_cotangents(fn, args, chunk=CHUNK):
    """y and the six cotangents under one fixed cotangent of y."""
    y, back = jax.vjp(lambda *t: fn(*t, chunk), *args)
    dy = jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(y.shape)
    return (y,) + back(dy.astype(y.dtype))


@pytest.fixture(scope="module")
def results():
    """shape name -> (kernels, XLA form, recurrence), each (y, dx .. dd)."""
    cache = {}

    def get(name):
        if name not in cache:
            sizes, chunk, states = SHAPES[name]
            args = operands(*sizes, states)
            assert ssm.ssd_kernel_chunks(args[0], args[3], args[4], chunk)
            cache[name] = (
                value_and_cotangents(ssm.ssd_chunked_scan, args, chunk),
                value_and_cotangents(ssm._ssd, args, chunk),
                value_and_cotangents(
                    lambda *t: ref.recurrence(*t[:-1], block=t[-1]), args,
                    chunk))
        return cache[name]

    return get


@pytest.mark.parametrize("other", ["xla_form", "recurrence"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernels_match(results, shape, other):
    mine, xla, literal = results(shape)
    theirs = xla if other == "xla_form" else literal
    assert mine[0].dtype == theirs[0].dtype and mine[0].shape == theirs[0].shape
    for name, got, want in zip(("y",) + NAMES, mine, theirs):
        assert got.dtype == want.dtype, name
        assert worst(got, want) <= (A_TOL if name == "a" else TOL), name


def test_a_dropped_state_is_seen(results, monkeypatch):
    """The comparison is not blind to the part only the kernels' scratch
    carries: with the state zeroed at every chunk the second chunk is far
    off."""
    real = ssd_scan._fwd_kernel

    def forgetful(*refs, **sizes):
        refs[-1][...] = jnp.zeros(refs[-1].shape, jnp.float32)  # state_scr
        real(*refs, **sizes)

    monkeypatch.setattr(ssd_scan, "_fwd_kernel", forgetful)
    args = operands(*SHAPES["pairs_of_64"][0])
    wrong = ssm.ssd_chunked_scan(*args, CHUNK)
    want = results("pairs_of_64")[2][0]
    assert worst(wrong[:, :CHUNK], want[:, :CHUNK]) <= TOL
    assert worst(wrong[:, CHUNK:], want[:, CHUNK:]) > 0.05


def test_bfloat16_operands_stay_within_two_percent():
    args = operands(*SHAPES["pairs_of_64"][0], dtype=jnp.bfloat16, seed=3)
    exact = tuple(t.astype(jnp.float32) for t in args)
    mine = value_and_cotangents(ssm.ssd_chunked_scan, args)
    xla = value_and_cotangents(ssm._ssd, args)
    literal = value_and_cotangents(
        lambda *t: ref.recurrence(*t[:-1], block=t[-1]), exact)
    for name, got, form, want in zip(("y",) + NAMES, mine, xla, literal):
        assert got.dtype == form.dtype, name  # bfloat16 where the operand is
        assert worst(got, want) <= BF16_TOL, name
        # and no further from the truth than the XLA form is, give or take
        assert worst(got, want) <= 2 * worst(form, want) + 1e-3, name


@pytest.mark.parametrize("heads,hdim,groups,states,chunk,dtype,takes", [
    (64, 64, 8, 128, 128, "bfloat16", True),    # the published widths
    (4, 64, 2, 128, 128, "float32", True),
    (2, 128, 2, 128, 256, "float32", True),
    (8, 16, 2, 16, 8, "float32", False),        # the CPU tests' tiny models
    (4, 64, 2, 128, 64, "float32", False),      # a chunk under a lane tile
    (4, 64, 2, 64, 128, "float32", False),      # states under a lane tile
    (3, 64, 3, 128, 128, "float32", False),     # a group of half a lane tile
    (256, 64, 8, 128, 128, "float32", False),   # more heads than lanes
])
def test_which_shapes_take_the_kernels(heads, hdim, groups, states, chunk,
                                       dtype, takes):
    x = jax.ShapeDtypeStruct((3, 2 * chunk + 1, heads, hdim), dtype)
    b = jax.ShapeDtypeStruct((3, 2 * chunk + 1, groups, states), dtype)
    chunks = ssm.ssd_kernel_chunks(x, b, b, chunk)
    assert chunks == (3 * 3 if takes else 0)  # rows x chunks, the ragged one too
    assert ssd_scan.fits(x.shape, b.shape, chunk) == takes


def test_mixed_dtypes_and_small_shapes_fall_to_the_xla_form():
    """What the kernels do not take runs ``_ssd`` itself: no Pallas call in
    the program, and the value is the XLA form's bit for bit."""
    small = operands(4, 8, 2, 24)
    small = small[:3] + tuple(t[..., :8] for t in small[3:5]) + small[5:]
    mixed = operands(4, 64, 2, 128)
    mixed = (mixed[0].astype(jnp.bfloat16),) + mixed[1:]
    for args, chunk in ((small, 8), (mixed, CHUNK)):
        assert ssm.ssd_kernel_chunks(args[0], args[3], args[4], chunk) == 0
        text = str(jax.make_jaxpr(
            lambda *t: ssm.ssd_chunked_scan(*t, chunk))(*args))
        assert "pallas_call" not in text
        np.testing.assert_array_equal(
            np.asarray(ssm.ssd_chunked_scan(*args, chunk), np.float32),
            np.asarray(ssm._ssd(*args, chunk), np.float32))
    fitting = operands(4, 64, 2, 128)
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda *t: ssm.ssd_chunked_scan(*t, CHUNK))(*fitting))


# -- the counter, through the program's own step ------------------------------

WIDE = dict(vocab_size=64, hidden_size=32, num_hidden_layers=3,
            hybrid_override_pattern="MEM", num_attention_heads=2,
            num_key_value_heads=1, head_dim=16, mamba_num_heads=2,
            mamba_head_dim=64, n_groups=1, ssm_state_size=STATES,
            chunk_size=CHUNK, conv_kernel=4, n_routed_experts=2, ep_size=2,
            ep_rank=0, num_experts_per_tok=1, moe_intermediate_size=16,
            moe_shared_expert_intermediate_size=16, moe_piece_multiple=8)


@pytest.mark.parametrize("widths,seq,chunks_a_row", [
    (WIDE, 2 * CHUNK + 7, 3),                        # the kernels, a ragged end
    (dict(WIDE, mamba_head_dim=16), 2 * CHUNK, 0),   # heads of 16: the XLA form
])
def test_the_counter_reads_layers_by_micro_batches_by_chunks(widths, seq,
                                                            chunks_a_row):
    model = build_pretraining_model(NemotronHConfig(**widths), jnp.float32,
                                    remat="full")
    tx = optim.adamw(1e-3, max_grad_norm=1.0,
                     weight_decay_mask=optim.no_decay_mask)
    state = pretrain.make_init_fn(
        model, tx, (jnp.zeros((1, 8), jnp.int32),), None)(jax.random.PRNGKey(0))
    step = pretrain.make_train_step(model, tx, next_sentence=False)
    micro, rows, mixers = 2, 1, 2
    ids = np.random.default_rng(0).integers(0, 64, (micro, rows, seq))
    _, metrics = step(state, {"input_ids": jnp.asarray(ids, jnp.int32)})
    assert float(metrics["finite"]) == 1.0
    assert float(metrics["ssd_chunks_run"]) == (
        mixers * micro * rows * chunks_a_row)
    assert model.COUNTERS[-1] == "ssd_chunks_run"
