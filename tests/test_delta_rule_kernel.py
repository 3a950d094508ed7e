"""The chunked delta rule's Pallas kernels (``ops/pallas/delta_rule.py``, run
by the interpreter on the CPU) against the literal token-by-token recurrence
of ``benchmarks/reference/qwen3next_f32.py`` and against the XLA form
(``ops/delta_rule.py _rule_of_rows``), values and all five cotangents; which
shapes take the kernels; and the counter that says they ran.

Tolerances. Float32 at ``highest`` on both sides (conftest), so kernels, XLA
form and recurrence differ in the ORDER of float32 sums only (and in the
inverse: substitution here, a Neumann product there): ``TOL`` is
``tests/test_qwen3_next.py``'s 5e-5 of the largest element for the rule. With
bfloat16 operands the kernels round where the XLA form rounds (U, W, the
scores, the corrected values, the state as read), so each stays within 3% of
the float32 recurrence's largest element, that file's tolerance for the rule
in bfloat16.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import qwen3next_f32 as ref
from bert_pytorch_tpu import optim, pretrain
from bert_pytorch_tpu.config import Qwen3NextConfig
from bert_pytorch_tpu.models import build_pretraining_model
from bert_pytorch_tpu.ops import delta_rule, gdn_mix
from bert_pytorch_tpu.ops.pallas import delta_rule as kernels

CHUNK, DIM = 64, 128
TOL, BF16_TOL = 5e-5, 0.03
NAMES = ("q", "k", "v", "g", "beta")
# (length, key heads, A a value head: g = -A softplus(.), the published range)
SHAPES = {
    "two_key_heads_three_chunks": (192, 2, (1e-6, 0.3, 4.0, 16.0)),
    # padded to 128 with k = 0, beta = 0, g = 0; two pairs a key head
    "ragged_length_four_value_heads_a_key_head": (
        70, 1, (0.5, 2.0, 1e-3, 8.0)),
}


def operands(seq, key_heads, a_values, dtype=jnp.float32, batch=2, seed=0):
    value_heads = len(a_values)
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = gdn_mix.unit_length(
        jax.random.normal(k[0], (batch, seq, key_heads, DIM))) / math.sqrt(DIM)
    key = gdn_mix.unit_length(
        jax.random.normal(k[1], (batch, seq, key_heads, DIM)))
    v = jax.random.normal(k[2], (batch, seq, value_heads, DIM))
    g = -jnp.asarray(a_values, jnp.float32) * jax.nn.softplus(
        jax.random.normal(k[3], (batch, seq, value_heads)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (batch, seq, value_heads)))
    return q.astype(dtype), key.astype(dtype), v.astype(dtype), g, beta


def recurrence(q, k, v, g, beta, _chunk=None):
    """The literal rule, the key heads repeated as the chunked form reads
    them."""
    ratio = v.shape[2] // k.shape[2]
    return ref.recurrence(jnp.repeat(q, ratio, axis=2),
                          jnp.repeat(k, ratio, axis=2), v, g, beta)


def xla_form(q, k, v, g, beta, chunk=CHUNK):
    """``_rule_of_rows`` as ``gated_delta_rule`` runs it where the kernels
    do not fit (every row at once: the rows share nothing)."""
    return delta_rule._rule_of_rows(q, k, v, g, beta, chunk)


def worst(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    return np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30)


def value_and_cotangents(fn):
    """args -> o and the five cotangents under one fixed cotangent of o, the
    same for every row (jitted: a shape compiles once a module)."""
    def run(*args):
        o, back = jax.vjp(lambda *t: fn(*t, CHUNK), *args)
        do = jnp.broadcast_to(jnp.cos(jnp.arange(
            o[0].size, dtype=jnp.float32)).reshape(o[0].shape), o.shape)
        return (o,) + back(do.astype(o.dtype))

    return jax.jit(run)


KERNELS, XLA_FORM, RECURRENCE = (value_and_cotangents(fn) for fn in (
    delta_rule.gated_delta_rule, xla_form, recurrence))


@pytest.fixture(scope="module")
def results():
    """shape name -> (kernels, XLA form, recurrence), each (o, dq .. dbeta)."""
    cache = {}

    def get(name):
        if name not in cache:
            args = operands(*SHAPES[name])
            assert delta_rule.kernel_chunks(*args[:3], CHUNK)
            cache[name] = tuple(run(*args) for run in (
                KERNELS, XLA_FORM, RECURRENCE))
        return cache[name]

    return get


@pytest.mark.parametrize("other", ["xla_form", "recurrence"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernels_match(results, shape, other):
    mine, xla, literal = results(shape)
    theirs = xla if other == "xla_form" else literal
    for name, got, want in zip(("o",) + NAMES, mine, theirs):
        assert got.dtype == want.dtype and got.shape == want.shape, name
        assert bool(jnp.all(jnp.isfinite(got))), name
        assert worst(got, want) <= TOL, name


def test_a_row_of_two_rows_equals_each_row_alone(results):
    """The state is zeroed at a row's first chunk, and so is the cotangent
    the backward carries: a row gives the same after any other row as after
    none (the two rows swapped; row 0 after itself; and the row that then
    runs second against the recurrence of that row alone)."""
    args = operands(*SHAPES["two_key_heads_three_chunks"])
    both = results("two_key_heads_three_chunks")[0]
    swapped = KERNELS(*(t[::-1] for t in args))
    twice = KERNELS(*(jnp.concatenate([t[:1], t[:1]]) for t in args))
    alone = RECURRENCE(*(t[:1] for t in args))
    for name, pair, back, same, one in zip(("o",) + NAMES, both, swapped,
                                          twice, alone):
        np.testing.assert_array_equal(back[::-1], pair, name)
        np.testing.assert_array_equal(same[1:], pair[:1], name)
        assert worst(back[1:], one) <= TOL, name


@pytest.mark.parametrize("fault", ["state_not_carried",
                                   "correction_before_decay"])
def test_a_planted_fault_is_seen(results, monkeypatch, fault):
    """The comparison is not blind to what only the kernels' scratch carries
    (with the state zeroed at every chunk the second chunk is far off), nor
    to the order of decay and read (a correction read from the state before
    its decay, the reference's own fault, moves every head that decays: by
    2.7% of the largest output here, keys of 128 being nearly orthogonal)."""
    args = operands(*SHAPES["two_key_heads_three_chunks"])
    want = results("two_key_heads_three_chunks")[2][0]
    if fault == "state_not_carried":
        real = kernels._fwd_kernel

        def forgetful(*refs, **sizes):
            refs[-1][...] = jnp.zeros(refs[-1].shape, jnp.float32)
            real(*refs, **sizes)

        monkeypatch.setattr(kernels, "_fwd_kernel", forgetful)
        wrong = delta_rule.gated_delta_rule(*args, CHUNK)
        assert worst(wrong[:, :CHUNK], want[:, :CHUNK]) <= TOL
        assert worst(wrong[:, CHUNK:], want[:, CHUNK:]) > 0.05
    else:
        q, k, v, g, beta = args
        ratio = v.shape[2] // k.shape[2]
        undecayed = ref.recurrence(
            jnp.repeat(q, ratio, axis=2), jnp.repeat(k, ratio, axis=2), v, g,
            beta, faults=("undecayed_read",))
        mine = results("two_key_heads_three_chunks")[0][0]
        assert worst(undecayed, want) > 200 * TOL
        assert worst(mine, undecayed) > 200 * TOL and worst(mine, want) <= TOL


def test_bfloat16_is_finite_down_to_e_minus_20_a_token_and_near_the_rule():
    """The chip's dtype: bfloat16 operands, float32 state and decays; where a
    head forgets everything within a token (A = 16, and g four times that in
    the gradient's run) nothing overflows, forward or backward."""
    args = operands(192, 2, (1e-6, 16.0, 16.0, 8.0), dtype=jnp.bfloat16,
                    seed=3)
    exact = tuple(t.astype(jnp.float32) for t in args)
    mine, xla, literal = KERNELS(*args), XLA_FORM(*args), RECURRENCE(*exact)
    for name, got, form, want in zip(("o",) + NAMES, mine, xla, literal):
        assert got.dtype == form.dtype, name  # bfloat16 where the operand is
        assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32)))), name
        assert worst(got, want) <= BF16_TOL, name
        # and no further from the truth than the XLA form is, give or take
        assert worst(got, want) <= 2 * worst(form, want) + 1e-3, name
    steep = KERNELS(*args[:3], args[3] * 4.0, args[4])
    for name, got in zip(("o",) + NAMES, steep):
        assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32)))), name


def test_the_inverse_of_a_pair_is_the_inverse_of_each():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64)),
                 -1) * 0.3
    [pair] = kernels.unit_lower_inverse_pairs([jnp.concatenate(list(a), axis=1)])
    for half in range(2):
        want = np.linalg.inv(np.eye(64) + np.asarray(a[half], np.float64))
        assert worst(pair[:, 64 * half:64 * (half + 1)], want) <= 1e-5
        assert worst(pair[:, 64 * half:64 * (half + 1)],
                     delta_rule.unit_lower_inverse(a[half])) <= 1e-5


@pytest.mark.parametrize("key_heads,value_heads,dim,chunk,dtype,takes", [
    (16, 32, 128, 64, "bfloat16", True),     # the published widths
    (2, 4, 128, 64, "float32", True),
    (1, 4, 128, 64, "float32", True),        # two pairs a key head
    (8, 16, 128, 64, "bfloat16", True),
    (2, 4, 16, 8, "float32", False),         # the CPU tests' tiny models
    (2, 4, 128, 8, "float32", False),        # a chunk under half a lane tile
    (2, 4, 128, 128, "float32", False),      # a chunk of a whole lane tile
    (2, 4, 64, 64, "float32", False),        # heads under a lane tile
    (2, 2, 128, 64, "float32", False),       # one value head a key head
    (2, 6, 128, 64, "float32", False),       # three: no pairs
    (12, 24, 128, 64, "float32", False),     # key heads in no whole blocks
    (64, 256, 128, 64, "float32", False),    # more value heads than lanes
])
def test_which_shapes_take_the_kernels(key_heads, value_heads, dim, chunk,
                                       dtype, takes):
    q = jax.ShapeDtypeStruct((3, 2 * chunk + 1, key_heads, dim), dtype)
    v = jax.ShapeDtypeStruct((3, 2 * chunk + 1, value_heads, dim), dtype)
    chunks = delta_rule.kernel_chunks(q, q, v, chunk)
    assert chunks == (3 * 3 if takes else 0)  # rows x chunks, the ragged one too
    assert kernels.fits(q.shape, v.shape, chunk) == takes


def _primitives(jaxpr, inside_kernels=False):
    """The names of a jaxpr's primitives, nested jaxprs included (but not,
    unless asked, a ``pallas_call``'s own body)."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call" and not inside_kernels:
            names.add(eqn.params["name"])
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub, inside_kernels)
    return names


def test_mixed_dtypes_and_small_shapes_fall_to_the_xla_form():
    """What the kernels do not take runs the XLA form itself, a row at a
    time: no Pallas call in the program, and the value is ``_rule_of_rows``'
    bit for bit. What they take holds the two kernels and, outside them, no
    loop at all: no ``lax.map`` over rows, no scan over chunks."""
    small = tuple(t[..., :16] if t.ndim == 4 else t
                  for t in operands(24, 2, (0.5, 1.0, 2.0, 4.0)))
    mixed = operands(128, 2, (0.5, 1.0, 2.0, 4.0))
    mixed = (mixed[0].astype(jnp.bfloat16),) + mixed[1:]
    for args, chunk in ((small, 8), (mixed, CHUNK)):
        assert delta_rule.kernel_chunks(*args[:3], chunk) == 0
        rule = lambda *t: delta_rule.gated_delta_rule(*t, chunk)
        names = _primitives(jax.make_jaxpr(rule)(*args).jaxpr)
        assert "pallas_call" not in names and "scan" in names
        np.testing.assert_array_equal(
            np.asarray(jax.jit(rule)(*args), np.float32),
            np.asarray(jax.jit(lambda *t: xla_form(*t, chunk))(*args),
                       np.float32))
    fitting = operands(128, 2, (0.5, 1.0, 2.0, 4.0))
    names = _primitives(jax.make_jaxpr(jax.grad(lambda *t: jnp.sum(
        delta_rule.gated_delta_rule(*t, CHUNK))))(*fitting).jaxpr)
    assert {"pallas_call", "delta_rule_fwd", "delta_rule_bwd"} <= names
    assert not {"scan", "while"} & names


# -- the counter, through the program's own step ------------------------------

WIDE = dict(vocab_size=64, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=2, num_key_value_heads=1, head_dim=16,
            linear_num_key_heads=1, linear_num_value_heads=2,
            linear_key_head_dim=DIM, linear_value_head_dim=DIM,
            linear_conv_kernel_dim=4, delta_chunk=CHUNK, num_experts=2,
            ep_size=2, num_experts_per_tok=1, moe_intermediate_size=16,
            shared_expert_intermediate_size=16, moe_piece_multiple=8)


@pytest.mark.parametrize("widths,seq,kernel_chunks_a_row", [
    (WIDE, CHUNK + 7, 2),                             # the kernels, a ragged end
    (dict(WIDE, linear_key_head_dim=16), CHUNK + 7, 0),  # keys of 16: XLA
])
def test_the_counter_reads_layers_by_micro_batches_by_rows_by_chunks(
        widths, seq, kernel_chunks_a_row):
    model = build_pretraining_model(Qwen3NextConfig(**widths), jnp.float32,
                                    remat="full")
    tx = optim.adamw(1e-3, max_grad_norm=1.0,
                     weight_decay_mask=optim.no_decay_mask)
    state = pretrain.make_init_fn(
        model, tx, (jnp.zeros((1, 8), jnp.int32),), None)(jax.random.PRNGKey(0))
    step = pretrain.make_train_step(model, tx, next_sentence=False)
    micro, rows, layers = 2, 2, 2  # both layers mix by the rule
    ids = np.random.default_rng(0).integers(0, 64, (micro, rows, seq))
    _, metrics = step(state, {"input_ids": jnp.asarray(ids, jnp.int32)})
    assert float(metrics["finite"]) == 1.0
    assert float(metrics["delta_chunks_run"]) == layers * micro * rows * 2
    assert float(metrics["delta_kernel_chunks_run"]) == (
        layers * micro * rows * kernel_chunks_a_row)
    assert model.COUNTERS[-3:-1] == ("delta_chunks_run",
                                     "delta_kernel_chunks_run")
