"""The delta-rule mixer's element-wise kernels (``ops/pallas/gdn_mix.py``, run
by the interpreter on the CPU): the convolution, silu and unit length before
the rule and the gated norm after it, against the plain float32 reference
(``benchmarks/reference/qwen3next_f32.py``) and against the XLA form
(``ops/gdn_mix.py``), values and every cotangent (the raw q, k, v and the
taps; o, z and the norm's scale); the rows a block borrows from its
neighbours, both ways; the rows of a batch apart; which shapes take the
kernels; and the counter that says they ran.

Tolerances. Float32 on both sides, so kernels, XLA form and reference differ
in the ORDER of float32 sums only: ``TOL`` is ``tests/test_qwen3_next.py``'s
5e-5 of the largest element. With bfloat16 operands the kernels round ONCE,
at the store, where the XLA form rounds after the convolution, after the silu
and after the norm: each stays within 3% of the float32 reference's largest
element (``tests/test_delta_rule_kernel.py``'s ``BF16_TOL``), and the kernels
no further from it than the XLA form.
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
from bert_pytorch_tpu.ops import gdn_mix
from bert_pytorch_tpu.ops.pallas import gdn_mix as kernels

DIM, KEY_HEADS, VALUE_HEADS, TAPS = 128, 1, 2, 4
EPSILON = 1e-6
TOL, BF16_TOL = 5e-5, 0.03
# positions a row: three blocks (a grid step's) of 64, or of 16
LENGTHS = {"three_blocks_of_64": 192, "three_blocks_of_sixteen": 48}
MIX_NAMES = ("q", "k", "v", "dq_raw", "dk_raw", "dv_raw", "dtaps_q",
             "dtaps_k", "dtaps_v")
NORM_NAMES = ("out", "do", "dz", "dscale")


def mix_operands(seq, dtype=jnp.float32, batch=2, seed=0, taps=TAPS):
    """The in-projection's three column blocks, each one's taps, and one
    cotangent a result."""
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    widths = (KEY_HEADS * DIM, KEY_HEADS * DIM, VALUE_HEADS * DIM)
    raw = tuple(jax.random.normal(k[i], (batch, seq, width)).astype(dtype)
                for i, width in enumerate(widths))
    kernel = tuple(jax.random.uniform(k[3 + i], (taps, width), jnp.float32,
                                      -0.5, 0.5)
                   for i, width in enumerate(widths))
    cotangents = tuple(jnp.cos(jnp.arange(
        batch * seq * width, dtype=jnp.float32) * (1 + i)).reshape(
            batch, seq, -1, DIM).astype(dtype)
        for i, width in enumerate(widths))
    return raw + kernel, cotangents


def norm_operands(seq, dtype=jnp.float32, batch=2, seed=1):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (batch, seq, VALUE_HEADS, DIM)
    o, z = (jax.random.normal(k[i], shape).astype(dtype) for i in (0, 1))
    scale = 1.0 + 0.3 * jax.random.normal(k[2], (DIM,))
    cotangent = jnp.sin(jnp.arange(o.size, dtype=jnp.float32)).reshape(
        shape).astype(dtype)
    return (o, z, scale), cotangent


def mix_kernels(*operands):
    assert gdn_mix.kernel_fit(*(t.reshape(t.shape[:2] + (-1, DIM))
                                for t in operands[:3]), operands[3].shape[0])
    return gdn_mix.conv_silu_unit(*operands, KEY_HEADS, VALUE_HEADS)


def mix_xla(*operands):
    """The form ``conv_silu_unit`` runs where the kernels do not fit."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(gdn_mix, "kernel_fit", lambda *_: False)
        return gdn_mix.conv_silu_unit(*operands, KEY_HEADS, VALUE_HEADS)


def mix_reference(q, k, v, taps_q, taps_k, taps_v):
    """``reference/qwen3next_f32.py delta_mixer``'s lines between the
    projection and the rule, with its own parts."""
    q, k, v = (jax.nn.silu(ref.causal_conv(t, w)).reshape(
        t.shape[:2] + (-1, DIM))
        for t, w in zip((q, k, v), (taps_q, taps_k, taps_v)))
    return ref.unit(q) / math.sqrt(DIM), ref.unit(k), v


def norm_kernels(o, z, scale):
    assert kernels.fits(o.shape)
    return gdn_mix.gated_head_norm(o, z, scale, EPSILON)


def norm_xla(o, z, scale):
    return gdn_mix._gated_head_norm_xla(o, z, scale, EPSILON)


def norm_reference(o, z, scale):
    """That function's two lines after the rule."""
    o = o * jax.lax.rsqrt(
        jnp.mean(jnp.square(o), axis=-1, keepdims=True) + EPSILON) * scale
    return o * jax.nn.silu(z)


def with_cotangents(fn):
    """(operands, cotangents) -> the results and every operand's cotangent,
    flat (jitted: a shape compiles once a module)."""
    def run(operands, cotangents):
        out, back = jax.vjp(fn, *operands)
        return jax.tree_util.tree_leaves((out, back(cotangents)))

    return jax.jit(run)


MIX = {"kernels": with_cotangents(mix_kernels), "xla_form": with_cotangents(
    mix_xla), "reference": with_cotangents(mix_reference)}
NORM = {"kernels": with_cotangents(norm_kernels), "xla_form": with_cotangents(
    norm_xla), "reference": with_cotangents(norm_reference)}


def worst(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    return np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30)


@pytest.fixture(scope="module")
def results():
    """(stage, form, length name) -> that form's results and cotangents."""
    cache = {}

    def get(stage, form, length):
        if (stage, form, length) not in cache:
            forms, operands = ((MIX, mix_operands) if stage == "mix"
                               else (NORM, norm_operands))
            cache[stage, form, length] = forms[form](
                *operands(LENGTHS[length]))
        return cache[stage, form, length]

    return get


@pytest.mark.parametrize("other", ["xla_form", "reference"])
@pytest.mark.parametrize("length", sorted(LENGTHS))
@pytest.mark.parametrize("stage", ["mix", "norm"])
def test_kernels_match(results, stage, length, other):
    names = MIX_NAMES if stage == "mix" else NORM_NAMES
    mine, theirs = (results(stage, form, length)
                    for form in ("kernels", other))
    assert len(mine) == len(theirs) == len(names)
    for name, got, want in zip(names, mine, theirs):
        assert got.dtype == want.dtype and got.shape == want.shape, name
        assert bool(jnp.all(jnp.isfinite(got))), name
        assert worst(got, want) <= TOL, name


@pytest.mark.parametrize("stage", ["mix", "norm"])
def test_bfloat16_rounds_once_and_no_further_from_float32_than_the_xla_form(
        stage):
    forms, make, names = ((MIX, mix_operands, MIX_NAMES) if stage == "mix"
                          else (NORM, norm_operands, NORM_NAMES))
    operands, cotangents = make(192, dtype=jnp.bfloat16, seed=3)
    exact = lambda tree: jax.tree_util.tree_map(
        lambda t: t.astype(jnp.float32), tree)
    mine = forms["kernels"](operands, cotangents)
    xla = forms["xla_form"](operands, cotangents)
    truth = forms["reference"](exact(operands), exact(cotangents))
    for name, got, form, want in zip(names, mine, xla, truth):
        assert got.dtype == form.dtype, name  # bfloat16 where the operand is
        assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32)))), name
        assert worst(got, want) <= BF16_TOL, name
        assert worst(got, want) <= worst(form, want) + 1e-3, name


@pytest.mark.parametrize("at", [0, 15, 31, 63, 64, 127, 191])
def test_one_position_reaches_the_three_after_it_and_no_other(at):
    """The rows a block (64 here) borrows, both ways. Forward: an operand
    that is zero but at ONE position gives ``silu(tap x)`` on that position
    and the three after it, across a block's end (63, 127), and exact zeros
    elsewhere (a row's first three positions see zeros before
    them). Backward: a cotangent on ONE position of v reaches the raw v of
    that position and the three before it, and the taps' gradient is the
    operand there times it."""
    seq = 192
    (q, k, v, taps_q, taps_k, taps_v), _ = mix_operands(seq, batch=1)
    spike = jnp.zeros_like(v).at[0, at].set(v[0, at])
    _, _, out = mix_kernels(q, k, spike, taps_q, taps_k, taps_v)
    out = out.reshape(seq, -1)
    for t in range(seq):
        if at <= t <= at + 3:
            want = jax.nn.silu(taps_v[3 - (t - at)] * v[0, at])
            assert worst(out[t], want) <= TOL, t
        else:
            np.testing.assert_array_equal(np.asarray(out[t]), 0.0)
    # the other way: the positions whose results read position ``at``
    reads = min(at + 3, seq - 1)
    dv = jnp.zeros((1, seq, VALUE_HEADS, DIM)).at[0, reads].set(1.0)
    loss = lambda v_, w_: jnp.sum(
        mix_kernels(q, k, v_, taps_q, taps_k, w_)[2] * dv)
    theirs = lambda v_, w_: jnp.sum(
        mix_reference(q, k, v_, taps_q, taps_k, w_)[2] * dv)
    for got, want in zip(jax.grad(loss, argnums=(0, 1))(v, taps_v),
                         jax.grad(theirs, argnums=(0, 1))(v, taps_v)):
        assert worst(got, want) <= TOL
    back = jax.grad(loss)(v, taps_v)[0]
    touched = np.flatnonzero(np.abs(np.asarray(back)).sum(axis=-1))
    assert list(touched) == list(range(max(reads - 3, 0), reads + 1))


@pytest.mark.parametrize("stage", ["mix", "norm"])
def test_a_row_of_two_rows_equals_each_row_alone(results, stage):
    """Nothing passes from a row of the batch to the next: not the rows
    before a block (zeros at a row's first), not the cotangent the backward
    carries from the block after (zeros at a row's last). The two rows
    swapped give the results swapped, and a row alone its own; the taps' and
    the scale's gradients are the rows' sum either way."""
    forms, make = (MIX, mix_operands) if stage == "mix" else (
        NORM, norm_operands)
    operands, cotangents = make(192)
    both = results(stage, "kernels", "three_blocks_of_64")
    per_row = lambda t: t.ndim >= 3
    flip = lambda tree: jax.tree_util.tree_map(
        lambda t: t[::-1] if per_row(t) else t, tree)
    one = lambda tree: jax.tree_util.tree_map(
        lambda t: t[:1] if per_row(t) else t, tree)
    swapped = forms["kernels"](flip(operands), flip(cotangents))
    alone = forms["reference"](one(operands), one(cotangents))
    for pair, back, single in zip(both, swapped, alone):
        if per_row(pair):
            np.testing.assert_array_equal(back[::-1], pair)
            assert worst(pair[:1], single) <= TOL
        else:
            assert worst(back, pair) <= TOL


@pytest.mark.parametrize("fault", ["rows_before_dropped",
                                   "cotangent_after_dropped"])
def test_a_planted_fault_is_seen(results, monkeypatch, fault):
    """The comparison is not blind to what only a neighbour lends: with the
    rows before a block read as zeros the forward is wrong from the second
    block on; with the carried cotangent zeroed the backward's raw cotangents
    are wrong at every block's last rows, and the forward is untouched."""
    operands, cotangents = mix_operands(192)
    want = results("mix", "reference", "three_blocks_of_64")
    for entry in ("gdn_mix_forward", "gdn_mix_backward"):
        # (traced anew: the jitted entry points remember the sound kernels)
        monkeypatch.setattr(kernels, entry,
                            getattr(kernels, entry).__wrapped__)
    if fault == "rows_before_dropped":
        monkeypatch.setattr(kernels, "_rows_before", lambda *_: jnp.zeros(
            (kernels.HALO, kernels.LANES), jnp.float32))
    else:
        real = kernels._mix_bwd_kernel

        def forgetful(*refs, **sizes):
            for carried in refs[-3:]:
                carried[...] = jnp.zeros(carried.shape, jnp.float32)
            real(*refs, **sizes)

        monkeypatch.setattr(kernels, "_mix_bwd_kernel", forgetful)
    wrong = with_cotangents(mix_kernels)(operands, cotangents)
    gaps = dict(zip(MIX_NAMES, (worst(got, ref_) for got, ref_ in zip(
        wrong, want))))
    if fault == "rows_before_dropped":
        assert gaps["v"] > 0.01 and gaps["q"] > 0.01
        assert worst(wrong[2][:, :64], want[2][:, :64]) <= TOL
    else:
        assert max(gaps["q"], gaps["k"], gaps["v"]) <= TOL
        assert gaps["dv_raw"] > 0.01 and gaps["dq_raw"] > 0.01


@pytest.mark.parametrize("seq,heads,dim,taps,dtypes,takes", [
    (8192, (16, 32), 128, 4, ("bfloat16",) * 3, True),   # the published widths
    (48, (1, 2), 128, 4, ("float32",) * 3, True),
    (64, (2, 2), 128, 2, ("bfloat16",) * 3, True),       # fewer taps
    (64, (1, 2), 128, 1, ("float32",) * 3, True),
    (71, (1, 2), 128, 4, ("float32",) * 3, False),       # no whole blocks of 16
    (8, (1, 2), 128, 4, ("float32",) * 3, False),
    (64, (2, 4), 16, 4, ("float32",) * 3, False),        # the tiny models' heads
    (64, (1, 2), 256, 4, ("float32",) * 3, False),       # heads of two tiles
    (64, (1, 2), 128, 5, ("float32",) * 3, False),       # more taps than four
    (64, (1, 2), 128, 4, ("bfloat16", "float32", "float32"), False),
    (64, (1, 2), 128, 4, ("float32", "float32", "bfloat16"), False),
])
def test_which_shapes_take_the_kernels(seq, heads, dim, taps, dtypes, takes):
    q, k, v = (jax.ShapeDtypeStruct((3, seq, count, dim), dtype)
               for count, dtype in zip((heads[0],) + heads, dtypes))
    assert gdn_mix.kernel_fit(q, k, v, taps) == takes
    same = len(set(dtypes)) == 1
    assert (kernels.fits(q.shape, taps) and kernels.fits(v.shape, taps)
            and same) == takes


def _primitives(jaxpr):
    """The names of a jaxpr's primitives and of its ``pallas_call``s, nested
    jaxprs included (but not a kernel's own body)."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call":
            names.add(eqn.params["name"])
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


@pytest.mark.parametrize("seq,dtype,kernel_names", [
    (48, "float32", {"gdn_mix_fwd", "gdn_mix_bwd", "gated_norm_fwd",
                     "gated_norm_bwd"}),
    (40, "float32", set()),      # a ragged length: the XLA form of both
])
def test_what_does_not_fit_runs_the_xla_form_itself(seq, dtype, kernel_names):
    """A call the kernels take holds the four of them and nothing kept but
    the operands; any other holds no Pallas call and IS the XLA form, bit for
    bit."""
    operands, cotangents = mix_operands(seq, jnp.dtype(dtype))
    norm, cotangent = norm_operands(seq, jnp.dtype(dtype))

    def both(operands, norm):
        q, k, v = gdn_mix.conv_silu_unit(*operands, KEY_HEADS, VALUE_HEADS)
        out = gdn_mix.gated_head_norm(*norm, EPSILON)
        return sum(jnp.sum(t * c) for t, c in zip(
            (q, k, v, out), cotangents + (cotangent,)))

    names = _primitives(jax.make_jaxpr(jax.grad(both, argnums=(0, 1)))(
        operands, norm).jaxpr)
    assert {n for n in names if n.startswith(("gdn_", "gated_"))} == (
        kernel_names)
    assert ("pallas_call" in names) == bool(kernel_names)
    if not kernel_names:
        for got, want in zip(
                gdn_mix.conv_silu_unit(*operands, KEY_HEADS, VALUE_HEADS),
                mix_xla(*operands)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(
            np.asarray(gdn_mix.gated_head_norm(*norm, EPSILON)),
            np.asarray(norm_xla(*norm)))


# -- the counter, through the program's own step ------------------------------

WIDE = dict(vocab_size=64, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=2, num_key_value_heads=1, head_dim=16,
            linear_num_key_heads=1, linear_num_value_heads=2,
            linear_key_head_dim=DIM, linear_value_head_dim=DIM,
            linear_conv_kernel_dim=4, delta_chunk=64, num_experts=2,
            ep_size=2, num_experts_per_tok=1, moe_intermediate_size=16,
            shared_expert_intermediate_size=16, moe_piece_multiple=8)


@pytest.mark.parametrize("widths,seq,rule_chunks,mix_chunks", [
    (WIDE, 80, 2, 2),    # both sides in their kernels; the rule pads to 128
    (WIDE, 71, 2, 0),    # no whole blocks of 16: the rule's kernels alone
    (dict(WIDE, linear_key_head_dim=16, linear_value_head_dim=16), 80, 0, 0),
])
def test_the_counter_reads_the_chunks_of_calls_whose_two_sides_ran_in_kernels(
        widths, seq, rule_chunks, mix_chunks):
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
    calls = layers * micro * rows
    assert float(metrics["delta_chunks_run"]) == calls * 2
    assert float(metrics["delta_kernel_chunks_run"]) == calls * rule_chunks
    assert float(metrics["delta_mix_kernel_chunks_run"]) == calls * mix_chunks
    assert model.COUNTERS[-3:] == (
        "delta_chunks_run", "delta_kernel_chunks_run",
        "delta_mix_kernel_chunks_run")
