"""The ``KeyeVL2`` family against its plain float32 reference
(``benchmarks/reference/keye_f32.py``) at a small size on the CPU: the sparse
attention layer (forward and every cotangent) at a row longer than ``topk``
and at one shorter, the exact choice and its tie rule (the XLA form, the
kernel and ``lax.top_k``), the Pallas kernels against the XLA form, the two
gradient paths kept apart, the sixteen expert shares adding up to the uncut
layer, the whole model's loss and gradients, two whole updates through
``pretrain.make_train_step`` with the model's objective term on the whole and
on the chunked head path, the pinned counts, and the normal path
(``run_pretraining.main``) from a config file.

Tolerances: everything here is float32 at ``highest`` on both sides
(conftest), so program and reference differ only in the ORDER of float32
sums: 2e-5 of the largest element leaves a decade of room and would not pass
a key dropped from a query's set (each moves the result by percents).
"""

import json

import flax
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import keye_f32 as ref
from benchmarks.reference import keye_map
from bert_pytorch_tpu import optim, pretrain
from bert_pytorch_tpu.config import KeyeVLConfig, load_model_config
from bert_pytorch_tpu.models import build_pretraining_model, keye_vl
from bert_pytorch_tpu.ops import sparse_attention as sparse
from bert_pytorch_tpu.ops.pallas import sparse_attention as kernels
from bert_pytorch_tpu.ops.remat import remat_policy
from bert_pytorch_tpu.utils import flops

# the published layer at a small size: 4 / 2 heads of 16 over the 8 keys a
# 4 x 8 indexer chooses, 4 of 8 experts held top-3; two layers
TINY = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_experts=4, ep_size=2, ep_rank=1, num_experts_per_tok=3,
    moe_intermediate_size=32, rope_theta=10000000, rms_norm_eps=1e-6,
    sa_config=dict(indexer_num_heads=4, indexer_head_dim=8,
                   indexer_num_kv_heads=1, topk=8, q_chunk_size=512,
                   kv_chunk_size=512),
    moe_piece_multiple=8)
TOL = 2e-5
CONFIG_FILE = "benchmarks/configs/keye-vl-2.0-30b-a3b.json"


def close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(np.max(np.abs(b)), 1e-30), (
        np.max(np.abs(a - b)), np.max(np.abs(b)))


def keys(n, seed=0):
    return jax.random.split(jax.random.PRNGKey(seed), n)


def _tiny(**changes):
    merged = dict(TINY, **{k: v for k, v in changes.items() if k != "topk"})
    if "topk" in changes:
        merged["sa_config"] = dict(TINY["sa_config"], topk=changes["topk"])
    return merged


def _seeded(seed=3, loud=False, **changes):
    """Sizes and seeded weights; ``loud``: the norms' scales away from one and
    every matrix ten times larger, so that every parameter shows in the
    output and the indexer's scores are far from equal."""
    c = ref.sizes(_tiny(**changes))
    p = ref.seeded_params(ref.key_from_seed(seed), c)
    if loud:
        table = ref.param_table(c)
        for index, name in enumerate(sorted(p)):
            draw = jax.random.fold_in(jax.random.PRNGKey(seed + 100), index)
            if table[name][1] == "ones":
                p[name] = p[name] + 0.3 * jax.random.normal(draw, p[name].shape)
            else:
                p[name] = 10.0 * p[name]
    return c, p


def _model(backend="xla", remat="full", **changes):
    return build_pretraining_model(
        KeyeVLConfig(**_tiny(**changes)), jnp.float32, remat=remat,
        attention_backend=backend)


def _layer(c, p, layer, x, **changes):
    """The program's attention layer ``layer`` over x: (output, counters)."""
    cfg = KeyeVLConfig(**_tiny(**changes))
    tree = keye_map.to_program(p, c)[f"layers_{layer}"]["attention"]
    return keye_vl.SparseAttention(cfg, jnp.float32).apply({"params": tree}, x)


# -- the choice ----------------------------------------------------------------------

def _top_k_mask(scores, topk):
    """The exact set by ``lax.top_k`` (equal scores: the lower position)."""
    rows, seq = scores.shape
    causal = np.arange(seq)[None, :] <= np.arange(rows)[:, None]
    _, order = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                             min(topk, seq))
    mask = np.zeros((rows, seq), bool)
    for t in range(rows):
        mask[t, np.asarray(order[t, :min(t + 1, topk)])] = True
    return mask


@pytest.mark.parametrize("seq,topk", [(48, 8), (48, 64), (40, 1), (33, 33)])
def test_the_choice_is_the_exact_largest_k_with_ties_to_the_lower_position(
        seq, topk):
    k = keys(3, seq)
    qi = jax.random.normal(k[0], (2, seq, 4, 8))
    ki = jax.random.normal(k[1], (2, seq, 8))
    # equal keys give equal scores: ties, at the k-th score too
    ki = ki.at[:, 5:19].set(ki[:, 5:6]).at[:, 30:].set(0.0)
    w = jax.random.normal(k[2], (2, seq, 4))
    mask = np.asarray(sparse.choose(qi, ki, w, topk))
    scores = sparse.index_scores(qi, ki, w)
    theirs = np.asarray(ref.chosen_mask(scores, 0, topk))  # the reference's
    for row in range(2):
        np.testing.assert_array_equal(mask[row], _top_k_mask(scores[row], topk))
        np.testing.assert_array_equal(theirs[row], mask[row])
    short = np.asarray(ref.chosen_mask(scores, 0, topk, short=1))
    assert short.sum() == mask.sum() - 2 * seq and not (short & ~theirs).any()
    assert mask.sum() == 2 * sum(min(t + 1, topk) for t in range(seq))


def test_ordered_keys_keep_the_order_of_floats_and_the_kth_is_exact():
    x = jnp.asarray([-jnp.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, jnp.inf],
                    jnp.float32)
    image = np.asarray(sparse.ordered_key(x))
    assert (np.diff(image.astype(np.int64)) > 0).all()
    row = jax.random.normal(keys(1, 5)[0], (3, 100))
    want = np.sort(np.asarray(sparse.ordered_key(row)), axis=-1)[:, ::-1]
    for k in (1, 7, 100):
        got = sparse.kth_largest(sparse.ordered_key(row), jnp.full((3,), k))
        np.testing.assert_array_equal(np.asarray(got), want[:, k - 1])


def _kernel_inputs(seq=1024, heads=4, kv=2, depth=128, index_heads=4,
                   width=64):
    k = keys(6, 1)
    q = jax.random.normal(k[0], (1, seq, heads, depth))
    kk = jax.random.normal(k[1], (1, seq, kv, depth))
    v = jax.random.normal(k[2], (1, seq, kv, depth))
    qi = jax.random.normal(k[3], (1, seq, index_heads, width))
    ki = jax.random.normal(k[4], (1, seq, width))
    ki = ki.at[:, 100:140].set(ki[:, 100:101])  # ties
    w = jax.random.normal(k[5], (1, seq, index_heads))
    return q, kk, v, qi, ki, w


@pytest.fixture(scope="module")
def kernel_case():
    q, k, v, qi, ki, w = _kernel_inputs()
    mask = sparse.choose(qi, ki, w, 300)
    return q, k, v, qi, ki, w, mask, kernels.select(qi, ki, w, 300)


def test_the_select_kernel_chooses_the_xla_forms_set_bit_for_bit(kernel_case):
    *_, mask, words = kernel_case
    np.testing.assert_array_equal(np.asarray(words),
                                  np.asarray(kernels.pack(mask)))
    np.testing.assert_array_equal(np.asarray(kernels.unpack(words, 1024)),
                                  np.asarray(mask))
    # a row of 1024 at topk 300: sum over t of min(t + 1, 300)
    assert int(jnp.sum(jax.lax.population_count(words))) == (
        300 * 301 // 2 + 724 * 300)


# (heads, key-value heads, row length, head width, rows whose first key tile
# is emptied): a group of 2, 4 and the published 8 heads a key-value head;
# rows 600-899 with NO chosen key among keys 0-511, so their first trip sums
# ones under m = NEG and the second must wipe them, each head by its own
# maximum; a row of 1536, where a program's first query block sees one key
# tile of 512 and its last sees three; and a head of two lane tiles, which
# the forward's statistics (one lane tile wide) meet by tiling.
CORE_CASES = {
    "group2": (4, 2, 1024, 128, None),
    "group4": (4, 1, 1024, 128, None),
    "group8": (8, 1, 1024, 128, None),
    "group8_first_tile_empty": (8, 1, 1024, 128, (600, 900)),
    "group2_seq1536": (4, 2, 1536, 128, None),
    "group2_depth256": (2, 1, 1024, 256, None),
}


@pytest.mark.parametrize("case", CORE_CASES)
def test_the_core_kernels_match_the_xla_form_forward_and_backward(case):
    heads, kv, seq, depth, emptied = CORE_CASES[case]
    q, k, v, qi, ki, w = _kernel_inputs(seq=seq, heads=heads, kv=kv,
                                        depth=depth)
    mask = sparse.choose(qi, ki, w, 300)
    if emptied:
        first, last = emptied
        at = jnp.arange(seq)
        late = ((at >= first) & (at < last))[:, None]
        # (each such row keeps its own key, so none is left without any)
        mask = (mask & ~(late & (at < kernels.WORD_LANES)[None, :])
                | (late & (at[:, None] == at[None, :])))
        assert not np.asarray(mask[0, first:last, :kernels.WORD_LANES]).any()
    words = kernels.pack(mask)
    weigh = lambda ctx: jnp.sum(ctx * jnp.cos(ctx))

    def xla(q, k, v):
        ctx, lse = sparse.attend_xla(q, k, v, mask)
        return weigh(ctx), (ctx, lse)

    def kernel(q, k, v):
        ctx, lse = kernels.masked_attention(q, k, v, words)
        return weigh(ctx), (ctx, lse)

    (_, want), want_grads = jax.value_and_grad(
        xla, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, got), got_grads = jax.value_and_grad(
        kernel, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    for a, b in zip(got + got_grads, want + want_grads):
        close(a, b)


def test_the_objectives_kernel_matches_the_xla_form_with_its_backward(
        kernel_case):
    q, k, _, qi, ki, w, mask, words = kernel_case
    _, lse = sparse.attend_xla(q, k, q[:, :, :2], mask)
    xla = lambda qi, ki, w: jnp.sum(
        sparse.index_loss_xla(qi, ki, w, q, k, lse, mask))
    kernel = lambda qi, ki, w: kernels.index_loss(qi, ki, w, q, k, lse, words)
    want, want_grads = jax.value_and_grad(xla, argnums=(0, 1, 2))(qi, ki, w)
    got, got_grads = jax.value_and_grad(kernel, argnums=(0, 1, 2))(qi, ki, w)
    close(got, want)
    close(kernel(qi, ki, w), want)  # the call that keeps no gradient
    for a, b in zip(got_grads, want_grads):
        close(a, b)
    # no gradient reaches the core's q, k or log-sum-exps
    through = jax.grad(lambda q, k, lse: kernels.index_loss(
        qi, ki, w, q, k, lse, words), argnums=(0, 1, 2))(q, k, lse)
    assert all(not np.asarray(t).any() for t in through)


def _layer_grad(kernel_case, remat):
    """The gradient to q, k, v, qi, ki and w of a sparse-attention layer (the
    kernels) under ``jax.checkpoint`` with ``remat``'s policy."""
    def layer(q, k, v, qi, ki, w):
        ctx, kl, _, _ = sparse.sparse_attention(q, k, v, qi, ki, w, 300,
                                                backend="pallas")
        return jnp.sum(ctx * jnp.cos(ctx)) + kl

    policy = remat_policy(remat)
    if policy is not None:
        layer = jax.checkpoint(layer, policy=policy)
    return jax.grad(layer, argnums=tuple(range(6)))


def _kernel_calls(jaxpr):
    """(name, number of outputs) of every ``pallas_call`` a jaxpr holds, the
    nested jaxprs' too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"], len(eqn.outvars)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _kernel_calls(sub)
    return found


@pytest.fixture(scope="module")
def layer_grads(kernel_case):
    """remat -> the six gradients, each policy's program run once a module."""
    made = {}

    def of(remat):
        if remat not in made:
            made[remat] = jax.jit(_layer_grad(kernel_case, remat))(
                *kernel_case[:6])
        return made[remat]
    return of


@pytest.mark.parametrize("remat", ["full", "dots", "none"])
def test_the_objectives_kernel_runs_once_under_a_gradient_whatever_the_remat(
        remat, kernel_case, layer_grads):
    """Under ``jax.grad`` of a ``jax.checkpoint`` the forward pass runs the
    objective's forward RULE, and the recompute would run it again: the three
    gradients it makes are kept by name (``ops/remat.py DSA_INDEX_GRADS``), so
    the gradient program holds ONE ``dsa_index_loss`` call (four outputs)
    under 'full' and 'dots' as it does without remat, and the gradients to
    qi, ki and w are those of ``remat='none'`` bit for bit (in float32, as
    here, the casts of the kept gradients are identities; in bfloat16 on the
    chip a kept gradient is rounded once more than one whose cast fuses with
    the scaling by the cotangent: PERF.md 6, "PR 46"). The core's forward
    kernel runs once too: its output and log-sum-exps, the residuals of its
    backward kernels, are kept by name (``DSA_CORE_OUT``, ``DSA_CORE_LSE``)."""
    grad = _layer_grad(kernel_case, remat)
    made = _kernel_calls(jax.make_jaxpr(grad)(*kernel_case[:6]).jaxpr)
    assert [c for c in made if c[0] == "dsa_index_loss"] == [
        ("dsa_index_loss", 4)], made
    assert made.count(("dsa_core_fwd", 2)) == 1, made
    assert made.count(("dsa_select", 1)) == 1
    if remat != "none":
        for got, want in zip(layer_grads(remat)[3:], layer_grads("none")[3:]):
            assert np.asarray(want).any()
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("remat", ["full", "dots"])
def test_the_cores_gradients_through_a_rematerialized_layer_are_those_without(
        remat, kernel_case, layer_grads):
    """With the forward kernel's output and log-sum-exps kept by name the
    backward kernels read the forward pass's own tensors: the gradients to q,
    k and v through ``jax.checkpoint`` equal ``remat='none'``'s bit for bit,
    and the two backward kernels run once each."""
    made = _kernel_calls(jax.make_jaxpr(_layer_grad(kernel_case, remat))(
        *kernel_case[:6]).jaxpr)
    assert made.count(("dsa_core_bwd_dq", 1)) == 1, made
    assert made.count(("dsa_core_bwd_dkv", 2)) == 1, made
    for got, want in zip(layer_grads(remat)[:3], layer_grads("none")[:3]):
        assert np.asarray(want).any()
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("keeping", [1, 2])
def test_the_layers_that_keep_the_cores_output_are_the_last_ones(
        keeping, monkeypatch):
    """``keye_vl.CORE_KEPT_LAYERS`` of a model's layers keep the core's output
    and log-sum-exps under ``--remat full``, and they are the LAST: read from
    the gradient program of three layers at fitting shapes, whose backward
    pass (last layer first) runs ``dsa_core_fwd`` again before a layer's two
    backward kernels in the layers that do not keep, and not in those that
    do; the choice and the objective's kernel run once in every layer."""
    monkeypatch.setattr(keye_vl, "CORE_KEPT_LAYERS", keeping)
    layers = 3
    model = _model(backend="pallas", num_hidden_layers=layers, head_dim=128,
                   num_attention_heads=2, num_key_value_heads=1, topk=64)
    ids = jnp.zeros((1, 512), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]

    def loss(params):
        logits, counters = model.apply({"params": params}, ids)
        return jnp.sum(logits) + counters["dsa_index_kl"]

    made = [name for name, _ in _kernel_calls(
        jax.make_jaxpr(jax.grad(loss))(nn.unbox(params)).jaxpr)]
    assert made.count("dsa_select") == made.count("dsa_index_loss") == layers
    core = [name for name in made if name.startswith("dsa_core_")]
    backward = ["dsa_core_bwd_dq", "dsa_core_bwd_dkv"]
    assert core == (["dsa_core_fwd"] * layers + backward * keeping
                    + (["dsa_core_fwd"] + backward) * (layers - keeping)), core


def test_kernels_take_the_published_shapes_and_refuse_others():
    fits = kernels.fits
    bf = jnp.bfloat16
    assert fits((1, 16384, 32, 128), (1, 16384, 4, 128), bf, bf, 64)
    assert fits((2, 1024, 4, 128), (2, 1024, 2, 128), bf, bf, 64)
    assert not fits((1, 32768, 32, 128), (1, 32768, 4, 128), bf, bf, 64)
    assert not fits((1, 16384, 32, 64), (1, 16384, 4, 64), bf, bf, 64)
    assert not fits((1, 1000, 32, 128), (1, 1000, 4, 128), bf, bf, 64)
    assert not fits((1, 1024, 32, 128), (1, 1024, 4, 128), bf, jnp.float32, 64)


# -- the attention layer ---------------------------------------------------------------

@pytest.mark.parametrize("seq,topk", [(40, 8), (24, 64)])
def test_sparse_attention_matches_the_reference_with_every_cotangent(seq, topk):
    """A row longer than ``topk`` (the choice cuts) and one shorter (every
    causal key is chosen)."""
    c, p = _seeded(5, loud=True, topk=topk)
    x = jax.random.normal(keys(1, 2)[0], (2, seq, c["H"]))
    names = [n for n in p if n.startswith("l1.") and n.split(".")[1] in (
        "wq", "wk", "wv", "wo", "q_norm", "k_norm", "wqi", "wki", "ww")]

    def program(sub, x):
        out, counters = _layer(c, {**p, **sub}, 1, x, topk=topk)
        return jnp.sum(out * jnp.sin(out)) + 3.0 * counters["dsa_index_kl"], (
            out, counters)

    def reference(sub, x):
        out, kl, _ = ref.attention({**p, **sub}, "l1.", c, x, "f32")
        return jnp.sum(out * jnp.sin(out)) + 3.0 * jnp.mean(kl) / c["L"], out

    sub = {n: p[n] for n in names}
    (_, (out, counters)), got = jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True)(sub, x)
    (_, want_out), want = jax.value_and_grad(
        reference, argnums=(0, 1), has_aux=True)(sub, x)
    close(out, want_out)
    close(got[1], want[1])
    for name in names:
        close(got[0][name], want[0][name])
    pairs = 2 * sum(min(t + 1, topk) for t in range(seq))
    assert float(counters["dsa_pairs_run"]) == pairs
    assert float(counters["dsa_scored_pairs_run"]) == seq * (seq + 1)
    close(counters["dsa_keep_share"], pairs / (seq * (seq + 1)) / c["L"])


def test_where_every_causal_key_is_chosen_it_is_plain_causal_attention():
    c, p = _seeded(6, loud=True, topk=64)
    x = jax.random.normal(keys(1, 3)[0], (2, 24, c["H"]))
    out, _ = _layer(c, p, 0, x, topk=64)
    q, k, v = (jnp.matmul(x, p[f"l0.{n}"]).reshape(2, 24, -1, c["hd"])
               for n in ("wq", "wk", "wv"))
    q, k = ref.norm(q, p["l0.q_norm"], c["eps"]), ref.norm(
        k, p["l0.k_norm"], c["eps"])
    q, k = ref.rotate(q, c["hd"], c["rope"]), ref.rotate(k, c["hd"], c["rope"])
    k, v = (jnp.repeat(t, 2, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 4.0
    probs = jax.nn.softmax(jnp.where(
        jnp.tril(jnp.ones((24, 24), bool)), scores, -1e30), axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(2, 24, -1)
    close(out, jnp.matmul(ctx, p["l0.wo"]))


def test_no_part_of_the_layer_reads_a_later_position():
    c, p = _seeded(7, loud=True)
    x = jax.random.normal(keys(1, 4)[0], (1, 32, c["H"]))
    y = x.at[:, 20:].set(jax.random.normal(keys(1, 5)[0], (1, 12, c["H"])))
    close(_layer(c, p, 0, x)[0][:, :20], _layer(c, p, 0, y)[0][:, :20])


@pytest.mark.parametrize("fault", ["top_k_one_short", "indexer_input_attached",
                                   "index_loss_left_out"])
def test_a_fault_planted_in_the_reference_is_seen(fault):
    c, p = _seeded(8, loud=True)
    ids = jax.random.randint(keys(1, 6)[0], (2, 32), 0, c["V"])
    sound = jax.grad(lambda p: ref.objective(p, c, ids)[0])(p)
    wrong = jax.grad(lambda p: ref.objective(p, c, ids, faults=(fault,))[0])(p)
    moved = max(float(jnp.linalg.norm(wrong[n] - sound[n])
                      / (jnp.linalg.norm(sound[n]) + 1e-30)) for n in p)
    assert moved > 0.02, moved


# -- the two gradient paths ------------------------------------------------------------

def _is_indexer(path) -> bool:
    return any(part.startswith("index_") for part in path)


@pytest.mark.parametrize("term", ["next_token", "index_kl"])
def test_the_two_gradient_paths_are_apart(term):
    """The next-token loss alone gives the indexer's leaves exactly zero; the
    KL alone gives every other leaf exactly zero."""
    c, p = _seeded(9, loud=True)
    model = _model()
    ids = jax.random.randint(keys(1, 7)[0], (2, 32), 0, c["V"])

    def loss(params):
        logits, counters = model.apply({"params": params}, ids)
        if term == "index_kl":
            return counters["dsa_index_kl"]
        return pretrain.next_token_loss(logits, ids)[0]

    grads = flax.traverse_util.flatten_dict(
        jax.grad(loss)(keye_map.to_program(p, c)))
    zero = {path for path, g in grads.items() if not np.asarray(g).any()}
    indexer = {path for path in grads if _is_indexer(path)}
    assert len(indexer) == 3 * c["L"]
    assert zero == (indexer if term == "next_token" else set(grads) - indexer)


def test_the_model_names_its_objective_term_and_the_others_name_none():
    from bert_pytorch_tpu.config import LagunaConfig

    assert _model().objective_terms() == {"dsa_index_kl": 1.0}
    assert _model(index_loss_coef=0.25).objective_terms() == {
        "dsa_index_kl": 0.25}
    assert build_pretraining_model(
        LagunaConfig(), jnp.float32).objective_terms() == {}


# -- the expert layer ------------------------------------------------------------------

def test_the_sixteen_expert_shares_add_up_to_the_uncut_layer():
    """Each of 16 ranks holds 2 of 32 experts (no shared expert): the ranks'
    outputs add up to the reference's uncut layer (every expert on one
    chip)."""
    changes = dict(num_experts=2, ep_size=16, ep_rank=0, num_experts_per_tok=5)
    c, p = _seeded(4, loud=True, **changes)
    held, every = c["held"], c["experts"]
    assert (held, every) == (2, 32)
    x = jax.random.normal(keys(1, 8)[0], (2, 32, c["H"]))
    k = keys(2, 9)
    q = dict(p)
    q["l1.w_gu"] = 5 * c["std"] * jax.random.normal(
        k[0], (every, c["H"], 2 * c["F"]))
    q["l1.w_down"] = 5 * c["std"] * jax.random.normal(
        k[1], (every, c["F"], c["H"]))
    uncut, _ = ref.expert_layer(q, "l1.", dict(c, held=every, first=0), x, "f32")
    total, slots = 0.0, 0.0
    for rank in range(every // held):
        mine = slice(rank * held, (rank + 1) * held)
        share = dict(q, **{"l1.w_gu": q["l1.w_gu"][mine],
                           "l1.w_down": q["l1.w_down"][mine]})
        cfg = KeyeVLConfig(**_tiny(**dict(changes, ep_rank=rank)))
        out, counters = keye_vl.expert_layer(cfg, jnp.float32).apply(
            {"params": keye_map.to_program(share, c)["layers_1"]["mlp"]}, x)
        close(out, ref.expert_layer(share, "l1.", dict(c, first=rank * held),
                                    x, "f32")[0])
        total = total + out
        slots += float(counters["moe_local_slots"])
        assert float(counters["moe_dropped_slots"]) == 0.0
    assert slots == 64 * 5  # every slot is some share's
    close(total, uncut)


# -- the whole model ---------------------------------------------------------------

@pytest.mark.parametrize("remat", ["full", "none"])
def test_loss_and_every_gradient_match_the_reference_over_two_layers(remat):
    c, p = _seeded(10, loud=True)
    model = _model(remat=remat)
    ids = jax.random.randint(keys(1, 10)[0], (2, 40), 0, c["V"])

    def loss(params):
        return pretrain._apply_causal_lm_loss(
            model, {"params": params}, {"input_ids": ids})

    (got, aux), grads = jax.value_and_grad(loss, has_aux=True)(
        keye_map.to_program(p, c))
    (want, (routed, selected)), want_grads = jax.value_and_grad(
        lambda p: ref.objective(p, c, ids), has_aux=True)(p)
    close(got, want)
    grads = keye_map.from_program(grads, c)
    for name in p:
        close(grads[name], want_grads[name])
    pairs = c["L"] * 2 * sum(min(t + 1, 8) for t in range(40))
    assert float(aux["dsa_pairs_run"]) == pairs
    assert sum(int(np.unpackbits(np.asarray(s)).sum()) for s in selected) == pairs
    assert float(aux["dsa_index_kl"]) > 1e-3
    assert float(aux["moe_dropped_slots"]) == 0.0


@pytest.mark.parametrize("head", ["whole", "chunked"])
def test_two_updates_through_make_train_step_match_the_reference(
        monkeypatch, head):
    """Through the program's own step (micro-batch scan, clipping, AdamW with
    the no-decay mask), the model's objective term added on the whole head
    path and on the chunked one, against the reference's AdamW: losses, and
    the parameters' change after two updates."""
    if head == "chunked":  # rows of 32 in two pieces of 16
        monkeypatch.setattr(pretrain, "LM_HEAD_PIECE", 16)
    # (wider weights spread the indexer's scores: an update's rounding then
    # flips few of the second update's choices)
    config = _tiny(initializer_range=0.1)
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
    params = keye_map.to_program(
        ref.seeded_params(ref.key_from_seed(seed), c), c)
    state = pretrain.TrainState(params=params, opt_state=tx.init(params),
                                rng=jax.random.PRNGKey(0))
    step = pretrain.make_train_step(model, tx, schedule=schedule,
                                    next_sentence=False)
    rng = np.random.default_rng(0)
    updates = [rng.integers(0, c["V"], (2, 2, 32)).astype(np.int32)
               for _ in range(2)]
    losses = []
    for upd in updates:
        state, metrics = step(state, {"input_ids": jnp.asarray(upd)})
        losses.append(float(metrics["loss"]))
        assert float(metrics["moe_dropped_slots"]) == 0.0
        assert float(metrics["finite"]) == 1.0
        # 2 layers x 2 micro-batches of 2 rows of 32 at topk 8
        assert float(metrics["dsa_pairs_run"]) == 2 * 2 * 2 * (36 + 24 * 8)
        assert float(metrics["dsa_scored_pairs_run"]) == 2 * 2 * 2 * 528
        assert float(metrics["dsa_index_kl"]) > 0
    followed = ref.follow(seed, config, recipe, updates)
    np.testing.assert_allclose(losses[0], followed["loss"][0], atol=2e-5)
    np.testing.assert_allclose(losses[1], followed["loss"][1], atol=1e-4)
    without = ref.follow(seed, config, recipe, updates[:1],
                         faults=("index_loss_left_out",))
    assert followed["loss"][0] - without["loss"][0] > 1e-3  # the term is in
    assert [r.shape for r in followed["chosen"]] == [(64, 3)] * 2
    assert [s.shape for s in followed["selected"]] == [(2, 32, 4)] * 2
    start = ref.seeded_params(ref.key_from_seed(seed), c)
    mine = keye_map.from_program(state.params, c)
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
    path.write_text(json.dumps(dict(TINY, model_type="KeyeVL2")))
    config = load_model_config(str(path))
    assert isinstance(config, KeyeVLConfig)
    assert (config.router_experts, config.first_expert) == (8, 4)
    assert (config.topk, config.indexer_num_heads, config.indexer_head_dim) == (
        8, 4, 8)
    assert config.to_dict()["model_type"] == "KeyeVL2"
    whole = KeyeVLConfig()
    assert (whole.topk, whole.indexer_num_heads, whole.indexer_head_dim,
            whole.num_experts, whole.num_hidden_layers) == (2048, 16, 64, 128, 48)
    nested = KeyeVLConfig(text_config=dict(hidden_size=128, num_hidden_layers=3),
                          vision_config={"depth": 27})
    assert (nested.hidden_size, nested.num_hidden_layers) == (128, 3)
    for wrong in (dict(tie_word_embeddings=True), dict(use_sliding_window=True),
                  dict(mlp_only_layers=[0]), dict(attention_bias=True),
                  dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}),
                  dict(sa_config=dict(indexer_num_kv_heads=2)),
                  dict(ep_size=2, ep_rank=2), dict(num_key_value_heads=3)):
        with pytest.raises(ValueError):
            KeyeVLConfig(**wrong)


def _count(tree):
    return sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(tree))


def _shapes(config):
    model = build_pretraining_model(config, jnp.bfloat16)
    return jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))["params"]


def test_published_configuration_counts_610_million():
    """The benchmark's configuration file, built abstractly: the cut's
    arithmetic (ISSUE 45) against the tree's own count, part by part."""
    config = load_model_config(CONFIG_FILE)
    shapes = _shapes(config)
    layer = shapes["layers_0"]
    attention = layer["attention"]
    indexer = sum(_count(attention[n]) for n in ("index_q", "index_k", "index_w"))
    assert indexer == 2048 * (16 * 64 + 64 + 16) == 2_260_992
    assert _count(attention) - indexer == 2 * 2048 * 4096 + 2 * 2048 * 512 + 256
    assert _count(layer["mlp"]["router_kernel"]) == 2048 * 128
    assert _count(layer["mlp"]["experts_up"]) + _count(
        layer["mlp"]["experts_down"]) == 8 * 3 * 2048 * 768
    assert _count(layer) == 59_150_592
    assert attention["index_q"]["kernel"].shape == (2048, 1024)
    assert attention["index_k"]["kernel"].shape == (2048, 64)
    assert attention["index_w"]["kernel"].shape == (2048, 16)
    assert attention["k_proj"]["kernel"].shape == (2048, 512)
    assert "shared_up" not in layer["mlp"]
    assert shapes["embedding"].shape == (19072, 2048)
    assert shapes["lm_head"]["kernel"].shape == (2048, 19072)
    assert config.num_hidden_layers >= 7
    assert _count(shapes) == 610_476_288 == (
        9 * 59_150_592 + 2 * 19072 * 2048 + 2048)
    assert 16 * _count(shapes) == pytest.approx(9.77e9, rel=1e-3)
    with open(CONFIG_FILE) as f:
        written = json.load(f)
    for key, value in dict(
            hidden_size=2048, head_dim=128, num_attention_heads=32,
            num_key_value_heads=4, moe_intermediate_size=768,
            num_experts_per_tok=8, num_experts=8, vocab_size=19072,
            intermediate_size=6144, rope_theta=10000000).items():
        assert written[key] == value, key
    assert written["sa_config"] == dict(
        indexer_head_dim=64, indexer_num_heads=16, indexer_num_kv_heads=1,
        kv_chunk_size=512, q_chunk_size=512, topk=2048)
    assert (config.router_experts, config.ep_size, config.ep_rank) == (128, 16, 0)
    for key in ("source", "reduced", "published", "assumed", "precision",
                "deployment"):
        assert written[key], key
    assert written["reduced"] == ["num_hidden_layers", "num_experts",
                                  "vocab_size"]
    assert 19072 == 149 * 128 >= 151936 / 8


def test_the_whole_language_model_counts_30_6_billion():
    shapes = _shapes(KeyeVLConfig())
    assert _count(shapes) == pytest.approx(30.6e9, rel=3e-3)
    embeddings = 2 * 151936 * 2048
    experts = 48 * 128 * 3 * 2048 * 768
    active = _count(shapes) - embeddings - experts + 48 * 8 * 3 * 2048 * 768
    assert active == pytest.approx(2.9e9, rel=0.03)  # the published A3B


def test_flops_are_the_issues_arithmetic():
    config = load_model_config(CONFIG_FILE)
    parts = {k: v / 1e6 / 9 for k, v in
             flops.keye_vl_forward_flops_per_token(config, 16384).items()}
    assert parts["attention_proj"] == pytest.approx(37.7, abs=0.1)
    assert parts["indexer_proj"] == pytest.approx(4.5, abs=0.1)
    assert parts["indexer_scores"] == pytest.approx(16.8, abs=0.1)
    assert parts["sparse_core"] == pytest.approx(31.5, abs=0.1)
    assert parts["experts"] == pytest.approx(4.7 + 0.5, abs=0.1)
    assert 9 * parts["head"] == pytest.approx(78.1, abs=0.1)
    whole = flops.keye_vl_forward_flops_per_token(config, 16384)
    assert sum(whole.values()) == pytest.approx(945e6, rel=0.01)
    share = (whole["indexer_proj"] + whole["indexer_scores"]
             + whole["sparse_core"]) / sum(whole.values())
    assert share == pytest.approx(0.50, abs=0.02)
    # 32,768 tokens an update
    assert flops.causal_lm_train_flops_per_seq(config, 16384) * 2 == (
        pytest.approx(92.9e12, rel=0.01))


def test_no_decay_mask_leaves_out_the_norms_alone():
    model = _model(remat="none")
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    mask = optim.no_decay_mask(params)
    flat = {"/".join(k): v for k, v in
            flax.traverse_util.flatten_dict(mask).items()}
    assert {k.split("/")[-1] for k, v in flat.items() if not v} == {"scale"}
    assert flat["layers_0/attention/index_w/kernel"] and flat["embedding"]
    c = ref.sizes(TINY)
    for name, path in keye_map.table(c).items():
        assert flat[path] == ref.decays(name, c), name


# -- the family's scopes reach the compiled step ------------------------------------

def _compiled_step_names(widths, seq, backend="xla"):
    """Every ``op_name`` of the family's compiled train step (bfloat16,
    ``--remat full``, 2 micro-batches of one row of ``seq`` tokens)."""
    import re

    model = build_pretraining_model(KeyeVLConfig(**widths), jnp.bfloat16,
                                    remat="full", attention_backend=backend)
    tx = optim.adamw(1e-3, max_grad_norm=1.0,
                     weight_decay_mask=optim.no_decay_mask)
    state = pretrain.make_init_fn(
        model, tx, (jnp.zeros((1, 8), jnp.int32),), None)(jax.random.PRNGKey(0))
    step = pretrain.make_train_step(model, tx, next_sentence=False)
    batch = {"input_ids": np.zeros((2, 1, seq), np.int32)}
    text = step.lower(state, batch).compile().as_text()
    return set(re.findall(r'op_name="([^"]+)"', text))


@pytest.fixture(scope="module")
def step_names():
    return _compiled_step_names(TINY, 24)


@pytest.mark.parametrize("scope", pretrain.KEYE_SCOPES)
def test_every_scope_of_the_family_reaches_the_compiled_step(step_names, scope):
    assert any(f"/{scope}/" in name or f"({scope})" in name
               for name in step_names), scope


def test_the_sparse_attentions_parts_lie_under_dsa(step_names):
    for inner in ("dsa_index_proj", "dsa_scores", "dsa_select", "dsa_core",
                  "dsa_index_loss"):
        assert any("/dsa/" in name and f"/{inner}/" in name
                   for name in step_names), inner
    assert not any("/dsa/" in name and "attn_" in name for name in step_names)


def test_at_fitting_shapes_the_scopes_hold_the_kernels_and_the_choice_is_kept():
    """With heads of 128 over rows of 512 the compiled step's ``dsa_select``,
    ``dsa_core`` and ``dsa_index_loss`` scopes hold the kernels' calls; the
    choice, the objective's gradients and the core's output and log-sum-exps
    are made in the forward pass alone (kept across remat by name): the
    recompute holds no kernel of the three, and the core's two backward
    kernels still run."""
    fitting = dict(TINY, num_hidden_layers=1, head_dim=128,
                   num_attention_heads=2, num_key_value_heads=1,
                   sa_config=dict(TINY["sa_config"], topk=64))
    names = _compiled_step_names(fitting, 512, backend="pallas")
    under = lambda scope, kernel: {n for n in names
                                   if f"/dsa/{scope}/" in n and kernel in n}
    for scope, kernel in (("dsa_select", "dsa_select"),
                          ("dsa_core", "dsa_core_fwd"),
                          ("dsa_core", "dsa_core_bwd_dq"),
                          ("dsa_core", "dsa_core_bwd_dkv"),
                          ("dsa_index_loss", "dsa_index_loss")):
        assert under(scope, kernel), (scope, kernel)
    for scope, kernel in (("dsa_select", "dsa_select"),
                          ("dsa_core", "dsa_core_fwd"),
                          ("dsa_index_loss", "dsa_index_loss")):
        assert not any("rematted_computation" in n
                       for n in under(scope, kernel)), kernel


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
        json.dumps(dict(TINY, model_type="KeyeVL2")))
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
    assert abs(result["loss"] - np.log(256)) < 0.5
    assert result["moe_dropped_slots"] == 0.0 and result["moe_local_slots"] > 0
    # 2 layers x 16 rows of 32 at topk 8
    assert result["dsa_pairs_run"] == 2 * 16 * (36 + 24 * 8)
    assert result["dsa_index_kl"] > 0
    log = (tmp_path / "out" / "pretraining.txt").read_text()
    assert "dsa_pairs_run" in log and "dsa_keep_share" in log
