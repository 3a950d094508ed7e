"""Plain reference for BERT pretraining: float32 ``jax.numpy`` and nothing else.

Follows Devlin et al. 2018 (post-LN encoder, erf GELU, tied MLM decoder, NSP
head) and You et al. 2019 (LAMB), as the recipe of NVIDIA's BERT and the
gpauloski/BERT-PyTorch trainer set them: LayerNorm eps 1e-12, additive mask
of -10000, MLM loss as the mean over the masked positions plus the NSP mean,
LAMB with bias correction, global-norm clipping at 1.0, weight decay 0.01 on
everything but biases and LayerNorm parameters, poly(0.5) decay after a
linear warm-up whose rate at 0-based update t is taken at t + 1.

It imports nothing of the program and takes nothing the program has made.
Weights come from the seed by ``seeded_params``; the program is handed the
same arrays through ``program_map``. No kernels, no remat, no packing, no
dropout (the step it is compared with is built with both rates at 0).

``precision`` chooses how every matmul is computed and is the only knob:
``f32`` is the reference proper (float32 at ``highest``); ``fp8`` is the
control, the step below the bf16 the configuration states: dense layers with
e4m3 operands, scaled per row and per column to the format's range, the
attention products in bf16.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("f32", "fp8")
MASK_BIAS = -10000.0
LN_EPS = 1e-12

# name -> (shape in the keys of ``sizes``, init kind). Per-layer tensors are
# stacked on a leading axis of length L and scanned.
_GLOBAL = {
    "word_emb": (("V", "H"), "normal"),
    "pos_emb": (("P", "H"), "normal"),
    "type_emb": (("T", "H"), "normal"),
    "emb_ln_g": (("H",), "ones"),
    "emb_ln_b": (("H",), "zeros"),
    "pool_w": (("H", "H"), "normal"),
    "pool_b": (("H",), "zeros"),
    "mlm_w": (("H", "H"), "normal"),
    "mlm_b": (("H",), "zeros"),
    "mlm_ln_g": (("H",), "ones"),
    "mlm_ln_b": (("H",), "zeros"),
    "mlm_bias": (("V",), "zeros"),
    "nsp_w": (("H", 2), "normal"),
    "nsp_b": ((2,), "zeros"),
}
_LAYER = {
    "wq": (("H", "H"), "normal"),
    "bq": (("H",), "zeros"),
    "wk": (("H", "H"), "normal"),
    "bk": (("H",), "zeros"),
    "wv": (("H", "H"), "normal"),
    "bv": (("H",), "zeros"),
    "wo": (("H", "H"), "normal"),
    "bo": (("H",), "zeros"),
    "ln1_g": (("H",), "ones"),
    "ln1_b": (("H",), "zeros"),
    "wi": (("H", "I"), "normal"),
    "bi": (("I",), "zeros"),
    "wf": (("I", "H"), "normal"),
    "bf": (("H",), "zeros"),
    "ln2_g": (("H",), "ones"),
    "ln2_b": (("H",), "zeros"),
}


def sizes(config: dict) -> dict:
    """The sizes the reference needs, from a published config. The vocabulary
    is padded up to a multiple of 8, as the trainer pads it (the padding rows
    are ordinary seeded rows that no label ever names)."""
    vocab = int(config["vocab_size"])
    return {
        "V": vocab + (-vocab) % 8,
        "H": int(config["hidden_size"]),
        "L": int(config["num_hidden_layers"]),
        "A": int(config["num_attention_heads"]),
        "I": int(config["intermediate_size"]),
        "P": int(config["max_position_embeddings"]),
        "T": int(config["type_vocab_size"]),
        "std": float(config["initializer_range"]),
    }


def param_names() -> list:
    return sorted(_GLOBAL) + sorted("layer." + k for k in _LAYER)


def key_from_seed(seed: int):
    """A threefry key from any non-negative whole number (seeds pass 2**31).
    The implementation is named, because the program changes JAX's default."""
    words = np.random.SeedSequence(int(seed)).generate_state(2).astype(np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def seeded_params(key, c: dict) -> dict:
    """Every weight from the seed, on the device, in one traced function:
    N(0, std) matrices and embeddings, zero biases, unit LayerNorm scales."""
    out = {}
    for index, name in enumerate(param_names()):
        stacked = name.startswith("layer.")
        dims, kind = (_LAYER[name[6:]] if stacked else _GLOBAL[name])
        shape = ((c["L"],) if stacked else ()) + tuple(
            c[d] if isinstance(d, str) else d for d in dims)
        if kind == "normal":
            out[name] = c["std"] * jax.random.normal(
                jax.random.fold_in(key, index), shape, jnp.float32)
        else:
            out[name] = (jnp.ones if kind == "ones" else jnp.zeros)(
                shape, jnp.float32)
    return out


# ---------------------------------------------------------------- matmuls

def _rounded(a, axis):
    """``a`` rounded to fp8 (e4m3) values, with one scale along ``axis`` that
    maps its largest magnitude to the format's (448); the gradient passes
    straight through."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return a + jax.lax.stop_gradient(q * scale - a)


def _dense(x, w, precision):
    """x [..., K] @ w [K, N] the way ``precision`` says."""
    if precision == "fp8":
        x, w = _rounded(x, -1), _rounded(w, 0)
    return jnp.matmul(x, w, precision="highest",
                      preferred_element_type=jnp.float32)


def _attn_einsum(spec, a, b, precision):
    if precision != "f32":
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    return jnp.einsum(spec, a, b, precision="highest",
                      preferred_element_type=jnp.float32)


def _layer_norm(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _gelu(x):
    return x * 0.5 * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


# ---------------------------------------------------------------- forward

def encode(p: dict, c: dict, input_ids, segment_ids, input_mask,
           precision: str = "f32"):
    """[B, S] ids -> ([B, S, H] sequence output, [B, H] pooled)."""
    batch, seq = input_ids.shape
    heads, hd = c["A"], c["H"] // c["A"]
    x = (p["word_emb"][input_ids] + p["pos_emb"][jnp.arange(seq)][None]
         + p["type_emb"][segment_ids])
    x = _layer_norm(x, p["emb_ln_g"], p["emb_ln_b"])
    bias = ((1.0 - input_mask.astype(jnp.float32)) * MASK_BIAS)[:, None, None, :]

    def layer(x, lp):
        def split(t):
            return t.reshape(batch, seq, heads, hd)
        q = split(_dense(x, lp["wq"], precision) + lp["bq"])
        k = split(_dense(x, lp["wk"], precision) + lp["bk"])
        v = split(_dense(x, lp["wv"], precision) + lp["bv"])
        scores = _attn_einsum("bqhd,bkhd->bhqk", q, k, precision)
        probs = jax.nn.softmax(scores / math.sqrt(hd) + bias, axis=-1)
        ctx = _attn_einsum("bhqk,bkhd->bqhd", probs, v, precision)
        attn = _dense(ctx.reshape(batch, seq, c["H"]), lp["wo"], precision)
        x1 = _layer_norm(attn + lp["bo"] + x, lp["ln1_g"], lp["ln1_b"])
        mid = _gelu(_dense(x1, lp["wi"], precision) + lp["bi"])
        out = _dense(mid, lp["wf"], precision) + lp["bf"]
        return _layer_norm(out + x1, lp["ln2_g"], lp["ln2_b"]), None

    stacked = {k[6:]: v for k, v in p.items() if k.startswith("layer.")}
    x, _ = jax.lax.scan(layer, x, stacked)
    pooled = jnp.tanh(_dense(x[:, 0], p["pool_w"], precision) + p["pool_b"])
    return x, pooled


def pretraining_loss_sums(p, c, rows: dict, precision: str = "f32"):
    """(sum of MLM cross-entropies over masked positions, sum of NSP
    cross-entropies) for a block of rows. ``rows`` holds input_ids,
    segment_ids, input_mask, masked_lm_labels (-1 = not masked), all [B, S],
    and next_sentence_labels [B]. Sums, so that blocks add up."""
    seq_out, pooled = encode(p, c, rows["input_ids"], rows["segment_ids"],
                             rows["input_mask"], precision)
    labels = rows["masked_lm_labels"]
    t = _gelu(_dense(seq_out, p["mlm_w"], precision) + p["mlm_b"])
    t = _layer_norm(t, p["mlm_ln_g"], p["mlm_ln_b"])
    masked = labels >= 0
    # Only masked positions reach the decoder: gather them (at most
    # ``max_masked`` per row keeps the logits small enough to hold).
    order = jnp.argsort(~masked, axis=-1, stable=True)[:, :rows["max_masked"]]
    t_m = jnp.take_along_axis(t, order[..., None], axis=1)
    lab_m = jnp.take_along_axis(labels, order, axis=1)
    logits = _dense(t_m, p["word_emb"].T, precision) + p["mlm_bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(lab_m, 0)[..., None], axis=-1)[..., 0]
    mlm_sum = -jnp.sum(jnp.where(lab_m >= 0, picked, 0.0))
    nsp_logp = jax.nn.log_softmax(
        _dense(pooled, p["nsp_w"], precision) + p["nsp_b"], axis=-1)
    nsp_sum = -jnp.sum(jnp.take_along_axis(
        nsp_logp, rows["next_sentence_labels"][:, None], axis=-1))
    return mlm_sum, nsp_sum


def make_block_grad(c: dict, precision: str, max_masked: int):
    """Jitted (params, block, 1/mlm_count, 1/rows) -> (loss share, grads) for
    one block of a micro-batch; the shares of a micro-batch's blocks add up to
    its loss (MLM mean + NSP mean) and its gradient."""

    def loss_fn(p, block, inv_mlm, inv_rows):
        mlm_sum, nsp_sum = pretraining_loss_sums(
            p, c, dict(block, max_masked=max_masked), precision)
        return mlm_sum * inv_mlm + nsp_sum * inv_rows

    return jax.jit(jax.value_and_grad(loss_fn))


# ------------------------------------------------------------------- LAMB

class Recipe(NamedTuple):
    learning_rate: float
    warmup_proportion: float
    max_steps: int
    weight_decay: float = 0.01
    max_grad_norm: float = 1.0
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-6
    degree: float = 0.5
    # DEPARTURE from You et al., who take one trust ratio per layer: the
    # program stacks the L layers of each kind of tensor into one array (its
    # encoder is an ``nn.scan``) and its LAMB takes ONE ratio for the stack.
    # True follows the program, so that the comparison is about the step's
    # arithmetic; False is LAMB as published (PERF.md, Findings: measured
    # difference up to half of a layer's change).
    stacked_trust_ratio: bool = True


def learning_rate(recipe: Recipe, update: int) -> float:
    progress = (update + 1) / recipe.max_steps
    if progress < recipe.warmup_proportion:
        return recipe.learning_rate * progress / recipe.warmup_proportion
    return recipe.learning_rate * max(1.0 - progress, 0.0) ** recipe.degree


def _decays(name: str) -> bool:
    """Weight decay on matrices and embeddings (the tensors drawn from the
    normal), none on biases and LayerNorm parameters."""
    table, key = (_LAYER, name[6:]) if name.startswith("layer.") else (_GLOBAL, name)
    return table[key][1] == "normal"


def make_lamb_update(recipe: Recipe):
    """Jitted (params, mu, nu, grads, lr, count) -> (params, mu, nu, clipped
    grads' scale). ``count`` is the 1-based number of this update."""

    def update(p, mu, nu, g, lr, count):
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(v)) for v in g.values()))
        gscale = jnp.minimum(1.0, recipe.max_grad_norm / (gnorm + 1e-6))
        c1 = 1.0 - recipe.b1 ** count
        c2 = 1.0 - recipe.b2 ** count
        new_p, new_mu, new_nu = {}, {}, {}
        for name in p:
            grad = g[name] * gscale
            m = recipe.b1 * mu[name] + (1.0 - recipe.b1) * grad
            v = recipe.b2 * nu[name] + (1.0 - recipe.b2) * jnp.square(grad)
            upd = (m / c1) / (jnp.sqrt(v / c2) + recipe.eps)
            if _decays(name):
                upd = upd + recipe.weight_decay * p[name]
            per_layer = name.startswith("layer.") and not recipe.stacked_trust_ratio
            axes = tuple(range(1 if per_layer else 0, upd.ndim))
            p_norm = jnp.sqrt(jnp.sum(jnp.square(p[name]), axis=axes, keepdims=True))
            u_norm = jnp.sqrt(jnp.sum(jnp.square(upd), axis=axes, keepdims=True))
            ratio = jnp.where((p_norm > 0) & (u_norm > 0), p_norm / u_norm, 1.0)
            new_p[name] = p[name] - lr * ratio * upd
            new_mu[name], new_nu[name] = m, v
        return new_p, new_mu, new_nu, gnorm, gscale

    return jax.jit(update, donate_argnums=(0, 1, 2))


@jax.jit
def leaf_norms(tree: dict) -> dict:
    """L2 norm of every tensor; of a stacked tensor, one per layer."""
    out = {}
    for name, v in tree.items():
        axes = tuple(range(1 if name.startswith("layer.") else 0, v.ndim))
        out[name] = jnp.sqrt(jnp.sum(jnp.square(v), axis=axes))
    return out


@jax.jit
def delta_norms(new: dict, old: dict) -> dict:
    return leaf_norms({k: new[k] - old[k] for k in new})


# ---------------------------------------------------------- following a run

def follow(seed: int, config: dict, recipe: Recipe, updates: list,
           precision: str = "f32", block_rows: int = 16, devices=None,
           keep_first_gradient: bool = False,
           first_gradient_to_compare: dict = None) -> dict:
    """Follow the first optimizer updates of a run from the same seed.

    ``updates`` is a list, one per update, of dicts of host arrays shaped
    [micro_batches, rows, ...] (what the trainer's step was fed). Returns the
    readings the comparison needs: each update's loss, the first update's
    gradient norm per tensor as the optimizer got it (after clipping) with the
    global norm before clipping, and the per-tensor norm of the parameters'
    change over all the updates.

    ``first_gradient_to_compare`` (host arrays under this file's names: the
    first gradient some other run handed its optimizer) adds
    ``grad_diff_norms``, the per-tensor norm of its difference from this
    run's; ``keep_first_gradient`` adds this run's as host arrays.

    With several ``devices`` the weights are replicated and each block holds
    ``block_rows`` rows for every device (the same sums, sooner).
    """
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    c = sizes(config)
    key = key_from_seed(seed)
    place = lambda block: block
    make = jax.jit(lambda k: seeded_params(k, c))
    if devices is not None and len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        mesh = Mesh(np.asarray(devices), ("rows",))
        make = jax.jit(lambda k: seeded_params(k, c),
                       out_shardings=NamedSharding(mesh, PartitionSpec()))
        by_rows = NamedSharding(mesh, PartitionSpec("rows"))
        place = lambda block: jax.device_put(block, by_rows)
        block_rows *= len(devices)
    p = make(key)
    mu = jax.tree_util.tree_map(jnp.zeros_like, p)
    nu = jax.tree_util.tree_map(jnp.zeros_like, p)
    max_masked = int(max(
        (u["masked_lm_labels"] >= 0).sum(axis=-1).max() for u in updates))
    block_grad = make_block_grad(c, precision, max_masked)
    lamb = make_lamb_update(recipe)
    add = jax.jit(lambda a, b, s: jax.tree_util.tree_map(
        lambda x, y: x + s * y, a, b), donate_argnums=(0,))
    out = {"loss": [], "grad_global_norm": None, "grad_norms": None}
    for index, upd in enumerate(updates):
        micro = upd["input_ids"].shape[0]
        grads = jax.tree_util.tree_map(jnp.zeros_like, p)
        loss = 0.0
        for m in range(micro):
            mb = {k: np.asarray(v[m], np.int32) for k, v in upd.items()}
            rows = mb["input_ids"].shape[0]
            inv_mlm = 1.0 / max(int((mb["masked_lm_labels"] >= 0).sum()), 1)
            for start in range(0, rows, block_rows):
                block = place({k: v[start:start + block_rows]
                               for k, v in mb.items()})
                share, g = block_grad(p, block, inv_mlm, 1.0 / rows)
                grads = add(grads, g, 1.0 / micro)
                loss += float(share) / micro
        out["loss"].append(loss)
        p, mu, nu, gnorm, gscale = lamb(
            p, mu, nu, grads, learning_rate(recipe, index), float(index + 1))
        if index == 0:
            out["grad_global_norm"] = float(gnorm)
            # what the optimizer got: the averaged gradient, before its clipping
            out["grad_norms"] = jax.device_get(leaf_norms(grads))
            if first_gradient_to_compare is not None:
                out["grad_diff_norms"] = {}
                for name, mine in grads.items():  # one tensor at a time
                    other = jnp.asarray(first_gradient_to_compare[name])
                    out["grad_diff_norms"].update(jax.device_get(
                        leaf_norms({name: other - mine})))
                    del other
            if keep_first_gradient:  # host copies
                out["first_gradient"] = {
                    k: np.asarray(v) for k, v in grads.items()}
        del grads
    out["delta_norms"] = jax.device_get(delta_norms(p, make(key)))
    return out
