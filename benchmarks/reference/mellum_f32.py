"""Plain reference for next-token pretraining of the ``mellum`` family:
float32 ``jax.numpy`` at ``highest`` and nothing else. It knows nothing of
chips: all 64 experts of a layer by a plain loop, the whole vocabulary, no
``shard_map``, no exchange, no collective.

The layer equations, from the published ``config.json`` of
JetBrains/Mellum2-12B-A2.5B-Instruct; what the config leaves open is listed
under ``assumed`` in ``benchmarks/configs/mellum2-12b-a2.5b.json`` and lives in
ONE line here or in ``laguna_f32`` (marked ``# assumed``):

* ``x <- x + Attn_l(norm(x))``; ``x <- x + Moe_l(norm(x))``; final norm;
  untied head. Every norm is RMSNorm, eps 1e-6, a learned scale. No bias.
* ``Attn_l(h)``: ``laguna_f32.attention`` WITHOUT its gate: 32 query heads on
  4 key-value heads of 128 (query head j reads key-value head ``j // 8``),
  rotary over the whole head in pairs ``(i, i + 64)`` (the default table on
  sliding layers, YaRN on full ones: ``laguna_f32.inverse_frequencies``),
  scores ``q k^T / sqrt(128)``, position i sees ``j <= i`` and on sliding
  layers ``i - j < 1024``, softmax, in blocks of query rows under an explicit
  mask. No norm on queries and keys, no gate on the output.  # assumed
* ``Moe_l(h)``: ``p = softmax(h Wr)`` over all 64 experts in float32, the 8
  largest choose, ``w = p_chosen / sum(p_chosen)``, no scaling factor, no
  correction bias, no shared expert; expert ``(silu(h G_e) * (h U_e)) D_e``
  (``laguna_f32.glu``), each over all tokens under its mask, one after the
  other (a scan over the stacked tensors: 256 unrolled experts took the TPU's
  compiler seven minutes), each rematerialized (64 experts' activations of
  32,768 tokens would not fit otherwise).
* Loss: mean next-token cross entropy over the whole vocabulary, the head and
  the log-softmax in blocks of positions (the logits of a micro-batch are
  12.9 GB in float32). AdamW as ``laguna_f32``'s, over this table.

**Where its arrays lie.** The parameters and their gradients are 17 GB in
float32, more than a chip: ``follow`` PLACES them over the devices it is given
(``placement``: the experts' stacked tensors by expert, embedding and head by
row of the vocabulary, a micro-batch's rows one a device; everything else
whole on each) and the compiler partitions the same ``jax.numpy`` program
over them. Nothing here names an axis inside the computation; on one device
(the tests) it runs unplaced. It runs when the program's state is not live.

It imports nothing of the program. Weights come from the seed by
``seeded_params``; the program is handed the same arrays through
``mellum_map``.

``precision``: ``f32`` is the reference proper; ``fp8`` is the control, the
step below the bf16 the configuration states: every dense and expert product
with e4m3 operands (``bert_f32._dense``), the attention products in bf16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from benchmarks.reference.bert_f32 import PRECISIONS, _dense, key_from_seed
from benchmarks.reference.laguna_f32 import attention, glu, route
from benchmarks.reference.nemotron_h_f32 import (Recipe, _rms_norm,
                                                 learning_rate)

__all__ = ["key_from_seed", "sizes", "seeded_params", "follow", "Recipe",
           "PRECISIONS"]

HEAD_BLOCK = 1024  # positions of a row the head and its loss take at a time


def sizes(config: dict) -> dict:
    """The sizes the reference needs, from the configuration file (the keys
    ``laguna_f32``'s parts read among them)."""
    layers = int(config["num_hidden_layers"])
    kinds = config["layer_types"]
    if len(kinds) != layers or set(config["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("layer_types and num_hidden_layers differ, or an MLP "
                         "is not sparse")
    hd = int(config["head_dim"])
    held = int(config["num_experts"])
    ep_size, ep_rank = int(config.get("ep_size", 1)), int(config.get("ep_rank", 0))
    ropes = [config["rope_parameters"][kind] for kind in kinds]
    return {
        "L": layers, "V": int(config["vocab_size"]),
        "H": int(config["hidden_size"]), "hd": hd,
        "KV": int(config["num_key_value_heads"]),
        "heads": [int(config["num_attention_heads"])] * layers,
        "windows": [int(config["sliding_window"])
                    if kind == "sliding_attention" else None for kind in kinds],
        "ropes": ropes, "rotary": [hd] * layers,
        "held": held, "experts": held * ep_size, "first": held * ep_rank,
        "top_k": int(config["num_experts_per_tok"]),
        "F": int(config["moe_intermediate_size"]),
        "route_scale": 1.0,  # assumed: the config has no scaling factor
        "norm_topk": bool(config.get("norm_topk_prob", True)),
        "eps": float(config["rms_norm_eps"]),
        "std": float(config.get("initializer_range", 0.02)),
    }


def param_table(c: dict) -> dict:
    """name -> (shape, init kind). ``out``: normal, smaller by sqrt(2 L): the
    projections that write into the residual stream, two a layer."""
    table = {"emb": ((c["V"], c["H"]), "normal"),
             "final_norm": ((c["H"],), "ones"),
             "head": ((c["H"], c["V"]), "normal")}
    for i in range(c["L"]):
        p, wide = f"l{i}.", c["heads"][i] * c["hd"]
        table.update({
            p + "attn_norm": ((c["H"],), "ones"),
            p + "wq": ((c["H"], wide), "normal"),
            p + "wk": ((c["H"], c["KV"] * c["hd"]), "normal"),
            p + "wv": ((c["H"], c["KV"] * c["hd"]), "normal"),
            p + "wo": ((wide, c["H"]), "out"),
            p + "mlp_norm": ((c["H"],), "ones"),
            p + "router": ((c["H"], c["experts"]), "normal"),
            p + "w_gu": ((c["held"], c["H"], 2 * c["F"]), "normal"),
            p + "w_down": ((c["held"], c["F"], c["H"]), "out")})
    return table


def seeded_params(key, c: dict) -> dict:
    """Every weight from the seed, on the device, in one traced function."""
    out = {}
    for index, (name, (shape, kind)) in enumerate(sorted(param_table(c).items())):
        if kind == "ones":
            out[name] = jnp.ones(shape, jnp.float32)
            continue
        std = c["std"] / (1.0 if kind == "normal" else math.sqrt(2 * c["L"]))
        out[name] = std * jax.random.normal(
            jax.random.fold_in(key, index), shape, jnp.float32)
    return out


# ------------------------------------------------------- where arrays lie

def _over(devices):
    return Mesh(np.asarray(devices), ("devices",))


def placement(name: str, shape: tuple, devices):
    """Where tensor ``name`` lies over ``devices``: the experts' stacked
    tensors by expert, embedding and head by row of the vocabulary, the rest
    whole on each (None on one device: unplaced)."""
    if len(devices) < 2:
        return None
    axis = (0 if name == "emb" or _per_expert(name) else
            1 if name == "head" else None)
    if axis is None or shape[axis] % len(devices):
        return NamedSharding(_over(devices), PartitionSpec())
    return NamedSharding(_over(devices), PartitionSpec(
        *([None] * axis + ["devices"])))


def rows_placement(devices):
    """A micro-batch's rows, one share a device."""
    return (NamedSharding(_over(devices), PartitionSpec("devices"))
            if len(devices) > 1 else None)


# ---------------------------------------------------------------- the parts

def expert_layer(p, prefix, c, x, precision):
    """(output, chosen): the held experts, one after the other (a scan over
    the stacked tensors), each over all tokens under its mask and each
    rematerialized."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    chosen, w = route(p, prefix, c, x)

    @jax.checkpoint
    def add_expert(out, expert):
        index, gu, down = expert
        mine = jnp.sum(jnp.where(chosen == c["first"] + index, w, 0.0), axis=-1)
        return out + mine[:, None] * glu(x, gu, down, precision), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (
        jnp.arange(c["held"]), p[prefix + "w_gu"], p[prefix + "w_down"]))
    return out.reshape(shape), chosen


def hidden(p: dict, c: dict, input_ids, precision: str = "f32"):
    """[B, S] ids -> (the final norm's output [B, S, H], [chosen experts of
    each layer])."""
    x = p["emb"][input_ids]
    routed = []
    for i in range(c["L"]):
        prefix = f"l{i}."

        def layer(p_, x_, i=i, prefix=prefix):
            h = _rms_norm(x_, p_[prefix + "attn_norm"], c["eps"])
            x_ = x_ + attention(p_, prefix, c, i, h, precision, gate=False)
            h = _rms_norm(x_, p_[prefix + "mlp_norm"], c["eps"])
            out, chosen = expert_layer(p_, prefix, c, h, precision)
            return x_ + out, chosen

        x, chosen = jax.checkpoint(layer)(
            {k: v for k, v in p.items() if k.startswith(prefix)}, x)
        routed.append(chosen)
    return _rms_norm(x, p["final_norm"], c["eps"]), routed


def forward(p: dict, c: dict, input_ids, precision: str = "f32"):
    """[B, S] ids -> (logits [B, S, V], the routing): whole, for small
    sizes."""
    x, routed = hidden(p, c, input_ids, precision)
    return _dense(x, p["head"], precision), routed


def next_token_loss(p, c, input_ids, precision: str = "f32"):
    """Mean cross entropy of position t against token t + 1 over the S - 1
    predicted positions of every row, and the routing of every layer; the
    head and the log-softmax over ``HEAD_BLOCK`` positions at a time."""
    x, routed = hidden(p, c, input_ids, precision)
    batch, seq, width = x.shape
    rows = min(HEAD_BLOCK, seq)
    pad = (-seq) % rows
    labels = jnp.pad(jnp.roll(input_ids, -1, axis=1), ((0, 0), (0, pad)))
    counted = jnp.pad(jnp.broadcast_to(jnp.arange(seq) < seq - 1, (batch, seq)),
                      ((0, 0), (0, pad)))
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))

    @jax.checkpoint
    def block(head, x_block, lab, keep):
        logp = jax.nn.log_softmax(_dense(x_block, head, precision), axis=-1)
        picked = jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
        return -jnp.sum(jnp.where(keep, picked, 0.0))

    split = lambda t: jnp.moveaxis(
        t.reshape((batch, -1, rows) + t.shape[2:]), 1, 0)
    total = jnp.sum(jax.lax.map(
        lambda args: block(p["head"], *args),
        (split(x), split(labels), split(counted))))
    return total / (batch * (seq - 1)), routed


# ------------------------------------------------------------------ AdamW

def decays(name: str, c: dict) -> bool:
    """Weight decay on the matrices (drawn from the normal); none on norms."""
    return param_table(c)[name][1] != "ones"


def make_adamw_update(recipe: Recipe, c: dict):
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
            if decays(name, c):
                upd = upd + recipe.weight_decay * p[name]
            new_p[name] = p[name] - lr * upd
            new_mu[name], new_nu[name] = m, v
        return new_p, new_mu, new_nu, gnorm

    return jax.jit(update, donate_argnums=(0, 1, 2))


def _per_expert(name: str) -> bool:
    return name.endswith((".w_gu", ".w_down"))


@jax.jit
def leaf_norms(tree: dict) -> dict:
    """L2 norm of every tensor; of the experts' stacked tensors, one per
    expert (every one of the layer's 64)."""
    return {name: jnp.sqrt(jnp.sum(jnp.square(v), axis=tuple(
        range(1 if _per_expert(name) else 0, v.ndim))))
        for name, v in tree.items()}


# ---------------------------------------------------------- following a run

def follow(seed: int, config: dict, recipe: Recipe, updates: list,
           precision: str = "f32", keep_first_gradient: bool = False,
           first_gradient_to_compare: dict = None, devices=None) -> dict:
    """Follow the first optimizer updates of a run from the same seed:
    ``laguna_f32.follow``'s contract over this family's tensors (each
    update's loss, the first update's gradient norm per tensor before clipping
    with the global norm, the per-tensor norm of the parameters' change over
    all the updates, ``chosen``: the experts the first micro-batch's tokens
    chose in each layer), with the arrays placed over ``devices`` (every
    local device by default). Between gradient computations the two moments
    wait on the host, as there."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    c = sizes(config)
    devices = list(jax.local_devices() if devices is None else devices)
    where = {name: placement(name, shape, devices)
             for name, (shape, _) in param_table(c).items()}
    key = key_from_seed(seed)
    make = jax.jit(lambda k: seeded_params(k, c),
                   out_shardings=where if len(devices) > 1 else None)
    p = make(key)
    mu = nu = None  # zeros until the first update; on the host between updates
    # (the gradients lie where the parameters do)
    grad = jax.jit(jax.value_and_grad(
        lambda p_, ids: next_token_loss(p_, c, ids, precision), has_aux=True),
        out_shardings=((None, None), where) if len(devices) > 1 else None)
    adamw = make_adamw_update(recipe, c)
    add = jax.jit(lambda a, b, s: jax.tree_util.tree_map(
        lambda x, y: x + s * y, a, b), donate_argnums=(0,))
    put = lambda tree: {name: jax.device_put(v, where[name])
                        for name, v in tree.items()}
    out = {"loss": [], "grad_global_norm": None, "grad_norms": None}
    for index, upd in enumerate(updates):
        micro = upd.shape[0]
        grads, loss = None, 0.0
        for m in range(micro):
            rows = np.asarray(upd[m], np.int32)
            ids = jax.device_put(rows, rows_placement(
                devices if rows.shape[0] % len(devices) == 0 else devices[:1]))
            (share, routed), g = grad(p, ids)
            if index == 0 and m == 0:
                out["chosen"] = [np.asarray(r) for r in routed]
            grads = (jax.tree_util.tree_map(lambda x: x / micro, g)
                     if grads is None else add(grads, g, 1.0 / micro))
            del g
            loss += float(share) / micro
        out["loss"].append(loss)
        if index == 0:
            out["grad_norms"] = jax.device_get(leaf_norms(grads))
            if first_gradient_to_compare is not None:
                out["grad_diff_norms"] = {}
                for name, mine in grads.items():  # one tensor at a time
                    other = jax.device_put(
                        np.asarray(first_gradient_to_compare[name]),
                        where[name])
                    out["grad_diff_norms"].update(jax.device_get(
                        leaf_norms({name: other - mine})))
                    del other
            if keep_first_gradient:
                out["first_gradient"] = {
                    k: np.asarray(v) for k, v in grads.items()}
        zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, p)
        mu = zeros() if mu is None else put(mu)
        nu = zeros() if nu is None else put(nu)
        p, mu, nu, gnorm = adamw(p, mu, nu, grads,
                                 learning_rate(recipe, index), float(index + 1))
        del grads
        if index == 0:
            out["grad_global_norm"] = float(gnorm)
        if index + 1 < len(updates):
            mu, nu = jax.device_get(mu), jax.device_get(nu)
    del mu, nu
    start = make(key)
    out["delta_norms"] = jax.device_get(leaf_norms(
        {k: p[k] - start[k] for k in p}))
    return out
