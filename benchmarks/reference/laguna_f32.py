"""Plain reference for next-token pretraining of the ``laguna`` family:
float32 ``jax.numpy`` at ``highest`` and nothing else.

The layer equations, from the published ``config.json`` of
poolside/Laguna-S-2.1; what the config leaves open is set by the convention
of the families whose key names it uses, each listed under ``assumed`` in
``benchmarks/configs/laguna-s-2.1.json`` and each living in ONE line here
(marked ``# assumed``):

* ``x <- x + Attn_l(norm(x))``; ``x <- x + Mlp_l(norm(x))``; final norm;
  untied head. Every norm is RMSNorm, eps 1e-6, a learned scale. No bias.
* ``Attn_l(h)``: ``n_l`` query heads (``num_attention_heads_per_layer``) on
  ``KV`` key-value heads of 128, query head j reading key-value head
  ``j // (n_l / KV)``. Rotary on the first ``r_l`` dimensions of every q and k
  head, pairs ``(i, i + r_l / 2)``: on full layers ``r_l`` = 64 with the YaRN
  table (each inverse frequency a blend of ``theta^(-2i/64)`` and that over
  ``factor``, by the linear ramp between the correction dimensions of
  ``beta_fast`` and ``beta_slow`` turns over the original positions, floored
  and ceiled; cos and sin times ``attention_factor``), on sliding layers
  ``r_l`` = 128 with ``theta^(-2i/128)``. Scores ``q k^T / sqrt(128)``;
  position i sees ``j <= i``, on sliding layers also ``i - j < window``;
  softmax; ``o_j = P_j v``; gate ``g = sigmoid(h Wg)``, one per head, ``o_j
  *= g_j``; output ``concat(o) Wo``. In blocks of query rows under an
  explicit mask.
* ``Mlp_l``: dense ``(silu(h W1) * (h W3)) W2``, or routed: ``p = softmax(h
  Wr)`` over ALL experts; the top-k choose; ``w = scale * p_chosen /
  sum(p_chosen)``; expert ``(silu(h G_e) * (h U_e)) D_e``; plus the shared
  expert of the same form on every token, ungated. Gate and up weights are
  kept side by side in one tensor, the gate's columns first.
* The reference is GIVEN THE SAME SHARE as the program: the experts
  ``[first, first + held)`` (a loop over them, each over all tokens under its
  mask), the heads and the slice of the vocabulary the configuration holds.
  What the absent experts and heads would add to the sums is left out.
* Loss: mean next-token cross entropy. AdamW as ``nemotron_h_f32``'s
  (decoupled decay on the matrices, global-norm clipping, bias correction,
  linear warm-up to a constant rate), written here for these tensors.

It imports nothing of the program. Weights come from the seed by
``seeded_params``; the program is handed the same arrays through
``laguna_map``.

``precision``: ``f32`` is the reference proper; ``fp8`` is the control, the
step below the bf16 the configuration states: every dense and expert product
with e4m3 operands (``bert_f32._dense``), the attention products in bf16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.bert_f32 import (PRECISIONS, _attn_einsum, _dense,
                                           key_from_seed)
from benchmarks.reference.nemotron_h_f32 import (Recipe, _rms_norm,
                                                 learning_rate)

__all__ = ["key_from_seed", "sizes", "seeded_params", "follow", "Recipe"]


def sizes(config: dict) -> dict:
    """The sizes the reference needs, from the configuration file."""
    layers = int(config["num_hidden_layers"])
    kinds, mlps = config["layer_types"], config["mlp_layer_types"]
    heads = [int(h) for h in config["num_attention_heads_per_layer"]]
    if not len(kinds) == len(mlps) == len(heads) == layers:
        raise ValueError("the per-layer lists and num_hidden_layers differ")
    hd = int(config["head_dim"])
    held = int(config["num_experts"])
    ep_size, ep_rank = int(config.get("ep_size", 1)), int(config.get("ep_rank", 0))
    ropes = [config["rope_parameters"][kind] for kind in kinds]
    return {
        "L": layers, "V": int(config["vocab_size"]),
        "H": int(config["hidden_size"]), "I": int(config["intermediate_size"]),
        "hd": hd, "KV": int(config["num_key_value_heads"]), "heads": heads,
        "windows": [int(config["sliding_window"])
                    if kind == "sliding_attention" else None for kind in kinds],
        "ropes": ropes,
        "rotary": [int(hd * r.get("partial_rotary_factor", 1)) for r in ropes],
        "mlps": list(mlps),
        "held": held, "experts": held * ep_size, "first": held * ep_rank,
        "top_k": int(config["num_experts_per_tok"]),
        "F": int(config["moe_intermediate_size"]),
        "FS": int(config["shared_expert_intermediate_size"]),
        "route_scale": float(config["moe_routed_scaling_factor"]),
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
            p + "wg": ((c["H"], c["heads"][i]), "normal"),
            p + "wo": ((wide, c["H"]), "out"),
            p + "mlp_norm": ((c["H"],), "ones")})
        if c["mlps"][i] == "dense":
            table.update({
                p + "w13": ((c["H"], 2 * c["I"]), "normal"),
                p + "w2": ((c["I"], c["H"]), "out")})
        else:
            table.update({
                p + "router": ((c["H"], c["experts"]), "normal"),
                p + "w_gu": ((c["held"], c["H"], 2 * c["F"]), "normal"),
                p + "w_down": ((c["held"], c["F"], c["H"]), "out"),
                p + "shared_gu": ((c["H"], 2 * c["FS"]), "normal"),
                p + "shared_down": ((c["FS"], c["H"]), "out")})
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


# ---------------------------------------------------------------- the parts

def inverse_frequencies(rotary: int, rope: dict):
    """([rotary / 2] float32, the factor on cos and sin) of one
    ``rope_parameters`` entry; float64 on the host, rounded once."""
    theta = float(rope["rope_theta"])
    i = np.arange(rotary // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / rotary)
    if rope.get("rope_type", "default") == "default":
        return plain.astype(np.float32), 1.0
    positions = float(rope["original_max_position_embeddings"])

    def dimension(turns):  # the pair that turns this often over the positions
        return rotary * math.log(positions / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dimension(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(dimension(float(rope["beta_slow"]))), rotary - 1)
    ramp = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    slowed = plain / float(rope["factor"])
    return ((1.0 - ramp) * plain + ramp * slowed).astype(np.float32), float(
        rope["attention_factor"])


def rotate(x, rotary: int, rope: dict):
    """x [B, S, heads, hd]: pairs (i, i + rotary / 2) of each head's first
    ``rotary`` dimensions turned by position x inv_freq[i]."""
    inv_freq, factor = inverse_frequencies(rotary, rope)
    angle = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
             * jnp.asarray(inv_freq)[None, :])[None, :, None, :]
    cos, sin = factor * jnp.cos(angle), factor * jnp.sin(angle)
    half = rotary // 2
    a, b, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def attention(p, prefix, c, layer, x, precision, block_rows: int = 512,
              window="config", rotary: bool = True, gate: bool = True):
    """Layer ``layer``'s attention over x [B, S, H] (already normalised)."""
    batch, seq, _ = x.shape
    heads, kv, hd = c["heads"][layer], c["KV"], c["hd"]
    if window == "config":
        window = c["windows"][layer]
    q = _dense(x, p[prefix + "wq"], precision).reshape(batch, seq, heads, hd)
    k = _dense(x, p[prefix + "wk"], precision).reshape(batch, seq, kv, hd)
    v = _dense(x, p[prefix + "wv"], precision).reshape(batch, seq, kv, hd)
    if rotary:
        q = rotate(q, c["rotary"][layer], c["ropes"][layer])
        k = rotate(k, c["rotary"][layer], c["ropes"][layer])
    k = jnp.repeat(k, heads // kv, axis=2)  # a key-value head serves
    v = jnp.repeat(v, heads // kv, axis=2)  # heads / kv consecutive query heads
    rows = min(block_rows, seq)
    pad = (-seq) % rows
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))

    @jax.checkpoint
    def block(start, q_block):
        scores = _attn_einsum("bqhd,bkhd->bhqk", q_block, k, precision
                              ) / math.sqrt(hd)
        i = start + jnp.arange(rows)[:, None]
        j = jnp.arange(seq)[None, :]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        # (finite: a padded row past a short window sees no key at all)
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return _attn_einsum("bhqk,bkhd->bqhd", probs, v, precision)

    starts = jnp.arange(0, seq + pad, rows)
    q_blocks = jnp.moveaxis(q.reshape(batch, -1, rows, heads, hd), 1, 0)
    ctx = jax.lax.map(lambda args: block(*args), (starts, q_blocks))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(batch, seq + pad, heads, hd)[:, :seq]
    if gate:
        ctx = ctx * jax.nn.sigmoid(  # assumed: head-wise sigmoid of the input
            _dense(x, p[prefix + "wg"], precision))[..., None]
    return _dense(ctx.reshape(batch, seq, heads * hd), p[prefix + "wo"],
                  precision)


def glu(x, w_gate_up, w_down, precision):
    """``(silu(x G) * (x U)) D``, G and U side by side in ``w_gate_up``."""
    g, u = jnp.split(_dense(x, w_gate_up, precision), 2, axis=-1)
    return _dense(jax.nn.silu(g) * u, w_down, precision)  # assumed: silu


def route(p, prefix, c, x):
    """x [T, H] -> (chosen [T, k], weights [T, k])."""
    logits = jnp.matmul(x, p[prefix + "router"], precision="highest")
    probs = jax.nn.softmax(logits, axis=-1)  # assumed: softmax scores
    w, chosen = jax.lax.top_k(probs, c["top_k"])
    if c["norm_topk"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w * c["route_scale"]


def expert_layer(p, prefix, c, x, precision, shared: bool = True):
    """(output, chosen). The experts this share holds, one after the other,
    each over all tokens under its mask; plus the shared expert."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    chosen, w = route(p, prefix, c, x)
    out = jnp.zeros_like(x)
    for e in range(c["held"]):
        mine = jnp.sum(jnp.where(chosen == c["first"] + e, w, 0.0), axis=-1)
        out = out + mine[:, None] * glu(
            x, p[prefix + "w_gu"][e], p[prefix + "w_down"][e], precision)
    if shared:  # assumed: no gate on the shared expert
        out = out + glu(x, p[prefix + "shared_gu"], p[prefix + "shared_down"],
                        precision)
    return out.reshape(shape), chosen


def forward(p: dict, c: dict, input_ids, precision: str = "f32"):
    """[B, S] ids -> (logits [B, S, V], [chosen experts of each routed layer])."""
    x = p["emb"][input_ids]
    routed = []
    for i in range(c["L"]):
        prefix = f"l{i}."

        def layer(p_, x_, i=i, prefix=prefix):
            h = _rms_norm(x_, p_[prefix + "attn_norm"], c["eps"])
            x_ = x_ + attention(p_, prefix, c, i, h, precision)
            h = _rms_norm(x_, p_[prefix + "mlp_norm"], c["eps"])
            if c["mlps"][i] == "dense":
                return x_ + glu(h, p_[prefix + "w13"], p_[prefix + "w2"],
                                precision), None
            out, chosen = expert_layer(p_, prefix, c, h, precision)
            return x_ + out, chosen

        x, chosen = jax.checkpoint(layer)(
            {k: v for k, v in p.items() if k.startswith(prefix)}, x)
        if chosen is not None:
            routed.append(chosen)
    x = _rms_norm(x, p["final_norm"], c["eps"])
    return _dense(x, p["head"], precision), routed


def next_token_loss(p, c, input_ids, precision: str = "f32"):
    """Mean cross entropy of position t against token t + 1 over the S - 1
    predicted positions of every row; and the routing of every routed layer."""
    logits, routed = forward(p, c, input_ids, precision)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, input_ids[:, 1:, None], axis=-1)
    return -jnp.mean(picked), routed


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
    expert."""
    return {name: jnp.sqrt(jnp.sum(jnp.square(v), axis=tuple(
        range(1 if _per_expert(name) else 0, v.ndim))))
        for name, v in tree.items()}


# ---------------------------------------------------------- following a run

def follow(seed: int, config: dict, recipe: Recipe, updates: list,
           precision: str = "f32", keep_first_gradient: bool = False,
           first_gradient_to_compare: dict = None) -> dict:
    """Follow the first optimizer updates of a run from the same seed:
    ``nemotron_h_f32.follow``'s contract over this family's tensors (each
    update's loss, the first update's gradient norm per tensor before clipping
    with the global norm, the per-tensor norm of the parameters' change over
    all the updates, ``chosen``: the experts the first micro-batch's tokens
    chose in each routed layer). Between gradient computations the two
    moments wait on the host, as there."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    c = sizes(config)
    key = key_from_seed(seed)
    make = jax.jit(lambda k: seeded_params(k, c))
    p = make(key)
    mu = nu = None  # zeros until the first update; on the host between updates
    grad = jax.jit(jax.value_and_grad(
        lambda p_, ids: next_token_loss(p_, c, ids, precision), has_aux=True))
    adamw = make_adamw_update(recipe, c)
    add = jax.jit(lambda a, b, s: jax.tree_util.tree_map(
        lambda x, y: x + s * y, a, b), donate_argnums=(0,))
    out = {"loss": [], "grad_global_norm": None, "grad_norms": None}
    for index, upd in enumerate(updates):
        micro = upd.shape[0]
        grads, loss = None, 0.0
        for m in range(micro):
            (share, routed), g = grad(p, jnp.asarray(upd[m], jnp.int32))
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
                    other = jnp.asarray(first_gradient_to_compare[name])
                    out["grad_diff_norms"].update(jax.device_get(
                        leaf_norms({name: other - mine})))
                    del other
            if keep_first_gradient:
                out["first_gradient"] = {
                    k: np.asarray(v) for k, v in grads.items()}
        zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, p)
        mu = zeros() if mu is None else jax.device_put(mu)
        nu = zeros() if nu is None else jax.device_put(nu)
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
