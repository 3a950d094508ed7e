"""Plain reference for next-token pretraining of the ``nemotron_h`` family:
float32 ``jax.numpy`` at ``highest`` and nothing else.

The layer equations, from the published ``config.json`` of
NVIDIA-Nemotron-3-Nano-30B-A3B and the descriptions of ``nemotron_h``,
Mamba-2 (Dao & Gu 2024) and the DeepSeek-V3 router; each departure is listed
under ``assumed`` in ``benchmarks/configs/nemotron-3-nano-30b-a3b.json``:

* ``x <- x + part_l(RMSNorm_l(x))``, l over ``hybrid_override_pattern``;
  final RMSNorm; untied head; eps 1e-5; no bias but the convolution's.
* ``M``: ``in_proj`` -> [z | xBC | dt]; causal depthwise conv (kernel 4, bias)
  and SiLU on xBC; x as heads, B and C as groups of states;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t``;
  ``y <- RMSNorm over groups of (y * silu(z))``, times its weight; ``out_proj``.
  The recurrence is the LITERAL per-token recurrence: a scan over blocks of
  tokens of a scan over tokens, each block checkpointed so that the backward
  pass keeps one state per block and not one per token. No chunked algebra.
* ``*``: query heads on fewer key-value heads, causal softmax at scale
  head_dim^-1/2, no positional embedding; computed in blocks of query rows.
* ``E``: router logits over ALL experts; ``s = sigmoid(logits)``; the top-k of
  ``s + correction_bias`` choose; weights ``s`` of the chosen over their sum,
  times ``routed_scaling_factor``; expert ``W_down relu(W_up x)^2``; plus the
  shared expert on every token. The reference is GIVEN THE SAME SHARE as the
  program: it holds the experts ``[first, first + held)`` and adds only
  their terms, as a loop over those experts, each over all tokens under its
  mask. ``held_experts = None`` is the uncut layer (every expert).
* Loss: next-token cross entropy, mean over the predicted positions.
* AdamW (b1, b2, eps, decoupled weight decay on the matrices only, global-norm
  clipping, bias correction), linear warm-up to a constant rate.

It imports nothing of the program and takes nothing the program has made but
the share (which experts are held, which slice of the vocabulary). Weights
come from the seed by ``seeded_params``; the program is handed the same
arrays through ``nemotron_h_map``.

``precision``: ``f32`` is the reference proper; ``fp8`` is the control, the
step below the bf16 the configuration states: dense and expert products with
e4m3 operands (``bert_f32._dense``), the attention products in bf16.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.bert_f32 import (PRECISIONS, _attn_einsum, _dense,
                                           key_from_seed)

__all__ = ["key_from_seed", "sizes", "seeded_params", "follow"]


def sizes(config: dict) -> dict:
    """The sizes the reference needs, from the configuration file."""
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != int(config["num_hidden_layers"]):
        raise ValueError("hybrid_override_pattern and num_hidden_layers differ")
    heads, hdim = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    groups, state = int(config["n_groups"]), int(config["ssm_state_size"])
    held = int(config["n_routed_experts"])
    ep_size, ep_rank = int(config.get("ep_size", 1)), int(config.get("ep_rank", 0))
    return {
        "pattern": pattern, "L": len(pattern),
        "V": int(config["vocab_size"]), "H": int(config["hidden_size"]),
        "mh": heads, "mp": hdim, "G": groups, "N": state,
        "inner": heads * hdim, "conv_dim": heads * hdim + 2 * groups * state,
        "K": int(config["conv_kernel"]),
        "A": int(config["num_attention_heads"]),
        "KV": int(config["num_key_value_heads"]), "hd": int(config["head_dim"]),
        "held": held, "experts": held * ep_size, "first": held * ep_rank,
        "top_k": int(config["num_experts_per_tok"]),
        "F": int(config["moe_intermediate_size"]),
        "FS": int(config["moe_shared_expert_intermediate_size"]),
        "route_scale": float(config["routed_scaling_factor"]),
        "norm_topk": bool(config.get("norm_topk_prob", True)),
        "eps": float(config["layer_norm_epsilon"]),
        "std": float(config.get("initializer_range", 0.02)),
        "dt_min": float(config["time_step_min"]),
        "dt_max": float(config["time_step_max"]),
        "dt_floor": float(config["time_step_floor"]),
    }


def param_table(c: dict) -> dict:
    """name -> (shape, init kind). ``out``: normal, smaller by sqrt(L)
    (``rescale_prenorm_residual``). ``down``: ``out``, and centred over its
    input rows: what it reads is ``relu(.)^2``, which has a positive mean, and
    an uncentred draw turns that mean into a component every token shares.
    The routers after it then see nearly one input, a few of the 128 experts
    take most tokens (loads 0..257 where 48 are expected) and the share this
    chip holds draws 27k..59k slots an update by the seed. The published
    model balances its routing by the correction bias, which is held at zero
    here; the centred draw is the seeded stand-in: every expert layer routes
    evenly (PERF.md 6). An entry moves by std / rows: the draw stays N(0, std)
    to one part in the rows."""
    table = {"emb": ((c["V"], c["H"]), "normal"),
             "final_norm": ((c["H"],), "ones"),
             "head": ((c["H"], c["V"]), "normal")}
    for i, kind in enumerate(c["pattern"]):
        p = f"l{i}."
        table[p + "norm"] = ((c["H"],), "ones")
        if kind == "M":
            table.update({
                p + "in_proj": ((c["H"], c["inner"] + c["conv_dim"] + c["mh"]),
                                "normal"),
                p + "conv_w": ((c["K"], c["conv_dim"]), "normal"),
                p + "conv_b": ((c["conv_dim"],), "zeros"),
                p + "dt_bias": ((c["mh"],), "dt_bias"),
                p + "A_log": ((c["mh"],), "a_log"),
                p + "D": ((c["mh"],), "ones"),
                p + "gate_norm": ((c["inner"],), "ones"),
                p + "out_proj": ((c["inner"], c["H"]), "out")})
        elif kind == "*":
            table.update({
                p + "wq": ((c["H"], c["A"] * c["hd"]), "normal"),
                p + "wk": ((c["H"], c["KV"] * c["hd"]), "normal"),
                p + "wv": ((c["H"], c["KV"] * c["hd"]), "normal"),
                p + "wo": ((c["A"] * c["hd"], c["H"]), "out")})
        else:
            table.update({
                p + "router": ((c["H"], c["experts"]), "normal"),
                p + "router_bias": ((c["experts"],), "zeros"),
                p + "w_up": ((c["held"], c["H"], c["F"]), "normal"),
                p + "w_down": ((c["held"], c["F"], c["H"]), "down"),
                p + "shared_up": ((c["H"], c["FS"]), "normal"),
                p + "shared_down": ((c["FS"], c["H"]), "down")})
    return table


def seeded_params(key, c: dict) -> dict:
    """Every weight from the seed, on the device, in one traced function."""
    out = {}
    for index, (name, (shape, kind)) in enumerate(sorted(param_table(c).items())):
        k = jax.random.fold_in(key, index)
        if kind in ("normal", "out", "down"):
            std = c["std"] / (1.0 if kind == "normal" else math.sqrt(c["L"]))
            out[name] = std * jax.random.normal(k, shape, jnp.float32)
            if kind == "down":
                out[name] -= jnp.mean(out[name], axis=-2, keepdims=True)
        elif kind == "a_log":
            out[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif kind == "dt_bias":  # inverse softplus of a log-uniform step
            u = jax.random.uniform(k, shape, jnp.float32)
            step = jnp.exp(u * (math.log(c["dt_max"]) - math.log(c["dt_min"]))
                           + math.log(c["dt_min"]))
            step = jnp.maximum(step, c["dt_floor"])
            out[name] = step + jnp.log(-jnp.expm1(-step))
        else:
            out[name] = (jnp.ones if kind == "ones" else jnp.zeros)(
                shape, jnp.float32)
    return out


# ---------------------------------------------------------------- the parts

def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w


def recurrence(x, dt, a, b, c, d, block: int = 128):
    """The literal selective recurrence. x [B, S, H, P], dt [B, S, H] (after
    softplus), a [H] negative, b / c [B, S, G, N], d [H] -> y [B, S, H, P]."""
    batch, seq, heads, hdim = x.shape
    per = heads // b.shape[2]
    pad = (-seq) % block
    if pad:  # dt = 0: the state passes unchanged, and no output is kept
        widths = lambda t: ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)
        x, dt, b, c = (jnp.pad(t, widths(t)) for t in (x, dt, b, c))

    def token(h, inputs):
        x_t, dt_t, b_t, c_t = inputs          # [B,H,P] [B,H] [B,G,N] [B,G,N]
        b_h = jnp.repeat(b_t, per, axis=1)    # group -> its heads
        c_h = jnp.repeat(c_t, per, axis=1)
        h = (jnp.exp(dt_t * a)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        y_t = jnp.sum(h * c_h[:, :, None, :], axis=-1) + d[:, None] * x_t
        return h, y_t

    @jax.checkpoint
    def tokens_of_block(h, inputs):
        return jax.lax.scan(token, h, inputs)

    def blocks(t):  # [B, S, ...] -> [S / block, block, B, ...]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((-1, block) + t.shape[1:])

    h0 = jnp.zeros((batch, heads, hdim, b.shape[3]), jnp.float32)
    _, ys = jax.lax.scan(tokens_of_block, h0,
                         (blocks(x), blocks(dt), blocks(b), blocks(c)))
    return jnp.moveaxis(ys.reshape((-1,) + ys.shape[2:]), 0, 1)[:, :seq]


def mamba_mixer(p, prefix, c, x, precision):
    batch, seq, _ = x.shape
    zxbcdt = _dense(x, p[prefix + "in_proj"], precision)
    z, xbc, dt = jnp.split(zxbcdt, [c["inner"], c["inner"] + c["conv_dim"]], -1)
    padded = jnp.pad(xbc, ((0, 0), (c["K"] - 1, 0), (0, 0)))
    conv = p[prefix + "conv_b"] + sum(
        padded[:, k:k + seq] * p[prefix + "conv_w"][k] for k in range(c["K"]))
    xbc = jax.nn.silu(conv)
    xs, b, cc = jnp.split(xbc, [c["inner"], c["inner"] + c["G"] * c["N"]], -1)
    y = recurrence(
        xs.reshape(batch, seq, c["mh"], c["mp"]),
        jax.nn.softplus(dt + p[prefix + "dt_bias"]),
        -jnp.exp(p[prefix + "A_log"]),
        b.reshape(batch, seq, c["G"], c["N"]),
        cc.reshape(batch, seq, c["G"], c["N"]), p[prefix + "D"])
    gated = (y.reshape(batch, seq, c["inner"]) * jax.nn.silu(z)).reshape(
        batch, seq, c["G"], -1)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + c["eps"])
    return _dense(normed.reshape(batch, seq, c["inner"]) * p[prefix + "gate_norm"],
                  p[prefix + "out_proj"], precision)


def causal_attention(p, prefix, c, x, precision, block_rows: int = 512,
                     causal: bool = True):
    batch, seq, _ = x.shape
    heads, kv, hd = c["A"], c["KV"], c["hd"]
    q = _dense(x, p[prefix + "wq"], precision).reshape(batch, seq, heads, hd)
    k = _dense(x, p[prefix + "wk"], precision).reshape(batch, seq, kv, hd)
    v = _dense(x, p[prefix + "wv"], precision).reshape(batch, seq, kv, hd)
    k = jnp.repeat(k, heads // kv, axis=2)  # a key-value head serves
    v = jnp.repeat(v, heads // kv, axis=2)  # heads / kv consecutive query heads
    rows = min(block_rows, seq)
    pad = (-seq) % rows
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))

    @jax.checkpoint
    def block(start, q_block):
        scores = _attn_einsum("bqhd,bkhd->bhqk", q_block, k, precision
                              ) / math.sqrt(hd)
        if causal:
            row = start + jnp.arange(rows)[:, None]
            scores = jnp.where(jnp.arange(seq)[None, :] <= row, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return _attn_einsum("bhqk,bkhd->bqhd", probs, v, precision)

    starts = jnp.arange(0, seq + pad, rows)
    q_blocks = jnp.moveaxis(q.reshape(batch, -1, rows, heads, hd), 1, 0)
    ctx = jax.lax.map(lambda args: block(*args), (starts, q_blocks))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(batch, seq + pad, heads * hd)[:, :seq]
    return _dense(ctx, p[prefix + "wo"], precision)


def route(p, prefix, c, x):
    """x [T, H] -> (chosen [T, k], weights [T, k])."""
    logits = jnp.matmul(x, p[prefix + "router"], precision="highest")
    s = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(s + p[prefix + "router_bias"], c["top_k"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if c["norm_topk"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
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
        mid = jnp.square(jax.nn.relu(_dense(x, p[prefix + "w_up"][e], precision)))
        out = out + mine[:, None] * _dense(mid, p[prefix + "w_down"][e], precision)
    if shared:
        mid = jnp.square(jax.nn.relu(
            _dense(x, p[prefix + "shared_up"], precision)))
        out = out + _dense(mid, p[prefix + "shared_down"], precision)
    return out.reshape(shape), chosen


def forward(p: dict, c: dict, input_ids, precision: str = "f32"):
    """[B, S] ids -> (logits [B, S, V], [chosen experts of each E layer])."""
    x = p["emb"][input_ids]
    routed = []
    for i, kind in enumerate(c["pattern"]):
        prefix = f"l{i}."

        def part(p_, x_, kind=kind, prefix=prefix):
            h = _rms_norm(x_, p_[prefix + "norm"], c["eps"])
            if kind == "M":
                return mamba_mixer(p_, prefix, c, h, precision), None
            if kind == "*":
                return causal_attention(p_, prefix, c, h, precision), None
            return expert_layer(p_, prefix, c, h, precision)

        out, chosen = jax.checkpoint(part)(
            {k: v for k, v in p.items() if k.startswith(prefix)}, x)
        x = x + out
        if chosen is not None:
            routed.append(chosen)
    x = _rms_norm(x, p["final_norm"], c["eps"])
    return _dense(x, p["head"], precision), routed


def next_token_loss(p, c, input_ids, precision: str = "f32"):
    """Mean cross entropy of position t against token t + 1 over the S - 1
    predicted positions of every row; and the routing of every E layer."""
    logits, routed = forward(p, c, input_ids, precision)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, input_ids[:, 1:, None], axis=-1)
    return -jnp.mean(picked), routed


# ------------------------------------------------------------------ AdamW

class Recipe(NamedTuple):
    learning_rate: float
    warmup_proportion: float
    max_steps: int
    weight_decay: float = 0.1
    max_grad_norm: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8


def learning_rate(recipe: Recipe, update: int) -> float:
    """Linear warm-up, then constant; the rate of 0-based update t is taken
    at t + 1."""
    progress = (update + 1) / recipe.max_steps
    if progress < recipe.warmup_proportion:
        return recipe.learning_rate * progress / recipe.warmup_proportion
    return recipe.learning_rate


def decays(name: str, c: dict) -> bool:
    """Weight decay on the tensors drawn from the normal; none on norms,
    biases, ``A_log``, ``D``, ``dt_bias``."""
    return param_table(c)[name][1] in ("normal", "out", "down")


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
    return name.endswith((".w_up", ".w_down"))


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
    """Follow the first optimizer updates of a run from the same seed.

    ``updates``: one host array of token ids [micro_batches, rows, S] per
    update (what the trainer's step was fed). Returns each update's loss,
    the first update's gradient norm per tensor as the optimizer got it (before
    clipping) with the global norm, the per-tensor norm of the parameters'
    change over all the updates, and ``chosen``: the experts the first
    micro-batch's tokens chose in each E layer. ``first_gradient_to_compare``
    and ``keep_first_gradient`` as in ``bert_f32.follow``.

    Between gradient computations the two moments wait on the host: with
    them the reference's state (16 bytes a parameter, as the program's) and
    a micro-batch's gradient beside the accumulated one would not leave the
    activations their room on one chip.
    """
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
