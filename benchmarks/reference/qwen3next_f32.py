"""Plain reference for next-token pretraining of the ``qwen3_next`` family:
float32 ``jax.numpy`` at ``highest`` and nothing else.

The layer equations, from the published ``config.json`` of
Qwen/Qwen3-Next-80B-A3B-Instruct and the published ``transformers`` model of
the same ``model_type``; what no key fixes is listed under ``assumed`` in
``benchmarks/configs/qwen3-next-80b-a3b.json`` and lives in ONE line here
(marked ``# assumed``). x is [S, H]; layer l is attention where
``(l + 1) % full_attention_interval == 0`` and the delta rule elsewhere;
``norm`` is RMSNorm with the configuration's epsilon that multiplies by
``1 + w`` (w from zero).

1. ``x <- x + mixer_l(norm_1(x))``; ``x <- x + MoE(norm_2(x))``.
2. Delta-rule mixer, ``h = norm_1(x)``: ``[q, k, v, z] = h W_qkvz`` (K, K, Vw,
   Vw columns; K = key heads x d_k, Vw = value heads x d_v), ``[b, a] = h
   W_ba``; ``[q, k, v] <- silu(conv([q, k, v]))``, one depthwise causal
   convolution over all channels, no bias, zeros before the row. ``beta =
   sigmoid(b)``; ``g = -exp(A_log) softplus(a + dt_bias)``; q and k a head at
   a time ``u rsqrt(sum u^2 + 1e-6)``, then ``q / sqrt(d_k)``; key head j
   serves value heads j r .. j r + r - 1 (r = value heads / key heads). A
   value head's state S [d_k, d_v], zero at the row's start, token by token:
   ``S <- exp(g_t) S``; ``r = S^T k_t``; ``S <- S + k_t (beta_t (v_t -
   r))^T``; ``o_t = S^T q_t``. This is the LITERAL recurrence: a scan over
   blocks of tokens of a scan over tokens, each block checkpointed so that
   the backward keeps one state a block; no chunked algebra. Then a head at a
   time ``rmsnorm(o) w_n silu(z)`` (w_n from one: NOT ``1 + w``) and ``o
   W_o``.
3. Attention mixer: ``[q, gate] = h W_q`` side by side a head, ``k = h W_k``,
   ``v = h W_v``; q and k normed over a head (``1 + w``); rotary on the first
   ``head_dim x partial_rotary_factor`` dimensions (``laguna_f32.rotate``:
   pairs (i, i + r / 2), the default table); causal softmax attention, scale
   1 / sqrt(head_dim), in blocks of query rows under an explicit mask, query
   head i on key-value head i // (heads / kv); ``(o * sigmoid(gate)) W_o``.
4. MoE, ``h = norm_2(x)``: ``p = softmax(h W_r)`` over every expert of the
   layer; the ``top_k`` largest, renormalised to sum 1 under
   ``norm_topk_prob``; expert e ``W_down (silu(W_gate h) * (W_up h))``. The
   reference is GIVEN THE SAME SHARE as the program: the experts ``[first,
   first + held)`` (a loop over them, each over all tokens under its mask)
   and the slice of the vocabulary; ``held`` may name another range (the
   share test). The shared expert, in the same form, times ``sigmoid(h
   w_g)``, on every token.
5. Embedding, final ``norm``, an untied head. Loss: mean next-token cross
   entropy. AdamW as ``nemotron_h_f32``'s (decoupled decay on the matrices,
   global-norm clipping, bias correction, linear warm-up to a constant rate).

It imports nothing of the program. Weights come from the seed by
``seeded_params``; the program is handed the same arrays through
``qwen3next_map``.

``precision``: ``f32`` is the reference proper; ``fp8`` is the control, the
step below the bf16 the configuration states: every dense and expert product
with e4m3 operands (``bert_f32._dense``), the attention products in bf16, and
the rule's state and decays in bfloat16 (the state rounded after every token);
the router stays in float32, as the program keeps it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.bert_f32 import (PRECISIONS, _attn_einsum, _dense,
                                           key_from_seed)
from benchmarks.reference.laguna_f32 import glu, leaf_norms, rotate
from benchmarks.reference.nemotron_h_f32 import Recipe, learning_rate

__all__ = ["key_from_seed", "sizes", "seeded_params", "follow", "Recipe"]

L2_EPS = 1e-6
# drawn at random and still outside the weight decay (a vector, not a matrix)
NO_DECAY_KINDS = ("ones", "zeros", "a_log", "vector")


def sizes(config: dict) -> dict:
    """The sizes the reference needs, from the configuration file."""
    layers = int(config["num_hidden_layers"])
    every = int(config["full_attention_interval"])
    kinds = config.get("layer_types") or [
        "full_attention" if (l + 1) % every == 0 else "linear_attention"
        for l in range(layers)]
    if len(kinds) != layers:
        raise ValueError("layer_types and num_hidden_layers differ")
    hd = int(config["head_dim"])
    held = int(config["num_experts"])
    ep_size, ep_rank = int(config.get("ep_size", 1)), int(config.get("ep_rank", 0))
    return {
        "L": layers, "kinds": list(kinds), "V": int(config["vocab_size"]),
        "H": int(config["hidden_size"]), "hd": hd,
        "heads": int(config["num_attention_heads"]),
        "KV": int(config["num_key_value_heads"]),
        "rotary": int(hd * float(config["partial_rotary_factor"])),
        "rope": {"rope_theta": config["rope_theta"], "rope_type": "default"},
        "kh": int(config["linear_num_key_heads"]),
        "vh": int(config["linear_num_value_heads"]),
        "dk": int(config["linear_key_head_dim"]),
        "dv": int(config["linear_value_head_dim"]),
        "taps": int(config["linear_conv_kernel_dim"]),
        "held": held, "experts": held * ep_size, "first": held * ep_rank,
        "top_k": int(config["num_experts_per_tok"]),
        "F": int(config["moe_intermediate_size"]),
        "FS": int(config["shared_expert_intermediate_size"]),
        "norm_topk": bool(config.get("norm_topk_prob", True)),
        "eps": float(config["rms_norm_eps"]),
        "std": float(config.get("initializer_range", 0.02)),
    }


def param_table(c: dict) -> dict:
    """name -> (shape, init kind). ``out``: normal, smaller by sqrt(2 L): the
    projections that write into the residual stream, two a layer. ``conv``:
    uniform within 1 / sqrt(taps), torch's ``Conv1d`` default. ``a_log``: the
    log of a uniform draw on (0, 16). ``vector``: normal, outside the decay.
    The ``1 + w`` norms start from zero, the gated norm's scale from one."""
    key_w, value_w = c["kh"] * c["dk"], c["vh"] * c["dv"]
    table = {"emb": ((c["V"], c["H"]), "normal"),
             "final_norm": ((c["H"],), "zeros"),
             "head": ((c["H"], c["V"]), "normal")}
    for i, kind in enumerate(c["kinds"]):
        p = f"l{i}."
        table.update({p + "mixer_norm": ((c["H"],), "zeros"),
                      p + "mlp_norm": ((c["H"],), "zeros")})
        if kind == "linear_attention":
            table.update({
                p + "w_qkvz": ((c["H"], 2 * key_w + 2 * value_w), "normal"),
                p + "w_ba": ((c["H"], 2 * c["vh"]), "normal"),
                p + "conv": ((c["taps"], 2 * key_w + value_w), "conv"),
                p + "a_log": ((c["vh"],), "a_log"),
                p + "dt_bias": ((c["vh"],), "ones"),
                p + "gnorm": ((c["dv"],), "ones"),
                p + "wo": ((value_w, c["H"]), "out")})
        else:
            wide = c["heads"] * c["hd"]
            table.update({
                p + "wq": ((c["H"], 2 * wide), "normal"),
                p + "wk": ((c["H"], c["KV"] * c["hd"]), "normal"),
                p + "wv": ((c["H"], c["KV"] * c["hd"]), "normal"),
                p + "q_norm": ((c["hd"],), "zeros"),
                p + "k_norm": ((c["hd"],), "zeros"),
                p + "wo": ((wide, c["H"]), "out")})
        table.update({
            p + "router": ((c["H"], c["experts"]), "normal"),
            p + "w_gu": ((c["held"], c["H"], 2 * c["F"]), "normal"),
            p + "w_down": ((c["held"], c["F"], c["H"]), "out"),
            p + "shared_gu": ((c["H"], 2 * c["FS"]), "normal"),
            p + "shared_down": ((c["FS"], c["H"]), "out"),
            p + "shared_gate": ((c["H"],), "vector")})
    return table


def seeded_params(key, c: dict) -> dict:
    """Every weight from the seed, on the device, in one traced function."""
    out = {}
    for index, (name, (shape, kind)) in enumerate(sorted(param_table(c).items())):
        draw = jax.random.fold_in(key, index)
        if kind in ("ones", "zeros"):
            out[name] = jnp.full(shape, float(kind == "ones"), jnp.float32)
        elif kind == "conv":
            bound = 1.0 / math.sqrt(shape[0])
            out[name] = jax.random.uniform(draw, shape, jnp.float32, -bound, bound)
        elif kind == "a_log":  # assumed: the published initialisation
            out[name] = jnp.log(jax.random.uniform(
                draw, shape, jnp.float32, 1e-6, 16.0))
        else:
            std = c["std"] / (math.sqrt(2 * c["L"]) if kind == "out" else 1.0)
            out[name] = std * jax.random.normal(draw, shape, jnp.float32)
    return out


# ---------------------------------------------------------------- the parts

def norm(x, w, eps):
    """RMSNorm that multiplies by ``1 + w``."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def unit(u):
    return u * jax.lax.rsqrt(jnp.sum(jnp.square(u), axis=-1, keepdims=True)
                             + L2_EPS)


def causal_conv(x, taps):
    """x [B, S, C], taps [K, C]: ``out_t = sum_k taps[k] x_{t - (K-1) + k}``,
    zeros before the row, no bias."""
    count, seq = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (count - 1, 0), (0, 0)))
    return sum(padded[:, k:k + seq] * taps[k] for k in range(count))


def recurrence(q, k, v, g, beta, precision: str = "f32", block: int = 64,
               faults=()):
    """The literal gated delta rule. q, k [B, S, Hv, d_k] (the key heads
    already repeated), v [B, S, Hv, d_v], g, beta [B, S, Hv] -> o [B, S, Hv,
    d_v]. ``faults`` (tests only) plants a wrong rule: ``undecayed_read`` (the
    correction read from the state before its decay), ``no_beta``."""
    batch, seq, heads, dk = k.shape
    low = precision == "fp8"  # the control: state and decays in bfloat16
    held = (lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)) if low else (
        lambda t: t)

    def read_with(state, key):  # S^T k, as a product and a sum in float32
        return jnp.sum(state * key[..., :, None], axis=-2)

    def token(state, inputs):
        q_t, k_t, v_t, g_t, b_t = inputs
        decayed = state * held(jnp.exp(g_t))[..., None, None]
        read = read_with(state if "undecayed_read" in faults else decayed, k_t)
        if "no_beta" in faults:
            b_t = jnp.ones_like(b_t)
        state = held(decayed + k_t[..., :, None] * (
            b_t[..., None] * (v_t - read))[..., None, :])
        return state, read_with(state, q_t)

    @jax.checkpoint
    def tokens_of_block(state, inputs):
        return jax.lax.scan(token, state, inputs, unroll=4)

    pad = (-seq) % block

    def blocks(t):  # [B, S, ...] -> [S / block, block, B, ...]
        t = jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((-1, block) + t.shape[1:])

    start = jnp.zeros((batch, heads, dk, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(tokens_of_block, start, tuple(
        blocks(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(out.reshape((-1,) + out.shape[2:]), 0, 1)[:, :seq]


def delta_mixer(p, prefix, c, h, precision, faults=()):
    """The delta-rule mixer over h [B, S, H] (already normalised)."""
    batch, seq, _ = h.shape
    kh, vh, dk, dv = c["kh"], c["vh"], c["dk"], c["dv"]
    key_w, value_w = kh * dk, vh * dv
    qkvz = _dense(h, p[prefix + "w_qkvz"], precision)
    b, a = jnp.split(_dense(h, p[prefix + "w_ba"], precision), 2, axis=-1)
    qkv, z = qkvz[..., :2 * key_w + value_w], qkvz[..., 2 * key_w + value_w:]
    qkv = jax.nn.silu(causal_conv(qkv, p[prefix + "conv"]))
    q, k, v = jnp.split(qkv, [key_w, 2 * key_w], axis=-1)  # assumed: the order
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p[prefix + "a_log"]) * jax.nn.softplus(a + p[prefix + "dt_bias"])
    q = unit(q.reshape(batch, seq, kh, dk)) / math.sqrt(dk)
    k = unit(k.reshape(batch, seq, kh, dk))
    q, k = (jnp.repeat(t, vh // kh, axis=2) for t in (q, k))
    o = recurrence(q, k, v.reshape(batch, seq, vh, dv), g, beta, precision,
                   faults=faults)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + c["eps"]) * p[prefix + "gnorm"]
    o = o * jax.nn.silu(z.reshape(batch, seq, vh, dv))
    return _dense(o.reshape(batch, seq, value_w), p[prefix + "wo"], precision)


def attention(p, prefix, c, h, precision, block_rows: int = 512):
    """The gated attention mixer over h [B, S, H] (already normalised)."""
    batch, seq, _ = h.shape
    heads, kv, hd = c["heads"], c["KV"], c["hd"]
    q, gate = jnp.split(  # assumed: q and its gate side by side a head
        _dense(h, p[prefix + "wq"], precision).reshape(batch, seq, heads, 2 * hd),
        2, axis=-1)
    k = _dense(h, p[prefix + "wk"], precision).reshape(batch, seq, kv, hd)
    v = _dense(h, p[prefix + "wv"], precision).reshape(batch, seq, kv, hd)
    q = norm(q, p[prefix + "q_norm"], c["eps"])
    k = norm(k, p[prefix + "k_norm"], c["eps"])
    q, k = rotate(q, c["rotary"], c["rope"]), rotate(k, c["rotary"], c["rope"])
    k = jnp.repeat(k, heads // kv, axis=2)  # a key-value head serves
    v = jnp.repeat(v, heads // kv, axis=2)  # heads / kv consecutive query heads
    rows = min(block_rows, seq)
    pad = (-seq) % rows
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))

    @jax.checkpoint
    def block(start, q_block):
        scores = _attn_einsum("bqhd,bkhd->bhqk", q_block, k, precision
                              ) / math.sqrt(hd)
        seen = jnp.arange(seq)[None, :] <= start + jnp.arange(rows)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return _attn_einsum("bhqk,bkhd->bqhd", probs, v, precision)

    starts = jnp.arange(0, seq + pad, rows)
    q_blocks = jnp.moveaxis(q.reshape(batch, -1, rows, heads, hd), 1, 0)
    ctx = jax.lax.map(lambda args: block(*args), (starts, q_blocks))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(batch, seq + pad, heads, hd)[:, :seq]
    ctx = ctx * jax.nn.sigmoid(gate)
    return _dense(ctx.reshape(batch, seq, heads * hd), p[prefix + "wo"],
                  precision)


def route(p, prefix, c, x, faults=()):
    """x [T, H] -> (chosen [T, k], weights [T, k])."""
    logits = jnp.matmul(x, p[prefix + "router"], precision="highest")
    probs = jax.nn.softmax(logits, axis=-1)
    w, chosen = jax.lax.top_k(probs, c["top_k"])
    if c["norm_topk"] and "no_renorm" not in faults:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w


def expert_layer(p, prefix, c, x, precision, held=None, shared: bool = True,
                 faults=()):
    """(output, chosen). The experts this share holds (``held``: a range of
    expert ids whose weights ``p`` holds in order; the configuration's by
    default), one after the other, each over all tokens under its mask; plus
    (``shared``) the gated shared expert."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    chosen, w = route(p, prefix, c, x, faults)
    mine = range(c["first"], c["first"] + c["held"]) if held is None else held

    @jax.checkpoint  # (an expert's intermediates are made again in the backward)
    def term(w_gu, w_down, e):
        weight = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        return weight[:, None] * glu(x, w_gu, w_down, precision)

    out, _ = jax.lax.scan(
        lambda total, expert: (total + term(*expert), None), jnp.zeros_like(x),
        (p[prefix + "w_gu"], p[prefix + "w_down"], jnp.asarray(list(mine))))
    if shared:
        term = glu(x, p[prefix + "shared_gu"], p[prefix + "shared_down"],
                   precision)
        if "no_shared_gate" not in faults:
            term = term * jax.nn.sigmoid(_dense(
                x, p[prefix + "shared_gate"][:, None], precision))
        out = out + term
    return out.reshape(shape), chosen


def forward(p: dict, c: dict, input_ids, precision: str = "f32", faults=()):
    """[B, S] ids -> (logits [B, S, V], [chosen experts of each layer])."""
    x = p["emb"][input_ids]
    routed = []
    for i, kind in enumerate(c["kinds"]):
        prefix = f"l{i}."

        def layer(p_, x_, kind=kind, prefix=prefix):
            h = norm(x_, p_[prefix + "mixer_norm"], c["eps"])
            if kind == "linear_attention":
                x_ = x_ + delta_mixer(p_, prefix, c, h, precision, faults)
            else:
                x_ = x_ + attention(p_, prefix, c, h, precision)
            h = norm(x_, p_[prefix + "mlp_norm"], c["eps"])
            out, chosen = expert_layer(p_, prefix, c, h, precision,
                                       faults=faults)
            return x_ + out, chosen

        x, chosen = jax.checkpoint(layer)(
            {k: v for k, v in p.items() if k.startswith(prefix)}, x)
        routed.append(chosen)
    x = norm(x, p["final_norm"], c["eps"])
    return _dense(x, p["head"], precision), routed


def next_token_loss(p, c, input_ids, precision: str = "f32", faults=()):
    """Mean cross entropy of position t against token t + 1 over the S - 1
    predicted positions of every row; and every layer's routing."""
    logits, routed = forward(p, c, input_ids, precision, faults)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, input_ids[:, 1:, None], axis=-1)
    return -jnp.mean(picked), routed


# ------------------------------------------------------------------ AdamW

def decays(name: str, c: dict) -> bool:
    """Weight decay on the matrices; none on norms, ``A_log``, ``dt_bias`` or
    the shared expert's gate vector."""
    return param_table(c)[name][1] not in NO_DECAY_KINDS


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


# ---------------------------------------------------------- following a run

def follow(seed: int, config: dict, recipe: Recipe, updates: list,
           precision: str = "f32", keep_first_gradient: bool = False,
           first_gradient_to_compare: dict = None, faults=()) -> dict:
    """Follow the first optimizer updates of a run from the same seed:
    ``nemotron_h_f32.follow``'s contract over this family's tensors (each
    update's loss, the first update's gradient norm per tensor before clipping
    with the global norm, the per-tensor norm of the parameters' change over
    all the updates, ``chosen``: the experts the first micro-batch's tokens
    chose in each layer). Between gradient computations the two moments wait
    on the host, as there. A micro-batch's rows pass ONE AT A TIME (rows are
    equally long, so the mean over a micro-batch is the mean of its rows'
    means): 32 held experts each over every token of two rows of 8192 keep
    10.5 GB for the backward, which does not fit beside the parameters, the
    gradient and its sum. ``faults`` (tests only) plants a wrong rule."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    c = sizes(config)
    key = key_from_seed(seed)
    make = jax.jit(lambda k: seeded_params(k, c))
    p = make(key)
    mu = nu = None  # zeros until the first update; on the host between updates
    grad = jax.jit(jax.value_and_grad(
        lambda p_, ids: next_token_loss(p_, c, ids, precision, faults),
        has_aux=True))
    adamw = make_adamw_update(recipe, c)
    add = jax.jit(lambda a, b, s: jax.tree_util.tree_map(
        lambda x, y: x + s * y, a, b), donate_argnums=(0,))
    out = {"loss": [], "grad_global_norm": None, "grad_norms": None}
    for index, upd in enumerate(updates):
        micro, rows = upd.shape[:2]
        grads, loss, first = None, 0.0, []
        for m, row in np.ndindex(micro, rows):
            (share, routed), g = grad(
                p, jnp.asarray(upd[m, row:row + 1], jnp.int32))
            if index == 0 and m == 0:
                first.append([np.asarray(r) for r in routed])
            grads = (jax.tree_util.tree_map(lambda x: x / (micro * rows), g)
                     if grads is None else add(grads, g, 1.0 / (micro * rows)))
            del g
            loss += float(share) / (micro * rows)
        if index == 0:  # the first micro-batch's routing, row after row
            out["chosen"] = [np.concatenate(layer) for layer in zip(*first)]
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
