"""Plain reference for next-token pretraining of the ``KeyeVL2`` family's
language model: float32 ``jax.numpy`` at ``highest`` and nothing else.

The layer equations, from the published ``config.json`` of
Kwai-Keye/Keye-VL-2.0-30B-A3B (its ``text_config`` and ``sa_config``) and
DeepSeek-V3.2-Exp's report, which is the published description of the indexer
and of how it is trained; what no key fixes is listed under ``assumed`` in
``benchmarks/configs/keye-vl-2.0-30b-a3b.json`` and lives in ONE line here
(marked ``# assumed``). x is [S, H]; every layer is alike; ``norm`` is RMSNorm
with the configuration's epsilon and a scale from one.

1. ``x <- x + attention_l(norm_1(x))``; ``x <- x + MoE(norm_2(x))``.
2. Attention, ``h = norm_1(x)``: ``q = h W_q`` [heads x hd], ``k = h W_k``,
   ``v = h W_v`` [KV x hd]; q and k normed over a head; rotary on the whole
   head (``laguna_f32.rotate``: pairs (i, i + hd / 2), the default table).
   The indexer reads ``u = stop_gradient(h)``: ``qI = u W_qI`` [J x E],
   ``kI = u W_kI`` [E], ``w = u W_w`` [J], the same rotary on qI and kI over
   their E; ``I[t, s] = (1 / sqrt(J E)) sum_j w[t, j] relu(qI[t, j] .
   kI[s])`` for ``s <= t``. ``S_t``: the ``min(t + 1, topk)`` causal
   positions of largest ``I[t, :]`` by ``lax.top_k`` (equal scores: the lower
   position first). Query head i on key-value head ``i // (heads / KV)``:
   ``o[t, i] = sum_{s in S_t} softmax_s(q[t, i] . k[s] / sqrt(hd)) v[s]``, as
   a masked softmax over the whole row, a block of query rows at a time;
   ``o W_o``.
3. The indexer's objective, a token: ``KL(p_t || softmax_{s in S_t} I[t,
   s])``, ``p_t`` the core's probabilities summed over the heads on ``S_t``,
   normalised to sum one, under ``stop_gradient``; the model's term is its
   mean over layers and tokens, times ``index_loss_coef``.
4. MoE, ``h = norm_2(x)``: ``qwen3next_f32``'s routed layer without the shared
   expert (softmax over every expert, the ``top_k`` largest renormalised,
   gated silu experts; the reference is GIVEN THE SAME SHARE as the program).
5. Embedding, final ``norm``, an untied head. Loss: mean next-token cross
   entropy plus the term of 3. AdamW as ``nemotron_h_f32``'s.

It imports nothing of the program. Weights come from the seed by
``seeded_params``; the program is handed the same arrays through
``keye_map``.

``precision``: ``f32`` is the reference proper; ``fp8`` is the control, the
step below what the configuration states: every dense and expert product with
e4m3 operands (``bert_f32._dense``), the core's products in bf16, and the
indexer's scores and both softmaxes of the KL rounded to bfloat16; the router
stays in float32, as the program keeps it.
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
from benchmarks.reference.qwen3next_f32 import route

__all__ = ["key_from_seed", "sizes", "seeded_params", "follow", "Recipe"]

INDEXER = ("wqi", "wki", "ww")


def sizes(config: dict) -> dict:
    """The sizes the reference needs, from the configuration file."""
    text = {**config, **(config.get("text_config") or {})}
    sparse = text["sa_config"]
    if int(sparse["indexer_num_kv_heads"]) != 1:
        raise ValueError("the indexer has one key head")
    held = int(text["num_experts"])
    ep_size, ep_rank = int(text.get("ep_size", 1)), int(text.get("ep_rank", 0))
    return {
        "L": int(text["num_hidden_layers"]), "V": int(text["vocab_size"]),
        "H": int(text["hidden_size"]), "hd": int(text["head_dim"]),
        "heads": int(text["num_attention_heads"]),
        "KV": int(text["num_key_value_heads"]),
        "rope": {"rope_theta": text["rope_theta"], "rope_type": "default"},
        "J": int(sparse["indexer_num_heads"]),
        "E": int(sparse["indexer_head_dim"]), "topk": int(sparse["topk"]),
        "kl_coef": float(text.get("index_loss_coef", 1.0)),
        "held": held, "experts": held * ep_size, "first": held * ep_rank,
        "top_k": int(text["num_experts_per_tok"]),
        "F": int(text["moe_intermediate_size"]),
        "norm_topk": bool(text.get("norm_topk_prob", True)),
        "eps": float(text["rms_norm_eps"]),
        "std": float(text.get("initializer_range", 0.02)),
        "emb_std": float(text.get("embedding_initializer_range")
                         or text.get("initializer_range", 0.02)),
    }


def param_table(c: dict) -> dict:
    """name -> (shape, init kind). ``out``: normal, smaller by sqrt(2 L): the
    projections that write into the residual stream, two a layer.
    ``embedding``: normal at ``embedding_initializer_range`` (1.0 in the
    cell's configuration). On this cut 15/16 of every token's expert outputs
    lie on other chips and the model has no shared expert, so at N(0, 0.02)
    the stream is the attention's output beside a small embedding: a near
    uniform softmax over a row returns the row's mean value, which every
    token shares, each layer's values carry it on, the routers see ONE input
    and all the tokens of a row choose the same eight experts (4043 of 4096
    in layer 1, and the share this chip holds draws 107k..136k slots an
    update by the seed: PERF.md 6). The embedding at unit scale is the seeded
    stand-in for the absent experts' token-by-token outputs, as
    ``nemotron_h_f32``'s centred down projections are for its balancing
    bias: every expert layer then routes evenly."""
    wide, narrow = c["heads"] * c["hd"], c["KV"] * c["hd"]
    table = {"emb": ((c["V"], c["H"]), "embedding"),
             "final_norm": ((c["H"],), "ones"),
             "head": ((c["H"], c["V"]), "normal")}
    for i in range(c["L"]):
        p = f"l{i}."
        table.update({
            p + "attn_norm": ((c["H"],), "ones"),
            p + "mlp_norm": ((c["H"],), "ones"),
            p + "wq": ((c["H"], wide), "normal"),
            p + "wk": ((c["H"], narrow), "normal"),
            p + "wv": ((c["H"], narrow), "normal"),
            p + "q_norm": ((c["hd"],), "ones"),
            p + "k_norm": ((c["hd"],), "ones"),
            p + "wo": ((wide, c["H"]), "out"),
            p + "wqi": ((c["H"], c["J"] * c["E"]), "normal"),
            p + "wki": ((c["H"], c["E"]), "normal"),
            p + "ww": ((c["H"], c["J"]), "normal"),
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
        std = (c["emb_std"] if kind == "embedding" else
               c["std"] / (math.sqrt(2 * c["L"]) if kind == "out" else 1.0))
        out[name] = std * jax.random.normal(
            jax.random.fold_in(key, index), shape, jnp.float32)
    return out


# ---------------------------------------------------------------- the parts

def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w


def _bf16(t):
    return t.astype(jnp.bfloat16).astype(jnp.float32)


def index_scores(qi, ki, w, precision):
    """qi [B, T, J, E], ki [B, S, E], w [B, T, J] -> I [B, T, S]."""
    dots = _attn_einsum("btje,bse->btjs", qi, ki, precision)
    scores = jnp.sum(w[..., None] * jax.nn.relu(dots), axis=2) / math.sqrt(
        qi.shape[2] * qi.shape[3])
    return scores if precision == "f32" else _bf16(scores)


def chosen_mask(scores, first_row, topk, short: int = 0):
    """scores [B, T, S], the block's rows ``first_row ..``: bool [B, T, S],
    row t true at its ``min(t + 1, topk) - short`` causal positions of
    largest score: the k-th largest value by ``lax.top_k``, every score above
    it, and of the scores EQUAL to it the lowest positions until the count is
    full (no scatter: on the chip a scatter of 2048 positions a query is
    slower than the rest of the layer)."""
    _, rows, seq = scores.shape
    t = first_row + jnp.arange(rows)
    causal = jnp.arange(seq)[None, :] <= t[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    values, _ = jax.lax.top_k(scores, min(topk, seq))
    count = jnp.minimum(t + 1, topk) - short
    kth = jnp.take_along_axis(
        values, jnp.broadcast_to(jnp.maximum(count - 1, 0)[None, :, None],
                                 values.shape[:2] + (1,)), axis=-1)
    above, equal = scores > kth, scores == kth
    of_equal = count[None, :, None] - jnp.sum(above, axis=-1, keepdims=True)
    taken = jnp.cumsum(equal, axis=-1) <= of_equal
    return (above | (equal & taken)) & causal & (count > 0)[None, :, None]


def attention(p, prefix, c, h, precision, block_rows: int = 128, faults=()):
    """(the attention layer's output over h [B, S, H] (already normalised),
    the KL a token [B, S], the chosen keys as packed bits [B, S, S / 8])."""
    batch, seq, _ = h.shape
    heads, kv, hd, index_heads, width = (c["heads"], c["KV"], c["hd"], c["J"],
                                         c["E"])
    q = _dense(h, p[prefix + "wq"], precision).reshape(batch, seq, heads, hd)
    k = _dense(h, p[prefix + "wk"], precision).reshape(batch, seq, kv, hd)
    v = _dense(h, p[prefix + "wv"], precision).reshape(batch, seq, kv, hd)
    q = norm(q, p[prefix + "q_norm"], c["eps"])  # assumed: a head at a time
    k = norm(k, p[prefix + "k_norm"], c["eps"])
    q, k = rotate(q, hd, c["rope"]), rotate(k, hd, c["rope"])
    k = jnp.repeat(k, heads // kv, axis=2)  # a key-value head serves
    v = jnp.repeat(v, heads // kv, axis=2)  # heads / kv consecutive query heads
    u = h if "indexer_input_attached" in faults else jax.lax.stop_gradient(h)
    qi = _dense(u, p[prefix + "wqi"], precision).reshape(
        batch, seq, index_heads, width)
    ki = _dense(u, p[prefix + "wki"], precision)
    w = _dense(u, p[prefix + "ww"], precision)
    qi = rotate(qi, width, c["rope"])  # assumed: the whole of the indexer's E
    ki = rotate(ki[:, :, None, :], width, c["rope"])[:, :, 0]
    rows = min(block_rows, seq)
    pad = (-seq) % rows
    padded = lambda t: jnp.moveaxis(jnp.pad(
        t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2)).reshape(
            (batch, -1, rows) + t.shape[2:]), 1, 0)

    @jax.checkpoint
    def block(start, q_b, qi_b, w_b):
        index = index_scores(qi_b, ki, w_b, precision)
        mask = chosen_mask(jax.lax.stop_gradient(index), start, c["topk"],
                           short=int("top_k_one_short" in faults))
        scores = _attn_einsum("bqhd,bkhd->bhqk", q_b, k, precision
                              ) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(mask[:, None], scores, -1e30), axis=-1)
        ctx = _attn_einsum("bhqk,bkhd->bqhd", probs, v, precision)
        target = jax.lax.stop_gradient(jnp.sum(probs, axis=1) / heads)
        log_index = jax.nn.log_softmax(jnp.where(mask, index, -1e30), axis=-1)
        if precision != "f32":  # the control: both softmaxes in bfloat16
            target, log_index = _bf16(target), _bf16(log_index)
        log_target = jnp.log(jnp.maximum(target, jnp.finfo(jnp.float32).tiny))
        kl = jnp.sum(jnp.where(mask, target * (log_target - log_index), 0.0),
                     axis=-1)
        return ctx, kl, jnp.packbits(mask, axis=-1)

    starts = jnp.arange(0, seq + pad, rows)
    ctx, kl, mask = jax.lax.map(lambda args: block(*args), (
        starts, padded(q), padded(qi), padded(w)))
    rows_of = lambda t: jnp.moveaxis(t, 0, 1).reshape(
        (batch, seq + pad) + t.shape[3:])[:, :seq]
    out = _dense(rows_of(ctx).reshape(batch, seq, heads * hd),
                 p[prefix + "wo"], precision)
    return out, rows_of(kl), rows_of(mask)


def expert_layer(p, prefix, c, x, precision, held=None):
    """(output, chosen). The experts this share holds (``held``: a range of
    expert ids whose weights ``p`` holds in order; the configuration's by
    default), one after the other, each over all tokens under its mask."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    chosen, w = route(p, prefix, c, x)
    mine = range(c["first"], c["first"] + c["held"]) if held is None else held

    @jax.checkpoint  # (an expert's intermediates are made again in the backward)
    def term(w_gu, w_down, e):
        weight = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        return weight[:, None] * glu(x, w_gu, w_down, precision)

    out, _ = jax.lax.scan(
        lambda total, expert: (total + term(*expert), None), jnp.zeros_like(x),
        (p[prefix + "w_gu"], p[prefix + "w_down"], jnp.asarray(list(mine))))
    return out.reshape(shape), chosen


def hidden(p: dict, c: dict, input_ids, precision: str = "f32", faults=()):
    """[B, S] ids -> (the final norm's output [B, S, H], the indexers' KL: its
    mean over layers and tokens, [chosen experts of each layer], [chosen keys
    of each layer, eight a byte])."""
    x = p["emb"][input_ids]
    routed, selected, kl = [], [], 0.0
    for i in range(c["L"]):
        prefix = f"l{i}."

        def layer(p_, x_, prefix=prefix):
            out, kl_, mask = attention(
                p_, prefix, c, norm(x_, p_[prefix + "attn_norm"], c["eps"]),
                precision, faults=faults)
            x_ = x_ + out
            out, chosen = expert_layer(
                p_, prefix, c, norm(x_, p_[prefix + "mlp_norm"], c["eps"]),
                precision)
            return x_ + out, jnp.mean(kl_), chosen, mask

        x, kl_, chosen, mask = jax.checkpoint(layer)(
            {k: v for k, v in p.items() if k.startswith(prefix)}, x)
        kl = kl + kl_ / c["L"]
        routed.append(chosen)
        selected.append(mask)
    return norm(x, p["final_norm"], c["eps"]), kl, routed, selected


def next_token_loss(x, head, input_ids, precision, piece: int = 2048):
    """Mean cross entropy of position t against token t + 1 over the S - 1
    predicted positions of every row, from the final norm's output x: the
    head and the log-softmax a piece of the row at a time, each made again in
    the backward (16,384 x 19,072 logits are 1.25 GB, several times over)."""
    batch, seq, _ = x.shape
    piece = piece if seq % piece == 0 else seq
    target = jnp.roll(input_ids, -1, axis=-1)
    counted = jnp.broadcast_to(jnp.arange(seq) < seq - 1, target.shape)
    pieces = lambda t: jnp.moveaxis(
        t.reshape((batch, seq // piece, piece) + t.shape[2:]), 1, 0)

    @jax.checkpoint
    def one(args):
        h, want, keep = args
        logp = jax.nn.log_softmax(_dense(h, head, precision), axis=-1)
        picked = jnp.take_along_axis(logp, want[..., None], axis=-1)[..., 0]
        return -jnp.sum(jnp.where(keep, picked, 0.0))

    total = jnp.sum(jax.lax.map(one, (pieces(x), pieces(target),
                                      pieces(counted))))
    return total / (batch * (seq - 1))


def objective(p, c, input_ids, precision: str = "f32", faults=()):
    """The next-token loss plus the indexers' term; and every layer's routing
    and choice (the choice as packed bits)."""
    x, kl, routed, selected = hidden(p, c, input_ids, precision, faults)
    loss = next_token_loss(x, p["head"], input_ids, precision)
    if "index_loss_left_out" not in faults:
        loss = loss + c["kl_coef"] * kl
    return loss, (routed, selected)


# ------------------------------------------------------------------ AdamW

def decays(name: str, c: dict) -> bool:
    """Weight decay on the matrices; none on the norms' scales."""
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


# ---------------------------------------------------------- following a run

def follow(seed: int, config: dict, recipe: Recipe, updates: list,
           precision: str = "f32", keep_first_gradient: bool = False,
           first_gradient_to_compare: dict = None, faults=()) -> dict:
    """Follow the first optimizer updates of a run from the same seed:
    ``qwen3next_f32.follow``'s contract over this family's tensors (each
    update's loss, the first update's gradient norm per tensor before clipping
    with the global norm, the per-tensor norm of the parameters' change over
    all the updates, ``chosen``: the experts the first micro-batch's tokens
    chose in each layer) and ``selected``: the keys its queries chose in each
    layer, [rows, S, S / 8] packed bits. A micro-batch's rows pass ONE AT A
    TIME, as there. ``faults`` (tests only) plants a wrong rule."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    c = sizes(config)
    key = key_from_seed(seed)
    make = jax.jit(lambda k: seeded_params(k, c))
    p = make(key)
    mu = nu = None  # zeros until the first update; on the host between updates
    grad = jax.jit(jax.value_and_grad(
        lambda p_, ids: objective(p_, c, ids, precision, faults),
        has_aux=True))
    adamw = make_adamw_update(recipe, c)
    add = jax.jit(lambda a, b, s: jax.tree_util.tree_map(
        lambda x, y: x + s * y, a, b), donate_argnums=(0,))
    out = {"loss": [], "grad_global_norm": None, "grad_norms": None}
    for index, upd in enumerate(updates):
        micro, rows = upd.shape[:2]
        grads, loss, first, picked = None, 0.0, [], []
        for m, row in np.ndindex(micro, rows):
            (share, (routed, selected)), g = grad(
                p, jnp.asarray(upd[m, row:row + 1], jnp.int32))
            if index == 0 and m == 0:
                first.append([np.asarray(r) for r in routed])
                picked.append([np.asarray(s) for s in selected])
            del selected
            grads = (jax.tree_util.tree_map(lambda x: x / (micro * rows), g)
                     if grads is None else add(grads, g, 1.0 / (micro * rows)))
            del g
            loss += float(share) / (micro * rows)
        if index == 0:  # the first micro-batch's routing, row after row
            out["chosen"] = [np.concatenate(layer) for layer in zip(*first)]
            out["selected"] = [np.concatenate(layer) for layer in zip(*picked)]
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
