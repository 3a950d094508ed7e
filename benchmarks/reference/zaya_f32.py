"""Plain reference for next-token pretraining of the ``zaya`` family: float32
``jax.numpy`` at ``highest`` and nothing else.

The layer equations, from the published ``config.json`` of Zyphra/ZAYA1-8B,
the switches its siblings' files carry (``cca``, ``zaya_use_eda``,
``zaya_use_mod``, ``scale_residual_merge``) and the two published
descriptions (Compressed Convolutional Attention, arXiv:2510.04476; the ZAYA1
report, arXiv:2511.17127); what no key fixes is listed under ``assumed`` in
``benchmarks/configs/zaya1-8b.json`` and lives in ONE line here (marked
``# assumed``). x is [S, H]; layer l; every norm RMSNorm with a learned scale;
what stands before the first position or the first layer is zero.

1. ``x <- merge_a(x, CCA(norm_a(x)))``; ``(m, r_l) = MoE(norm_m(x), r_{l-1})``;
   ``x <- merge_m(x, m)``; ``merge(x, y) = s_x x + b_x + s_y y + b_y``.
2. CCA, ``h = norm_a(x)``: ``q0 = h Wq`` [S, n d], ``k0 = h Wk`` [S, KV d],
   ``z = [q0, k0]``. Convolution 0, depthwise over positions: ``z1_t = a0
   z_{t-1} + a1 z_t + c``. Convolution 1, in n + KV groups of d channels (the
   query heads, then the key heads): ``z2_t[g] = A0[g] z1_{t-1}[g] + A1[g]
   z1_t[g] + d[g]``. q-k mean, query head i of group j = i // (n / KV):
   ``mq[i] = (q0[i] + k0[j]) / 2``, ``mk[j] = (mean_{i in j} q0[i] + k0[j]) /
   2``; ``q = z2[:n] + mq``, ``k = z2[n:] + mk``. Norm, a head at a time: ``q
   <- q sqrt(d) / |q|``, ``k <- k sqrt(d) / |k| tau[j]``. Values ``v = [h_t
   Wv[:, first half], h_{t-1} Wv[:, second half]]``: the first half of the
   key-value heads read this token, the second half the one before. Rotary on
   the first ``d x partial_rotary_factor`` dimensions of every q and k head
   (``laguna_f32.rotate``: pairs (i, i + r / 2), the default table). Causal
   softmax attention, scale 1 / sqrt(d), query head i on key-value head j, in
   blocks of query rows under an explicit mask. ``CCA = o Wo``.
3. Router, ``h = norm_m(x)``: ``r = h Wd + bd``; ``r_l = r + gamma_l r_{l-1}``
   (the first layer has no gamma), handed on before its norm; ``u =
   gelu(norm(r_l) W1 + b1)``, ``u = gelu(u W2 + b2)``, ``logits = u W3``
   [S, experts + 1]; ``p = softmax(logits)``; ``e = argmax(p + beta)``, beta a
   buffer at zero outside the gradient; weight ``p_e``, not renormalised.
4. Experts: ``e < experts``: ``y = p_e Wdown_e (silu(Wgate_e h) * (Wup_e h))``,
   gate and up side by side in one tensor, the gate's columns first. ``e =
   experts`` is the skip: the token adds nothing here. The reference is GIVEN
   THE SAME SHARE as the program: the experts ``[first, first + held)`` (a
   loop over them, each over all tokens under its mask) and the slice of the
   vocabulary; what the absent experts would add is left out. No shared
   expert.
5. Embedding, final norm, head = the embedding's transpose (ONE tensor,
   ``head`` [V, H], read by row for the lookup and as a matrix for the
   logits). Loss: mean next-token cross entropy. AdamW as
   ``nemotron_h_f32``'s (decoupled decay on the matrices, global-norm
   clipping, bias correction, linear warm-up to a constant rate).

It imports nothing of the program. Weights come from the seed by
``seeded_params``; the program is handed the same arrays through ``zaya_map``.

``precision``: ``f32`` is the reference proper; ``fp8`` is the control, the
step below the bf16 the configuration states: every dense, convolution-1 and
expert product with e4m3 operands (``bert_f32._dense``), the attention
products in bf16; the router stays in float32, as the program keeps it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.bert_f32 import (PRECISIONS, _attn_einsum, _dense,
                                           _gelu, _rounded, key_from_seed)
from benchmarks.reference.laguna_f32 import glu, leaf_norms, rotate
from benchmarks.reference.nemotron_h_f32 import (Recipe, _rms_norm,
                                                 learning_rate)

__all__ = ["key_from_seed", "sizes", "seeded_params", "follow", "Recipe"]

MERGES = ("sx", "bx", "sy", "by")


def sizes(config: dict) -> dict:
    """The sizes the reference needs, from the configuration file."""
    layers = int(config["num_hidden_layers"])
    if list(config["layer_types"]) != ["hybrid"] * layers:
        raise ValueError("layer_types and num_hidden_layers differ")
    hd = int(config["head_dim"])
    held = int(config["num_experts"])
    ep_size, ep_rank = int(config.get("ep_size", 1)), int(config.get("ep_rank", 0))
    rope = config["rope_parameters"]["hybrid"]
    return {
        "L": layers, "V": int(config["vocab_size"]),
        "H": int(config["hidden_size"]), "hd": hd,
        "heads": int(config["num_attention_heads"]),
        "KV": int(config["num_key_value_heads"]),
        "taps0": int(config["cca_time0"]), "taps1": int(config["cca_time1"]),
        "rope": rope, "rotary": int(hd * rope.get("partial_rotary_factor", 1)),
        "held": held, "experts": held * ep_size, "first": held * ep_rank,
        "F": int(config["moe_intermediate_size"]),
        "R": int(config["router_hidden_size"]),
        "eps": float(config["rms_norm_eps"]),
        "std": float(config.get("initializer_range", 0.02)),
    }


def param_table(c: dict) -> dict:
    """name -> (shape, init kind). ``out``: normal, smaller by sqrt(2 L): the
    projections that write into the residual stream, two a layer. ``conv``:
    uniform within 1 / sqrt(fan in), torch's ``Conv1d`` default.
    ``router_hidden``: normal, 1 / sqrt(fan in) (the hidden layers then pass
    what they read at its own size: logits 0.3 apart from token to token, the
    chosen probability about 0.1; at 0.02 they are 0.008 apart and a few AdamW
    steps turn the routing over), every column centred over its input rows;
    ``router_out``: the same, centred both ways within each chip's block of
    outputs (the skip with the last chip's). What
    the router's layers read are gelu's outputs, which have a positive mean,
    and an uncentred draw turns that mean into an offset of its own on every
    output's logit: one layer then sends 2 to 5 times the even share to one
    expert and this chip draws 125k to 172k slots an update by the seed. The
    published model balances its routing by the bias ``beta``, which is held
    at zero here; the centred draw is the seeded stand-in, as
    ``nemotron_h_f32``'s centred down projections are: every token's logits
    add up to zero over each chip's outputs, so each chip draws its share."""
    groups = c["heads"] + c["KV"]
    wide, outputs = groups * c["hd"], c["experts"] + 1
    table = {"head": ((c["V"], c["H"]), "normal"),
             "final_norm": ((c["H"],), "ones")}
    for i in range(c["L"]):
        p = f"l{i}."
        table.update({
            p + "attn_norm": ((c["H"],), "ones"),
            p + "wq": ((c["H"], c["heads"] * c["hd"]), "normal"),
            p + "wk": ((c["H"], c["KV"] * c["hd"]), "normal"),
            p + "wv": ((c["H"], c["KV"] * c["hd"]), "normal"),
            p + "conv0": ((c["taps0"], wide), "conv"),
            p + "conv0_b": ((wide,), "zeros"),
            p + "conv1": ((c["taps1"], groups, c["hd"], c["hd"]), "conv"),
            p + "conv1_b": ((groups, c["hd"]), "zeros"),
            p + "tau": ((c["KV"],), "ones"),
            p + "wo": ((c["heads"] * c["hd"], c["H"]), "out"),
            p + "mlp_norm": ((c["H"],), "ones"),
            p + "wd": ((c["H"], c["R"]), "normal"),
            p + "bd": ((c["R"],), "zeros"),
            p + "rnorm": ((c["R"],), "ones"),
            p + "w1": ((c["R"], c["R"]), "router_hidden"),
            p + "b1": ((c["R"],), "zeros"),
            p + "w2": ((c["R"], c["R"]), "router_hidden"),
            p + "b2": ((c["R"],), "zeros"),
            p + "w3": ((c["R"], outputs), "router_out"),
            p + "beta": ((outputs,), "zeros"),
            p + "w_gu": ((c["held"], c["H"], 2 * c["F"]), "normal"),
            p + "w_down": ((c["held"], c["F"], c["H"]), "out")})
        if i:  # the first layer has no state before it
            table[p + "gamma"] = ((c["R"],), "ones")
        for merge in ("ma_", "mm_"):
            for part in MERGES:
                table[p + merge + part] = (
                    (c["H"],), "ones" if part[0] == "s" else "zeros")
    return table


def seeded_params(key, c: dict) -> dict:
    """Every weight from the seed, on the device, in one traced function."""
    out = {}
    for index, (name, (shape, kind)) in enumerate(sorted(param_table(c).items())):
        draw = jax.random.fold_in(key, index)
        if kind in ("ones", "zeros"):
            out[name] = jnp.full(shape, float(kind == "ones"), jnp.float32)
        elif kind == "conv":  # fan in: the taps, times the channels mixed
            bound = 1.0 / math.sqrt(shape[0] * (shape[2] if len(shape) == 4 else 1))
            out[name] = jax.random.uniform(draw, shape, jnp.float32, -bound, bound)
        elif kind == "router_hidden":
            w = jax.random.normal(draw, shape, jnp.float32) / math.sqrt(shape[0])
            out[name] = w - jnp.mean(w, axis=0, keepdims=True)
        elif kind == "router_out":
            w = jax.random.normal(draw, shape, jnp.float32) / math.sqrt(shape[0])
            edges = list(range(0, c["experts"], c["held"]))[1:]  # a chip's block
            out[name] = jnp.concatenate([
                b - jnp.mean(b, axis=0, keepdims=True)
                - jnp.mean(b, axis=1, keepdims=True) + jnp.mean(b)
                for b in jnp.split(w, edges, axis=1)], axis=1)
        else:
            std = c["std"] / (1.0 if kind == "normal" else math.sqrt(2 * c["L"]))
            out[name] = std * jax.random.normal(draw, shape, jnp.float32)
    return out


# ---------------------------------------------------------------- the parts

def before(x, steps: int = 1):
    """x [B, S, ...] as it stood ``steps`` positions earlier; zeros before
    the row's first position."""
    if steps == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:, :steps]), x[:, :-steps]], axis=1)


def merge(p, prefix, x, y):
    return (p[prefix + "sx"] * x + p[prefix + "bx"]  # assumed: the merge's form
            + p[prefix + "sy"] * y + p[prefix + "by"])


def latent_qk(p, prefix, c, h, precision):
    """h [B, S, H] -> (q [B, S, n, d], k [B, S, KV, d]) after the two
    convolutions, the q-k mean and the norm; before the rotary."""
    batch, seq, _ = h.shape
    heads, kv, hd = c["heads"], c["KV"], c["hd"]
    q0 = _dense(h, p[prefix + "wq"], precision)
    k0 = _dense(h, p[prefix + "wk"], precision)
    z = jnp.concatenate([q0, k0], axis=-1)  # assumed: q and k pass together
    z1 = p[prefix + "conv0_b"]
    for tap in range(c["taps0"]):  # the last tap is the current position
        z1 = z1 + p[prefix + "conv0"][tap] * before(z, c["taps0"] - 1 - tap)
    z1 = z1.reshape(batch, seq, heads + kv, hd)
    z2 = p[prefix + "conv1_b"]
    for tap in range(c["taps1"]):  # assumed: one d x d matrix a tap and head
        then, w = before(z1, c["taps1"] - 1 - tap), p[prefix + "conv1"][tap]
        if precision == "fp8":
            then, w = _rounded(then, -1), _rounded(w, 1)
        z2 = z2 + jnp.einsum("bsgi,gio->bsgo", then, w, precision="highest")
    q0 = q0.reshape(batch, seq, kv, heads // kv, hd)
    k0 = k0.reshape(batch, seq, kv, hd)
    mq = (q0 + k0[:, :, :, None]) / 2          # assumed: the mean per group
    mk = (jnp.mean(q0, axis=3) + k0) / 2
    q = z2[:, :, :heads] + mq.reshape(batch, seq, heads, hd)
    k = z2[:, :, heads:] + mk
    length = lambda t: jnp.sqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True))
    q = q * math.sqrt(hd) / length(q)          # assumed: sqrt(d), no epsilon
    k = k * math.sqrt(hd) / length(k) * p[prefix + "tau"][:, None]
    return q, k


def values(p, prefix, c, h, precision):
    """[B, S, KV, d]: the second half of the heads read the token before."""
    half = c["KV"] // 2 * c["hd"]
    wv = p[prefix + "wv"]
    now = _dense(h, wv[:, :half], precision)
    then = _dense(before(h), wv[:, half:], precision)  # assumed: which half
    return jnp.concatenate([now, then], axis=-1).reshape(
        h.shape[0], h.shape[1], c["KV"], c["hd"])


def cca(p, prefix, c, h, precision, block_rows: int = 512):
    """The layer's attention over h [B, S, H] (already normalised)."""
    batch, seq, _ = h.shape
    heads, kv, hd = c["heads"], c["KV"], c["hd"]
    q, k = latent_qk(p, prefix, c, h, precision)
    v = values(p, prefix, c, h, precision)
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
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(batch, seq + pad, heads * hd)[:, :seq]
    return _dense(ctx, p[prefix + "wo"], precision)


def router(p, prefix, c, h, state_before):
    """h [T, H], r_{l-1} [T, R] or None -> (r_l, e [T], p_e [T], p [T, E + 1]);
    float32 whatever the precision."""
    dot = lambda a, b: jnp.matmul(a, b, precision="highest")
    state = dot(h, p[prefix + "wd"]) + p[prefix + "bd"]
    if state_before is not None:  # assumed: before the norm, a gamma a channel
        state = state + p[prefix + "gamma"] * state_before
    u = _rms_norm(state, p[prefix + "rnorm"], c["eps"])
    u = _gelu(dot(u, p[prefix + "w1"]) + p[prefix + "b1"])  # assumed: gelu, two
    u = _gelu(dot(u, p[prefix + "w2"]) + p[prefix + "b2"])  # hidden layers
    probs = jax.nn.softmax(dot(u, p[prefix + "w3"]), axis=-1)
    chosen = jnp.argmax(  # assumed: beta a buffer at zero, outside the gradient
        probs + jax.lax.stop_gradient(p[prefix + "beta"]), axis=-1)
    weight = jnp.take_along_axis(probs, chosen[:, None], axis=-1)[:, 0]
    return state, chosen, weight, probs


def expert_layer(p, prefix, c, h, state_before, precision, held=None):
    """(output, r_l, chosen). The experts this share holds (``held``: a range
    of expert ids; the configuration's by default), one after the other, each
    over all tokens under its mask. The skip (id ``experts``) is no expert."""
    shape = h.shape
    h = h.reshape(-1, shape[-1])
    state, chosen, weight, _ = router(
        p, prefix, c, h,
        None if state_before is None else state_before.reshape(h.shape[0], -1))
    out = jnp.zeros_like(h)
    mine = range(c["first"], c["first"] + c["held"]) if held is None else held
    for e in mine:
        local = e - c["first"]
        out = out + jnp.where(chosen == e, weight, 0.0)[:, None] * glu(
            h, p[prefix + "w_gu"][local], p[prefix + "w_down"][local], precision)
    return (out.reshape(shape), state.reshape(shape[:-1] + (-1,)),
            chosen.reshape(shape[:-1]))


def forward(p: dict, c: dict, input_ids, precision: str = "f32"):
    """[B, S] ids -> (logits [B, S, V], [chosen output of each layer's router,
    [B, S]])."""
    x = p["head"][input_ids]
    state, routed = None, []
    for i in range(c["L"]):
        prefix = f"l{i}."

        def layer(p_, x_, state_, prefix=prefix):
            h = _rms_norm(x_, p_[prefix + "attn_norm"], c["eps"])
            x_ = merge(p_, prefix + "ma_", x_, cca(p_, prefix, c, h, precision))
            h = _rms_norm(x_, p_[prefix + "mlp_norm"], c["eps"])
            out, state_, chosen = expert_layer(p_, prefix, c, h, state_,
                                               precision)
            return merge(p_, prefix + "mm_", x_, out), state_, chosen

        x, state, chosen = jax.checkpoint(layer)(
            {k: v for k, v in p.items() if k.startswith(prefix)}, x, state)
        routed.append(chosen)
    x = _rms_norm(x, p["final_norm"], c["eps"])
    return _dense(x, p["head"].T, precision), routed


def next_token_loss(p, c, input_ids, precision: str = "f32"):
    """Mean cross entropy of position t against token t + 1 over the S - 1
    predicted positions of every row; and every layer's routing."""
    logits, routed = forward(p, c, input_ids, precision)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, input_ids[:, 1:, None], axis=-1)
    return -jnp.mean(picked), routed


# ------------------------------------------------------------------ AdamW

def decays(name: str, c: dict) -> bool:
    """Weight decay on the matrices (the tensors drawn at random); none on
    norms, merges, biases, temperatures."""
    return param_table(c)[name][1] not in ("ones", "zeros")


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
           first_gradient_to_compare: dict = None) -> dict:
    """Follow the first optimizer updates of a run from the same seed:
    ``nemotron_h_f32.follow``'s contract over this family's tensors (each
    update's loss, the first update's gradient norm per tensor before clipping
    with the global norm, the per-tensor norm of the parameters' change over
    all the updates, ``chosen``: what the first micro-batch's tokens chose in
    each layer, [tokens, 1], the skip included). Between gradient computations the two
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
                out["chosen"] = [np.asarray(r).reshape(-1, 1) for r in routed]
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
