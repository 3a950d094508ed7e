"""Plain reference for pretraining of the ``joyai_llm_flash`` family with its
multi-token-prediction objective: float32 ``jax.numpy`` at ``highest`` and
nothing else.

The layer equations, from the published ``config.json`` of
jdopensource/JoyAI-LLM-Flash (its keys are DeepSeek-V3's) and the DeepSeek-V2
and DeepSeek-V3 reports, which are the published description of the latent
attention and of the multi-token-prediction module; what no key fixes is
listed under ``assumed`` in ``benchmarks/configs/joyai-llm-flash.json`` and
lives in ONE line here (marked ``# assumed``). x is [B, S, H]; ``norm`` is
RMSNorm with the configuration's epsilon and a scale from one.

1. ``x <- x + attention(norm_1(x))``; ``x <- x + mlp(norm_2(x))``.
2. Attention, ``u = norm_1(x)``: ``c_q = norm(u W_qa)``, ``q = c_q W_qb`` on
   ``heads`` heads of ``[q_nope | q_rope]``; ``[c_kv | k_r] = u W_kva``,
   ``c_kv = norm(c_kv)``, ``[k_nope | v] = c_kv W_kvb`` a head. Rotary by
   interleaved pairs (2 i, 2 i + 1), angle ``position x theta^(-2 i / rope)``,
   on every head's ``q_rope`` and on the ONE ``k_r``. THE KEY IS BUILT: head
   i's key is ``[k_nope[i] | k_r]``, ``k_r`` repeated over the heads. ``s[t,
   j, i] = q[t, i] . k[j, i] / sqrt(nope + rope)`` for ``j <= t`` (no further
   scale: ``rope_scaling`` is null); ``o[t, i] = sum_j softmax_j(s) v[j, i]``;
   ``o W_o``. A block of query rows at a time under an explicit mask.
3. MLP: the first ``first_k_dense_replace`` layers ``(silu(h W_g) * (h W_u))
   W_d``; after them the routed layer: ``sc = sigmoid(h W_r)`` in float32,
   the ``top_k`` largest of ``sc + b`` choose (``b`` the balancing bias, held
   at zero, no gradient; one group, no group limit), weights ``sc_e / sum of
   the chosen sc`` x ``routed_scaling_factor``; experts and the ONE shared
   expert (ungated, on every token) of the same form. The reference is GIVEN
   THE SAME SHARE as the program: the experts ``[first, first + held)``; what
   the absent ones would add is left out. ``expert_layer(..., held=...)``
   takes any share, for the test that adds the shares up.
4. The multi-token-prediction module (depth 1): ``z_t = [norm_e(emb[id_{t+1}])
   ; norm_h(h_t)] W_eh`` with ``h_t`` the last layer's output before the final
   norm; one more block of the family (an expert layer) over ``z``; a final
   norm of its own; the SHARED head; its target at position t is token t + 2,
   positions 0 .. S - 3. The last position's ``id_{t+1}`` wraps to the row's
   first token: causal attention shows it to no counted position.
5. Embedding, final ``norm``, an untied head. Objective: mean next-token
   cross entropy + ``mtp_loss_coef`` x the mean next-next-token one. AdamW as
   ``nemotron_h_f32``'s.

It imports nothing of the program. Weights come from the seed by
``seeded_params``; the program is handed the same arrays through
``joyai_map``.

``precision``: ``f32`` is the reference proper; ``fp8`` is the control, the
step below what the configuration states: every dense and expert product with
e4m3 operands (``bert_f32._dense``), the core's products in bf16 and the two
latent norms' outputs rounded to bfloat16; the router stays in float32, as
the program keeps it. ``faults`` (tests and rehearsals only) plants a wrong
rule: ``FAULTS``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.bert_f32 import (PRECISIONS, _attn_einsum, _dense,
                                           key_from_seed)
from benchmarks.reference.laguna_f32 import glu, leaf_norms
from benchmarks.reference.nemotron_h_f32 import Recipe, learning_rate, route

__all__ = ["key_from_seed", "sizes", "seeded_params", "follow", "Recipe"]

MTP = "mtp"
# the down projections into the two latents and their norms
LATENT = ("wqa", "wkva", "q_norm", "kv_norm")
FAULTS = ("mtp_left_out", "mtp_input_shifted_back", "mtp_target_next",
          "latent_norms_left_out", "shared_key_first_head_only")


def sizes(config: dict) -> dict:
    """The sizes the reference needs, from the configuration file."""
    held = int(config["n_routed_experts"])
    ep_size, ep_rank = int(config.get("ep_size", 1)), int(config.get("ep_rank", 0))
    nope, turned = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    if int(config.get("qk_head_dim", nope + turned)) != nope + turned:
        raise ValueError("qk_head_dim is not qk_nope_head_dim + qk_rope_head_dim")
    if int(config.get("n_group", 1)) != 1 or config.get("rope_scaling"):
        raise ValueError("one routing group and no rotary scaling")
    return {
        "L": int(config["num_hidden_layers"]), "V": int(config["vocab_size"]),
        "H": int(config["hidden_size"]), "I": int(config["intermediate_size"]),
        "dense": int(config["first_k_dense_replace"]),
        "heads": int(config["num_attention_heads"]),
        "nope": nope, "rope": turned, "vd": int(config["v_head_dim"]),
        "qr": int(config["q_lora_rank"]), "kvr": int(config["kv_lora_rank"]),
        "theta": float(config["rope_theta"]),
        "held": held, "experts": held * ep_size, "first": held * ep_rank,
        "top_k": int(config["num_experts_per_tok"]),
        "F": int(config["moe_intermediate_size"]),
        "FS": int(config["n_shared_experts"]) * int(config["moe_intermediate_size"]),
        "route_scale": float(config["routed_scaling_factor"]),
        "norm_topk": bool(config.get("norm_topk_prob", True)),
        "eps": float(config["rms_norm_eps"]),
        "std": float(config.get("initializer_range", 0.02)),
        "mtp": int(config.get("num_nextn_predict_layers", 1)),
        "mtp_coef": float(config.get("mtp_loss_coef", 0.3)),  # assumed
    }


def block_table(c: dict, p: str, dense: bool) -> dict:
    """One block's tensors under the prefix ``p``."""
    qk, wide = c["nope"] + c["rope"], c["heads"] * c["vd"]
    table = {
        p + "attn_norm": ((c["H"],), "ones"),
        p + "mlp_norm": ((c["H"],), "ones"),
        p + "wqa": ((c["H"], c["qr"]), "normal"),
        p + "q_norm": ((c["qr"],), "ones"),
        p + "wqb": ((c["qr"], c["heads"] * qk), "normal"),
        p + "wkva": ((c["H"], c["kvr"] + c["rope"]), "normal"),
        p + "kv_norm": ((c["kvr"],), "ones"),
        p + "wkvb": ((c["kvr"], c["heads"] * (c["nope"] + c["vd"])), "normal"),
        p + "wo": ((wide, c["H"]), "out")}
    if dense:
        table.update({p + "w13": ((c["H"], 2 * c["I"]), "normal"),
                      p + "w2": ((c["I"], c["H"]), "out")})
    else:
        table.update({
            p + "router": ((c["H"], c["experts"]), "normal"),
            p + "router_bias": ((c["experts"],), "zeros"),
            p + "w_gu": ((c["held"], c["H"], 2 * c["F"]), "normal"),
            p + "w_down": ((c["held"], c["F"], c["H"]), "out"),
            p + "shared_gu": ((c["H"], 2 * c["FS"]), "normal"),
            p + "shared_down": ((c["FS"], c["H"]), "out")})
    return table


def param_table(c: dict) -> dict:
    """name -> (shape, init kind). ``out``: normal, smaller by sqrt(2 x the
    blocks kept, the module's among them): the projections that write into the
    residual stream, two a block."""
    table = {"emb": ((c["V"], c["H"]), "normal"),
             "final_norm": ((c["H"],), "ones"),
             "head": ((c["H"], c["V"]), "normal")}
    for i in range(c["L"]):
        table.update(block_table(c, f"l{i}.", i < c["dense"]))
    if c["mtp"]:
        p = MTP + "."
        table.update(block_table(c, p, False))
        table.update({p + "enorm": ((c["H"],), "ones"),
                      p + "hnorm": ((c["H"],), "ones"),
                      p + "weh": ((2 * c["H"], c["H"]), "normal"),
                      p + "final_norm": ((c["H"],), "ones")})
    return table


def seeded_params(key, c: dict) -> dict:
    """Every weight from the seed, on the device, in one traced function."""
    out = {}
    for index, (name, (shape, kind)) in enumerate(sorted(param_table(c).items())):
        if kind in ("ones", "zeros"):
            out[name] = (jnp.ones if kind == "ones" else jnp.zeros)(
                shape, jnp.float32)
            continue
        std = c["std"] / (math.sqrt(2 * (c["L"] + c["mtp"]))
                          if kind == "out" else 1.0)
        out[name] = std * jax.random.normal(
            jax.random.fold_in(key, index), shape, jnp.float32)
    return out


# ---------------------------------------------------------------- the parts

def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w


def turn_pairs(x, theta: float):
    """x [B, S, heads, d]: the pairs (2 i, 2 i + 1) of the whole last axis
    turned by position x theta^(-2 i / d) (``rope_interleave``); the inverse
    frequencies float64 on the host, rounded once."""
    d = x.shape[-1]
    inv_freq = (theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)).astype(
        np.float32)
    angle = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
             * jnp.asarray(inv_freq)[None, :])[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                      odd * jnp.cos(angle) + even * jnp.sin(angle)],
                     axis=-1).reshape(x.shape)


def latent_norm(x, w, c, precision, faults):
    if "latent_norms_left_out" in faults:
        return x
    out = norm(x, w, c["eps"])
    if precision != "f32":  # the control: the latent norms in bfloat16
        out = out.astype(jnp.bfloat16).astype(jnp.float32)
    return out


def attention(p, prefix, c, u, precision, block_rows: int = 512, faults=()):
    """One block's latent attention over u [B, S, H] (already normalised)."""
    batch, seq, _ = u.shape
    heads, nope, turned, vd = c["heads"], c["nope"], c["rope"], c["vd"]
    c_q = latent_norm(_dense(u, p[prefix + "wqa"], precision),
                      p[prefix + "q_norm"], c, precision, faults)
    q = _dense(c_q, p[prefix + "wqb"], precision).reshape(
        batch, seq, heads, nope + turned)
    kva = _dense(u, p[prefix + "wkva"], precision)
    c_kv = latent_norm(kva[..., :c["kvr"]], p[prefix + "kv_norm"], c,
                       precision, faults)
    kv = _dense(c_kv, p[prefix + "wkvb"], precision).reshape(
        batch, seq, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate(
        [q[..., :nope], turn_pairs(q[..., nope:], c["theta"])], axis=-1)
    k_r = turn_pairs(kva[..., None, c["kvr"]:], c["theta"])  # ONE head
    shared = jnp.repeat(k_r, heads, axis=2)
    if "shared_key_first_head_only" in faults:  # the other heads' share lost
        shared = jnp.concatenate(
            [k_r, jax.lax.stop_gradient(shared[:, :, 1:])], axis=2)
    k = jnp.concatenate([k_nope, shared], axis=-1)  # the key, built
    rows = min(block_rows, seq)
    pad = (-seq) % rows
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))

    @jax.checkpoint
    def block(start, q_block):
        scores = _attn_einsum("bqhd,bkhd->bhqk", q_block, k, precision
                              ) / math.sqrt(nope + turned)
        seen = (jnp.arange(seq)[None, :]
                <= start + jnp.arange(rows)[:, None])
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return _attn_einsum("bhqk,bkhd->bqhd", probs, v, precision)

    starts = jnp.arange(0, seq + pad, rows)
    q_blocks = jnp.moveaxis(
        q.reshape(batch, -1, rows, heads, nope + turned), 1, 0)
    ctx = jax.lax.map(lambda args: block(*args), (starts, q_blocks))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(batch, seq + pad, heads, vd)[:, :seq]
    return _dense(ctx.reshape(batch, seq, heads * vd), p[prefix + "wo"],
                  precision)


def expert_layer(p, prefix, c, x, precision, held=None, shared: bool = True):
    """(output, chosen). The experts this share holds (``held``: a range of
    expert ids whose weights ``p`` holds in order; the configuration's by
    default), one after the other, each over all tokens under its mask; plus
    the shared expert."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    chosen, w = route(p, prefix, c, x)  # sigmoid, the bias, norm_topk, scale
    mine = range(c["first"], c["first"] + c["held"]) if held is None else held

    @jax.checkpoint  # (an expert's intermediates are made again in the backward)
    def term(w_gu, w_down, e):
        weight = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        return weight[:, None] * glu(x, w_gu, w_down, precision)

    out, _ = jax.lax.scan(
        lambda total, expert: (total + term(*expert), None), jnp.zeros_like(x),
        (p[prefix + "w_gu"], p[prefix + "w_down"], jnp.asarray(list(mine))))
    if shared:  # one shared expert on every token, ungated
        out = out + glu(x, p[prefix + "shared_gu"], p[prefix + "shared_down"],
                        precision)
    return out.reshape(shape), chosen


def block(p, prefix, c, x, dense: bool, precision, faults=()):
    """(x after one block, its routing or None), rematerialized."""
    def run(p_, x_):
        x_ = x_ + attention(p_, prefix, c, norm(
            x_, p_[prefix + "attn_norm"], c["eps"]), precision, faults=faults)
        h = norm(x_, p_[prefix + "mlp_norm"], c["eps"])
        if dense:
            return x_ + glu(h, p_[prefix + "w13"], p_[prefix + "w2"],
                            precision), None
        out, chosen = expert_layer(p_, prefix, c, h, precision)
        return x_ + out, chosen

    return jax.checkpoint(run)(
        {k: v for k, v in p.items() if k.startswith(prefix)}, x)


def hidden(p: dict, c: dict, input_ids, precision: str = "f32", faults=()):
    """[B, S] ids -> (the final norm's output, the module's final norm's
    output or None, [chosen experts of each routed layer, the module's
    last])."""
    embedded = p["emb"][input_ids]
    x, routed = embedded, []
    for i in range(c["L"]):
        x, chosen = block(p, f"l{i}.", c, x, i < c["dense"], precision, faults)
        if chosen is not None:
            routed.append(chosen)
    further = None
    if c["mtp"] and "mtp_left_out" not in faults:
        pre = MTP + "."
        move = 1 if "mtp_input_shifted_back" in faults else -1
        z = _dense(jnp.concatenate(  # assumed: the embedding's half first
            [norm(jnp.roll(embedded, move, axis=1), p[pre + "enorm"], c["eps"]),
             norm(x, p[pre + "hnorm"], c["eps"])],  # assumed: h before the norm
            axis=-1), p[pre + "weh"], precision)
        z, chosen = block(p, pre, c, z, False, precision, faults)
        routed.append(chosen)
        further = norm(z, p[pre + "final_norm"], c["eps"])
    return norm(x, p["final_norm"], c["eps"]), further, routed


def shifted_token_loss(x, head, input_ids, precision, shift: int = 1,
                       piece: int = 2048):
    """Mean cross entropy of position t against token t + ``shift`` over the
    S - ``shift`` predicted positions of every row, from a final norm's
    output x: the head and the log-softmax a piece of the row at a time, each
    made again in the backward."""
    batch, seq, _ = x.shape
    piece = piece if seq % piece == 0 else seq
    target = jnp.roll(input_ids, -shift, axis=-1)
    counted = jnp.broadcast_to(jnp.arange(seq) < seq - shift, target.shape)
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
    return total / (batch * (seq - shift))


def objective(p, c, input_ids, precision: str = "f32", faults=()):
    """(next-token loss + ``mtp_coef`` x the module's next-next-token loss,
    (the module's loss alone, every routed layer's routing))."""
    with jax.default_matmul_precision("highest"):  # (a TPU's float32 default
        # is lower; every product here also asks for it by name)
        x, further, routed = hidden(p, c, input_ids, precision, faults)
        loss = shifted_token_loss(x, p["head"], input_ids, precision)
        second = jnp.zeros(())
        if further is not None:
            second = shifted_token_loss(
                further, p["head"], input_ids, precision,
                shift=1 if "mtp_target_next" in faults else 2)
            loss = loss + c["mtp_coef"] * second  # assumed: lambda 0.3
    return loss, (second, routed)


# ------------------------------------------------------------------ AdamW

def decays(name: str, c: dict) -> bool:
    """Weight decay on the matrices; none on the norms' scales or the
    router's balancing bias."""
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
           first_gradient_to_compare: dict = None, faults=()) -> dict:
    """Follow the first optimizer updates of a run from the same seed:
    ``keye_f32.follow``'s contract over this family's tensors (each update's
    loss, the WHOLE objective; the first update's gradient norm per tensor
    before clipping with the global norm, the per-tensor norm of the
    parameters' change over all the updates, ``chosen``: the experts the
    first micro-batch's tokens chose in each routed layer, the module's last)
    and ``mtp_loss``: each update's second term alone. A micro-batch's rows
    pass ONE AT A TIME, as there. ``faults`` plants a wrong rule."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    if set(faults) - set(FAULTS):
        raise ValueError(f"faults are {FAULTS}, not {faults}")
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
    out = {"loss": [], "mtp_loss": [], "grad_global_norm": None,
           "grad_norms": None}
    for index, upd in enumerate(updates):
        micro, rows = upd.shape[:2]
        grads, loss, second, first = None, 0.0, 0.0, []
        for m, row in np.ndindex(micro, rows):
            (share, (term, routed)), g = grad(
                p, jnp.asarray(upd[m, row:row + 1], jnp.int32))
            if index == 0 and m == 0:
                first.append([np.asarray(r) for r in routed])
            grads = (jax.tree_util.tree_map(lambda x: x / (micro * rows), g)
                     if grads is None else add(grads, g, 1.0 / (micro * rows)))
            del g
            loss += float(share) / (micro * rows)
            second += float(term) / (micro * rows)
        if index == 0:  # the first micro-batch's routing, row after row
            out["chosen"] = [np.concatenate(layer) for layer in zip(*first)]
        out["loss"].append(loss)
        out["mtp_loss"].append(second)
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
