"""Plain reference for next-token pretraining of the ``phi4flash`` family:
float32 ``jax.numpy`` at ``highest`` and nothing else.

The layer equations, from the published ``config.json`` of
microsoft/Phi-4-mini-flash-reasoning and the papers its parts come from; what
the config leaves open is listed under ``assumed`` in
``benchmarks/configs/phi-4-mini-flash-reasoning.json`` and lives in ONE line
here (marked ``# assumed``). ``x`` is [S, H]; ``LN`` is LayerNorm with weight
and bias; no rotary and no positional embedding anywhere.

* Layer: ``h = x + Mix(LN1(x))``; ``x' = h + W2 (silu(g) * u)``, ``[g, u] =
  W1 LN2(h)`` (the gate's half first). Final ``LN``; logits ``LN(x) E^T``
  with ``E`` the embedding (the head is tied).
* ``Mix`` by the layer's kind (``layer_types``): ``mamba`` /
  ``mamba_memory``: Mamba-1 (Gu & Dao 2023): ``[u, z] = W_in x``; ``u =
  silu(conv(u))`` (causal, depthwise, 4 taps, a bias); ``[r, B, C] = W_x u``;
  ``dt = softplus(W_dt r + b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(dt_t (x)
  A) * h_{t-1} + (dt_t * u_t) (x) B_t``; ``y_t = h_t C_t + D * u_t``; out
  ``W_out (y * silu(z))``. The state ``h`` is [D, N]; the scan is a plain loop
  over positions, in blocks that are rematerialized. ``mamba_memory`` also
  hands ``m = y`` (before the gate) to the later layers.
* ``gmu``: out ``W_b (silu(W_a x) * m)``.
* ``sliding_attention`` / ``full_attention`` / ``cross_attention``:
  differential attention (Ye et al. 2024). ``q`` as [S, P, 2, d] gives the
  pairs (q1, q2); ``k`` as [S, KP, 2, d] gives (k1, k2); ``v`` as [S, KP, 2 d]
  (a key pair's two value heads joined); key pair j serves the P / KP query
  pairs from j P / KP on. ``A_i = softmax(q_i k_i^T / sqrt(d) + mask) v``;
  ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0``, ``lam0 = 0.8 - 0.6
  exp(-0.3 l)`` with ``l`` the layer's PUBLISHED index; ``o = RMSNorm_2d(A1 -
  lam A2) * (1 - lam0)``; out ``concat(o) Wo + bo``. ``Wqkv``'s bias is kept
  as three tensors (``bq``, ``bk``, ``bv``): a bias on the keys moves every
  score of a row alike, so its gradient is zero but for rounding, and the
  comparison leaves such a tensor's change out. Position i sees ``j <=
  i``, under the window also ``i - j < window``. The full layer's ``k`` and
  ``v`` are handed on; a cross layer has ``Wq`` and ``Wo`` alone and reads
  them. In blocks of query rows under an explicit mask.
* The reference is GIVEN THE SAME SHARE as the program: the heads and the
  slice of the vocabulary the configuration holds; ``bo`` only on the rank
  that holds the first heads.
* Loss: mean next-token cross entropy. AdamW as ``nemotron_h_f32``'s, written
  here for these tensors: decay on the matrices, none on norms, biases,
  ``A_log``, ``D``, ``dt_bias`` and the lambdas.

It imports nothing of the program. Weights come from the seed by
``seeded_params``; the program is handed the same arrays through
``phi4flash_map``.

``precision``: ``f32`` is the reference proper; ``fp8`` is the control, the
step below the bf16 the configuration states: every dense product with e4m3
operands (``bert_f32._dense``), the attention products in bf16 and the
scan's state rounded to bfloat16 after every position.

``faults`` (tests only) names parts to leave out, so that a test can show the
program is NOT equal to a reference without them: ``window``, ``lambda`` (the
``lam A2`` term), ``subln``, ``memory_gate``, ``cross_kv`` (the cross layer
reads the FIRST attention layer's keys and values), ``skip`` (``D * u``),
``tied`` (a head of its own).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.bert_f32 import (PRECISIONS, _attn_einsum, _dense,
                                           key_from_seed)
from benchmarks.reference.nemotron_h_f32 import Recipe, learning_rate

__all__ = ["key_from_seed", "sizes", "seeded_params", "follow", "Recipe"]

MAMBA = ("mamba", "mamba_memory")


def layer_rule(n: int) -> list:
    """The published rule for a model of ``n`` layers (n % 4 == 0)."""
    half = n // 2
    return [("mamba" if l % 2 == 0 else "sliding_attention") if l < half else
            "mamba_memory" if l == half else
            "full_attention" if l == half + 1 else
            "gmu" if l % 2 == 0 else "cross_attention" for l in range(n)]


def sizes(config: dict) -> dict:
    """The sizes the reference needs, from the configuration file."""
    layers = int(config["num_hidden_layers"])
    published = int(config.get("published_num_hidden_layers", layers))
    indices = [int(i) for i in config.get("layer_indices", range(layers))]
    kinds = list(config.get("layer_types")
                 or [layer_rule(published)[i] for i in indices])
    if not len(kinds) == len(indices) == layers:
        raise ValueError("the per-layer lists and num_hidden_layers differ")
    heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    tp = int(config.get("tp_size", 1))
    hidden = int(config["hidden_size"])
    return {
        "L": layers, "V": int(config["vocab_size"]), "H": hidden,
        "I": int(config["intermediate_size"]), "kinds": kinds,
        "indices": indices, "heads": heads, "KV": kv,
        "hd": hidden // (heads * tp), "bias_out": int(config.get("tp_rank", 0)) == 0,
        "window": int(config["sliding_window"]),
        "inner": int(config.get("mamba_expand", 2)) * hidden,
        "N": int(config.get("mamba_d_state", 16)),
        "K": int(config.get("mamba_d_conv", 4)),
        "R": int(config.get("mamba_dt_rank") or -(-hidden // 16)),
        "dt_min": float(config.get("time_step_min", 0.001)),
        "dt_max": float(config.get("time_step_max", 0.1)),
        "lambda_std": float(config.get("lambda_std", 0.1)),
        "eps": float(config["layer_norm_eps"]),
        "std": float(config.get("initializer_range", 0.02)),
    }


def param_table(c: dict) -> dict:
    """name -> (shape, init kind). ``out``: normal, smaller by sqrt(2 L): the
    projections that write into the residual stream, two a layer."""
    h, hd, inner = c["H"], c["hd"], c["inner"]
    table = {"emb": ((c["V"], h), "normal"),
             "final_norm_w": ((h,), "ones"), "final_norm_b": ((h,), "zeros")}
    for i, kind in enumerate(c["kinds"]):
        p = f"l{i}."
        table.update({
            p + "ln1_w": ((h,), "ones"), p + "ln1_b": ((h,), "zeros"),
            p + "ln2_w": ((h,), "ones"), p + "ln2_b": ((h,), "zeros"),
            p + "fc1": ((h, 2 * c["I"]), "normal"),
            p + "fc2": ((c["I"], h), "out")})
        if kind in MAMBA:
            table.update({
                p + "in_proj": ((h, 2 * inner), "normal"),
                p + "conv_w": ((c["K"], inner), "conv"),
                p + "conv_b": ((inner,), "zeros"),
                p + "x_proj": ((inner, c["R"] + 2 * c["N"]), "normal"),
                p + "dt_proj": ((c["R"], inner), "normal"),
                p + "dt_bias": ((inner,), "dt_bias"),
                p + "A_log": ((inner, c["N"]), "a_log"),
                p + "D": ((inner,), "ones"),
                p + "out_proj": ((inner, h), "out")})
        elif kind == "gmu":
            table.update({p + "gmu_in": ((h, inner), "normal"),
                          p + "gmu_out": ((inner, h), "out")})
        else:
            wide = c["heads"] * hd
            if kind == "cross_attention":
                table.update({p + "wq": ((h, wide), "normal"),
                              p + "bq": ((wide,), "zeros")})
            else:  # the bias in three tensors: the keys' has no gradient
                table.update({
                    p + "wqkv": ((h, wide + 2 * c["KV"] * hd), "normal"),
                    p + "bq": ((wide,), "zeros"),
                    p + "bk": ((c["KV"] * hd,), "zeros"),
                    p + "bv": ((c["KV"] * hd,), "zeros")})
            table.update({p + "wo": ((wide, h), "out"),
                          p + "subln": ((2 * hd,), "ones")})
            if c["bias_out"]:
                table[p + "bo"] = ((h,), "zeros")
            for name in ("lq1", "lk1", "lq2", "lk2"):
                table[p + name] = ((hd,), "lambda")
    return table


def seeded_params(key, c: dict) -> dict:
    """Every weight from the seed, on the device, in one traced function."""
    out = {}
    for index, (name, (shape, kind)) in enumerate(sorted(param_table(c).items())):
        k = jax.random.fold_in(key, index)
        if kind in ("normal", "out"):
            std = c["std"] / (1.0 if kind == "normal" else math.sqrt(2 * c["L"]))
            out[name] = std * jax.random.normal(k, shape, jnp.float32)
        elif kind == "lambda":  # assumed: normal(0, 0.1), the paper's
            out[name] = c["lambda_std"] * jax.random.normal(k, shape, jnp.float32)
        elif kind == "conv":  # assumed: torch's Conv1d default over 4 taps
            bound = 1.0 / math.sqrt(shape[0])
            out[name] = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        elif kind == "a_log":  # assumed: A = -(1..N) on every channel
            out[name] = jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[1] + 1, dtype=jnp.float32)), shape)
        elif kind == "dt_bias":  # assumed: inverse softplus of a log-uniform step
            step = jnp.exp(jax.random.uniform(k, shape, jnp.float32) * (
                math.log(c["dt_max"]) - math.log(c["dt_min"]))
                + math.log(c["dt_min"]))
            out[name] = step + jnp.log(-jnp.expm1(-step))
        else:
            out[name] = (jnp.ones if kind == "ones" else jnp.zeros)(
                shape, jnp.float32)
    return out


# ---------------------------------------------------------------- the parts

def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def selective_scan(u, dt, a, b, c, precision: str = "f32", block: int = 128):
    """The literal recurrence, position by position. u, dt [B, S, D] (dt after
    softplus), a [D, N] negative, b / c [B, S, N] -> y [B, S, D] (without the
    skip term)."""
    batch, seq, channels = u.shape
    pad = (-seq) % block
    if pad:  # dt = 0: the state passes unchanged, and no output is kept
        widths = lambda t: ((0, 0), (0, pad), (0, 0))
        u, dt, b, c = (jnp.pad(t, widths(t)) for t in (u, dt, b, c))

    def token(h, inputs):
        u_t, dt_t, b_t, c_t = inputs          # [B,D] [B,D] [B,N] [B,N]
        h = (jnp.exp(dt_t[..., None] * a) * h
             + (dt_t * u_t)[..., None] * b_t[:, None, :])
        if precision != "f32":  # the control: the state kept in bfloat16
            h = h.astype(jnp.bfloat16).astype(jnp.float32)
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def tokens_of_block(h, inputs):
        return jax.lax.scan(token, h, inputs)

    def blocks(t):  # [B, S, ...] -> [S / block, block, B, ...]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((-1, block) + t.shape[1:])

    h0 = jnp.zeros((batch, channels, a.shape[1]), jnp.float32)
    _, ys = jax.lax.scan(tokens_of_block, h0,
                         (blocks(u), blocks(dt), blocks(b), blocks(c)))
    return jnp.moveaxis(ys.reshape((-1,) + ys.shape[2:]), 0, 1)[:, :seq]


def mamba_mixer(p, prefix, c, x, precision, faults=()):
    """(output, y): y = the scan's output with the skip term, before the
    gate."""
    seq = x.shape[1]
    u, z = jnp.split(_dense(x, p[prefix + "in_proj"], precision), 2, axis=-1)
    padded = jnp.pad(u, ((0, 0), (c["K"] - 1, 0), (0, 0)))
    u = jax.nn.silu(p[prefix + "conv_b"] + sum(
        padded[:, k:k + seq] * p[prefix + "conv_w"][k] for k in range(c["K"])))
    r, b, cc = jnp.split(_dense(u, p[prefix + "x_proj"], precision),
                         [c["R"], c["R"] + c["N"]], axis=-1)
    dt = jax.nn.softplus(_dense(r, p[prefix + "dt_proj"], precision)
                         + p[prefix + "dt_bias"])
    y = selective_scan(u, dt, -jnp.exp(p[prefix + "A_log"]), b, cc, precision)
    if "skip" not in faults:
        y = y + p[prefix + "D"] * u
    return _dense(y * jax.nn.silu(z), p[prefix + "out_proj"], precision), y


def gated_memory_unit(p, prefix, x, memory, precision, faults=()):
    gate = jax.nn.silu(_dense(x, p[prefix + "gmu_in"], precision))
    if "memory_gate" in faults:
        gate = jnp.ones_like(gate)
    return _dense(gate * memory, p[prefix + "gmu_out"], precision)


def softmax_values(q, k, v, window, precision, block_rows: int = 512):
    """``softmax(q k^T / sqrt(d) + mask) v`` for q [B, S, M, d], k [B, S, M,
    d], v [B, S, M, dv], in blocks of query rows under an explicit mask."""
    batch, seq, maps, hd = q.shape
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
            seen &= i - j < window  # assumed: i - window < j <= i
        # (finite: a padded row past a short window sees no key at all)
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return _attn_einsum("bhqk,bkhd->bqhd", probs, v, precision)

    starts = jnp.arange(0, seq + pad, rows)
    q_blocks = jnp.moveaxis(q.reshape(batch, -1, rows, maps, hd), 1, 0)
    out = jax.lax.map(lambda args: block(*args), (starts, q_blocks))
    return jnp.moveaxis(out, 0, 1).reshape(
        batch, seq + pad, maps, v.shape[-1])[:, :seq]


def differential_attention(p, prefix, c, layer, x, precision, kept=None,
                           faults=()):
    """(output, (k, v)) of layer ``layer``'s attention over x [B, S, H]
    (already normalised); ``kept``: another layer's (k, v) to read."""
    batch, seq, _ = x.shape
    heads, kv, hd = c["heads"], c["KV"], c["hd"]
    window = c["window"] if c["kinds"][layer] == "sliding_attention" else None
    if "window" in faults:
        window = None
    if kept is None:
        q, k, v = jnp.split(_dense(x, p[prefix + "wqkv"], precision),
                            [heads * hd, (heads + kv) * hd], axis=-1)
        q, k, v = (q + p[prefix + "bq"], k + p[prefix + "bk"],
                   v + p[prefix + "bv"])
        k = k.reshape(batch, seq, kv // 2, 2, hd)
        v = v.reshape(batch, seq, kv // 2, 2 * hd)  # the pair's two values joined
    else:
        q = _dense(x, p[prefix + "wq"], precision) + p[prefix + "bq"]
        k, v = kept
    q = q.reshape(batch, seq, heads // 2, 2, hd)
    serves = (heads // 2) // k.shape[2]  # query pairs a key pair serves
    wide = lambda t: jnp.repeat(t, serves, axis=2)
    a1 = softmax_values(q[..., 0, :], wide(k[..., 0, :]), wide(v), window, precision)
    a2 = softmax_values(q[..., 1, :], wide(k[..., 1, :]), wide(v), window, precision)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * c["indices"][layer])
    lam = (jnp.exp(jnp.sum(p[prefix + "lq1"] * p[prefix + "lk1"]))
           - jnp.exp(jnp.sum(p[prefix + "lq2"] * p[prefix + "lk2"])) + lam0)
    o = a1 if "lambda" in faults else a1 - lam * a2
    if "subln" not in faults:
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                              + c["eps"]) * p[prefix + "subln"]
    o = (o * (1.0 - lam0)).reshape(batch, seq, heads * hd)
    out = _dense(o, p[prefix + "wo"], precision)
    if c["bias_out"]:
        out = out + p[prefix + "bo"]
    return out, (k, v)


def mlp(p, prefix, x, precision):
    g, u = jnp.split(_dense(x, p[prefix + "fc1"], precision), 2, axis=-1)
    return _dense(jax.nn.silu(g) * u, p[prefix + "fc2"], precision)  # assumed: gate first


def forward(p: dict, c: dict, input_ids, precision: str = "f32", faults=()):
    """[B, S] ids -> logits [B, S, V]."""
    x = p["emb"][input_ids]
    carried = {}
    for i, kind in enumerate(c["kinds"]):
        prefix = f"l{i}."

        def layer(p_, x_, carried_, i=i, kind=kind, prefix=prefix):
            h = layer_norm(x_, p_[prefix + "ln1_w"], p_[prefix + "ln1_b"], c["eps"])
            if kind in MAMBA:
                out, y = mamba_mixer(p_, prefix, c, h, precision, faults)
                if kind == "mamba_memory":
                    carried_ = {**carried_, "memory": y}
            elif kind == "gmu":
                out = gated_memory_unit(p_, prefix, h, carried_["memory"],
                                        precision, faults)
            else:
                kept = carried_["kv"] if kind == "cross_attention" else None
                out, kv = differential_attention(
                    p_, prefix, c, i, h, precision, kept, faults)
                writes = (kind == "full_attention" if "cross_kv" not in faults
                          else kept is None and "kv" not in carried_)
                if writes:
                    carried_ = {**carried_, "kv": kv}
            x_ = x_ + out
            h = layer_norm(x_, p_[prefix + "ln2_w"], p_[prefix + "ln2_b"], c["eps"])
            return x_ + mlp(p_, prefix, h, precision), carried_

        x, carried = jax.checkpoint(layer)(
            {k: v for k, v in p.items() if k.startswith(prefix)}, x, carried)
    x = layer_norm(x, p["final_norm_w"], p["final_norm_b"], c["eps"])
    head = jnp.roll(p["emb"], 1, axis=0) if "tied" in faults else p["emb"]
    return _dense(x, head.T, precision)


def next_token_loss(p, c, input_ids, precision: str = "f32", faults=()):
    """Mean cross entropy of position t against token t + 1 over the S - 1
    predicted positions of every row."""
    logits = forward(p, c, input_ids, precision, faults)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, input_ids[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


# ------------------------------------------------------------------ AdamW

def decays(name: str, c: dict) -> bool:
    """Weight decay on the matrices (the projections, the embedding, the
    convolution's taps); none on norms, biases, ``A_log``, ``D``, ``dt_bias``
    and the lambdas."""
    return param_table(c)[name][1] in ("normal", "out", "conv")


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


@jax.jit
def leaf_norms(tree: dict) -> dict:
    """L2 norm of every tensor."""
    return {name: jnp.sqrt(jnp.sum(jnp.square(v))) for name, v in tree.items()}


# ---------------------------------------------------------- following a run

def follow(seed: int, config: dict, recipe: Recipe, updates: list,
           precision: str = "f32", keep_first_gradient: bool = False,
           first_gradient_to_compare: dict = None) -> dict:
    """Follow the first optimizer updates of a run from the same seed:
    ``nemotron_h_f32.follow``'s contract over this family's tensors (each
    update's loss, the first update's gradient norm per tensor before clipping
    with the global norm, the per-tensor norm of the parameters' change over
    all the updates; no routing: the family routes nothing). Between gradient
    computations the two moments wait on the host, as there."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    c = sizes(config)
    key = key_from_seed(seed)
    make = jax.jit(lambda k: seeded_params(k, c))
    p = make(key)
    mu = nu = None  # zeros until the first update; on the host between updates
    grad = jax.jit(jax.value_and_grad(
        lambda p_, ids: next_token_loss(p_, c, ids, precision)))
    adamw = make_adamw_update(recipe, c)
    add = jax.jit(lambda a, b, s: jax.tree_util.tree_map(
        lambda x, y: x + s * y, a, b), donate_argnums=(0,))
    out = {"loss": [], "grad_global_norm": None, "grad_norms": None}
    for index, upd in enumerate(updates):
        micro = upd.shape[0]
        grads, loss = None, 0.0
        for m in range(micro):
            share, g = grad(p, jnp.asarray(upd[m], jnp.int32))
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
