"""The gated delta rule (Yang, Kautz & Hatamizadeh 2024, arXiv:2412.06464): a
linear-attention recurrence whose state each token first DECAYS, then READS
with its key, and corrects by the difference between what it read and the
token's value. A value head's state S is [Dk (key), Dv (value)], zero at the
row's start; token t with key k_t, value v_t, query q_t, log-decay g_t <= 0
and write strength beta_t in (0, 1):

    S <- exp(g_t) S;   r = S^T k_t;   S <- S + k_t (beta_t (v_t - r))^T;
    o_t = S^T q_t

so the state's transition is ``exp(g_t) (I - beta_t k_t k_t^T)``, a matrix and
not a number a head: ``ops/ssm.py``'s scans (decay and add) do not compute it.

``gated_delta_rule`` computes it in chunks of ``chunk`` tokens (the WY form).
With G the running sum of g inside a chunk and L_ij = exp(G_i - G_j) for
i >= j (0 above the diagonal), a chunk that starts from the state S0 has

    A = strictly_lower(diag(beta) (K K^T * L)),   T = (I + A)^-1,
    U = T diag(beta) V,   W = T diag(beta exp(G)) K,
    V' = U - W S0                              (the corrected values)
    O  = diag(exp(G)) Q S0 + (Q K^T * L) V'
    S1 = exp(G_last) S0 + K^T diag(exp(G_last - G)) V'

Everything before ``V'`` is computed for all chunks at once; the state then
passes from chunk to chunk in a ``lax.scan``. Decays are only ever
exponentiated as differences ``G_i - G_j`` with i >= j, as ``G_i`` itself
(the distance to the chunk's start) or as ``G_last - G_i``: all <= 0, so a
head whose decay reaches e^-20 a token gives zeros, never an inf, and the
masked half of L is masked BEFORE the exponential, so its gradient is finite
too. The state, the decays and the inverse are float32 (the inverse's
products at ``highest``); the other products take operands in ``q``'s dtype
and sum in float32, and U, W and the chunks' scores are kept in that dtype.
The backward is autodiff's, except through the inverse, which has its own
rule (``dA = -T^T dT T^T``: the forward's five squarings are not kept), and
with each step of the scan over chunks rematerialized (it keeps the state a
chunk started from and computes the chunk's products again).

The inverse of a unit lower-triangular [C, C] matrix is the finite Neumann
product ``(I + X)(I + X^2)(I + X^4)...`` with X = -A (A is nilpotent: the
series ends at X^(C-1)): log2(C) steps of two [C, C] products each, all
chunks and heads at once, where forward substitution takes C dependent steps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST


def delta_chunks(batch: int, seq: int, chunk: int = CHUNK) -> int:
    """Chunks one call over [batch, seq] runs (from shapes alone)."""
    return batch * -(-seq // chunk)


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular a [..., C, C], float32."""
    size = a.shape[-1]
    power = -a
    inverse = jnp.eye(size, dtype=a.dtype) + power
    reach = 2  # the series so far holds the powers below ``reach``
    while reach < size:
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inverse = inverse + jnp.matmul(inverse, power, precision=_HIGHEST)
        reach *= 2
    return inverse


def _inverse_fwd(a):
    inverse = unit_lower_inverse(a)
    return inverse, inverse


def _inverse_bwd(inverse, d_inverse):
    t = jnp.swapaxes(inverse, -1, -2)
    d_a = -jnp.matmul(jnp.matmul(t, d_inverse, precision=_HIGHEST), t,
                      precision=_HIGHEST)
    size = d_a.shape[-1]
    return (jnp.where(jnp.tri(size, k=-1, dtype=bool), d_a, 0.0),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _chunked(t, chunk: int, heads: int):
    """[B, S, H, ...] -> [B, Hk, R, N, C, ...] with H = Hk x R, heads of one
    key head side by side (value head h reads key head h // R)."""
    batch, seq = t.shape[:2]
    t = t.reshape((batch, seq // chunk, chunk, heads, t.shape[2] // heads)
                  + t.shape[3:])
    return jnp.moveaxis(t, (1, 2), (3, 4))


def gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """q, k [B, S, Hk, Dk] (normed and scaled by the caller), v [B, S, Hv, Dv]
    with Hv a multiple of Hk (key head j serves value heads j Hv/Hk ...),
    g (log-decay, <= 0) and beta [B, S, Hv] float32 -> o [B, S, Hv, Dv] in
    q's dtype. A length that is no multiple of ``chunk`` is padded at the end
    with k = 0, beta = 0, g = 0 (no write, no decay), which leaves the
    positions before it untouched.

    The rows of a batch pass ONE AT A TIME, each rematerialized
    (``lax.map`` over ``jax.checkpoint``): a row of 8192 positions at the
    published widths keeps about 1.5 GB between its forward and its backward
    (the decays, the inverse, U, W, the scores and a state a chunk), and two
    rows at once do not fit beside 10 GB of training state. The price is one
    more forward of the rule in the backward."""
    if q.shape[0] == 1:
        return _rule_of_rows(q, k, v, g, beta, chunk)
    one_row = jax.checkpoint(lambda *row: _rule_of_rows(
        *(t[None] for t in row), chunk)[0])
    return jax.lax.map(lambda row: one_row(*row), (q, k, v, g, beta))


def _rule_of_rows(q, k, v, g, beta, chunk: int):
    batch, seq, key_heads, _ = k.shape
    value_heads, dv = v.shape[2:]
    dtype = q.dtype
    pad = (-seq) % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                            for t in (q, k, v, g, beta))
    f32 = jnp.float32
    dot = lambda spec, x, y: jnp.einsum(
        spec, x.astype(dtype), y.astype(dtype), preferred_element_type=f32)
    qc = _chunked(q, chunk, key_heads)[:, :, 0]          # [B, Hk, N, C, Dk]
    kc = _chunked(k, chunk, key_heads)[:, :, 0]
    # beta V, rounded once, before the chunks' layout: [B, Hk, R, N, C, Dv]
    vc = _chunked((v.astype(f32) * beta.astype(f32)[..., None]).astype(dtype),
                  chunk, key_heads)
    gc = _chunked(g.astype(f32), chunk, key_heads)       # [B, Hk, R, N, C]
    bc = _chunked(beta.astype(f32), chunk, key_heads)

    run = jnp.cumsum(gc, axis=-1)                        # G
    lower = jnp.tri(chunk, dtype=bool)
    span = run[..., :, None] - run[..., None, :]         # G_i - G_j
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, span, 0.0)), 0.0)
    kk = dot("bhncd,bhnmd->bhncm", kc, kc)[:, :, None]   # shared by R heads
    a = jnp.where(jnp.tri(chunk, k=-1, dtype=bool),
                  bc[..., None] * kk * decay, 0.0)
    t = unit_lower_inverse(a)                            # [B, Hk, R, N, C, C]
    # (u and w are products' operands again below: kept in the operands'
    # dtype, as the chunks' scores are)
    u = dot("bhrncm,bhrnmd->bhrncd", t, vc).astype(dtype)
    into = jnp.exp(run)                                  # chunk start -> i
    w = dot("bhrncm,bhrnmd->bhrncd", t, kc.astype(f32)[:, :, None]
            * (bc * into)[..., None]).astype(dtype)
    qk = (dot("bhncd,bhnmd->bhncm", qc, kc)[:, :, None] * decay).astype(dtype)
    out_of = jnp.exp(run[..., -1:] - run)                # i -> chunk end
    whole = jnp.exp(run[..., -1])                        # over the chunk

    @jax.checkpoint  # the backward keeps a chunk's state, not its products
    def step(state, xs):
        q_n, k_n, u_n, w_n, qk_n, into_n, out_n, whole_n = xs
        corrected = u_n - dot("bhrcd,bhrde->bhrce", w_n, state)
        out = (dot("bhcd,bhrde->bhrce", q_n, state) * into_n[..., None]
               + dot("bhrcm,bhrme->bhrce", qk_n, corrected))
        state = state * whole_n[..., None, None] + dot(
            "bhcd,bhrce->bhrde", k_n, corrected * out_n[..., None])
        return state, out.astype(dtype)

    first = lambda x, axis: jnp.moveaxis(x, axis, 0)     # chunks lead
    ratio = value_heads // key_heads
    state = jnp.zeros((batch, key_heads, ratio, k.shape[-1], dv), f32)
    _, out = jax.lax.scan(step, state, (
        first(qc, 2), first(kc, 2), first(u, 3), first(w, 3), first(qk, 3),
        first(into, 3), first(out_of, 3), first(whole, 3)))
    # [N, B, Hk, R, C, Dv] -> [B, S, Hv, Dv]
    out = jnp.moveaxis(out, (0, 4), (1, 2)).reshape(
        batch, seq + pad, value_heads, dv)
    return out[:, :seq]
