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

Decays are only ever exponentiated as differences ``G_i - G_j`` with i >= j,
as ``G_i`` itself (the distance to the chunk's start) or as ``G_last - G_i``:
all <= 0, so a head whose decay reaches e^-20 a token gives zeros, never an
inf, and the masked half of L is masked BEFORE the exponential, so its
gradient is finite too. The state, the decays and the inverse are float32
(the inverse's products at ``highest``); the other products take operands in
``q``'s dtype and sum in float32, and U, W and the chunks' scores are kept in
that dtype. One algorithm, two realisations, chosen by the shapes and dtypes
the call sees (``kernel_chunks``), as ``ops/ssm.py`` chooses for its scan:

* where the chunk is 64, keys and values are 128 wide in one dtype and the
  value heads come in pairs a key head (``ops/pallas/delta_rule.py fits``:
  the published widths), a ``jax.custom_vjp`` over the Pallas kernels
  ``delta_rule_fwd`` and ``delta_rule_bwd``, every row of the batch in one
  call: a chunk's decays, A, T, U, W, scores and corrected values stay in
  VMEM and so does the running state; q, k, v and o keep the layouts of the
  convolution and the gated norm; XLA is left with the running sum of g over
  each chunk (and its transpose in the backward) on [B, S, Hv] float32.
  Between forward and backward the call keeps its operands and the state each
  chunk started from ([B, S / 64, Hv, 128, 128] float32: 268 MB a row of
  8192 at 32 value heads); the backward makes a chunk's factors again.
* every other shape (float32 runs with mixed dtypes, chunks of 8, the CPU
  tests' tiny widths) in plain XLA (``_rule_of_rows``): everything before
  ``V'`` for all chunks at once, then the state from chunk to chunk in a
  ``lax.scan``. Its backward is autodiff's, except through the inverse, which
  has its own rule (``dA = -T^T dT T^T``: the forward's five squarings are
  not kept), and with each step of the scan over chunks rematerialized (it
  keeps the state a chunk started from and computes the chunk's products
  again); the rows of a batch pass one at a time, each rematerialized
  (``gated_delta_rule``). It is also the tests' second opinion on the
  kernels.

The inverse of a unit lower-triangular [C, C] matrix is, in the XLA form, the
finite Neumann product ``(I + X)(I + X^2)(I + X^4)...`` with X = -A (A is
nilpotent: the series ends at X^(C-1)): log2(C) steps of two [C, C] products
each, all chunks and heads at once, where forward substitution takes C
dependent steps; the kernels substitute, sixteen rows at a time
(``ops/pallas/delta_rule.py unit_lower_inverse_pairs``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bert_pytorch_tpu.ops.ssm import _chunk_sums, _pad_positions

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

    One algorithm, two realisations, chosen by the shapes and dtypes the
    call sees (``kernel_chunks``): the Pallas kernels where they fit, every
    row at once (between forward and backward they keep the operands and the
    state each chunk started from, 64 KB a value head and chunk); else the
    XLA form, the rows of a batch ONE AT A TIME, each rematerialized
    (``lax.map`` over ``jax.checkpoint``): a row of 8192 positions at the
    published widths keeps about 1.5 GB there (the decays, the inverse, U, W,
    the scores and a state a chunk), at the price of one more forward of the
    rule in the backward."""
    if kernel_chunks(q, k, v, chunk):
        return _rule_kernels(q, k, v, g, beta, chunk)
    if q.shape[0] == 1:
        return _rule_of_rows(q, k, v, g, beta, chunk)
    one_row = jax.checkpoint(lambda *row: _rule_of_rows(
        *(t[None] for t in row), chunk)[0])
    return jax.lax.map(lambda row: one_row(*row), (q, k, v, g, beta))


def kernel_chunks(q, k, v, chunk: int = CHUNK) -> int:
    """Chunks the Pallas kernels run in one pass of ``gated_delta_rule`` over
    these operands (shapes and dtypes are all it looks at): ``delta_chunks``,
    or 0 where the call takes the XLA form."""
    from bert_pytorch_tpu.ops.pallas.delta_rule import fits

    if not (q.dtype == k.dtype == v.dtype and q.shape == k.shape
            and fits(k.shape, v.shape, chunk)):
        return 0
    return delta_chunks(q.shape[0], q.shape[1], chunk)


def _rows_of_pairs(t, chunk: int):
    """[B, S, H] -> [B, S / chunk, H / 2, 2 chunk]: a chunk's positions on
    the lanes, heads 2p and 2p + 1 side by side on row p."""
    batch, seq, heads = t.shape
    return t.reshape(batch, seq // chunk, chunk, heads).swapaxes(2, 3).reshape(
        batch, seq // chunk, heads // 2, 2 * chunk)


def _kernel_operands(q, k, v, g, beta, chunk, *more):
    """The kernels' operands: positions padded up to whole chunks (and
    ``more``, which is do, with them), q, k and v flat as the layers round
    the rule hold them, the log decays' running sum G and beta float32 with
    the heads on 128 lanes, both again with a chunk's positions on the
    lanes."""
    from bert_pytorch_tpu.ops.pallas.delta_rule import LANES

    batch, seq = q.shape[:2]
    pad = (-seq) % chunk
    padded = lambda t: _pad_positions(t, pad)
    flat = lambda t: padded(t).reshape(batch, seq + pad, -1)
    on_lanes = lambda t: jnp.pad(
        t, ((0, 0), (0, 0), (0, LANES - t.shape[2])))
    run = _chunk_sums(padded(g.astype(jnp.float32)), chunk)
    beta = padded(beta.astype(jnp.float32))
    return (flat(q), flat(k), flat(v), on_lanes(run), on_lanes(beta),
            _rows_of_pairs(run, chunk), _rows_of_pairs(beta, chunk)
            ) + tuple(flat(t) for t in more)


def _forward(q, k, v, g, beta, chunk, keep):
    """[o, and with ``keep`` the state each chunk started from]."""
    from bert_pytorch_tpu.ops.pallas.delta_rule import delta_rule_forward

    out, *starts = delta_rule_forward(
        *_kernel_operands(q, k, v, g, beta, chunk), key_heads=k.shape[2],
        value_heads=v.shape[2], keep=keep)
    return [out[:, :q.shape[1]].reshape(v.shape)] + starts


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule_kernels(q, k, v, g, beta, chunk):
    return _forward(q, k, v, g, beta, chunk, keep=False)[0]


def _rule_kernels_fwd(q, k, v, g, beta, chunk):
    out, starts = _forward(q, k, v, g, beta, chunk, keep=True)
    # the operands are kept as they came; the backward pads them again
    return out, (q, k, v, g, beta, starts)


def _rule_kernels_bwd(chunk, residuals, do):
    from bert_pytorch_tpu.ops.pallas.delta_rule import delta_rule_backward

    q, k, v, g, beta, starts = residuals
    seq, heads = v.shape[1:3]
    operands = _kernel_operands(q, k, v, g, beta, chunk, do.astype(q.dtype))
    dq, dk, dv, drun, dbeta, drun_rows, dbeta_rows = delta_rule_backward(
        *operands[:7], starts, operands[7], key_heads=k.shape[2],
        value_heads=heads)
    batch, chunks = drun_rows.shape[:2]
    # the two forms each of G and beta came in, back in one: [B, S, Hv]
    whole = lambda on_lanes, on_rows: (
        jnp.sum(on_lanes, axis=1)[..., :heads] + on_rows.reshape(
            batch, chunks, heads, chunk).swapaxes(2, 3).reshape(
                batch, chunks * chunk, heads))
    # G is the running sum of g: its cotangent runs back through the sum
    dg = _chunk_sums(whole(drun, drun_rows), chunk, reverse=True)[:, :seq]
    return (dq[:, :seq].reshape(q.shape), dk[:, :seq].reshape(k.shape),
            dv[:, :seq].reshape(v.shape), dg.astype(g.dtype),
            whole(dbeta, dbeta_rows)[:, :seq].astype(beta.dtype))


_rule_kernels.defvjp(_rule_kernels_fwd, _rule_kernels_bwd)


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
