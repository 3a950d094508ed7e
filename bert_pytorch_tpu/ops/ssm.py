"""The state-space mixers' cores: the causal depthwise convolution both share,
Mamba-2's selective recurrence as a chunked scan in matmul form (the SSD form
of Dao & Gu 2024, arXiv:2405.21060) with the gated group RMSNorm that follows
it, and Mamba-1's selective scan (``selective_scan``, at the end of the file:
a decay for every channel and state, so no matmul form; Pallas kernels,
``ops/pallas/selective_scan.py``).

The recurrence, per head h with state [P, N] (P = head dim, N = state size):

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T        y_t = h_t C_t + D x_t

``ssd_chunked_scan`` computes it in chunks of ``chunk`` positions, in matmul
form: inside a chunk the outputs are a masked, decay-weighted (C B^T) X
product; each chunk's contribution to the state is one more product; the
float32 state passes from chunk to chunk; and the carried state reaches the
chunk's outputs through a last product. Matmul operands are in the
activations' dtype with float32 accumulation; the decays (cumulative sums of
dt A and their exponentials) and the carried state are float32. One
algorithm, two realisations, chosen by the shapes the call sees
(``ssd_kernel_chunks``), as ``ops/moe.py grouped_dot`` chooses ``gmm``:

* where chunks and states are whole lane tiles and a head is half a tile or a
  whole one (``ops/pallas/ssd_scan.py fits``: the published Mamba-2 widths),
  a ``jax.custom_vjp`` over the Pallas kernels ``ssd_scan_fwd`` and
  ``ssd_scan_bwd``: a chunk's decay matrix and the running state stay in
  VMEM, x, B, C and y keep the layouts of the ops round the scan, and XLA is
  left with the cumulative sum of dt A over each chunk (and its transpose in
  the backward) on [B, S, H] float32;
* every other shape (the tiny models of the CPU tests) in plain XLA
  (``_ssd``), with autodiff's backward; it is also the tests' second opinion
  on the kernels.

Everything runs under ``jax.named_scope`` names that ``pretrain.CAUSAL_LM_SCOPES``
lists (``ssm_conv``, ``ssd_scan``, ``ssm_gate_norm``), so a profiler trace
tells the recurrence from the projections round it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def causal_depthwise_conv(x, weight, bias):
    """x [B, S, C], weight [K, C], bias [C] -> [B, S, C]:
    ``out_t = bias + sum_k weight[k] x_{t - (K - 1) + k}`` with zeros before
    the sequence (torch ``Conv1d(groups=C, padding=K-1)`` cut to S)."""
    with jax.named_scope("ssm_conv"):
        taps, seq = weight.shape[0], x.shape[1]
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        out = bias.astype(x.dtype)
        for k in range(taps):
            out = out + padded[:, k:k + seq, :] * weight[k].astype(x.dtype)
        return out


def ssd_chunked_scan(x, dt, a, b, c, d, chunk: int):
    """The selective recurrence over a whole sequence.

    x [B, S, H, P]; dt [B, S, H] float32, already positive; a [H] float32,
    negative; b, c [B, S, G, N] with H a multiple of G (heads of one group
    share B and C); d [H]. Returns y [B, S, H, P] in x's dtype. A length that
    is no multiple of ``chunk`` is padded at the end with dt = 0 (decay 1, no
    input), which leaves the positions before it untouched.
    """
    with jax.named_scope("ssd_scan"):
        if ssd_kernel_chunks(x, b, c, chunk):
            return _ssd_kernels(x, dt, a, b, c, d, chunk)
        return _ssd(x, dt, a, b, c, d, chunk)


def ssd_kernel_chunks(x, b, c, chunk: int) -> int:
    """Chunks the Pallas kernels run in one pass of ``ssd_chunked_scan`` over
    these operands (shapes and dtypes are all it looks at): rows x chunks a
    row, or 0 where the call takes the XLA form."""
    from bert_pytorch_tpu.ops.pallas.ssd_scan import fits

    if not (x.dtype == b.dtype == c.dtype and fits(x.shape, b.shape, chunk)):
        return 0
    return x.shape[0] * scan_chunks(x.shape[1], chunk)


def _pad_positions(t, pad: int):
    """[B, S, ...] with ``pad`` zero positions after the last."""
    return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)
                   ) if pad else t


def _ssd(x, dt, a, b, c, d, chunk):
    batch, seq, heads, hdim = x.shape
    groups, state = b.shape[2], b.shape[3]
    per = heads // groups
    dtype = x.dtype
    pad = (-seq) % chunk
    x, dt, b, c = (_pad_positions(t, pad) for t in (x, dt, b, c))
    n = (seq + pad) // chunk
    # Heads before positions, so that the two minor axes of every large
    # tensor are (position, position), (position, width) or (width, state).
    xs = x.reshape(batch, n, chunk, groups, per, hdim).transpose(0, 1, 3, 4, 2, 5)
    dts = dt.astype(jnp.float32).reshape(
        batch, n, chunk, groups, per).transpose(0, 1, 3, 4, 2)
    bs = b.reshape(batch, n, chunk, groups, state).transpose(0, 1, 3, 2, 4)
    cs = c.reshape(batch, n, chunk, groups, state).transpose(0, 1, 3, 2, 4)
    # log decays, cumulative inside the chunk: float32, [B, n, G, per, Q]
    cum = jnp.cumsum(
        dts * a.reshape(groups, per, 1).astype(jnp.float32), axis=-1)
    xdt = xs.astype(jnp.float32) * dts[..., None]

    # 1. inside the chunk:
    #    y_i += sum_{j <= i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j
    cb = jnp.einsum("bngis,bngjs->bngij", cs, bs,
                    preferred_element_type=jnp.float32)
    gap = cum[..., :, None] - cum[..., None, :]          # [B, n, G, per, i, j]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, gap, -jnp.inf))
    weights = cb[:, :, :, None] * decay
    y = jnp.einsum("bnghij,bnghjp->bnghip", weights.astype(dtype),
                   xdt.astype(dtype), preferred_element_type=jnp.float32)

    # 2. what each chunk adds to the state at its end: [B, n, G, per, P, N]
    to_end = jnp.exp(cum[..., -1:] - cum)
    added = jnp.einsum("bngjs,bnghjp->bnghps", bs,
                       (xdt * to_end[..., None]).astype(dtype),
                       preferred_element_type=jnp.float32)
    # 3. the state from chunk to chunk: a float32 carry
    whole = jnp.exp(cum[..., -1])                          # [B, n, G, per]

    def carry_on(h, step):
        keep, add = step
        return h * keep[..., None, None] + add, h

    h0 = jnp.zeros((batch, groups, per, hdim, state), jnp.float32)
    _, before = jax.lax.scan(
        carry_on, h0, (whole.swapaxes(0, 1), added.swapaxes(0, 1)))
    before = before.swapaxes(0, 1)
    # 4. the carried state's part of the outputs
    y = y + jnp.einsum("bngis,bnghps->bnghip", cs, before.astype(dtype),
                       preferred_element_type=jnp.float32
                       ) * jnp.exp(cum)[..., None]
    y = y + xs.astype(jnp.float32) * d.reshape(
        groups, per, 1, 1).astype(jnp.float32)
    y = y.transpose(0, 1, 4, 2, 3, 5).reshape(batch, n * chunk, heads, hdim)
    return y[:, :seq].astype(dtype)


def _chunk_sums(t, chunk, reverse=False):
    """[B, S, H] -> the running sum inside each chunk of ``chunk`` positions
    (``reverse``: from the chunk's end, the transpose)."""
    batch, seq, heads = t.shape
    return jax.lax.cumsum(t.reshape(batch, seq // chunk, chunk, heads),
                          axis=2, reverse=reverse).reshape(t.shape)


def _ssd_operands(x, dt, a, b, c, d, chunk, *more):
    """The kernels' operands: positions padded up to whole chunks (and
    ``more``, which is dy, with them), x, B and C flat as the layers round
    the scan hold them, dt and the log decays' running sum float32 with the
    heads on 128 lanes, the sum again with the heads on rows, D on lanes."""
    from bert_pytorch_tpu.ops.pallas.ssd_scan import LANES, SUBLANES

    batch, seq, heads, hdim = x.shape
    pad = (-seq) % chunk
    flat = lambda t: _pad_positions(t, pad).reshape(batch, seq + pad, -1)
    on_lanes = lambda t: jnp.pad(t, ((0, 0),) * (t.ndim - 1)
                                 + ((0, LANES - heads),))
    dt = _pad_positions(dt.astype(jnp.float32), pad)
    cum = _chunk_sums(dt * a.astype(jnp.float32), chunk)
    cum_rows = jnp.pad(cum.swapaxes(1, 2),
                       ((0, 0), (0, (-heads) % SUBLANES), (0, 0)))
    d_lanes = jnp.broadcast_to(on_lanes(d.astype(jnp.float32)),
                               (SUBLANES, LANES))
    return (flat(x), on_lanes(dt), on_lanes(cum), cum_rows, flat(b), flat(c),
            d_lanes) + tuple(flat(t) for t in more)


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd_kernels(x, dt, a, b, c, d, chunk):
    return _ssd_kernels_fwd(x, dt, a, b, c, d, chunk)[0]


def _ssd_kernels_fwd(x, dt, a, b, c, d, chunk):
    from bert_pytorch_tpu.ops.pallas.ssd_scan import ssd_forward

    y, starts = ssd_forward(*_ssd_operands(x, dt, a, b, c, d, chunk),
                            x.shape[2], b.shape[2], chunk)
    # the operands are kept as they came; the backward pads them again
    return (y[:, :x.shape[1]].reshape(x.shape), (x, dt, a, b, c, d, starts))


def _ssd_kernels_bwd(chunk, residuals, dy):
    from bert_pytorch_tpu.ops.pallas.ssd_scan import ssd_backward

    x, dt, a, b, c, d, starts = residuals
    (batch, seq, heads, hdim), f32 = x.shape, jnp.float32
    operands = _ssd_operands(x, dt, a, b, c, d, chunk, dy.astype(x.dtype))
    dx, ddt, dcum, dcum_rows, db, dc, dd = ssd_backward(
        *operands[:7], starts, operands[7], heads, b.shape[2], chunk)
    # cum is the running sum of dt a: its cotangent runs back through the sum
    dlog = _chunk_sums(dcum[..., :heads] + dcum_rows[:, :heads].swapaxes(1, 2),
                       chunk, reverse=True)[:, :seq]
    ddt = ddt[:, :seq, :heads] + dlog * a.astype(f32)
    da = jnp.sum(dlog * dt.astype(f32), axis=(0, 1))
    return (dx[:, :seq].reshape(x.shape), ddt.astype(dt.dtype),
            da.astype(a.dtype), db[:, :seq].reshape(b.shape),
            dc[:, :seq].reshape(c.shape),
            jnp.sum(dd.reshape(batch, heads, hdim), axis=(0, 2)).astype(
                d.dtype))


_ssd_kernels.defvjp(_ssd_kernels_fwd, _ssd_kernels_bwd)


def gated_group_rms_norm(y, z, weight, groups: int, eps: float):
    """``RMSNorm_groups(y * silu(z)) * weight``: the mean square is taken
    over each of ``groups`` equal slices of the last axis (float32)."""
    with jax.named_scope("ssm_gate_norm"):
        gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        shaped = gated.reshape(gated.shape[:-1] + (groups, -1))
        normed = shaped * jax.lax.rsqrt(
            jnp.mean(jnp.square(shaped), axis=-1, keepdims=True) + eps)
        return (normed.reshape(gated.shape) * weight).astype(y.dtype)


# ---------------------------------------------------------------- Mamba-1

def selective_scan(u, dt, a, b, c, chunk: int = 128):
    """Mamba-1's recurrence over a whole sequence (Gu & Dao 2023,
    arXiv:2312.00752), a decay for every (channel, state) pair:

        h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * u_t) (x) B_t    y_t = h_t C_t

    u [B, S, D]; dt [B, S, D], already positive; a [D, N], negative; b, c
    [B, S, N]. Returns y [B, S, D] float32 (the caller adds ``D * u``). State
    and decays are float32 whatever the operands' dtype (they are cast on the
    way into the kernels and kept for the backward as they came; the
    cotangents go back in the operands' dtypes); the [S, D, N] states
    never reach HBM, in either pass: the forward kernel carries the state
    through chunks of ``chunk`` positions and keeps it at each chunk's start,
    the backward kernel makes a chunk's states again from there
    (``ops/pallas/selective_scan.py``). D is a multiple of 128, ``chunk`` of
    8; a length that is no multiple of ``chunk`` is padded at the end with dt
    = 0 (decay 1, no input), which leaves the positions before it untouched.
    A row runs ``scan_chunks(S, chunk)`` chunks a pass.
    """
    with jax.named_scope("selective_scan"):
        return _selective_scan(u, dt, a, b, c, chunk)


def scan_chunks(seq: int, chunk: int) -> int:
    """Chunks one row's scan runs in one pass."""
    return -(-seq // chunk)


def _wide(t):
    """[B, S, N] -> [B, S, N, 128]: each number over one tile of lanes."""
    from bert_pytorch_tpu.ops.pallas.selective_scan import LANES

    return jnp.broadcast_to(t[..., None], t.shape + (LANES,))


def _padded(chunk, *tensors):
    """Float32, and the positions padded up to whole chunks."""
    pad = (-tensors[0].shape[1]) % chunk
    return [_pad_positions(t.astype(jnp.float32), pad) for t in tensors]


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _selective_scan(u, dt, a, b, c, chunk):
    return _selective_scan_fwd(u, dt, a, b, c, chunk)[0]


def _selective_scan_fwd(u, dt, a, b, c, chunk):
    from bert_pytorch_tpu.ops.pallas.selective_scan import scan_forward

    seq = u.shape[1]
    up, dtp, bp, cp = _padded(chunk, u, dt, b, c)
    y, starts = scan_forward(up, dtp, _wide(bp), _wide(cp),
                             a.astype(jnp.float32).T, chunk)
    # the operands are kept as they came (bfloat16 where the caller's are)
    return y[:, :seq], (u, dt, a, b, c, starts)


def _selective_scan_bwd(chunk, residuals, dy):
    from bert_pytorch_tpu.ops.pallas.selective_scan import scan_backward

    u, dt, a, b, c, starts = residuals
    seq = u.shape[1]
    up, dtp, bp, cp, dyp = _padded(chunk, u, dt, b, c, dy)
    du, ddt, db, dc, da = scan_backward(
        up, dtp, _wide(bp), _wide(cp), a.astype(jnp.float32).T, starts, dyp,
        chunk)
    return (du[:, :seq].astype(u.dtype), ddt[:, :seq].astype(dt.dtype),
            jnp.sum(da, axis=(0, 1)).T.astype(a.dtype),
            jnp.sum(db, axis=-1)[:, :seq].astype(b.dtype),
            jnp.sum(dc, axis=-1)[:, :seq].astype(c.dtype))


_selective_scan.defvjp(_selective_scan_fwd, _selective_scan_bwd)
