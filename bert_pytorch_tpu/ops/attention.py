"""Multi-head dot-product attention core.

TPU-native replacement for the reference's unfused score/softmax/context chain
(src/modeling.py:403-429 ``BertSelfAttention``): batched einsums land on the
MXU, the softmax runs in fp32 for bf16 safety, and the additive mask uses the
reference's ``(1 - mask) * -10000`` bias convention (modeling.py:862-870).

``backend='pallas'`` routes to the fused flash-style kernel with in-kernel
dropout (ops/pallas/attention.py); the XLA path below materializes the
[B, H, S, S] probabilities and their dropout mask. Rule of thumb, which
``backend='auto'`` follows (``resolve_backend``): 'xla' for phase 1
(seq <= 128), 'pallas' for phase 2 (seq >= 256) and anything longer. What
each path costs on the chip is in PERF.md (sections 5 and 6), from the
benchmark's cells.

Across remat: with dropout on, the XLA path names its boolean keep mask
(``ops/remat.py`` ``KEEP_MASK``) and ``remat='dots'`` keeps it, B x H x S x S
bytes a layer and micro-batch (16.7 MB at the phase-1 shape 64 x 16 x 128 x
128, 0.40 GB over 24 layers), so the backward pass does not draw the random
words a second time. The mask grows with S squared: at seq 512 and
micro-batch 16 it is 67 MB a layer, 1.6 GB over 24 — a shape 'auto' never
sends down this path on a TPU; a caller who forces 'xla' there and needs the
memory back has ``remat='full'``, which keeps nothing.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from bert_pytorch_tpu.ops.dropout import keep_mask
from bert_pytorch_tpu.ops.remat import KEEP_MASK


def make_attention_bias(
    input_mask: jnp.ndarray,
    dtype=jnp.float32,
    sequence_ids: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """[B, S] {0,1} mask -> [B, 1, 1, S] additive bias, (1-m) * -10000.

    Parity with reference modeling.py:862-870 (``extended_attention_mask``).

    With ``sequence_ids`` ([B, S] int, 0 = pad, k = k-th packed sequence;
    data/packing.py), returns the BLOCK-DIAGONAL [B, 1, S, S] bias instead:
    position q may attend to position k iff both carry the same nonzero
    sequence id — the cross-contamination-free packing mask of Krell et al.
    2021 (arXiv:2107.02027). Padding is excluded by id 0, so ``input_mask``
    is redundant (and ignored) on this path.
    """
    if sequence_ids is not None:
        seg = sequence_ids
        same = (seg[:, :, None] == seg[:, None, :]) & (seg[:, :, None] > 0)
        bias = (1.0 - same.astype(jnp.float32)) * -10000.0
        return bias[:, None, :, :].astype(dtype)
    bias = (1.0 - input_mask.astype(jnp.float32)) * -10000.0
    return bias[:, None, None, :].astype(dtype)


def dot_product_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: jnp.ndarray | None = None,
    dropout_rng=None,
    dropout_rate: float = 0.0,
    deterministic: bool = True,
    backend: str = "xla",
    sequence_ids: jnp.ndarray | None = None,
    causal: bool = False,
    window: int | None = None,
    label: str = "",
) -> jnp.ndarray:
    """Attention over [B, S, H, D] query/key/value tensors.

    Returns [B, S, H, D]. Scores are scaled by 1/sqrt(D), D the width of the
    queries and keys, and softmaxed in fp32 (modeling.py:403-429's score path,
    bf16-safe). The values may be of another width than the queries and keys
    ([B, S, H, Dv]; the 'xla' and 'pallas' paths): wider (the differential
    attention's joined value heads) or narrower (latent attention: queries
    and keys of 128 + 64 turned dimensions over values of 128, models/joyai.py);
    the result is then [B, S, H, Dv]. The key comes in ONE part: a caller
    whose heads share a part of it (the latent attention's one turned key)
    builds each head's key first. ``label`` (static) is written into the
    Pallas kernels' names and changes nothing else.

    ``causal`` (static) lets position q attend to positions <= q only: a
    mask on the XLA path; on the Pallas path a static flag of the kernels,
    which then mask the tiles on the diagonal and skip those above it
    (ops/pallas/attention.py). ``window`` (static, with ``causal``, unpacked
    rows, the same two paths) narrows that to the ``window`` positions up to
    and including q: the same band as a mask on the XLA path, and on the
    Pallas path kernels whose loops follow the band (the tiles below it are
    skipped like those above the diagonal). ``k`` and ``v`` may have fewer heads than
    ``q`` (grouped-query attention, H a multiple of theirs): each key-value
    head then serves H / H_kv consecutive query heads; they are repeated here,
    before either path, and autodiff sums the repeats' gradients.

    ``sequence_ids`` ([B, S], 0 = pad) marks a PACKED batch
    (data/packing.py): on the XLA path the caller's ``bias`` is then the
    [B, 1, S, S] block-diagonal mask from :func:`make_attention_bias`; the
    Pallas path ignores that bias and regenerates the block-diagonal tile
    mask inside the kernel from the per-token id vectors, preserving its
    no-[B,H,S,S]-in-HBM property.

    Everything here runs under ``jax.named_scope("attention_core")`` (the
    XLA path's dropout under ``attention_dropout`` inside it), so a profiler
    trace tells the core from the projections round it on every backend.
    """
    with jax.named_scope("attention_core"):
        if k.shape[2] != q.shape[2]:
            if q.shape[2] % k.shape[2]:
                raise ValueError(
                    f"{q.shape[2]} query heads on {k.shape[2]} key-value heads")
            repeats = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, repeats, axis=2)
            v = jnp.repeat(v, repeats, axis=2)
        return _attention_core(q, k, v, bias, dropout_rng, dropout_rate,
                               deterministic, backend, sequence_ids, causal,
                               window, label)


def differential_attention(q, k, v, backend: str = "xla",
                           window: int | None = None, label: str = "diff"):
    """The two causal softmax maps of differential attention (Ye et al.
    2024, arXiv:2410.05258, in its flash form) in ONE call of the core.

    q [B, S, H, 2, D]: query pair j is (q[..., j, 0, :], q[..., j, 1, :]); k
    [B, S, KV, 2, D] likewise, H a multiple of KV; v [B, S, KV, Dv], one value
    a key PAIR (Dv = 2 D where the pair's two value heads are joined). Returns
    (A1, A2), each [B, S, H, Dv]: ``A_i = softmax(q_i k_i^T / sqrt(D) + mask)
    v``. The caller subtracts. Both maps are heads of one call: the first
    components of every pair, then the second, over values as wide as ``v``
    (the kernels take a value width of their own), so no score is computed
    twice and no value half is read apart."""
    heads = q.shape[2]
    both = lambda t: jnp.concatenate([t[..., 0, :], t[..., 1, :]], axis=2)
    out = dot_product_attention(
        both(q), both(k), jnp.concatenate([v, v], axis=2), backend=backend,
        causal=True, window=window, label=label)
    return out[:, :, :heads], out[:, :, heads:]


def resolve_backend(backend: str, seq: int, dropout: bool) -> str:
    """The path a call with ``backend`` takes at sequence length ``seq``.

    'auto' goes by the measured crossover: the fused kernel wins from seq
    ~256 up; below that the XLA path is faster. On the CPU backend (tests)
    the kernel would run in the Pallas interpreter, so auto never picks it
    there, and 'pallas' with ``dropout`` on computes attention on the XLA
    path (the hardware PRNG has no interpreter lowering; never so on a
    TPU). The runners' start-up log says which of the two a process is
    (ops/pallas/common.py device_report).
    """
    from bert_pytorch_tpu.ops.pallas.common import interpret_mode

    if backend == "auto":
        backend = "pallas" if seq >= 256 and not interpret_mode() else "xla"
    if backend == "pallas" and dropout and interpret_mode():
        return "xla"
    return backend


def _attention_core(q, k, v, bias, dropout_rng, dropout_rate, deterministic,
                    backend, sequence_ids, causal=False, window=None,
                    label=""):
    active = not deterministic and dropout_rate > 0.0
    resolved = resolve_backend(backend, q.shape[1], active)
    if window is not None and (not causal or window < 1
                               or sequence_ids is not None):
        raise ValueError(
            "a window is a positive width on the causal mask of unpacked "
            f"rows (window={window}, causal={causal}, "
            f"packed={sequence_ids is not None})")
    if causal and resolved not in ("xla", "pallas"):
        raise ValueError(
            f"causal attention runs on the 'xla' and 'pallas' paths, not "
            f"{resolved!r}")
    if backend == "pallas" and resolved == "xla":
        warnings.warn(
            "backend='pallas' with dropout on the CPU backend: the Pallas "
            "interpreter has no PRNG, attention uses the XLA path",
            RuntimeWarning, stacklevel=3)
    backend = resolved
    if backend in ("pallas_infer", "pallas_infer_int8"):
        # INFERENCE-ONLY fused forwards (ops/pallas/attention.py
        # flash_attention_infer / flash_attention_infer_int8): no dropout
        # plumbing, no lse/residuals for a backward that never runs —
        # selected by serve/engine.py's forwards. Deliberately NOT
        # reachable from training (no vjp is defined); dropout args are
        # rejected rather than ignored so a misrouted training call
        # fails loudly. The int8 variant quantizes QK^T with per-head
        # symmetric scales (softmax and PV stay higher precision —
        # docs/serving.md "Raw-speed kernels" for the parity bounds).
        from bert_pytorch_tpu.ops.pallas.attention import (
            flash_attention_infer, flash_attention_infer_int8)

        if not deterministic and dropout_rate > 0.0:
            raise ValueError(
                f"backend={backend!r} is forward-only; training "
                "dropout needs backend='pallas' or 'xla'")
        kbias = None if sequence_ids is not None else bias
        kernel = (flash_attention_infer_int8
                  if backend == "pallas_infer_int8"
                  else flash_attention_infer)
        return kernel(q, k, v, bias=kbias, sequence_ids=sequence_ids)
    if backend == "pallas":
        # Fused kernel incl. in-kernel dropout from the TPU hardware PRNG
        # (the [B,H,S,S] mask never reaches HBM; see ops/pallas/attention.py).
        from bert_pytorch_tpu.ops.pallas.attention import flash_attention

        # Packed batches: the caller's bias is the [B, 1, S, S] block
        # diagonal, which the kernel must NOT consume — it rebuilds the
        # tile mask from the id vectors (pad keys carry id 0, so no
        # separate key bias is needed).
        kbias = None if sequence_ids is not None else bias
        if not active:
            return flash_attention(q, k, v, bias=kbias,
                                   sequence_ids=sequence_ids, causal=causal,
                                   window=window, label=label)
        return flash_attention(
            q, k, v, bias=kbias,
            dropout_rate=dropout_rate, dropout_rng=dropout_rng,
            sequence_ids=sequence_ids, causal=causal, window=window)
    if backend in ("ring", "ring_manual") and sequence_ids is not None:
        # Ring attention shards the sequence axis across chips; the
        # block-diagonal mask would need per-shard id exchange alongside
        # the K/V rotation — not implemented. Packing targets the padded
        # phase-1/2 shapes, context parallelism targets long single
        # documents; the combination has no workload yet.
        raise ValueError(
            "sequence packing (sequence_ids) is not supported with "
            "backend='ring'/'ring_manual'; use 'xla' or 'pallas'")
    if backend == "ring_manual":
        # Ring attention's per-shard body, for callers ALREADY inside a
        # region that is manual over the mesh 'seq' axis (the pipeline
        # engine's {pipe, seq} shard_map). q/k/v here are the LOCAL
        # [B, S/n, H, D] sequence shards and bias is the local
        # [B, 1, 1, S/n] key-bias slice; the K/V rotation happens via
        # ppermute over the ambient manual axis, with no nested shard_map
        # (Shardy rejects the nested-manual backward — parallel/pipeline.py).
        from bert_pytorch_tpu.ops.ring import _ring_shard
        from bert_pytorch_tpu.parallel.mesh import AXIS_SEQ

        batch, s_local = q.shape[0], q.shape[1]
        if bias is None:
            kbias = jnp.zeros((batch, s_local), jnp.float32)
        else:
            kbias = bias.reshape(batch, s_local).astype(jnp.float32)
        return _ring_shard(
            q, k, v, kbias,
            dropout_rng if active else None,
            axis_name=AXIS_SEQ,
            dropout_rate=dropout_rate if active else 0.0,
        )
    if backend == "ring":
        # Context parallelism: sequence sharded over the mesh 'seq' axis
        # with K/V ring rotation (ops/ring.py). Falls back to dense when no
        # seq sharding is active (e.g. single-device tests of an sp model).
        from bert_pytorch_tpu.ops.ring import ring_attention
        from bert_pytorch_tpu.parallel.mesh import AXIS_SEQ, current_mesh

        mesh = current_mesh()
        if mesh is not None and mesh.shape.get(AXIS_SEQ, 1) > 1:
            if q.shape[1] % mesh.shape[AXIS_SEQ] != 0:
                # Silently densifying here would materialize the O(S²)
                # scores exactly in the long-context regime ring exists for.
                raise ValueError(
                    f"backend='ring': sequence length {q.shape[1]} is not "
                    f"divisible by the mesh 'seq' axis "
                    f"({mesh.shape[AXIS_SEQ]}); "
                    "pad the sequence or resize the mesh")
            return ring_attention(
                q, k, v, bias=bias,
                dropout_rng=None if deterministic else dropout_rng,
                dropout_rate=0.0 if deterministic else dropout_rate,
                mesh=mesh,
            )

    depth = q.shape[-1]
    scale = 1.0 / jnp.sqrt(depth).astype(q.dtype)
    # [B, H, Sq, Sk]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    scores = scores.astype(jnp.float32)
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    if causal:
        seq_q, seq_k = scores.shape[-2:]
        seen = jnp.tril(jnp.ones((seq_q, seq_k), bool))
        if window is not None:
            seen &= ~jnp.tril(seen, -window)
        scores = jnp.where(seen, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = probs.astype(q.dtype)
    if active:
        with jax.named_scope("attention_dropout"):
            # Named as the boolean, before the cast: remat='dots' keeps it
            # (ops/remat.py) at one byte an element, and the backward pass
            # does not draw the random words a second time.
            keep = checkpoint_name(
                keep_mask(dropout_rng, 1.0 - dropout_rate, probs.shape),
                KEEP_MASK)
            probs = probs * keep.astype(probs.dtype) / (1.0 - dropout_rate)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
