"""The ``phi4flash`` family: a pre-norm residual decoder for next-token
prediction whose every layer is a mixer, then a gated MLP,

    h = x + Mix_l(LN(x));   x' = h + W2 (silu(g) * u),  [g, u] = W1 LN(h)

under LayerNorm with weight and bias, with no rotary and no positional
embedding anywhere, and whose head is the embedding's transpose. The mixers
DIFFER (``PhiFlashConfig.layer_types``; ``config.phi_flash_layer_types`` is
the published rule): in the first half of the model a Mamba-1 selective scan
(``ops/ssm.py selective_scan``) alternates with differential attention under
a sliding window; the layer in the middle is a Mamba-1 mixer whose scan
output ``m`` (before its gate) is KEPT, the next full causal differential
attention whose keys and values are KEPT; and from there on a gated memory
unit on ``m`` (``W_b (silu(W_a x) * m)``) alternates with differential
cross-attention that has queries of its own and reads the kept keys and
values. So one scan memory and one K/V serve every later layer; they travel
from block to block on ``models/decoder.py``'s carried path.

Differential attention (Ye et al. 2024, arXiv:2410.05258, flash form): the
query heads come in pairs (q1, q2) and so do the key heads; a key pair's two
value heads are joined into one value twice as wide; ``A_i = softmax(q_i
k_i^T / sqrt(d) + mask) v``; ``o = RMSNorm_2d(A1 - lam A2) * (1 - lam0)``
with ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0`` learned per layer and
``lam0 = 0.8 - 0.6 exp(-0.3 l)`` from the layer's PUBLISHED index. Both maps
are heads of one call of the attention core (``ops/attention.py
differential_attention``); the kernels carry names of their own
(``flash_diff_window_*``, ``flash_diff_*``, ``flash_diff_cross_*``).

The chip's share is the config's: ``num_attention_heads`` query heads on
``num_key_value_heads`` key-value heads are HELD of ``tp_size`` times as
many, with the matching columns of ``Wqkv`` / ``Wq`` and rows of
``out_proj``, whose bias only rank 0 holds; what the absent heads would add
lies on other chips.

Counters, from shapes, summed over layers and (``*_run``) micro-batches:
``scan_chunks_run`` (chunks the selective scan runs in one pass),
``attn_window_tiles_run`` / ``attn_full_tiles_run`` (score tiles the core
computes in one pass, as ``models/laguna.py`` counts them; the cross layers
count as full), ``memory_readers`` and ``shared_kv_readers`` (layers that
read the kept memory / the kept keys and values).

Scopes (``pretrain.PHI_FLASH_SCOPES``): ``s6_mixer`` > ``s6_in_proj``,
``s6_conv``, ``s6_dt``, ``selective_scan``, ``s6_gate``, ``s6_out_proj``;
``gmu``; ``attn_qkv``, ``attention_core``, ``attn_diff`` (subtract, norm),
``attn_out``; ``dense_mlp``.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from bert_pytorch_tpu.config import PhiFlashConfig
from bert_pytorch_tpu.models.decoder import (CausalDecoder, LayerNorm,
                                             RMSNorm, dense, normal)
from bert_pytorch_tpu.ops import ssm
from bert_pytorch_tpu.ops.attention import (differential_attention,
                                            resolve_backend)
from bert_pytorch_tpu.ops.pallas.attention import tiles_visited

Dtype = Any
COUNTERS = ("scan_chunks_run", "attn_window_tiles_run", "attn_full_tiles_run",
            "memory_readers", "shared_kv_readers")


def _out_std(config: PhiFlashConfig) -> float:
    """The projections that write into the residual stream (two a layer)
    start smaller by sqrt(2 x number of layers)."""
    return config.initializer_range / math.sqrt(2 * config.num_hidden_layers)


def _biased(features: int, std: float, dtype, name):
    return nn.Dense(features, use_bias=True, dtype=dtype,
                    param_dtype=jnp.float32, kernel_init=normal(std),
                    name=name)


class Mamba1Mixer(nn.Module):
    """The Mamba-1 mixer (Gu & Dao 2023). Returns (output, y): ``y`` is the
    scan's output with its skip term, before the gate: the memory, where the
    layer is the one that writes it."""
    config: PhiFlashConfig
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        inner, states, rank = (cfg.mamba_inner, cfg.mamba_d_state,
                               cfg.mamba_dt_rank)
        std = cfg.initializer_range

        def dt_bias_init(key, shape, dtype=jnp.float32):
            # inverse softplus of a log-uniform step in [min, max]
            step = jnp.exp(jax.random.uniform(key, shape, dtype) * (
                math.log(cfg.time_step_max) - math.log(cfg.time_step_min))
                + math.log(cfg.time_step_min))
            return step + jnp.log(-jnp.expm1(-step))

        def a_log_init(key, shape, dtype=jnp.float32):
            return jnp.broadcast_to(
                jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape)

        def conv_init(key, shape, dtype=jnp.float32):
            bound = 1.0 / math.sqrt(shape[0])  # torch's Conv1d default
            return jax.random.uniform(key, shape, dtype, -bound, bound)

        with jax.named_scope("s6_mixer"):
            with jax.named_scope("s6_in_proj"):
                u, z = jnp.split(dense(2 * inner, std, self.dtype,
                                       "in_proj")(x), 2, axis=-1)
            with jax.named_scope("s6_conv"):
                u = jax.nn.silu(ssm.causal_depthwise_conv(
                    u,
                    self.param("conv_kernel", conv_init,
                               (cfg.mamba_d_conv, inner), jnp.float32),
                    self.param("conv_bias", nn.initializers.zeros, (inner,),
                               jnp.float32)))
            with jax.named_scope("s6_dt"):
                r, b, c = jnp.split(
                    dense(rank + 2 * states, std, self.dtype, "x_proj")(u),
                    [rank, rank + states], axis=-1)
                dt = jax.nn.softplus(
                    dense(inner, std, self.dtype, "dt_proj")(r).astype(
                        jnp.float32)
                    + self.param("dt_bias", dt_bias_init, (inner,),
                                 jnp.float32))
            a = -jnp.exp(self.param("A_log", a_log_init, (inner, states),
                                    jnp.float32))
            y = ssm.selective_scan(u, dt, a, b, c, chunk=cfg.scan_chunk)
            with jax.named_scope("s6_gate"):
                y = y + u.astype(jnp.float32) * self.param(
                    "D", nn.initializers.ones, (inner,), jnp.float32)
                gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(
                    self.dtype)
            with jax.named_scope("s6_out_proj"):
                out = dense(cfg.hidden_size, _out_std(cfg), self.dtype,
                            "out_proj")(gated)
        return out, y.astype(self.dtype)


class GatedMemoryUnit(nn.Module):
    """``W_b (silu(W_a x) * m)``: the layer's input gates the kept memory."""
    config: PhiFlashConfig
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, memory):
        cfg = self.config
        with jax.named_scope("gmu"):
            gate = dense(cfg.mamba_inner, cfg.initializer_range, self.dtype,
                         "in_proj")(x)
            return dense(cfg.hidden_size, _out_std(cfg), self.dtype,
                         "out_proj")(jax.nn.silu(gate) * memory)


class DifferentialAttention(nn.Module):
    """Layer ``layer``'s differential attention. With ``kept`` None the layer
    has keys and values of its own (``Wqkv``) and returns them beside its
    output; given ``kept = (k, v)`` it has queries alone (``Wq``) and reads
    those."""
    config: PhiFlashConfig
    layer: int
    dtype: Dtype = jnp.float32
    attention_backend: str = "xla"

    @nn.compact
    def __call__(self, x, kept=None):
        cfg = self.config
        heads, kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
        window = cfg.window_of(self.layer)
        batch, seq = x.shape[:2]
        std = cfg.initializer_range
        with jax.named_scope("attn_qkv"):
            if kept is None:
                q, k, v = jnp.split(
                    _biased((heads + 2 * kv) * hd, std, self.dtype, "Wqkv")(x),
                    [heads * hd, (heads + kv) * hd], axis=-1)
                k = k.reshape(batch, seq, kv // 2, 2, hd)
                # a key pair's two value heads, side by side: one value of 2 hd
                v = v.reshape(batch, seq, kv // 2, 2 * hd)
            else:
                q = _biased(heads * hd, std, self.dtype, "Wq")(x)
                k, v = kept
            q = q.reshape(batch, seq, heads // 2, 2, hd)
        a1, a2 = differential_attention(
            q, k, v, backend=self.attention_backend, window=window,
            label="diff" if kept is None else "diff_cross")
        with jax.named_scope("attn_diff"):
            lambdas = [self.param(name, normal(cfg.lambda_std), (hd,),
                                  jnp.float32)
                       for name in ("lambda_q1", "lambda_k1", "lambda_q2",
                                    "lambda_k2")]
            lam0 = cfg.lambda_init(self.layer)
            lam = (jnp.exp(jnp.sum(lambdas[0] * lambdas[1]))
                   - jnp.exp(jnp.sum(lambdas[2] * lambdas[3])) + lam0)
            diff = a1.astype(jnp.float32) - lam * a2.astype(jnp.float32)
            ctx = RMSNorm(cfg.layer_norm_eps, jnp.float32, name="subln")(
                diff) * (1.0 - lam0)
            ctx = ctx.astype(self.dtype).reshape(batch, seq, heads * hd)
        with jax.named_scope("attn_out"):
            # the whole layer's bias is added once: by the rank that holds
            # the first heads
            out = nn.Dense(
                cfg.hidden_size, use_bias=cfg.tp_rank == 0, dtype=self.dtype,
                param_dtype=jnp.float32, kernel_init=normal(_out_std(cfg)),
                name="out_proj")(ctx)
        skipping = resolve_backend(self.attention_backend, seq, False) == "pallas"
        tiles = float(batch * heads) * (
            tiles_visited(seq, True, window) if skipping else tiles_visited(seq))
        name = "attn_window_tiles_run" if window else "attn_full_tiles_run"
        return out, (k, v), {name: jnp.asarray(tiles, jnp.float32)}


class PhiFlashMLP(nn.Module):
    """``W2 (silu(g) * u)``, ``[g, u] = W1 h``: the gate's columns first."""
    config: PhiFlashConfig
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        with jax.named_scope("dense_mlp"):
            gate, up = jnp.split(dense(
                2 * cfg.intermediate_size, cfg.initializer_range, self.dtype,
                "fc1")(x), 2, axis=-1)
            return dense(cfg.hidden_size, _out_std(cfg), self.dtype, "fc2")(
                jax.nn.silu(gate) * up)


class PhiFlashBlock(nn.Module):
    """One layer: takes and returns ``(x, carried)`` (``models/decoder.py``,
    the carried path); ``carried`` holds ``memory`` from the layer that
    writes it on and ``k``, ``v`` likewise."""
    config: PhiFlashConfig
    layer: int
    dtype: Dtype = jnp.float32
    attention_backend: str = "xla"

    @nn.compact
    def __call__(self, x, carried):
        cfg = self.config
        kind = cfg.layer_types[self.layer]
        one = jnp.ones((), jnp.float32)
        h = LayerNorm(cfg.layer_norm_eps, self.dtype, name="norm1")(x)
        if kind in ("mamba", "mamba_memory"):
            out, y = Mamba1Mixer(cfg, self.dtype, name="mixer")(h)
            counters = {"scan_chunks_run": one * x.shape[0] * ssm.scan_chunks(
                x.shape[1], cfg.scan_chunk)}
            if kind == "mamba_memory":
                carried = {**carried, "memory": y}
        elif kind == "gmu":
            out = GatedMemoryUnit(cfg, self.dtype, name="mixer")(
                h, carried["memory"])
            counters = {"memory_readers": one}
        else:
            attention = DifferentialAttention(
                cfg, self.layer, self.dtype, self.attention_backend,
                name="mixer")
            if kind == "cross_attention":
                out, _, counters = attention(h, (carried["k"], carried["v"]))
                counters = {**counters, "shared_kv_readers": one}
            else:
                out, (k, v), counters = attention(h)
                if kind == "full_attention":
                    carried = {**carried, "k": k, "v": v}
        x = x + out
        h = LayerNorm(cfg.layer_norm_eps, self.dtype, name="norm2")(x)
        return x + PhiFlashMLP(cfg, self.dtype, name="mlp")(h), carried, counters


class PhiFlashForCausalLM(CausalDecoder):
    config: PhiFlashConfig

    COUNTERS = COUNTERS
    NORM = LayerNorm
    TIED_HEAD = True
    CARRIES = True

    def blocks(self, wrap):
        block = wrap(PhiFlashBlock)
        return [block(self.config, layer, self.dtype, self.attention_backend)
                for layer in range(self.config.num_hidden_layers)]

    def norm_epsilon(self):
        return self.config.layer_norm_eps
