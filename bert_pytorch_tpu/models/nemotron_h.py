"""The ``nemotron_h`` family: a pre-norm residual decoder for next-token
prediction whose every layer is ONE mixer or feed-forward part,

    x <- x + part_l(RMSNorm_l(x)),        l over ``hybrid_override_pattern``

with ``M`` a Mamba-2 state-space mixer (ops/ssm.py), ``E`` a routed expert
layer with a shared expert (ops/moe.py) and ``*`` grouped-query causal
attention (ops/attention.py) without positional embedding; then a final
RMSNorm and an untied output head. No bias but the convolution's, no dropout.

The wrapper (embedding, ``layers_0 .. layers_{L-1}`` each rematerialized on
its own, final norm, head), RMSNorm and the expert layer are
``models/decoder.py``'s, shared with ``models/laguna.py``. The expert layer
holds the chip's share of the experts (``NemotronHConfig``:
``n_routed_experts`` held of ``n_routed_experts * ep_size``) and adds only
their terms.

The model returns ``(logits [B, S, V], counters)``; the counters are sums,
maxima and a mean over its expert layers (``moe_local_slots``,
``moe_dropped_slots``, ``moe_load_max_over_mean``, ``moe_pieces_run``,
``moe_tile_fill``) and, from shapes,
``ssd_chunks_run``: chunks the Mamba-2 scan's Pallas kernels run in one pass,
summed over the mixers (0 where the shapes take the scan's XLA form,
``ops/ssm.py``); they ride out of the train step as step metrics.
Parameters carry no logical axis names: under the meshes the trainer builds
they are replicated (data parallelism); sharding them is the expert-axis work
ROADMAP.md queues.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from bert_pytorch_tpu.config import NemotronHConfig
from bert_pytorch_tpu.models.decoder import (MOE_COUNTERS, CausalDecoder,
                                             ExpertLayer, RMSNorm)
from bert_pytorch_tpu.models.decoder import dense as _dense
from bert_pytorch_tpu.models.decoder import normal as _normal
from bert_pytorch_tpu.ops import ssm
from bert_pytorch_tpu.ops.attention import dot_product_attention

Dtype = Any


def _out_std(config: NemotronHConfig) -> float:
    """``rescale_prenorm_residual``: the projections that write into the
    residual stream start smaller by sqrt(number of layers)."""
    scale = (math.sqrt(config.num_hidden_layers)
             if config.rescale_prenorm_residual else 1.0)
    return config.initializer_range / scale


def relu2(x):
    return jnp.square(jax.nn.relu(x))


class Mamba2Mixer(nn.Module):
    """Returns (output, {``ssd_chunks_run``})."""
    config: NemotronHConfig
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        heads, hdim = cfg.mamba_num_heads, cfg.mamba_head_dim
        inner, conv_dim = cfg.mamba_inner, cfg.mamba_conv_dim
        groups, state = cfg.n_groups, cfg.ssm_state_size

        def dt_bias_init(key, shape, dtype=jnp.float32):
            # inverse softplus of a log-uniform step in [min, max], floored
            u = jax.random.uniform(key, shape, dtype)
            step = jnp.exp(u * (math.log(cfg.time_step_max)
                                - math.log(cfg.time_step_min))
                           + math.log(cfg.time_step_min))
            step = jnp.maximum(step, cfg.time_step_floor)
            return step + jnp.log(-jnp.expm1(-step))

        def a_log_init(key, shape, dtype=jnp.float32):
            return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))

        with jax.named_scope("ssm_mixer"):
            with jax.named_scope("ssm_in_proj"):
                zxbcdt = _dense(inner + conv_dim + heads,
                                cfg.initializer_range, self.dtype,
                                "in_proj")(x)
            z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], axis=-1)
            conv_w = self.param("conv_kernel", _normal(cfg.initializer_range),
                                (cfg.conv_kernel, conv_dim), jnp.float32)
            conv_b = self.param("conv_bias", nn.initializers.zeros,
                                (conv_dim,), jnp.float32)
            dt_bias = self.param("dt_bias", dt_bias_init, (heads,), jnp.float32)
            a_log = self.param("A_log", a_log_init, (heads,), jnp.float32)
            d_skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
            xbc = jax.nn.silu(ssm.causal_depthwise_conv(xbc, conv_w, conv_b))
            xs, b, c = jnp.split(xbc, [inner, inner + groups * state], axis=-1)
            batch, seq = x.shape[:2]
            xs = xs.reshape(batch, seq, heads, hdim)
            b = b.reshape(batch, seq, groups, state)
            c = c.reshape(batch, seq, groups, state)
            y = ssm.ssd_chunked_scan(
                xs, jax.nn.softplus(dt.astype(jnp.float32) + dt_bias),
                -jnp.exp(a_log), b, c, d_skip, cfg.chunk_size)
            counters = {"ssd_chunks_run": jnp.float32(
                ssm.ssd_kernel_chunks(xs, b, c, cfg.chunk_size))}
            norm_w = self.param("norm_scale", nn.initializers.ones, (inner,),
                                jnp.float32)
            y = ssm.gated_group_rms_norm(
                y.reshape(batch, seq, inner), z, norm_w, groups,
                cfg.layer_norm_epsilon)
            with jax.named_scope("ssm_out_proj"):
                return _dense(cfg.hidden_size, _out_std(cfg), self.dtype,
                              "out_proj")(y), counters


class CausalAttention(nn.Module):
    config: NemotronHConfig
    dtype: Dtype = jnp.float32
    attention_backend: str = "xla"

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        heads, kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
        batch, seq = x.shape[:2]
        std = cfg.initializer_range
        q = _dense(heads * hd, std, self.dtype, "q_proj")(x)
        k = _dense(kv * hd, std, self.dtype, "k_proj")(x)
        v = _dense(kv * hd, std, self.dtype, "v_proj")(x)
        ctx = dot_product_attention(
            q.reshape(batch, seq, heads, hd), k.reshape(batch, seq, kv, hd),
            v.reshape(batch, seq, kv, hd), backend=self.attention_backend,
            causal=True)
        return _dense(cfg.hidden_size, _out_std(cfg), self.dtype, "o_proj")(
            ctx.reshape(batch, seq, heads * hd))


def expert_layer(cfg: NemotronHConfig, dtype, name=None) -> ExpertLayer:
    """The family's expert layer: sigmoid scores with a correction bias,
    plain relu^2 experts, the share ``cfg`` states."""
    return ExpertLayer(
        width=cfg.moe_intermediate_size,
        shared_width=cfg.moe_shared_expert_intermediate_size,
        held=cfg.n_routed_experts, router_experts=cfg.router_experts,
        first_expert=cfg.first_expert, top_k=cfg.num_experts_per_tok,
        route_scale=cfg.routed_scaling_factor, norm_topk=cfg.norm_topk_prob,
        activation=relu2, std=cfg.initializer_range, out_std=_out_std(cfg),
        piece_multiple=getattr(cfg, "moe_piece_multiple",
                               ExpertLayer.piece_multiple),
        dtype=dtype, name=name)


class NemotronHBlock(nn.Module):
    config: NemotronHConfig
    kind: str
    dtype: Dtype = jnp.float32
    attention_backend: str = "xla"

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = RMSNorm(cfg.layer_norm_epsilon, self.dtype, name="norm")(x)
        counters = None
        if self.kind == "M":
            out, counters = Mamba2Mixer(cfg, self.dtype, name="mixer")(h)
        elif self.kind == "*":
            out = CausalAttention(cfg, self.dtype, self.attention_backend,
                                  name="mixer")(h)
        else:
            out, counters = expert_layer(cfg, self.dtype, name="mixer")(h)
        return x + out, counters


class NemotronHForCausalLM(CausalDecoder):
    config: NemotronHConfig

    COUNTERS = MOE_COUNTERS + ("ssd_chunks_run",)

    def blocks(self, wrap):
        block = wrap(NemotronHBlock)
        return [block(self.config, kind, self.dtype, self.attention_backend)
                for kind in self.config.hybrid_override_pattern]

    def norm_epsilon(self):
        return self.config.layer_norm_epsilon
