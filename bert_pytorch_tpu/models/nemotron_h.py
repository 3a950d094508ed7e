"""The ``nemotron_h`` family: a pre-norm residual decoder for next-token
prediction whose every layer is ONE mixer or feed-forward part,

    x <- x + part_l(RMSNorm_l(x)),        l over ``hybrid_override_pattern``

with ``M`` a Mamba-2 state-space mixer (ops/ssm.py), ``E`` a routed expert
layer with a shared expert (ops/moe.py) and ``*`` grouped-query causal
attention (ops/attention.py) without positional embedding; then a final
RMSNorm and an untied output head. No bias but the convolution's, no dropout.

Layers of unlike kinds hold unlike parameters, so they cannot be stacked and
scanned the way ``models/bert.py`` scans its encoder: the layers are
``layers_0 .. layers_{L-1}``, each rematerialized on its own
(``ops/remat.py``'s policy). The expert layer holds the chip's share of the
experts (``NemotronHConfig``: ``n_routed_experts`` held of
``n_routed_experts * ep_size``) and adds only their terms.

The model returns ``(logits [B, S, V], counters)``; the counters are sums and
maxima over its expert layers (``moe_local_slots``, ``moe_dropped_slots``,
``moe_load_max_over_mean``, ``moe_pieces_run``) and ride out of the train
step as step metrics.
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
from bert_pytorch_tpu.ops import moe, ssm
from bert_pytorch_tpu.ops.attention import dot_product_attention
from bert_pytorch_tpu.ops.remat import remat_policy

Dtype = Any


def _normal(std: float):
    return nn.initializers.normal(stddev=std)


def _out_std(config: NemotronHConfig) -> float:
    """``rescale_prenorm_residual``: the projections that write into the
    residual stream start smaller by sqrt(number of layers)."""
    scale = (math.sqrt(config.num_hidden_layers)
             if config.rescale_prenorm_residual else 1.0)
    return config.initializer_range / scale


def relu2(x):
    return jnp.square(jax.nn.relu(x))


class RMSNorm(nn.Module):
    epsilon: float = 1e-5
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.epsilon)
        return (normed * scale).astype(self.dtype)


def _dense(features: int, std: float, dtype, name):
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    param_dtype=jnp.float32, kernel_init=_normal(std),
                    name=name)


class Mamba2Mixer(nn.Module):
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
            y = ssm.ssd_chunked_scan(
                xs.reshape(batch, seq, heads, hdim),
                jax.nn.softplus(dt.astype(jnp.float32) + dt_bias),
                -jnp.exp(a_log),
                b.reshape(batch, seq, groups, state),
                c.reshape(batch, seq, groups, state),
                d_skip, cfg.chunk_size)
            norm_w = self.param("norm_scale", nn.initializers.ones, (inner,),
                                jnp.float32)
            y = ssm.gated_group_rms_norm(
                y.reshape(batch, seq, inner), z, norm_w, groups,
                cfg.layer_norm_epsilon)
            with jax.named_scope("ssm_out_proj"):
                return _dense(cfg.hidden_size, _out_std(cfg), self.dtype,
                              "out_proj")(y)


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


class ExpertLayer(nn.Module):
    """Router over every expert of the layer, the held experts' terms, and
    the shared expert on every token."""
    config: NemotronHConfig
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        hidden, width = cfg.hidden_size, cfg.moe_intermediate_size
        held, std = cfg.n_routed_experts, cfg.initializer_range
        router_w = self.param("router_kernel", _normal(std),
                              (hidden, cfg.router_experts), jnp.float32)
        # The published rule moves this bias outside the gradient, towards
        # balance; here it is a buffer at zero (route() stops its gradient).
        correction = self.param("router_correction_bias",
                                nn.initializers.zeros, (cfg.router_experts,),
                                jnp.float32)
        w_up = self.param("experts_up", _normal(std), (held, hidden, width),
                          jnp.float32)
        w_down = self.param("experts_down", _normal(_out_std(cfg)),
                            (held, width, hidden), jnp.float32)
        batch, seq = x.shape[:2]
        flat = x.reshape(batch * seq, hidden)
        with jax.named_scope("moe"):
            chosen, weights = moe.route(
                flat, router_w, correction, cfg.num_experts_per_tok,
                cfg.routed_scaling_factor, cfg.norm_topk_prob)
            # for a caller that asks (``mutable=["intermediates"]``): which
            # experts each token chose; otherwise nothing is kept
            self.sow("intermediates", "chosen", chosen)
            routed, counters = moe.held_experts(
                flat, chosen, weights, w_up, w_down, cfg.first_expert,
                cfg.router_experts, relu2,
                # (tests at a small size set a smaller rounding of the pieces)
                multiple=getattr(cfg, "moe_piece_multiple", moe.GMM_TILE_ROWS))
            with jax.named_scope("moe_shared"):
                shared_w = cfg.moe_shared_expert_intermediate_size
                mid = relu2(_dense(shared_w, std, self.dtype, "shared_up")(x))
                shared = _dense(hidden, _out_std(cfg), self.dtype,
                                "shared_down")(mid)
            return shared + routed.reshape(x.shape), counters


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
            out = Mamba2Mixer(cfg, self.dtype, name="mixer")(h)
        elif self.kind == "*":
            out = CausalAttention(cfg, self.dtype, self.attention_backend,
                                  name="mixer")(h)
        else:
            out, counters = ExpertLayer(cfg, self.dtype, name="mixer")(h)
        return x + out, counters


class NemotronHForCausalLM(nn.Module):
    config: NemotronHConfig
    dtype: Dtype = jnp.float32
    remat: str = "none"
    attention_backend: str = "xla"

    # What pretrain.make_train_step trains this family on.
    objective = "causal_lm"

    def setup(self):
        cfg = self.config
        self.embedding = self.param(
            "embedding", _normal(cfg.initializer_range),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        block = NemotronHBlock
        policy = remat_policy(self.remat)
        if policy is not None:
            block = nn.remat(NemotronHBlock, policy=policy, prevent_cse=True)
        self.layers = [
            block(cfg, kind, self.dtype, self.attention_backend)
            for kind in cfg.hybrid_override_pattern]
        self.final_norm = RMSNorm(cfg.layer_norm_epsilon, self.dtype)
        self.lm_head = _dense(cfg.vocab_size, cfg.initializer_range,
                              self.dtype, None)

    def hidden_states(self, input_ids):
        """[B, S] ids -> (the final norm's output [B, S, H], counters): all
        but the head, for a caller that takes the head in pieces
        (models/losses.py ``chunked_next_token_loss``)."""
        x = jnp.take(self.embedding, input_ids, axis=0).astype(self.dtype)
        slots, dropped, skew, pieces = [], [], [], []
        for layer in self.layers:
            x, counters = layer(x)
            if counters is not None:
                slots.append(counters["local_slots"])
                dropped.append(counters["dropped_slots"])
                skew.append(counters["load_max_over_mean"])
                pieces.append(counters["pieces_run"])
        zero = jnp.zeros((), jnp.float32)
        return self.final_norm(x), {
            "moe_local_slots": sum(slots, zero),
            "moe_dropped_slots": sum(dropped, zero),
            "moe_load_max_over_mean": jnp.max(jnp.stack(skew)) if skew else zero,
            "moe_pieces_run": sum(pieces, zero),
        }

    def __call__(self, input_ids):
        x, counters = self.hidden_states(input_ids)
        with jax.named_scope("lm_head"):
            return self.lm_head(x), counters
