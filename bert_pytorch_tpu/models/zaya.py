"""The ``zaya`` family (Zyphra's ZAYA1: arXiv:2511.17127; its attention:
Compressed Convolutional Attention, arXiv:2510.04476): a residual decoder for
next-token prediction whose every layer is attention inside a compressed
latent, then a routed expert layer with no shared expert, each joined to the
stream by a learned merge,

    x <- merge_a(x, CCA(norm_a(x)));   (m, r_l) = MoE(norm_m(x), r_{l-1});
    x <- merge_m(x, m);   merge(x, y) = s_x * x + b_x + s_y * y + b_y

(four vectors a merge, ``s`` from 1 and ``b`` from 0), and whose head is the
embedding's transpose. All norms are RMSNorm.

**CCA** (``CompressedConvAttention``), ``h`` the layer's normalised input.
Queries and keys are projected into a latent narrower than the stream (``q0``
[S, heads x d], ``k0`` [S, kv x d]) and pass, side by side as ``z = [q0,
k0]``, two causal convolutions over positions: a depthwise one of
``cca_time0`` taps, then one of ``cca_time1`` taps that mixes the ``d``
channels of each head among themselves (one ``d x d`` matrix a tap and head).
The mean of q and k BEFORE the convolutions is added to both after them, per
key-value group: query head i of group j gets ``(q0[i] + k0[j]) / 2``, key
head j gets ``(mean of its queries' q0 + k0[j]) / 2``. Then each head of q
and k is normed to length ``sqrt(d)`` in float32 and the keys take a learned
temperature per head; half of each head is turned (``ops/rope.py``). The
first half of the value heads read this token, the second half THE PREVIOUS
one (zeros at position 0). Causal softmax attention over the whole prefix
(``ops/attention.py``; the flash kernels under the label ``cca``), and an
output projection from the queries' width back to the stream.

**The router** (``ZayaRouter``), in float32 throughout: ``r = h Wd + bd``
(``router_hidden_size`` wide); ``r_l = r + gamma_l * r_{l-1}``, the state of
the layer before (none in the first layer), handed to the next layer BEFORE
its norm on ``models/decoder.py``'s carried path (``carried["router"]``);
``u = gelu(norm(r_l) W1 + b1)``, ``u = gelu(u W2 + b2)``, ``logits = u W3``,
one for every expert of the layer and one more, the skip; ``p =
softmax(logits)``, the largest of ``p + beta`` chooses (``beta`` a buffer at
zero, outside the gradient), and the weight is ``p`` of the chosen, never
renormalised (``ops/moe.py choose``). A token that draws the skip adds
nothing in this layer. The experts are gated silu experts (``models/decoder.py
ExpertLayer`` with ``shared_width`` 0 and the routing handed in).

The chip's share is the config's: ``num_experts`` of ``num_experts *
ep_size`` experts; heads, router and merges are whole on every chip. What
the absent experts would add lies on other chips; the skip lies on none.

Counters beside the expert layers' (``decoder.MOE_COUNTERS``):
``moe_skip_slots`` (tokens that drew the skip, summed over layers and
micro-batches) and ``router_carried_layers`` (layers that read a router
state from the layer before).

Scopes (``pretrain.ZAYA_SCOPES``): ``cca`` > ``attn_qkv``, ``cca_conv``,
``cca_qk_mean``, ``cca_value_shift``, ``cca_norm``, ``attn_rope``,
``attention_core``, ``attn_out``; ``moe`` > ``moe_route`` > ``router_down``,
``router_eda``, ``router_mlp``; ``residual_merge``.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from bert_pytorch_tpu.config import ZayaConfig
from bert_pytorch_tpu.models.decoder import (MOE_COUNTERS, CausalDecoder,
                                             ExpertLayer, RMSNorm, dense,
                                             normal)
from bert_pytorch_tpu.ops import moe, rope, ssm
from bert_pytorch_tpu.ops.attention import dot_product_attention
from bert_pytorch_tpu.ops.remat import FLASH_LSE, FLASH_OUT

Dtype = Any
COUNTERS = MOE_COUNTERS + ("moe_skip_slots", "router_carried_layers")


def _out_std(config: ZayaConfig) -> float:
    """The projections that write into the residual stream (two a layer)
    start smaller by sqrt(2 x number of layers)."""
    return config.initializer_range / math.sqrt(2 * config.num_hidden_layers)


def previous(x, steps: int = 1):
    """x [B, S, ...] read ``steps`` positions earlier: zeros before the row."""
    if not steps:
        return x
    pad = [(0, 0), (steps, 0)] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x, pad)[:, :x.shape[1]]


def to_length(t, length: float):
    """Every head of t [..., d] scaled to ``length``, in float32."""
    t = t.astype(jnp.float32)
    return t * (length * jax.lax.rsqrt(
        jnp.sum(jnp.square(t), axis=-1, keepdims=True)))


def _conv_init(fan_in: int):
    """torch's ``Conv1d`` default: uniform within 1 / sqrt(fan in)."""
    bound = 1.0 / math.sqrt(fan_in)

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


class ResidualMerge(nn.Module):
    """``s_x * x + b_x + s_y * y + b_y`` in float32, back in the stream's
    dtype."""
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, y):
        width = x.shape[-1]
        vector = lambda name, init: self.param(name, init, (width,),
                                               jnp.float32)
        with jax.named_scope("residual_merge"):
            merged = (
                x.astype(jnp.float32) * vector("x_scale", nn.initializers.ones)
                + y.astype(jnp.float32) * vector("y_scale", nn.initializers.ones)
                + (vector("x_bias", nn.initializers.zeros)
                   + vector("y_bias", nn.initializers.zeros)))
            return merged.astype(self.dtype)


class CompressedConvAttention(nn.Module):
    """Attention inside the latent (the module's docstring); ``rotary`` is
    the (cos, sin) the model made once (a layer called alone makes its own)."""
    config: ZayaConfig
    dtype: Dtype = jnp.float32
    attention_backend: str = "xla"

    @nn.compact
    def __call__(self, h, rotary=None):
        cfg = self.config
        heads, kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
        serves, groups = heads // kv, heads + kv
        batch, seq = h.shape[:2]
        std = cfg.initializer_range
        with jax.named_scope("cca"):
            with jax.named_scope("attn_qkv"):
                q0 = dense(heads * hd, std, self.dtype, "q_proj")(h)
                k0 = dense(kv * hd, std, self.dtype, "k_proj")(h)
                v = dense(kv * hd, std, self.dtype, "v_proj")(h)
            with jax.named_scope("cca_conv"):
                # convolution 0: depthwise over positions, q and k together
                z1 = ssm.causal_depthwise_conv(
                    jnp.concatenate([q0, k0], axis=-1),
                    self.param("conv0_kernel", _conv_init(cfg.cca_time0),
                               (cfg.cca_time0, groups * hd), jnp.float32),
                    self.param("conv0_bias", nn.initializers.zeros,
                               (groups * hd,), jnp.float32))
                # convolution 1: a head's channels mixed among themselves;
                # the taps' products in one call, the earlier ones shifted
                # AFTER the product (the same sum: the map is linear)
                taps1 = self.param(
                    "conv1_kernel", _conv_init(cfg.cca_time1 * hd),
                    (cfg.cca_time1, groups, hd, hd), jnp.float32)
                mixed = jnp.einsum(
                    "bsgi,kgio->kbsgo", z1.reshape(batch, seq, groups, hd),
                    taps1.astype(self.dtype))
                z2 = self.param("conv1_bias", nn.initializers.zeros,
                                (groups, hd), jnp.float32).astype(self.dtype)
                for tap in range(cfg.cca_time1):
                    z2 = z2 + previous(mixed[tap], cfg.cca_time1 - 1 - tap)
            with jax.named_scope("cca_qk_mean"):
                q0 = q0.reshape(batch, seq, kv, serves, hd).astype(jnp.float32)
                k0 = k0.reshape(batch, seq, kv, 1, hd).astype(jnp.float32)
                q = z2[:, :, :heads].astype(jnp.float32) + (
                    (q0 + k0) / 2).reshape(batch, seq, heads, hd)
                k = z2[:, :, heads:].astype(jnp.float32) + (
                    (jnp.mean(q0, axis=3) + k0[:, :, :, 0]) / 2)
            with jax.named_scope("cca_norm"):
                q = to_length(q, math.sqrt(hd)).astype(self.dtype)
                k = (to_length(k, math.sqrt(hd)) * self.param(
                    "k_scale", nn.initializers.ones, (kv,),
                    jnp.float32)[:, None]).astype(self.dtype)
            with jax.named_scope("cca_value_shift"):
                v = v.reshape(batch, seq, kv, hd)
                v = jnp.concatenate(
                    [v[:, :, :kv // 2], previous(v[:, :, kv // 2:])], axis=2)
            with jax.named_scope("attn_rope"):
                cos, sin = rotary or rope.rotary_tables(seq, *cfg.rope)
                q = rope.apply_rotary(q, cos, sin)
                k = rope.apply_rotary(k, cos, sin)
            ctx = dot_product_attention(
                q, k, v, backend=self.attention_backend, causal=True,
                label="cca")
            with jax.named_scope("attn_out"):
                return dense(cfg.hidden_size, _out_std(cfg), self.dtype,
                             "o_proj")(ctx.reshape(batch, seq, heads * hd))


class ZayaRouter(nn.Module):
    """h [T, H], the state of the layer before [T, R] or None -> (this
    layer's state [T, R], ids [T, 1], weights [T, 1]); float32 throughout.
    The caller opens ``moe`` > ``moe_route``."""
    config: ZayaConfig

    @nn.compact
    def __call__(self, h, state_before):
        cfg = self.config
        width, std = cfg.router_hidden_size, cfg.initializer_range
        full = lambda features, name, bias=True: nn.Dense(
            features, use_bias=bias, dtype=jnp.float32,
            param_dtype=jnp.float32, kernel_init=normal(std),
            precision="highest", name=name)
        with jax.named_scope("router_down"):
            state = full(width, "down_proj")(h.astype(jnp.float32))
        if state_before is not None:
            with jax.named_scope("router_eda"):
                state = state + state_before * self.param(
                    "eda_scale", nn.initializers.ones, (width,), jnp.float32)
        with jax.named_scope("router_mlp"):
            u = RMSNorm(cfg.rms_norm_eps, jnp.float32, name="norm")(state)
            u = jax.nn.gelu(full(width, "fc1")(u), approximate=False)
            u = jax.nn.gelu(full(width, "fc2")(u), approximate=False)
            logits = full(cfg.router_outputs, "out_proj", bias=False)(u)
        # The published rule moves this bias towards balance, outside the
        # gradient; here it is a buffer at zero (choose() stops its gradient).
        correction = self.param("router_correction_bias",
                                nn.initializers.zeros,
                                (cfg.router_outputs,), jnp.float32)
        chosen, weights = moe.choose(logits, correction, 1, 1.0,
                                     norm_topk=False, score="softmax")
        return state, chosen, weights


def expert_layer(cfg: ZayaConfig, dtype, name=None) -> ExpertLayer:
    """The family's expert layer: gated silu experts, no shared expert, the
    routing handed in; the router's width counts the skip."""
    return ExpertLayer(
        width=cfg.moe_intermediate_size, shared_width=0,
        held=cfg.num_experts, router_experts=cfg.router_outputs,
        first_expert=cfg.first_expert, top_k=1, route_scale=1.0,
        norm_topk=False, activation=jax.nn.silu,
        std=cfg.initializer_range, out_std=_out_std(cfg), score="softmax",
        gated=True,
        piece_multiple=getattr(cfg, "moe_piece_multiple",
                               ExpertLayer.piece_multiple),
        dtype=dtype, name=name)


class ZayaBlock(nn.Module):
    """One layer: takes and returns ``(x, carried)`` (``models/decoder.py``,
    the carried path); ``carried["router"]`` is the router's state of the
    layer before, [B, S, R] float32, replaced by this layer's."""
    config: ZayaConfig
    dtype: Dtype = jnp.float32
    attention_backend: str = "xla"

    @nn.compact
    def __call__(self, x, carried, rotary):
        cfg = self.config
        batch, seq, hidden = x.shape
        h = RMSNorm(cfg.rms_norm_eps, self.dtype, name="attn_norm")(x)
        x = ResidualMerge(self.dtype, name="attn_merge")(
            x, CompressedConvAttention(
                cfg, self.dtype, self.attention_backend, name="attn")(
                    h, rotary))
        h = RMSNorm(cfg.rms_norm_eps, self.dtype, name="mlp_norm")(x)
        before = carried.get("router")
        with jax.named_scope("moe"), jax.named_scope("moe_route"):
            state, chosen, weights = ZayaRouter(cfg, name="router")(
                h.reshape(batch * seq, hidden),
                None if before is None else before.reshape(batch * seq, -1))
        out, counters = expert_layer(cfg, self.dtype, name="mlp")(
            h, (chosen, weights))
        counters = {
            **counters,
            "moe_skip_slots": jnp.sum(
                chosen == cfg.router_experts).astype(jnp.float32),
            "router_carried_layers": jnp.asarray(
                0.0 if before is None else 1.0, jnp.float32)}
        carried = {**carried, "router": state.reshape(batch, seq, -1)}
        return (ResidualMerge(self.dtype, name="mlp_merge")(x, out), carried,
                counters)


# What every block keeps under ``--remat full`` beside ops/remat.py
# ``KEPT_UNDER_FULL`` (the mechanism is ``remat_policy(keeping=)``; its first
# user and the reasons are models/joyai.py's): the causal core's output and
# log-sum-exps, so that a block's recompute does not run ``flash_cca_fwd``
# again. By what ONE chip holds at the published widths on a micro-batch of
# two rows of 8192 tokens: 2 x 8 heads x 8192 x 128 bfloat16 + 2 x 8 x 8192
# float32 = 33,554,432 + 524,288 B = 34.1 MB a layer, 170.4 MB over the five
# layers of the chip's share, where 1.13 GB are free. What the chip read
# (PERF.md 6, "PR 49"): ``memory_peak_bytes`` 15,780,656,128 for
# 15,782,489,600, no op of the compiler's own rematerialization, 20 forward
# calls of the core an update for 40, tokens/s +4.4%.
KEPT_ACROSS_REMAT = (FLASH_OUT, FLASH_LSE)


class ZayaForCausalLM(CausalDecoder):
    config: ZayaConfig

    COUNTERS = COUNTERS
    TIED_HEAD = True
    CARRIES = True

    def blocks(self, wrap):
        block = wrap(ZayaBlock, keeping=KEPT_ACROSS_REMAT)
        return [block(self.config, self.dtype, self.attention_backend)
                for _ in range(self.config.num_hidden_layers)]

    def norm_epsilon(self):
        return self.config.rms_norm_eps

    def kept_across_remat(self) -> dict:
        cfg = self.config
        return dict(keeping=KEPT_ACROSS_REMAT, regions=cfg.num_hidden_layers,
                    heads=cfg.num_attention_heads, head_dim=cfg.head_dim)

    def shared_inputs(self, seq):
        """The rotary tables, made once a call and not in every layer of
        every pass."""
        with jax.named_scope("attn_rope"):
            return (rope.rotary_tables(seq, *self.config.rope),)
