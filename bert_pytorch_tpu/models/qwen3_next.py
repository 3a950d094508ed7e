"""The ``qwen3_next`` family (Qwen3-Next: the published ``transformers`` model
of the same ``model_type``; its linear layers: Gated DeltaNet,
arXiv:2412.06464): a pre-norm residual decoder for next-token prediction whose
every layer is a token mixer, then a routed expert layer,

    x <- x + Mixer_l(norm(x));   x <- x + MoE_l(norm(x))

with ``norm`` an RMSNorm that multiplies by ``1 + w`` (w from zero:
``decoder.RMSNorm(offset=1)``). Three layers in four mix by the GATED DELTA
RULE, the fourth by GATED SOFTMAX ATTENTION (``Qwen3NextConfig.layer_types``).

**Delta-rule mixer** (``GatedDeltaNet``), ``h`` the layer's normalised input:
``[q, k, v, z] = h W_qkvz`` and ``[b, a] = h W_ba`` without bias; ``[q, k, v]``
pass one causal depthwise convolution of 4 taps together and a silu, then q
and k a head at a time to unit length (``u rsqrt(sum u^2 + 1e-6)``, float32),
q over ``sqrt(d)`` (``ops/gdn_mix.py conv_silu_unit``: at these widths ONE
pass of the Pallas kernels ``gdn_mix_fwd`` / ``gdn_mix_bwd``, float32 from
the load to the store; at any other shape ``ops/ssm.py
causal_depthwise_conv`` and plain XLA); in float32 ``beta = sigmoid(b)``,
``g = -exp(A_log) softplus(a + dt_bias)``; key
head j serves value heads 2j and 2j + 1; the rule in chunks of 64
(``ops/delta_rule.py gated_delta_rule``: at these widths in one dtype its
Pallas kernels, every row at once, which keep the state each chunk started
from for the backward; at any other shape plain XLA, the rows one at a time,
each rematerialized); a value head at a time ``rmsnorm(o)
w_n silu(z)`` (w_n from ONE: this norm has no offset;
``ops/gdn_mix.py gated_head_norm``: the kernels ``gated_norm_fwd`` /
``gated_norm_bwd`` or rematerialized XLA, by the same rule); ``o W_o``.

**Attention mixer** (``GatedSoftmaxAttention``): ``[q, gate] = h W_q`` side by
side a head, k and v on fewer heads; q and k normed over a head (``1 + w``);
the first quarter of each head turned (``ops/rope.py``); causal softmax
attention (``ops/attention.py``; the flash kernels under the label
``gated``); ``(o * sigmoid(gate)) W_o``.

**Expert layer**: ``models/decoder.py ExpertLayer`` as the ``laguna`` family
builds it (softmax over every expert of the layer, the largest
``num_experts_per_tok`` renormalised, gated silu experts) with
``shared_gate``: the shared expert's output times ``sigmoid(h w_g)`` a token.

The chip's share is the config's: ``num_experts`` of ``num_experts * ep_size``
experts; mixers, router and shared expert are whole on every chip. What the
absent experts would add lies on other chips and nothing stands in for it.

Counters beside the expert layers' (``decoder.MOE_COUNTERS``), summed over
micro-batches: ``delta_chunks_run``, the chunks the rule runs in one pass
(delta-rule layers x rows x chunks a row, from shapes), and
``delta_kernel_chunks_run``, those of them that the Pallas kernels run
(``ops/delta_rule.py kernel_chunks``: all of them at the published widths in
bfloat16, 0 where a call takes the XLA form), and
``delta_mix_kernel_chunks_run``, the chunks of the calls whose two
element-wise sides ran in THEIR kernels (``ops/gdn_mix.py kernel_fit``: heads
of 128 in one dtype and positions in whole blocks of 16; 0 otherwise).

Scopes (``pretrain.QWEN3_NEXT_SCOPES``): ``gdn`` > ``gdn_in_proj``,
``gdn_conv``, ``gdn_gates``, ``delta_rule``, ``gdn_gate_norm``,
``gdn_out_proj``; ``attn_qkv``, ``attn_qk_norm``, ``attn_rope``,
``attention_core``, ``attn_gate``, ``attn_out``; the expert layer's ``moe_*``
and ``moe_shared_gate``.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from bert_pytorch_tpu.config import Qwen3NextConfig
from bert_pytorch_tpu.models.decoder import (MOE_COUNTERS, CausalDecoder,
                                             ExpertLayer, RMSNorm, dense,
                                             normal)
from bert_pytorch_tpu.ops import delta_rule, gdn_mix, rope
from bert_pytorch_tpu.ops.attention import dot_product_attention
from bert_pytorch_tpu.ops.remat import FLASH_LSE, FLASH_OUT

Dtype = Any
DELTA_COUNTERS = ("delta_chunks_run", "delta_kernel_chunks_run",
                  "delta_mix_kernel_chunks_run")
COUNTERS = MOE_COUNTERS + DELTA_COUNTERS


def _out_std(config: Qwen3NextConfig) -> float:
    """The projections that write into the residual stream (two a layer)
    start smaller by sqrt(2 x number of layers)."""
    return config.initializer_range / math.sqrt(2 * config.num_hidden_layers)


def a_log_init(key, shape, dtype=jnp.float32):
    """The published initialisation: the log of a uniform draw on (0, 16)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-6, 16.0))


def conv_init(key, shape, dtype=jnp.float32):
    """torch's ``Conv1d`` default for a depthwise [taps, channels] kernel:
    uniform within 1 / sqrt(taps)."""
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class GatedDeltaNet(nn.Module):
    """The delta-rule mixer (the module's docstring). Returns (output,
    ``DELTA_COUNTERS``' values)."""
    config: Qwen3NextConfig
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        key_heads, value_heads = (cfg.linear_num_key_heads,
                                  cfg.linear_num_value_heads)
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        key_w, value_w = key_heads * dk, value_heads * dv
        conv_w = 2 * key_w + value_w
        batch, seq = h.shape[:2]
        std = cfg.initializer_range
        # One published tensor each, read in column blocks: slicing a weight
        # is cheap, slicing [rows, 12288] activations along lanes is a copy
        # a block on a TPU (and a pad a block in the backward).
        w_qkvz = self.param("in_proj_qkvz", normal(std),
                            (cfg.hidden_size, conv_w + value_w), jnp.float32)
        taps = self.param("conv_kernel", conv_init,
                          (cfg.linear_conv_kernel_dim, conv_w), jnp.float32)
        blocks = {"q": (0, key_w), "k": (key_w, 2 * key_w),
                  "v": (2 * key_w, conv_w), "z": (conv_w, conv_w + value_w)}
        with jax.named_scope("gdn"):
            with jax.named_scope("gdn_in_proj"):
                q, k, v, z = (jnp.matmul(h, w_qkvz[:, lo:hi].astype(self.dtype))
                              for lo, hi in blocks.values())
                ba = dense(2 * value_heads, std, self.dtype, "in_proj_ba")(h)
            with jax.named_scope("gdn_conv"):
                # depthwise: the blocks pass their own channels' taps apart
                q, k, v = gdn_mix.conv_silu_unit(q, k, v, *(
                    taps[:, slice(*blocks[name])] for name in "qkv"),
                    key_heads, value_heads)
            with jax.named_scope("gdn_gates"):
                b, a = jnp.split(ba.astype(jnp.float32), 2, axis=-1)
                beta = jax.nn.sigmoid(b)
                g = -jnp.exp(self.param(
                    "A_log", a_log_init, (value_heads,), jnp.float32)
                ) * jax.nn.softplus(a + self.param(
                    "dt_bias", nn.initializers.ones, (value_heads,),
                    jnp.float32))
            with jax.named_scope("delta_rule"):
                o = delta_rule.gated_delta_rule(q, k, v, g, beta,
                                                cfg.delta_chunk)
            with jax.named_scope("gdn_gate_norm"):
                o = gdn_mix.gated_head_norm(
                    o, z.reshape(o.shape),
                    self.param("norm_scale", nn.initializers.ones, (dv,),
                               jnp.float32), cfg.rms_norm_eps)
            with jax.named_scope("gdn_out_proj"):
                out = dense(cfg.hidden_size, _out_std(cfg), self.dtype,
                            "out_proj")(
                    o.reshape(batch, seq, value_w))
        chunks = delta_rule.delta_chunks(batch, seq, cfg.delta_chunk)
        return out, {
            "delta_chunks_run": jnp.float32(chunks),
            "delta_kernel_chunks_run": jnp.float32(
                delta_rule.kernel_chunks(q, k, v, cfg.delta_chunk)),
            "delta_mix_kernel_chunks_run": jnp.float32(
                chunks * gdn_mix.kernel_fit(q, k, v, taps.shape[0]))}


class GatedSoftmaxAttention(nn.Module):
    """The attention mixer (the module's docstring); ``rotary`` is the (cos,
    sin) the model made once (a layer called alone makes its own)."""
    config: Qwen3NextConfig
    dtype: Dtype = jnp.float32
    attention_backend: str = "xla"

    @nn.compact
    def __call__(self, h, rotary=None):
        cfg = self.config
        heads, kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
        batch, seq = h.shape[:2]
        std = cfg.initializer_range
        norm = functools.partial(RMSNorm, cfg.rms_norm_eps, self.dtype,
                                 offset=1)
        # (a head's query columns, then its gate's: read as two weight blocks,
        # not as lane slices of the activations)
        w_q = self.param("q_proj", normal(std),
                         (cfg.hidden_size, heads * 2 * hd), jnp.float32).reshape(
                             cfg.hidden_size, heads, 2, hd).astype(self.dtype)
        with jax.named_scope("attn_qkv"):
            q, gate = (jnp.einsum("bsh,hnd->bsnd", h, w_q[:, :, part])
                       for part in (0, 1))
            k = dense(kv * hd, std, self.dtype, "k_proj")(h).reshape(
                batch, seq, kv, hd)
            v = dense(kv * hd, std, self.dtype, "v_proj")(h).reshape(
                batch, seq, kv, hd)
        with jax.named_scope("attn_qk_norm"):
            q, k = norm(name="q_norm")(q), norm(name="k_norm")(k)
        with jax.named_scope("attn_rope"):
            cos, sin = rotary or rope.rotary_tables(seq, *cfg.rope)
            q, k = rope.apply_rotary(q, cos, sin), rope.apply_rotary(k, cos, sin)
        ctx = dot_product_attention(
            q, k, v, backend=self.attention_backend, causal=True,
            label="gated")
        with jax.named_scope("attn_gate"):
            ctx = (ctx.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(self.dtype)
        with jax.named_scope("attn_out"):
            return dense(cfg.hidden_size, _out_std(cfg), self.dtype,
                         "o_proj")(ctx.reshape(batch, seq, heads * hd))


def expert_layer(cfg: Qwen3NextConfig, dtype, name=None) -> ExpertLayer:
    """The family's expert layer: softmax scores, gated silu experts, a gate
    on the shared expert, the share ``cfg`` states."""
    return ExpertLayer(
        width=cfg.moe_intermediate_size,
        shared_width=cfg.shared_expert_intermediate_size,
        held=cfg.num_experts, router_experts=cfg.router_experts,
        first_expert=cfg.first_expert, top_k=cfg.num_experts_per_tok,
        route_scale=1.0, norm_topk=cfg.norm_topk_prob,
        activation=jax.nn.silu, std=cfg.initializer_range,
        out_std=_out_std(cfg), score="softmax", gated=True, shared_gate=True,
        piece_multiple=getattr(cfg, "moe_piece_multiple",
                               ExpertLayer.piece_multiple),
        dtype=dtype, name=name)


class Qwen3NextBlock(nn.Module):
    config: Qwen3NextConfig
    layer: int
    dtype: Dtype = jnp.float32
    attention_backend: str = "xla"

    @nn.compact
    def __call__(self, x, rotary=None):
        cfg = self.config
        norm = functools.partial(RMSNorm, cfg.rms_norm_eps, self.dtype,
                                 offset=1)
        h = norm(name="mixer_norm")(x)
        chunks = dict.fromkeys(DELTA_COUNTERS, jnp.zeros((), jnp.float32))
        if cfg.layer_types[self.layer] == "linear_attention":
            out, chunks = GatedDeltaNet(cfg, self.dtype, name="mixer")(h)
        else:
            out = GatedSoftmaxAttention(
                cfg, self.dtype, self.attention_backend, name="mixer")(
                    h, rotary)
        x = x + out
        out, counters = expert_layer(cfg, self.dtype, name="mlp")(
            norm(name="mlp_norm")(x))
        return x + out, {**counters, **chunks}


# What every block keeps under ``--remat full`` beside ops/remat.py
# ``KEPT_UNDER_FULL`` (the mechanism is ``remat_policy(keeping=)``; its first
# user and the reasons are models/joyai.py's): the gated softmax attention's
# flash output and log-sum-exps, so that its block's recompute does not run
# ``flash_gated_fwd`` again; a delta-rule block carries neither name and
# keeps what it kept. By what ONE chip holds at the published widths on a
# micro-batch of two rows of 8192 tokens: 2 x 16 heads x 8192 x 256 bfloat16
# + 2 x 16 x 8192 float32 = 134,217,728 + 1,048,576 B = 135.3 MB in the one
# attention layer of the chip's period of four, where 0.98 GB are free. What
# the chip read (PERF.md 6, "PR 49"): ``memory_peak_bytes`` 15,934,228,992
# for 15,933,512,704, no op of the compiler's own rematerialization, 4
# forward calls of the core an update for 8, tokens/s +2.5%.
KEPT_ACROSS_REMAT = (FLASH_OUT, FLASH_LSE)


class Qwen3NextForCausalLM(CausalDecoder):
    config: Qwen3NextConfig

    COUNTERS = COUNTERS
    NORM = staticmethod(functools.partial(RMSNorm, offset=1))

    def blocks(self, wrap):
        block = wrap(Qwen3NextBlock, keeping=KEPT_ACROSS_REMAT)
        return [block(self.config, layer, self.dtype, self.attention_backend)
                for layer in range(self.config.num_hidden_layers)]

    def norm_epsilon(self):
        return self.config.rms_norm_eps

    def kept_across_remat(self) -> dict:
        cfg = self.config
        return dict(
            keeping=KEPT_ACROSS_REMAT,
            regions=sum(kind != "linear_attention" for kind in cfg.layer_types),
            heads=cfg.num_attention_heads, head_dim=cfg.head_dim)

    def shared_inputs(self, seq):
        """The rotary tables, made once a call and not in every attention
        layer of every pass."""
        with jax.named_scope("attn_rope"):
            return (rope.rotary_tables(seq, *self.config.rope),)
