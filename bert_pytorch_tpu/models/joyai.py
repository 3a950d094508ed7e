"""The ``joyai_llm_flash`` family (jdopensource/JoyAI-LLM-Flash: the published
``config.json``, whose keys are DeepSeek-V3's; the layer equations are the
DeepSeek-V2 and DeepSeek-V3 reports'): a pre-norm residual decoder for
next-token prediction whose every layer is LATENT attention, then an MLP,

    x <- x + Attention_l(norm(x));   x <- x + Mlp_l(norm(x))

with ``norm`` an RMSNorm whose scale starts from one, and one
multi-token-prediction module in the objective.

**Latent attention** (``LatentAttention``), ``u`` the layer's normalised
input: ``c_q = norm(u W_qa)`` (``q_lora_rank`` wide), ``q = c_q W_qb`` on
``num_attention_heads`` heads of ``[q_nope | q_rope]`` (``qk_nope_head_dim`` +
``qk_rope_head_dim``); ``[c_kv | k_r] = u W_kva`` (``kv_lora_rank`` +
``qk_rope_head_dim``), ``c_kv = norm(c_kv)``, ``[k_nope | v] = c_kv W_kvb`` a
head (``qk_nope_head_dim`` + ``v_head_dim``). Rotary by interleaved pairs
(``ops/rope.py apply_rotary_interleaved``) on every head's ``q_rope`` and on
the ONE ``k_r``, which every head reads: a head's key is ``[k_nope | k_r]``.
Causal softmax attention at ``1 / sqrt(qk_head_dim)`` over values narrower
than the keys; ``W_o``. No bias. **The core takes the key built whole**:
``k_r`` broadcast over the heads and joined to ``k_nope``, through the causal
flash kernels under the label ``mla`` (``ops/attention.py``: q and k 192 wide,
v 128); autodiff sums the broadcast's gradient over the heads.

**MLP**: ``W_down(silu(W_gate h) * W_up h)`` in the first
``first_k_dense_replace`` layers (``models/decoder.py DenseMLP``, laguna's
too); after them ``models/decoder.py ExpertLayer``: sigmoid scores with the
correction bias (a buffer at zero: its update rule is not built), the largest
``num_experts_per_tok`` renormalised and scaled, gated silu experts, one
shared expert of the experts' form on every token, ungated. No balancing
loss (the config has no coefficient).

**The multi-token-prediction module** (``MTPModule``; DeepSeek-V3 report,
section 2.2, depth 1): ``z_t = [norm_e(e_{t+1}) ; norm_h(h_t)] W_eh`` with
``h_t`` the last layer's output BEFORE the final norm and ``e_{t+1}`` the
SHARED embedding of the next token (the embedded input moved one place; the
last position reads the row's first token, which only its own, uncounted,
output sees), one more block of the family (an expert layer) over ``z``, a
final norm of its own. The model returns it as the stream ``mtp``
(``prediction_streams``: shift 2, coefficient ``mtp_loss_coef``) and
``pretrain._apply_causal_lm_loss`` takes it through the SHARED head against
token t + 2.

The chip's share is the config's: ``n_routed_experts`` of ``n_routed_experts
* ep_size`` experts; attention, router, shared expert and the module are
whole on every chip.

Counters beside the expert layers' (``decoder.MOE_COUNTERS``):
``mla_tiles_run`` (the score tiles the cores compute in one pass, from
shapes, as ``models/laguna.py`` counts its own); ``pretrain`` adds
``mtp_loss`` and ``mtp_token_accuracy``.

Scopes (``pretrain.JOYAI_SCOPES``): ``mla`` > ``mla_q_proj``,
``mla_kv_proj``, ``attn_rope``, ``mla_core``, ``attn_out``; ``dense_mlp``;
the expert layer's ``moe_*``; ``mtp`` > ``mtp_merge`` and the block's own;
``mtp_head``, ``mtp_loss``.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from bert_pytorch_tpu.config import JoyAIConfig
from bert_pytorch_tpu.models.decoder import (MOE_COUNTERS, CausalDecoder,
                                             DenseMLP, ExpertLayer, RMSNorm,
                                             dense, rematerialized)
from bert_pytorch_tpu.ops import rope
from bert_pytorch_tpu.ops.attention import (dot_product_attention,
                                            resolve_backend)
from bert_pytorch_tpu.ops.pallas.attention import tiles_visited
from bert_pytorch_tpu.ops.remat import FLASH_LSE, FLASH_OUT

Dtype = Any
MTP = "mtp"
# (the stream's two are zero as the model returns them: ``pretrain`` reads them
# through the shared head and writes them over)
COUNTERS = MOE_COUNTERS + ("mla_tiles_run", MTP + "_loss",
                           MTP + "_token_accuracy")


def share_key(k_r, heads: int):
    """The one turned key [B, S, 1, d] as every head's, [B, S, heads, d]:
    autodiff sums the heads' gradients back into the one."""
    return jnp.broadcast_to(k_r, k_r.shape[:2] + (heads, k_r.shape[-1]))


def _out_std(config: JoyAIConfig) -> float:
    """The projections that write into the residual stream (two a block)
    start smaller by sqrt(2 x number of blocks, the module's among them)."""
    blocks = config.num_hidden_layers + config.num_nextn_predict_layers
    return config.initializer_range / math.sqrt(2 * blocks)


class LatentAttention(nn.Module):
    """The attention layer (the module's docstring). ``rotary`` is the (cos,
    sin) pair the model made once (a layer called alone makes its own).
    Returns (output, {``mla_tiles_run``})."""
    config: JoyAIConfig
    dtype: Dtype = jnp.float32
    attention_backend: str = "xla"

    @nn.compact
    def __call__(self, u, rotary=None):
        cfg = self.config
        heads, nope, turned, wide = (
            cfg.num_attention_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim)
        batch, seq = u.shape[:2]
        std = cfg.initializer_range
        norm = functools.partial(RMSNorm, cfg.rms_norm_eps, self.dtype)
        project = lambda name, width, x: dense(width, std, self.dtype, name)(x)
        with jax.named_scope("mla"):
            with jax.named_scope("mla_q_proj"):
                c_q = norm(name="q_a_norm")(
                    project("q_a_proj", cfg.q_lora_rank, u))
                q = project("q_b_proj", heads * (nope + turned), c_q).reshape(
                    batch, seq, heads, nope + turned)
            with jax.named_scope("mla_kv_proj"):
                c_kv, k_r = jnp.split(
                    project("kv_a_proj", cfg.kv_lora_rank + turned, u),
                    [cfg.kv_lora_rank], axis=-1)
                k_nope, v = jnp.split(
                    project("kv_b_proj", heads * (nope + wide),
                            norm(name="kv_a_norm")(c_kv)).reshape(
                                batch, seq, heads, nope + wide),
                    [nope], axis=-1)
            with jax.named_scope("attn_rope"):
                cos, sin = rotary or rope.rotary_tables(seq, *cfg.rope)
                q_nope, q_r = jnp.split(q, [nope], axis=-1)
                q_r = rope.apply_rotary_interleaved(q_r, cos, sin)
                k_r = rope.apply_rotary_interleaved(
                    k_r[:, :, None, :], cos, sin)
            with jax.named_scope("mla_core"):
                q = jnp.concatenate([q_nope, q_r], axis=-1)
                k = jnp.concatenate([k_nope, share_key(k_r, heads)], axis=-1)
                ctx = dot_product_attention(
                    q, k, v, backend=self.attention_backend, causal=True,
                    label="mla")
            with jax.named_scope("attn_out"):
                out = dense(cfg.hidden_size, _out_std(cfg), self.dtype,
                            "o_proj")(ctx.reshape(batch, seq, heads * wide))
        skipping = resolve_backend(self.attention_backend, seq, False) == "pallas"
        tiles = float(batch * heads) * tiles_visited(seq, skipping)
        return out, {"mla_tiles_run": jnp.asarray(tiles, jnp.float32)}


def expert_layer(cfg: JoyAIConfig, dtype, name=None) -> ExpertLayer:
    """The family's expert layer: sigmoid scores with the correction bias,
    gated silu experts, the shared expert, the share ``cfg`` states."""
    return ExpertLayer(
        width=cfg.moe_intermediate_size, shared_width=cfg.shared_width,
        held=cfg.n_routed_experts, router_experts=cfg.router_experts,
        first_expert=cfg.first_expert, top_k=cfg.num_experts_per_tok,
        route_scale=cfg.routed_scaling_factor, norm_topk=cfg.norm_topk_prob,
        activation=jax.nn.silu, std=cfg.initializer_range,
        out_std=_out_std(cfg), score="sigmoid", gated=True,
        piece_multiple=getattr(cfg, "moe_piece_multiple",
                               ExpertLayer.piece_multiple),
        dtype=dtype, name=name)


class JoyAIBlock(nn.Module):
    """One block; ``dense_mlp`` says which MLP it has."""
    config: JoyAIConfig
    dense_mlp: bool
    dtype: Dtype = jnp.float32
    attention_backend: str = "xla"

    @nn.compact
    def __call__(self, x, rotary=None):
        cfg = self.config
        norm = functools.partial(RMSNorm, cfg.rms_norm_eps, self.dtype)
        out, counters = LatentAttention(
            cfg, self.dtype, self.attention_backend, name="attention")(
                norm(name="attention_norm")(x), rotary)
        x = x + out
        h = norm(name="mlp_norm")(x)
        if self.dense_mlp:
            return x + DenseMLP(
                cfg.intermediate_size, cfg.hidden_size, cfg.initializer_range,
                _out_std(cfg), self.dtype, name="mlp")(h), counters
        out, routed = expert_layer(cfg, self.dtype, name="mlp")(h)
        return x + out, {**counters, **routed}


class MTPModule(nn.Module):
    """The multi-token-prediction module (the module's docstring): the last
    layer's output ``x`` and the next token's embedding in, the module's
    final norm's output and its block's counters out."""
    config: JoyAIConfig
    dtype: Dtype = jnp.float32
    attention_backend: str = "xla"

    @nn.compact
    def __call__(self, x, next_embedded, rotary=None):
        cfg = self.config
        norm = functools.partial(RMSNorm, cfg.rms_norm_eps, self.dtype)
        with jax.named_scope("mtp"):
            with jax.named_scope("mtp_merge"):
                z = dense(cfg.hidden_size, cfg.initializer_range, self.dtype,
                          "eh_proj")(jnp.concatenate(
                              [norm(name="enorm")(next_embedded),
                               norm(name="hnorm")(x)], axis=-1))
            z, counters = JoyAIBlock(
                cfg, False, self.dtype, self.attention_backend, name="block")(
                    z, rotary)
            return norm(name="final_norm")(z), counters


# What every rematerialized region of the family (a block, the module) keeps
# under ``--remat full`` beside ops/remat.py ``KEPT_UNDER_FULL``: the flash
# forward kernel's output and log-sum-exps, the residuals of its two backward
# kernels, so that a region's recompute does not run the core a second time
# only to hand them over. By what ONE chip holds at the published widths on a
# micro-batch of one row of 8192 tokens: a region's two tensors are 32 x 8192
# x 128 bfloat16 + 32 x 8192 float32 = 67,108,864 + 1,048,576 B = 68.2 MB,
# 545.3 MB over the eight regions of the chip's share (seven layers and the
# module; one micro-batch is live at a time), and with none kept 883-889 MB of
# the chip's 16,909 MB are free. What the chip read with all eight keeping
# (PERF.md 6, "PR 49"): ``memory_peak_bytes`` 16,024,163,840 for
# 16,019,849,728, the step's temporaries 3,451,392 B more (the peak is not
# where a region's residuals are live), no op of the compiler's own
# rematerialization in the traced step, 32 forward calls of the core an
# update for 64: so all regions keep, and there is no count to choose.
KEPT_ACROSS_REMAT = (FLASH_OUT, FLASH_LSE)


class JoyAIForCausalLM(CausalDecoder):
    config: JoyAIConfig

    COUNTERS = COUNTERS

    def setup(self):
        super().setup()
        if self.config.num_nextn_predict_layers:
            self.mtp = rematerialized(
                self.remat, MTPModule, keeping=KEPT_ACROSS_REMAT)(
                    self.config, self.dtype, self.attention_backend)

    def __call__(self, input_ids):
        if self.is_initializing() and self.prediction_streams():
            self.streams(input_ids)  # the module's parameters too
        return super().__call__(input_ids)

    def blocks(self, wrap):
        cfg = self.config
        block = wrap(JoyAIBlock, keeping=KEPT_ACROSS_REMAT)
        return [block(cfg, layer < cfg.first_k_dense_replace, self.dtype,
                      self.attention_backend)
                for layer in range(cfg.num_hidden_layers)]

    def norm_epsilon(self):
        return self.config.rms_norm_eps

    def kept_across_remat(self) -> dict:
        cfg = self.config
        return dict(
            keeping=KEPT_ACROSS_REMAT,
            regions=cfg.num_hidden_layers + cfg.num_nextn_predict_layers,
            heads=cfg.num_attention_heads, head_dim=cfg.v_head_dim)

    def shared_inputs(self, seq):
        """The rotary tables of the turned part, made once a call."""
        with jax.named_scope("attn_rope"):
            return (rope.rotary_tables(seq, *self.config.rope),)

    def prediction_streams(self) -> dict:
        cfg = self.config
        if not cfg.num_nextn_predict_layers:
            return {}
        return {MTP: (2, float(cfg.mtp_loss_coef))}

    def further_streams(self, x, embedded, shared):
        hidden, counters = self.mtp(
            x, jnp.roll(embedded, -1, axis=1), *shared)
        return {MTP: hidden}, counters
