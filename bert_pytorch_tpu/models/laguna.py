"""The ``laguna`` family: a pre-norm residual decoder for next-token
prediction whose every layer is attention, then an MLP,

    x <- x + Attn_l(RMSNorm(x));   x <- x + Mlp_l(RMSNorm(x))

and whose layers DIFFER (``LagunaConfig``): in the attention's reach (the
whole prefix, or the last ``sliding_window`` positions), in its count of
query heads, in its rotary table (YaRN over half of each head on the full
layers, the default table over the whole head on the sliding ones:
``ops/rope.py``) and in the MLP (a dense SwiGLU, or 256-wide softmax routing
top-10 over gated experts with a shared expert: ``ops/moe.py``). Every
attention output is gated per head: ``o_j *= sigmoid(h Wg)_j`` before the
output projection, ``h`` the layer's normalised input. No bias, no dropout,
no norm on queries and keys.

The wrapper, RMSNorm and the expert layer are ``models/decoder.py``'s, shared
with ``models/nemotron_h.py``. The chip's share is the config's: the expert
layers hold ``num_experts`` of ``num_experts * ep_size`` experts and the
attention layers ``num_key_value_heads`` of ``num_key_value_heads *
tp_size`` key-value heads with their query heads, their columns of ``Wg`` and
their rows of ``Wo``; what the absent experts and heads would add to the sums
lies on other chips and nothing stands in for it.

Counters beside the expert layers' (``decoder.MOE_COUNTERS``), from shapes,
summed over layers and micro-batches: ``attn_window_tiles_run`` and
``attn_full_tiles_run``, the score tiles the attention core computes in one
pass for the sliding and for the full layers (batch x heads x the tiles a
head's kernel visits: ``ops/pallas/attention.py tiles_visited``; on the XLA
path the whole square is computed and masked, and counted so). A window that
is masked reads like a full layer there; one that is skipped reads about a
quarter of it at 8192 positions.

Scopes (``pretrain.LAGUNA_SCOPES``): ``attn_qkv``, ``attn_rope``,
``attention_core``, ``attn_gate``, ``attn_out``, ``dense_mlp``, and the
expert layer's ``moe_*``.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from bert_pytorch_tpu.config import LagunaConfig
from bert_pytorch_tpu.models.decoder import (MOE_COUNTERS, CausalDecoder,
                                             DenseMLP, ExpertLayer, RMSNorm,
                                             dense)
from bert_pytorch_tpu.ops import rope
from bert_pytorch_tpu.ops.attention import (dot_product_attention,
                                            resolve_backend)
from bert_pytorch_tpu.ops.pallas.attention import tiles_visited

Dtype = Any


def _out_std(config: LagunaConfig) -> float:
    """The projections that write into the residual stream (two a layer)
    start smaller by sqrt(2 x number of layers)."""
    return config.initializer_range / math.sqrt(2 * config.num_hidden_layers)


class GatedAttention(nn.Module):
    """Layer ``layer``'s attention: grouped-query, causal (within the window
    on a sliding layer), rotary on the first dimensions of each head (by the
    tables ``rotary`` the model made once for the layer's kind; a layer called
    alone makes its own), a sigmoid gate per head on the output (``gate``;
    ``models/mellum.py`` builds the layer without one)."""
    config: LagunaConfig
    layer: int
    dtype: Dtype = jnp.float32
    attention_backend: str = "xla"
    gate: bool = True

    @nn.compact
    def __call__(self, x, rotary=None):
        cfg = self.config
        heads = cfg.num_attention_heads_per_layer[self.layer]
        kv, hd = cfg.num_key_value_heads, cfg.head_dim
        window = cfg.window_of(self.layer)
        batch, seq = x.shape[:2]
        std = cfg.initializer_range
        with jax.named_scope("attn_qkv"):
            q = dense(heads * hd, std, self.dtype, "q_proj")(x)
            k = dense(kv * hd, std, self.dtype, "k_proj")(x)
            v = dense(kv * hd, std, self.dtype, "v_proj")(x)
        q = q.reshape(batch, seq, heads, hd)
        k = k.reshape(batch, seq, kv, hd)
        with jax.named_scope("attn_rope"):
            cos, sin = rotary or rope.rotary_tables(
                seq, *cfg.rope_of(self.layer))
            q, k = rope.apply_rotary(q, cos, sin), rope.apply_rotary(k, cos, sin)
        ctx = dot_product_attention(
            q, k, v.reshape(batch, seq, kv, hd),
            backend=self.attention_backend, causal=True, window=window)
        if self.gate:
            with jax.named_scope("attn_gate"):
                gate = jax.nn.sigmoid(dense(heads, std, self.dtype, "g_proj")(
                    x).astype(jnp.float32))
                ctx = (ctx * gate[..., None]).astype(self.dtype)
        with jax.named_scope("attn_out"):
            out = dense(cfg.hidden_size, _out_std(cfg), self.dtype, "o_proj")(
                ctx.reshape(batch, seq, heads * hd))
        skipping = resolve_backend(self.attention_backend, seq, False) == "pallas"
        tiles = float(batch * heads) * (
            tiles_visited(seq, True, window) if skipping else tiles_visited(seq))
        name = "attn_window_tiles_run" if window else "attn_full_tiles_run"
        return out, {name: jnp.asarray(tiles, jnp.float32)}


def expert_layer(cfg: LagunaConfig, dtype, name=None, **axis) -> ExpertLayer:
    """The family's expert layer: softmax scores, gated silu experts, the
    share ``cfg`` states (``axis``: ``ExpertLayer``'s ``axis_names``,
    ``expert_axis`` and ``expert_shards``, for a family that runs under an
    expert axis)."""
    return ExpertLayer(
        width=cfg.moe_intermediate_size,
        shared_width=cfg.shared_expert_intermediate_size,
        held=cfg.num_experts, router_experts=cfg.router_experts,
        first_expert=cfg.first_expert, top_k=cfg.num_experts_per_tok,
        route_scale=cfg.moe_routed_scaling_factor,
        norm_topk=cfg.norm_topk_prob, activation=jax.nn.silu,
        std=cfg.initializer_range, out_std=_out_std(cfg), score="softmax",
        gated=True,
        piece_multiple=getattr(cfg, "moe_piece_multiple",
                               ExpertLayer.piece_multiple),
        dtype=dtype, name=name, **axis)


class LagunaBlock(nn.Module):
    """One layer: attention, then the MLP its ``mlp_layer_types`` entry
    names. ``gate`` and the three fields it hands on to ``expert_layer`` are
    ``models/mellum.py``'s to set."""
    config: LagunaConfig
    layer: int
    dtype: Dtype = jnp.float32
    attention_backend: str = "xla"
    gate: bool = True
    axis_names: bool = False
    expert_axis: Optional[str] = None
    expert_shards: int = 1

    @nn.compact
    def __call__(self, x, tables):
        cfg = self.config
        h = RMSNorm(cfg.rms_norm_eps, self.dtype, name="attn_norm")(x)
        out, counters = GatedAttention(
            cfg, self.layer, self.dtype, self.attention_backend, self.gate,
            name="attn")(h, tables[cfg.layer_types[self.layer]])
        x = x + out
        h = RMSNorm(cfg.rms_norm_eps, self.dtype, name="mlp_norm")(x)
        if cfg.mlp_layer_types[self.layer] == "dense":
            out = DenseMLP(cfg.intermediate_size, cfg.hidden_size,
                           cfg.initializer_range, _out_std(cfg),
                           self.dtype, name="mlp")(h)
        else:
            out, routed = expert_layer(
                cfg, self.dtype, name="mlp", axis_names=self.axis_names,
                expert_axis=self.expert_axis,
                expert_shards=self.expert_shards)(h)
            counters = {**counters, **routed}
        return x + out, counters


class LagunaForCausalLM(CausalDecoder):
    config: LagunaConfig

    COUNTERS = MOE_COUNTERS + ("attn_window_tiles_run", "attn_full_tiles_run")

    def blocks(self, wrap):
        block = wrap(LagunaBlock)
        return [block(self.config, layer, self.dtype, self.attention_backend)
                for layer in range(self.config.num_hidden_layers)]

    def norm_epsilon(self):
        return self.config.rms_norm_eps

    def shared_inputs(self, seq):
        return (rotary_tables_by_kind(self.config, seq),)


def rotary_tables_by_kind(cfg: LagunaConfig, seq: int) -> dict:
    """The rotary tables, one (cos, sin) for each kind of layer, made once a
    call and not in every layer of every pass."""
    with jax.named_scope("attn_rope"):
        return {kind: rope.rotary_tables(
            seq, *cfg.rope_of(cfg.layer_types.index(kind)))
            for kind in dict.fromkeys(cfg.layer_types)}
