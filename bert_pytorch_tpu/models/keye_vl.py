"""The ``KeyeVL2`` family's language model (Kwai-Keye/Keye-VL-2.0-30B-A3B:
the published ``config.json``; its sparse attention is the indexer of
DeepSeek-V3.2-Exp's report): a pre-norm residual decoder for next-token
prediction whose every layer is SPARSE softmax attention, then a routed
expert layer,

    x <- x + Attention_l(norm(x));   x <- x + MoE_l(norm(x))

with ``norm`` an RMSNorm whose scale starts from one. The vision tower is not
built: rows are text, on which the three position streams of the published
multi-axis rotary (``mrope_section``) are equal and it IS the one-axis table.

**Sparse attention** (``SparseAttention``), ``h`` the layer's normalised
input: ``q = h W_q`` on ``num_attention_heads`` heads, ``k``, ``v`` on
``num_key_value_heads``; q and k normed over a head; rotary over the whole
head (``ops/rope.py``). **The indexer** reads ``u = stop_gradient(h)``:
``qI = u W_qI`` on ``indexer_num_heads`` heads of ``indexer_head_dim``,
``kI = u W_kI`` on ONE head, ``w = u W_w`` a weight a head (float32), the
same rotary (at the indexer's width) on qI and kI. ``ops/sparse_attention.py``
scores every causal pair, chooses each query's ``topk`` keys exactly, runs
the core over them and returns the indexer's objective: the KL, a token, from
the core's probabilities summed over the heads (detached) to the softmax of
the indexer's scores on the chosen set. So the three indexer matrices get
their gradient from the KL alone and every other parameter from the
next-token loss alone, in one backward pass.

**The objective term.** The model returns the KL's mean over layers and
tokens beside its counters (``dsa_index_kl``) and names it, with its
coefficient, in ``objective_terms``; ``pretrain._apply_causal_lm_loss`` adds
what a model names there to the next-token loss, on both its head paths. The
dense warm-up stage of the published recipe (indexer alone, the rest frozen)
is not built.

**Expert layer**: ``models/decoder.py ExpertLayer`` as the ``laguna`` family
builds it (softmax over every expert of the layer, the largest
``num_experts_per_tok`` renormalised, gated silu experts), without a shared
expert and without a balancing loss (the config has no coefficient).

The chip's share is the config's: ``num_experts`` of ``num_experts * ep_size``
experts; attention, indexer and router are whole on every chip.

Counters beside the expert layers' (``decoder.MOE_COUNTERS``):
``dsa_pairs_run`` (query-key pairs the cores ran over: the chosen ones,
counted from the masks, summed over layers and micro-batches),
``dsa_scored_pairs_run`` (causal pairs the indexers scored, from shapes),
``dsa_keep_share`` (the first over the second) and ``dsa_index_kl``.

Scopes (``pretrain.KEYE_SCOPES``): ``attn_qkv``, ``attn_qk_norm``,
``attn_rope``, ``attn_out``; ``dsa`` > ``dsa_index_proj``, ``dsa_scores``,
``dsa_select``, ``dsa_core``, ``dsa_index_loss``; the expert layer's
``moe_*``.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from bert_pytorch_tpu.config import KeyeVLConfig
from bert_pytorch_tpu.models.decoder import (MOE_COUNTERS, CausalDecoder,
                                             ExpertLayer, RMSNorm, dense)
from bert_pytorch_tpu.ops import rope
from bert_pytorch_tpu.ops.attention import resolve_backend
from bert_pytorch_tpu.ops.remat import DSA_CORE_LSE, DSA_CORE_OUT
from bert_pytorch_tpu.ops.sparse_attention import sparse_attention

Dtype = Any
DSA_COUNTERS = ("dsa_pairs_run", "dsa_scored_pairs_run", "dsa_keep_share",
                "dsa_index_kl")
COUNTERS = MOE_COUNTERS + DSA_COUNTERS
# what keeps the indexer's input out of the next-token loss's graph
detach = jax.lax.stop_gradient


def _out_std(config: KeyeVLConfig) -> float:
    """The projections that write into the residual stream (two a layer)
    start smaller by sqrt(2 x number of layers)."""
    return config.initializer_range / math.sqrt(2 * config.num_hidden_layers)


class SparseAttention(nn.Module):
    """The attention layer (the module's docstring). ``rotary`` is the pair
    of (cos, sin) tables the model made once, the core's and the indexer's (a
    layer called alone makes its own). Returns (output, ``DSA_COUNTERS``'
    values, each already divided so that the wrapper's sum over layers is
    what the name says)."""
    config: KeyeVLConfig
    dtype: Dtype = jnp.float32
    attention_backend: str = "xla"

    @nn.compact
    def __call__(self, h, rotary=None):
        cfg = self.config
        heads, kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
        index_heads, index_hd = cfg.indexer_num_heads, cfg.indexer_head_dim
        batch, seq = h.shape[:2]
        std = cfg.initializer_range
        norm = functools.partial(RMSNorm, cfg.rms_norm_eps, self.dtype)
        project = lambda name, width, x: dense(width, std, self.dtype, name)(x)
        with jax.named_scope("attn_qkv"):
            q = project("q_proj", heads * hd, h).reshape(batch, seq, heads, hd)
            k = project("k_proj", kv * hd, h).reshape(batch, seq, kv, hd)
            v = project("v_proj", kv * hd, h).reshape(batch, seq, kv, hd)
        with jax.named_scope("attn_qk_norm"):
            q, k = norm(name="q_norm")(q), norm(name="k_norm")(k)
        core_tables, index_tables = rotary or (
            rope.rotary_tables(seq, *cfg.rope_of(hd)),
            rope.rotary_tables(seq, *cfg.rope_of(index_hd)))
        with jax.named_scope("attn_rope"):
            q = rope.apply_rotary(q, *core_tables)
            k = rope.apply_rotary(k, *core_tables)
        with jax.named_scope("dsa"):
            with jax.named_scope("dsa_index_proj"):
                u = detach(h)
                qi = project("index_q", index_heads * index_hd, u).reshape(
                    batch, seq, index_heads, index_hd)
                ki = project("index_k", index_hd, u)[:, :, None, :]
                w = project("index_w", index_heads, u).astype(jnp.float32)
                qi = rope.apply_rotary(qi, *index_tables)
                ki = rope.apply_rotary(ki, *index_tables)[:, :, 0]
            ctx, kl, pairs, mask = sparse_attention(
                q, k, v, qi, ki, w, cfg.topk,
                resolve_backend(self.attention_backend, seq, False))
            pairs = pairs.astype(jnp.float32)
            # for a caller that asks (``mutable=["intermediates"]``): each
            # query's chosen keys, eight a byte; otherwise nothing is kept
            self.sow("intermediates", "selected", jnp.packbits(mask, axis=-1))
        with jax.named_scope("attn_out"):
            out = dense(cfg.hidden_size, _out_std(cfg), self.dtype, "o_proj")(
                ctx.reshape(batch, seq, heads * hd))
        layers = cfg.num_hidden_layers
        scored = batch * seq * (seq + 1) // 2
        return out, {
            "dsa_pairs_run": pairs,
            "dsa_scored_pairs_run": jnp.float32(scored),
            "dsa_keep_share": pairs / float(scored * layers),
            "dsa_index_kl": kl / layers}


def expert_layer(cfg: KeyeVLConfig, dtype, name=None) -> ExpertLayer:
    """The family's expert layer: softmax scores, gated silu experts, no
    shared expert, the share ``cfg`` states."""
    return ExpertLayer(
        width=cfg.moe_intermediate_size, shared_width=0,
        held=cfg.num_experts, router_experts=cfg.router_experts,
        first_expert=cfg.first_expert, top_k=cfg.num_experts_per_tok,
        route_scale=1.0, norm_topk=cfg.norm_topk_prob,
        activation=jax.nn.silu, std=cfg.initializer_range,
        out_std=_out_std(cfg), score="softmax", gated=True,
        piece_multiple=getattr(cfg, "moe_piece_multiple",
                               ExpertLayer.piece_multiple),
        dtype=dtype, name=name)


class KeyeBlock(nn.Module):
    config: KeyeVLConfig
    dtype: Dtype = jnp.float32
    attention_backend: str = "xla"

    @nn.compact
    def __call__(self, x, rotary=None):
        cfg = self.config
        norm = functools.partial(RMSNorm, cfg.rms_norm_eps, self.dtype)
        out, chosen = SparseAttention(
            cfg, self.dtype, self.attention_backend, name="attention")(
                norm(name="attention_norm")(x), rotary)
        x = x + out
        out, counters = expert_layer(cfg, self.dtype, name="mlp")(
            norm(name="mlp_norm")(x))
        return x + out, {**counters, **chosen}


# How many layers keep the sparse core's output and log-sum-exps across remat
# (ops/remat.py DSA_CORE_OUT, DSA_CORE_LSE), by what ONE chip holds at the
# published widths on a micro-batch of one row of 16,384 tokens (PERF.md 6,
# "PR 47"): a layer's two tensors are 32 x 16,384 x 128 bfloat16 + 32 x 16,384
# float32 = 136.3 MB, and the step's temporaries rose by 158 MB a layer that
# keeps them (1,106 MB for seven). With none kept 1,194 MB of the chip's
# 16,909 MB are free: seven layers leave 90 MB, and with eight or nine the
# chip's compiler makes room by rematerializing on its own account. A model
# of fewer layers keeps them in all.
CORE_KEPT_LAYERS = 7


class KeyeVLForCausalLM(CausalDecoder):
    config: KeyeVLConfig

    COUNTERS = COUNTERS

    def blocks(self, wrap):
        """The last ``CORE_KEPT_LAYERS`` layers keep the core's output and
        log-sum-exps across remat, the layers before them make them again:
        the backward pass comes to the last layers first, so what they keep
        is gone soonest."""
        layers = self.config.num_hidden_layers
        again = max(0, layers - CORE_KEPT_LAYERS)
        classes = ([wrap(KeyeBlock, without=(DSA_CORE_OUT, DSA_CORE_LSE))]
                   * again + [wrap(KeyeBlock)] * (layers - again))
        return [block(self.config, self.dtype, self.attention_backend)
                for block in classes]

    def norm_epsilon(self):
        return self.config.rms_norm_eps

    def objective_terms(self) -> dict:
        return {"dsa_index_kl": float(self.config.index_loss_coef)}

    def shared_inputs(self, seq):
        """The rotary tables at the core's and the indexer's width, made
        once a call and not in every layer of every pass."""
        cfg = self.config
        with jax.named_scope("attn_rope"):
            return ((rope.rotary_tables(seq, *cfg.rope_of(cfg.head_dim)),
                     rope.rotary_tables(
                         seq, *cfg.rope_of(cfg.indexer_head_dim))),)
