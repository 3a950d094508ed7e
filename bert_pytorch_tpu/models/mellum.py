"""The ``mellum`` family (JetBrains/Mellum2-12B-A2.5B): the ``laguna``
family's decoder (``models/laguna.py``) less what that family adds,

    x <- x + Attn_l(RMSNorm(x));   x <- x + Moe_l(RMSNorm(x))

Attention: 32 query heads on 4 key-value heads of 128, causal, within the
last ``sliding_window`` positions on three layers of four; rotary over the
whole head, the default table on the sliding layers and YaRN on the full
ones. No gate on the output, no norm on queries and keys, one count of heads
for every layer. Every MLP is routed: softmax over all 64 experts in float32,
the 8 largest choose, their weights normalised over the chosen, no scaling
factor, no correction bias, no shared expert; an expert is ``(silu(h G) * (h
U)) D``. No bias, no dropout, an untied head.

What is ``models/laguna.py``'s, used here and not written again:
``LagunaForCausalLM`` itself (this model is a subclass: the final norm, the
rotary tables made once a call by ``rotary_tables_by_kind``, the attention
counters), ``LagunaBlock`` (the block's shape, built with ``gate=False``),
through it ``GatedAttention`` and ``expert_layer`` (``models/decoder.py
ExpertLayer`` with ``shared_width`` 0, ``score="softmax"``, ``route_scale``
1 by ``MellumConfig``'s derived keys).

**The expert axis.** This is the family that runs with its experts on several
chips (``AXIS_NAMES``): under ``--mesh ep=4`` a layer's stacked expert tensors
are divided over the ``expert`` axis by expert (chip r holds experts ``16 r
.. 16 r + 15`` of 64), embedding and head by row of the vocabulary, the rows
of the batch as over ``data``; attention, norms and routers are whole on
every chip. ``pretrain.make_train_step`` runs the model under a
``shard_map`` over that axis, and the slots cross it (``ops/moe.py
exchanged_experts``). Told a share instead (``ep_size`` / ``ep_rank`` in the
config, no mesh) the family runs one chip's part as the other expert families
do.

Counters: ``decoder.MOE_COUNTERS``, ``decoder.EXCHANGE_COUNTERS`` (zero
without the axis), ``attn_window_tiles_run``, ``attn_full_tiles_run``.
Scopes: ``pretrain.MELLUM_SCOPES``.
"""

from __future__ import annotations

from bert_pytorch_tpu.config import MellumConfig
from bert_pytorch_tpu.models.decoder import EXCHANGE_COUNTERS
from bert_pytorch_tpu.models.laguna import LagunaBlock, LagunaForCausalLM


class MellumForCausalLM(LagunaForCausalLM):
    """``LagunaForCausalLM`` (its final norm's epsilon, its rotary tables made
    once a call) over blocks without a gate that name their axes and hand the
    expert axis on."""
    config: MellumConfig

    AXIS_NAMES = True
    COUNTERS = LagunaForCausalLM.COUNTERS + EXCHANGE_COUNTERS

    def blocks(self, wrap):
        block = wrap(LagunaBlock)
        return [block(self.config, layer, self.dtype, self.attention_backend,
                      gate=False, axis_names=True,
                      expert_axis=self.expert_axis,
                      expert_shards=self.expert_shards)
                for layer in range(self.config.num_hidden_layers)]
