"""Model FLOPs of next-token pretraining of a ``mellum`` configuration over
the chips that share its layers, and the bytes of its exchange: the
yardstick's copy (the program has its own in ``utils/flops.py``; a later PR
may change that one, not this).

A matmul of (m, k) x (k, n) costs 2mkn. Per token, forward, from the
configuration file (the WHOLE layers: under an expert axis the chips hold all
of a layer's experts and all of the vocabulary between them, so the count is
of all the chips together and is divided by their number where a share of a
chip's peak is taken), layer by layer:

* attention projections: q and o 2 H n hd each, k and v 2 H KV hd each (no
  gate).
* attention core: the two S x S products OVER THE PAIRS A ROW SEES
  (``flops_laguna.band_pairs``), 4 hd a pair.
* routed layer: router 2 H E + top_k x 6 H F (every slot is computed
  somewhere: no share is left out).
* head: 2 H V.

Training is three times forward. Recomputation under remat is not counted;
embedding lookup, norms, rotary, activations and the optimizer are left out
(not matmul work).

**The exchange.** A slot that crosses to another chip carries a row of H
bfloat16 numbers. In one update it crosses seven times under ``--remat
full``: out and back in the forward pass, out and back in the block's
recompute, and in the backward pass out twice (the row again, and the
cotangent of its token's sum) and back once (the row's cotangent), with 8
bytes of float32 weight and weight's cotangent beside them. ``ops/moe.py``
sends whole rounds of ``exchange_rows`` places a pair of chips, so more bytes
move than these (the places that carry no slot): the count here is of the
slots REALLY sent (``moe_exchange_slots_out``, the program's own counter of
one forward pass), which is what a share of the links' peak has to be of,
whatever implements the exchange.
"""

from __future__ import annotations

from benchmarks.trace.flops_laguna import (WINDOW_KERNELS, band_pairs,
                                           windows)
from benchmarks.trace.flops_lm import FLASH_MATMULS, flash_causal_call

CROSSINGS_PER_UPDATE = 7  # of a remote slot's row under --remat full
ROW_ITEM_BYTES = 2        # bfloat16
WEIGHT_BYTES = 8          # float32 weight out, its cotangent back


def forward_flops_per_token(config: dict, seq_len: int) -> dict:
    h, hd, kv = (config["hidden_size"], config["head_dim"],
                 config["num_key_value_heads"])
    heads = config["num_attention_heads"]
    held = config["num_experts"]
    every = held * config.get("ep_size", 1)
    parts = dict.fromkeys(("attention_proj", "attention_full",
                           "attention_window", "experts"), 0.0)
    for window in windows(config):
        parts["attention_proj"] += 4 * h * heads * hd + 4 * h * kv * hd
        parts["attention_window" if window else "attention_full"] += (
            4 * hd * heads * band_pairs(seq_len, window) / seq_len)
        parts["experts"] += (
            2 * h * every + config["num_experts_per_tok"] * held / every
            * 6 * h * config["moe_intermediate_size"])
    return dict(parts, head=float(2 * h * config["vocab_size"]))


def train_flops_per_update(config: dict, mix: dict, chips: int) -> float:
    tokens = mix["seq_len"] * mix["global_batch_size_per_chip"] * chips
    return 3.0 * tokens * sum(
        forward_flops_per_token(config, mix["seq_len"]).values())


def routed_expert_train_flops(config: dict, local_slots: float) -> float:
    """Training FLOPs of the slots that ARRIVED at their experts: each passes
    the gate, the up and the down product (2 H F each), three times."""
    return (3.0 * 6 * config["hidden_size"] * config["moe_intermediate_size"]
            * local_slots)


def flash_call(config: dict, mix: dict, kernel: str) -> tuple:
    """(FLOPs, HBM bytes) of ONE call of a flash kernel on a chip's rows of a
    micro-batch. A causal kernel: ``flops_lm.flash_causal_call`` (the causal
    half). A windowed one: the pairs INSIDE THE BAND (``band_pairs``; the
    tiles an edge crosses are computed whole and masked, so the kernel visits
    more), every layer with ``num_attention_heads`` heads; bytes are each
    operand and result once, bfloat16, the key-value heads as the wrapper
    repeats them."""
    if kernel not in WINDOW_KERNELS:
        return flash_causal_call(config, mix, kernel)
    full_name = WINDOW_KERNELS[kernel]
    s, hd = mix["seq_len"], config["head_dim"]
    bh = mix["local_batch_size"] * config["num_attention_heads"]
    flops = (FLASH_MATMULS[full_name] * 2.0 * hd
             * band_pairs(s, config["sliding_window"]) * bh)
    tensors = {"flash_fwd": 4, "flash_bwd_dq": 5, "flash_bwd_dkv": 6}[full_name]
    return flops, float(tensors * bh * s * hd * 2)


def exchange_bytes_per_update(config: dict, slots_out: float) -> float:
    """Bytes that left the chips in an update for the ``slots_out`` remote
    slots of its forward passes (summed over the chips)."""
    return slots_out * (CROSSINGS_PER_UPDATE * config["hidden_size"]
                        * ROW_ITEM_BYTES + WEIGHT_BYTES)
