"""Model FLOPs of next-token pretraining of a ``laguna`` configuration ON THIS
CHIP, and of its windowed kernels: the yardstick's copy (the program has its
own in ``utils/flops.py``; a later PR may change that one, not this).

A matmul of (m, k) x (k, n) costs 2mkn. Per token, forward, from the
configuration file (what this chip holds: the heads of
``num_attention_heads_per_layer`` on ``num_key_value_heads``, ``num_experts``
experts of ``num_experts * ep_size``, ``vocab_size`` rows), layer by layer:

* attention projections: q and o 2 H n hd each, k and v 2 H KV hd each, the
  per-head gate 2 H n.
* attention core: the two S x S products OVER THE PAIRS A ROW SEES: position i
  sees min(i + 1, window) keys, S (S + 1) / 2 pairs a head on a full layer and
  ``band_pairs`` on a sliding one; 4 hd a pair.
* dense MLP: three products, 6 H I. Routed layer: router 2 H experts + shared
  expert 6 H FS + the routed experts by the EXPECTED top_k x held / experts of
  the tokens: that x 6 H F.
* head: 2 H V.

Training is three times forward. Recomputation under remat is not counted;
embedding lookup, norms, rotary, activations and the optimizer are left out
(not matmul work).
"""

from __future__ import annotations

from benchmarks.trace.flops_lm import FLASH_MATMULS

WINDOW_KERNELS = {"flash_window_fwd": "flash_fwd",
                  "flash_window_bwd_dq": "flash_bwd_dq",
                  "flash_window_bwd_dkv": "flash_bwd_dkv"}


def band_pairs(seq: int, window) -> float:
    """(query, key) pairs of one head under the causal mask and a window:
    sum over i < seq of min(i + 1, window)."""
    w = min(window or seq, seq)
    return w * seq - w * (w - 1) / 2


def windows(config: dict) -> list:
    return [config["sliding_window"] if kind == "sliding_attention" else None
            for kind in config["layer_types"]]


def forward_flops_per_token(config: dict, seq_len: int) -> dict:
    h, hd, kv = (config["hidden_size"], config["head_dim"],
                 config["num_key_value_heads"])
    held = config["num_experts"]
    every = held * config.get("ep_size", 1)
    parts = dict.fromkeys(("attention_proj", "attention_full",
                           "attention_window", "dense_mlp", "experts"), 0.0)
    for heads, window, mlp in zip(config["num_attention_heads_per_layer"],
                                  windows(config), config["mlp_layer_types"]):
        parts["attention_proj"] += 4 * h * heads * hd + 4 * h * kv * hd + 2 * h * heads
        parts["attention_window" if window else "attention_full"] += (
            4 * hd * heads * band_pairs(seq_len, window) / seq_len)
        if mlp == "dense":
            parts["dense_mlp"] += 6 * h * config["intermediate_size"]
        else:
            parts["experts"] += (
                2 * h * every + 6 * h * config["shared_expert_intermediate_size"]
                + config["num_experts_per_tok"] * held / every
                * 6 * h * config["moe_intermediate_size"])
    return dict(parts, head=float(2 * h * config["vocab_size"]))


def train_flops_per_update(config: dict, mix: dict, chips: int) -> float:
    tokens = mix["seq_len"] * mix["global_batch_size_per_chip"] * chips
    return 3.0 * tokens * sum(
        forward_flops_per_token(config, mix["seq_len"]).values())


def routed_expert_train_flops(config: dict, local_slots: float) -> float:
    """Training FLOPs of the slots REALLY routed to the held experts: each
    slot passes the gate, the up and the down product (2 H F each), three
    times."""
    return (3.0 * 6 * config["hidden_size"] * config["moe_intermediate_size"]
            * local_slots)


def flash_window_call(config: dict, mix: dict, kernel: str) -> tuple:
    """(FLOPs, HBM bytes) of ONE call of a windowed flash kernel on one
    micro-batch: the pairs counted are THE BAND's (``band_pairs``), not the
    triangle's; the tiles a kernel visits round that up (the two tiles an
    edge crosses are computed whole and masked). Bytes are each operand and
    result once, bfloat16, the key-value heads as the wrapper repeats them.
    The sliding layers of a configuration have one head count."""
    heads = {n for n, w in zip(config["num_attention_heads_per_layer"],
                               windows(config)) if w}
    if len(heads) != 1:
        raise ValueError(f"sliding layers of unlike head counts: {heads}")
    s, hd = mix["seq_len"], config["head_dim"]
    bh = mix["local_batch_size"] * heads.pop()
    full_name = WINDOW_KERNELS[kernel]
    flops = (FLASH_MATMULS[full_name] * 2.0 * hd
             * band_pairs(s, config["sliding_window"]) * bh)
    tensors = {"flash_fwd": 4, "flash_bwd_dq": 5, "flash_bwd_dkv": 6}[full_name]
    return flops, float(tensors * bh * s * hd * 2)
