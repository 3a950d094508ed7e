"""Model FLOPs of next-token pretraining of a ``qwen3_next`` configuration ON
THIS CHIP, of its flash kernels and of the chunked delta rule: the yardstick's
copy (the program has its own in ``utils/flops.py``; a later PR may change
that one, not this).

A matmul of (m, k) x (k, n) costs 2mkn. Per token, forward, from the
configuration file (what this chip holds: ``num_experts`` experts of
``num_experts * ep_size``, ``vocab_size`` rows); K = key heads x d_k, Vw =
value heads x d_v, C the rule's chunk:

* ``gdn_proj``, a delta-rule layer: ``W_qkvz`` 2 H (2 K + 2 Vw), ``W_ba``
  4 H value heads, the output projection 2 Vw H.
* ``delta_rule``, a delta-rule layer: the chunked rule's products. A key head's
  K K^T and Q K^T over a chunk, 2 C d_k each a token; a value head's two
  products with the inverse (on V and on K), 2 C d_v + 2 C d_k, its product
  of the chunk's scores with the corrected values, 2 C d_v, and its three
  products with the state (the correction's read, the query's read, the
  state's update), 2 d_k d_v each. The inverse itself (log2 C steps of [C, C]
  products) is left out: it is the price of the chunked form, not the model's
  work.
* ``attention_proj``: q with its gate 4 H n hd, k and v 2 H KV hd each, o
  2 n hd H. ``attention_core``: the two S x S products over the causal half:
  S (S + 1) / 2 pairs a head, 4 hd a pair.
* ``experts``, every layer: router 2 H experts, shared expert 6 H FS and its
  gate vector 2 H, the routed experts by the EXPECTED top_k x held / experts
  of the tokens: that x 6 H F.
* head: 2 H V.

Training is three times forward. Recomputation under remat is not counted;
embedding lookup, norms, the convolution, rotary, gates, activations and the
optimizer are left out (not matmul work).
"""

from __future__ import annotations

from benchmarks.trace.flops_lm import FLASH_MATMULS

GATED_KERNELS = {"flash_gated_fwd": "flash_fwd",
                 "flash_gated_bwd_dq": "flash_bwd_dq",
                 "flash_gated_bwd_dkv": "flash_bwd_dkv"}
RULE_PASSES = ("forward", "recompute", "backward")


def layer_kinds(config: dict) -> list:
    every = config["full_attention_interval"]
    return config.get("layer_types") or [
        "full_attention" if (l + 1) % every == 0 else "linear_attention"
        for l in range(config["num_hidden_layers"])]


def _rule_sizes(config: dict) -> tuple:
    return (config["linear_num_key_heads"], config["linear_num_value_heads"],
            config["linear_key_head_dim"], config["linear_value_head_dim"],
            config.get("delta_chunk", 64))


def rule_flops_per_token(config: dict) -> float:
    """Forward matmul FLOPs a token of one delta-rule layer's chunked rule."""
    kh, vh, dk, dv, chunk = _rule_sizes(config)
    return float(4 * chunk * kh * dk
                 + vh * (2 * chunk * (2 * dv + dk) + 6 * dk * dv))


def forward_flops_per_token(config: dict, seq_len: int) -> dict:
    h, hd = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    kh, vh, dk, dv, _ = _rule_sizes(config)
    key_w, value_w = kh * dk, vh * dv
    held = config["num_experts"]
    every = held * config.get("ep_size", 1)
    kinds = layer_kinds(config)
    linear, full = kinds.count("linear_attention"), kinds.count("full_attention")
    return {
        "gdn_proj": float(linear * (2 * h * (2 * key_w + 2 * value_w)
                                    + 4 * h * vh + 2 * value_w * h)),
        "delta_rule": linear * rule_flops_per_token(config),
        "attention_proj": float(full * (4 * h * heads * hd + 4 * h * kv * hd
                                        + 2 * heads * hd * h)),
        "attention_core": full * 4 * heads * hd * (seq_len + 1) / 2,
        "experts": len(kinds) * (
            2.0 * h * every + 6 * h * config["shared_expert_intermediate_size"]
            + 2 * h + config["num_experts_per_tok"] * held / every
            * 6 * h * config["moe_intermediate_size"]),
        "head": float(2 * h * config["vocab_size"]),
    }


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


def micro_batches(mix: dict) -> int:
    return mix["global_batch_size_per_chip"] // mix["local_batch_size"]


def flash_gated_call(config: dict, mix: dict, kernel: str) -> tuple:
    """(FLOPs, HBM bytes) of ONE call of a causal flash kernel of the gated
    attention layer on one micro-batch: the pairs counted are the causal
    half, S (S + 1) / 2 a query head of 256 (the tiles a skipping kernel
    visits round this up); bytes are each operand and result once, bfloat16,
    the key-value heads as the wrapper repeats them."""
    s, hd = mix["seq_len"], config["head_dim"]
    bh = mix["local_batch_size"] * config["num_attention_heads"]
    full_name = GATED_KERNELS[kernel]
    flops = FLASH_MATMULS[full_name] * 2.0 * hd * (s * (s + 1) / 2) * bh
    tensors = {"flash_fwd": 4, "flash_bwd_dq": 5, "flash_bwd_dkv": 6}[full_name]
    return flops, float(tensors * bh * s * hd * 2)


def delta_rule_call(config: dict, mix: dict, which: str) -> tuple:
    """(FLOPs, least HBM bytes) of ONE pass of one delta-rule layer's rule
    over one micro-batch, kernel or not: the chunked rule's products
    (``rule_flops_per_token``; twice over in the backward: both operands'
    cotangents) and each operand and result ONCE: ``forward`` (and
    ``recompute``, the same work again) reads q, k (bfloat16, key heads), v
    (bfloat16, value heads), g and beta (float32 a value head) and writes o;
    ``backward`` reads those and o's cotangent and writes the five
    cotangents."""
    if which not in RULE_PASSES:
        raise ValueError(f"pass must be one of {RULE_PASSES}, got {which!r}")
    kh, vh, dk, dv, _ = _rule_sizes(config)
    tokens = mix["local_batch_size"] * mix["seq_len"]
    read = tokens * (2 * (2 * kh * dk + vh * dv) + 2 * 4 * vh)
    written = tokens * 2 * vh * dv
    products = tokens * rule_flops_per_token(config)
    if which == "backward":
        return 2 * products, float(2 * read + written)
    return products, float(read + written)
