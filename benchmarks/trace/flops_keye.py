"""Model FLOPs of next-token pretraining of a ``KeyeVL2`` configuration ON THIS
CHIP, and the operations and least bytes of its indexer and of its sparse
core: the yardstick's copy (the program has its own in ``utils/flops.py``; a
later PR may change that one, not this).

A matmul of (m, k) x (k, n) costs 2mkn. Per token, forward, from the
configuration file (what this chip holds: ``num_experts`` experts of
``num_experts * ep_size``, ``vocab_size`` rows), every layer alike; J x E the
indexer's heads, ``topk`` the keys a query attends to:

* ``attention_proj``: q and o 2 H n hd each, k and v 2 H KV hd each.
* ``indexer_proj``: qI 2 H J E, kI 2 H E, w 2 H J.
* ``indexer_scores``: the CAUSAL pairs of a row, S (S + 1) / 2, each 2 J E.
* ``sparse_core``: the CHOSEN pairs of a row, sum over t of min(t + 1, topk),
  each 4 hd a query head (q . k and p v): THE MODEL'S PAIRS, whatever form
  the kernels take: a mask over dense tiles runs the causal half and is
  counted as the chosen pairs, so it reads as low as it is.
* ``experts``: router 2 H experts, the routed experts by the EXPECTED top_k x
  held / experts of the tokens: that x 6 H F. No shared expert.
* head: 2 H V.

Training is three times forward. Recomputation under remat is not counted, nor
the second scoring and the rebuilt probabilities the indexer's KL needs (the
price of the form, not the model's work); embedding lookup, norms, rotary,
the choice itself (no matmul), activations and the optimizer are left out.
"""

from __future__ import annotations

PASSES = ("forward", "recompute", "backward")


def _sparse(config: dict) -> tuple:
    sparse = config["sa_config"]
    return (int(sparse["indexer_num_heads"]), int(sparse["indexer_head_dim"]),
            int(sparse["topk"]))


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def chosen_pairs(seq: int, topk: int) -> int:
    """sum over t < seq of min(t + 1, topk)."""
    full = min(seq, topk)
    return full * (full + 1) // 2 + (seq - full) * topk


def forward_flops_per_token(config: dict, seq_len: int) -> dict:
    h, hd = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    j, e, topk = _sparse(config)
    held = config["num_experts"]
    every = held * config.get("ep_size", 1)
    layers = config["num_hidden_layers"]
    return {
        "attention_proj": float(layers * (4 * h * heads * hd
                                          + 4 * h * kv * hd)),
        "indexer_proj": float(layers * 2 * h * (j * e + e + j)),
        "indexer_scores": layers * 2.0 * j * e * causal_pairs(seq_len) / seq_len,
        "sparse_core": (layers * 4.0 * hd * heads
                        * chosen_pairs(seq_len, topk) / seq_len),
        "experts": layers * (
            2.0 * h * every + config["num_experts_per_tok"] * held / every
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


def _check(which: str):
    if which not in PASSES:
        raise ValueError(f"pass must be one of {PASSES}, got {which!r}")


def indexer_call(config: dict, mix: dict, which: str) -> tuple:
    """(FLOPs, least HBM bytes) of ONE pass of one layer's indexer over one
    micro-batch, kernel or not. ``forward`` (and ``recompute``, the same work
    again): the scores of the causal pairs, 2 J E each; qI and kI (bfloat16)
    and w (float32) read once, the choice written as one bit a pair of the
    square. ``backward``: the KL's cotangent through the scores of the CHOSEN
    pairs, both operands', 4 J E each; the operands, the bits and the core's
    q, k and log-sum-exps read, the three cotangents written."""
    _check(which)
    j, e, topk = _sparse(config)
    rows, seq = mix["local_batch_size"], mix["seq_len"]
    operands = rows * seq * (2 * (j * e + e) + 4 * j)
    bits = rows * seq * seq // 8
    if which == "backward":
        core = rows * seq * (2 * config["head_dim"] * (
            config["num_attention_heads"] + config["num_key_value_heads"])
            + 4 * config["num_attention_heads"])
        return (4.0 * j * e * rows * chosen_pairs(seq, topk),
                float(2 * operands + bits + core))
    return 2.0 * j * e * rows * causal_pairs(seq), float(operands + bits)


def sparse_core_call(config: dict, mix: dict, which: str) -> tuple:
    """(FLOPs, least HBM bytes) of ONE pass of one layer's core over one
    micro-batch, kernel or not: THE CHOSEN PAIRS, 2 hd a product and query
    head; ``forward`` (and ``recompute``) q . k and p v; ``backward`` the
    four cotangent products (the scores made again are the price of a
    flash form, not counted). Bytes: q and the output (and in the backward
    their cotangents) on every query head, k and v (and theirs) on the
    key-value heads, bfloat16, once; the choice's bits once."""
    _check(which)
    _, _, topk = _sparse(config)
    rows, seq, hd = mix["local_batch_size"], mix["seq_len"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    pairs = rows * chosen_pairs(seq, topk)
    tensors = rows * seq * hd * 2 * (2 * heads + 2 * kv)
    bits = rows * seq * seq // 8
    if which == "backward":
        return 4 * 2.0 * hd * heads * pairs, float(2 * tensors + bits)
    return 2 * 2.0 * hd * heads * pairs, float(tensors + bits)
