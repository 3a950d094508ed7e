"""Model FLOPs of next-token pretraining of a ``nemotron_h`` configuration ON
THIS CHIP, and of its kernels: the yardstick's copy (the program has its own
in ``utils/flops.py``; a later PR may change that one, not this).

A matmul of (m, k) x (k, n) costs 2mkn. Per token, forward, from the
configuration file (what this chip holds: ``n_routed_experts`` experts of
``n_routed_experts * ep_size``, ``vocab_size`` rows):

* ``M``: in_proj 2 H (2 inner + 2 G N + heads) + out_proj 2 inner H + the
  chunked scan's four products at chunk Q: 2 Q G N (C B^T) + 2 Q inner (the
  decay-weighted product with X) + 4 N inner (the chunk's state, and the
  carried state's part of the outputs).
* ``*``: the four projections, and the CAUSAL HALF of the two S x S products:
  2 S heads head_dim.
* ``E``: router 2 H experts + shared expert 4 H FS + the routed experts by the
  EXPECTED top_k x held / experts of the tokens: that x 4 H F.
* head: 2 H V.

Training is three times forward. Recomputation under remat is not counted;
embedding lookup, norms, convolution, activations and the optimizer are left
out (not matmul work).
"""

from __future__ import annotations


def forward_flops_per_token(config: dict, seq_len: int) -> dict:
    h = config["hidden_size"]
    kinds = config["hybrid_override_pattern"]
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    g, n, q = config["n_groups"], config["ssm_state_size"], config["chunk_size"]
    ssm = (2 * h * (2 * inner + 2 * g * n + config["mamba_num_heads"])
           + 2 * inner * h + 2 * q * g * n + 2 * q * inner + 4 * n * inner)
    heads, kv, hd = (config["num_attention_heads"],
                     config["num_key_value_heads"], config["head_dim"])
    attention = 4 * h * heads * hd + 4 * h * kv * hd + 2 * seq_len * heads * hd
    held = config["n_routed_experts"]
    every = held * config.get("ep_size", 1)
    experts = (2 * h * every + 4 * h * config["moe_shared_expert_intermediate_size"]
               + config["num_experts_per_tok"] * held / every
               * 4 * h * config["moe_intermediate_size"])
    return {"ssm": float(kinds.count("M") * ssm),
            "attention": float(kinds.count("*") * attention),
            "experts": float(kinds.count("E") * experts),
            "head": float(2 * h * config["vocab_size"])}


def train_flops_per_update(config: dict, mix: dict, chips: int) -> float:
    tokens = mix["seq_len"] * mix["global_batch_size_per_chip"] * chips
    return 3.0 * tokens * sum(
        forward_flops_per_token(config, mix["seq_len"]).values())


def routed_expert_train_flops(config: dict, local_slots: float) -> float:
    """Training FLOPs of the slots REALLY routed to the held experts: each
    slot passes W_up and W_down (2 H F each), three times."""
    return (3.0 * 4 * config["hidden_size"] * config["moe_intermediate_size"]
            * local_slots)


# Matmuls of [block_q, block_k, head_dim] tiles each flash kernel runs per
# pair of positions it visits: fwd QK^T, PV; dq: QK^T, dO V^T, dS K; dk/dv:
# QK^T, dO V^T, P^T dO, dS^T Q.
FLASH_MATMULS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}


def flash_causal_call(config: dict, mix: dict, kernel: str) -> tuple:
    """(FLOPs, HBM bytes) of ONE call of a causal flash kernel on one
    micro-batch: the pairs counted are the causal half, S (S + 1) / 2 a head
    (a kernel that skips the tiles above the diagonal does no more than the
    tiles on it round this up to); bytes are each operand and result once,
    bfloat16, the key-value heads as the wrapper repeats them."""
    s, hd = mix["seq_len"], config["head_dim"]
    bh = mix["local_batch_size"] * config["num_attention_heads"]
    pairs = s * (s + 1) / 2
    flops = FLASH_MATMULS[kernel] * 2.0 * hd * pairs * bh
    tensors = {"flash_fwd": 4, "flash_bwd_dq": 5, "flash_bwd_dkv": 6}[kernel]
    return flops, float(tensors * bh * s * hd * 2)
