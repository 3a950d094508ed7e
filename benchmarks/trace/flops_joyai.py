"""Model FLOPs of pretraining of a ``joyai_llm_flash`` configuration ON THIS
CHIP with its multi-token-prediction objective, and the operations and least
bytes of its latent attention core: the yardstick's copy (the program has its
own in ``utils/flops.py``; a later PR may change that one, not this).

A matmul of (m, k) x (k, n) costs 2mkn. Per token, forward, from the
configuration file (what this chip holds: ``n_routed_experts`` experts of
``n_routed_experts * ep_size``, ``vocab_size`` rows); the module's block is
counted with the layers (``num_hidden_layers`` + ``num_nextn_predict_layers``
blocks of latent attention, all but the ``first_k_dense_replace`` leading ones
with an expert layer):

* ``mla_proj``: 2 x (H q_lora + q_lora n (nope + rope) + H (kv_lora + rope) +
  kv_lora n (nope + v) + n v H) a block.
* ``mla_core``: the CAUSAL pairs of a row, S (S + 1) / 2, each 2 (nope + rope)
  + 2 v a head: THE MODEL'S PAIRS, whatever form the kernels take.
* ``dense_mlp``: 6 H I a leading dense layer.
* ``experts``: router 2 H experts, the shared expert 6 H F, the routed experts
  by the EXPECTED top_k x held / experts of the tokens: that x 6 H F.
* ``mtp_merge``: 2 x 2H x H. ``head`` and ``mtp_head``: 2 H V each (the
  shared head runs twice).

Training is three times forward. Recomputation under remat is not counted;
embedding lookup, norms, rotary, activations and the optimizer are left out.
"""

from __future__ import annotations

KERNELS = ("flash_mla_fwd", "flash_mla_bwd_dq", "flash_mla_bwd_dkv")


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def blocks(config: dict) -> int:
    """Blocks of latent attention: the layers and the module's."""
    return config["num_hidden_layers"] + config.get("num_nextn_predict_layers", 1)


def expert_layers(config: dict) -> int:
    return blocks(config) - config["first_k_dense_replace"]


def forward_flops_per_token(config: dict, seq_len: int) -> dict:
    h, heads = config["hidden_size"], config["num_attention_heads"]
    nope, turned = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    wide, q_rank, kv_rank = (config["v_head_dim"], config["q_lora_rank"],
                             config["kv_lora_rank"])
    held = config["n_routed_experts"]
    every = held * config.get("ep_size", 1)
    width = config["moe_intermediate_size"]
    module = config.get("num_nextn_predict_layers", 1)
    head = float(2 * h * config["vocab_size"])
    return {
        "mla_proj": blocks(config) * 2.0 * (
            h * q_rank + q_rank * heads * (nope + turned)
            + h * (kv_rank + turned) + kv_rank * heads * (nope + wide)
            + heads * wide * h),
        "mla_core": (blocks(config) * 2.0 * heads * (nope + turned + wide)
                     * causal_pairs(seq_len) / seq_len),
        "dense_mlp": (config["first_k_dense_replace"]
                      * 6.0 * h * config["intermediate_size"]),
        "experts": expert_layers(config) * (
            2.0 * h * every + 6 * h * width * config["n_shared_experts"]
            + config["num_experts_per_tok"] * held / every * 6 * h * width),
        "mtp_merge": module * 4.0 * h * h,
        "head": head, "mtp_head": module * head,
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


def expected_local_slots(config: dict, mix: dict) -> float:
    """``moe_local_slots`` an update if routing were even: expert layers x
    tokens x top_k x held / experts."""
    every = config["n_routed_experts"] * config.get("ep_size", 1)
    return (expert_layers(config) * mix["seq_len"]
            * mix["global_batch_size_per_chip"] * config["num_experts_per_tok"]
            * config["n_routed_experts"] / every)


def mla_core_call(config: dict, mix: dict, kernel: str) -> tuple:
    """(FLOPs, least HBM bytes) of ONE call of one of the core's three
    kernels (``KERNELS``) over one micro-batch of one block: THE SAME WORK
    WHATEVER FORM IMPLEMENTS IT. The causal pairs, S (S + 1) / 2 a row and
    head. Forward: q . k over nope + rope and p v over v, 2 each a pair.
    Backward: the four cotangent products (dV and dP over v, dQ and dK over
    nope + rope), two to each kernel: dP and dQ to ``bwd_dq``, dV and dK to
    ``bwd_dkv``; the scores each makes again (and the dP the second makes
    again) are the price of a flash form, not counted. Bytes, bfloat16, once:
    q, k_nope, v and the output (or its cotangent) on every head, THE SHARED
    k_r ONCE (not a copy a head), the log-sum-exps in float32 (and delta in
    the backward); the backward kernels also write their cotangents."""
    if kernel not in KERNELS:
        raise ValueError(f"kernel must be one of {KERNELS}, got {kernel!r}")
    rows, seq = mix["local_batch_size"], mix["seq_len"]
    heads = config["num_attention_heads"]
    nope, turned, wide = (config["qk_nope_head_dim"],
                          config["qk_rope_head_dim"], config["v_head_dim"])
    pairs = rows * heads * causal_pairs(seq)
    work = 2.0 * (nope + turned + wide) * pairs
    q, k_nope, v = (2 * rows * seq * heads * width
                    for width in (nope + turned, nope, wide))
    k_r, lse = 2 * rows * seq * turned, 4 * rows * seq * heads
    read = q + k_nope + k_r + v
    if kernel == "flash_mla_fwd":
        return work, float(read + v + lse)  # the output is as wide as v
    written = q if kernel == "flash_mla_bwd_dq" else k_nope + k_r + v
    return work, float(read + v + 2 * lse + written)
