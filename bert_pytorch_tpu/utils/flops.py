"""Analytic model-FLOP accounting for MFU reporting.

The reference repo reports raw sequences/second only
(run_pretraining.py:597-599); judging a TPU number against an A100 anchor
then needs a hardware-normalised metric. Model FLOPs Utilisation (MFU)
divides the *model* FLOPs actually required per step (forward + backward,
NOT counting rematerialisation recompute) by the chip's peak matmul
throughput — the convention from the PaLM appendix.

Matmul FLOP accounting per sequence of length S, hidden H, layers L,
intermediate F, masked positions M, vocab V (a matmul of (m,k)x(k,n)
costs 2mkn FLOPs):

  per layer, forward:
    QKV + output projections:  4 * 2*S*H*H
    attention scores QK^T:     2 * S*S*H
    attention context AV:      2 * S*S*H
    FFN (two mats):            2 * 2*S*H*F
  encoder forward  = L * (8*S*H^2 + 4*S^2*H + 4*S*H*F)
  heads forward:
    pooler:                    2*H*H
    NSP classifier:            2*H*2
    MLM transform:             M * 2*H*H
    MLM decoder (tied vocab):  M * 2*H*V
  training multiplier: 3x forward (one backward pass costs ~2x forward
  in matmul FLOPs — dL/dW and dL/dx per matmul).

Embedding lookups, layernorms, biases, softmax and activations are
omitted (sub-1% and not MXU work).
"""

from __future__ import annotations

from typing import Optional

# Peak dense bf16 matmul TFLOP/s per chip, keyed by the exact (lowercased)
# PJRT ``device_kind``. Public numbers from cloud.google.com/tpu/docs.
# libtpu reports "TPU v5 lite" for v5e but plain "TPU v5" for v5p, and
# "TPU v6 lite" for v6e/Trillium — hence exact keys, not substrings: a kind
# that merely contains "v5" must not be handed the v5p peak.
_PEAK_TFLOPS_BY_KIND = {
    "tpu v6 lite": 918.0,
    "tpu v6e": 918.0,
    "tpu v5p": 459.0,
    "tpu v5": 459.0,
    "tpu v5 lite": 197.0,
    "tpu v5e": 197.0,
    "tpu v4": 275.0,
    "tpu v3": 123.0,
    "tpu v2": 45.0,
}


def peak_tflops(device_kind: str) -> Optional[float]:
    """Peak bf16 TFLOP/s for a device kind. None for a device that is not a
    TPU (the CPU test mesh: no peak, so no MFU). A TPU kind that the table
    does not hold is an error — an assumed peak would make every MFU
    computed from it wrong without saying so."""
    kind = device_kind.strip().lower()
    if not kind.startswith("tpu"):
        return None
    if kind not in _PEAK_TFLOPS_BY_KIND:
        raise ValueError(
            f"no peak FLOP/s known for device kind {device_kind!r}; add it "
            "to utils/flops.py _PEAK_TFLOPS_BY_KIND with its source")
    return _PEAK_TFLOPS_BY_KIND[kind]


def bert_encoder_flops_per_seq(config, seq_len: int) -> float:
    """Forward matmul FLOPs of the encoder stack for ONE sequence."""
    h = config.hidden_size
    f = config.intermediate_size
    ll = config.num_hidden_layers
    s = seq_len
    # Pure host math: every operand is a Python int off the config / CLI
    # (never a device array), so this float() is not a device fetch.
    # jaxlint: disable=HS101
    return float(ll * (8 * s * h * h + 4 * s * s * h + 4 * s * h * f))


def bert_train_flops_per_seq(config, seq_len: int, max_pred_per_seq: int,
                             next_sentence: bool = True) -> float:
    """Model FLOPs (fwd+bwd) for ONE sequence of the pretraining objective."""
    h = config.hidden_size
    v = config.vocab_size
    m = max_pred_per_seq
    heads = m * (2 * h * h + 2 * h * v)
    if next_sentence:
        heads += 2 * h * h + 2 * h * 2  # pooler + NSP classifier
    return 3.0 * (bert_encoder_flops_per_seq(config, seq_len) + heads)


def bert_finetune_flops_per_seq(config, seq_len: int, head_outputs: int = 2,
                                per_token_head: bool = True,
                                pooled: bool = False) -> float:
    """Model FLOPs (fwd+bwd) for ONE sequence of a finetuning objective.

    The task head is one linear: H -> ``head_outputs`` applied per token
    (``per_token_head``, e.g. QA span / NER logits) or once on the pooled
    [CLS] vector (``pooled`` adds the H x H pooler matmul first, e.g.
    GLUE / SWAG classification)."""
    h = config.hidden_size
    head = 2.0 * h * head_outputs
    if per_token_head:
        head *= seq_len
    if pooled:
        head += 2.0 * h * h  # pooler
    return 3.0 * (bert_encoder_flops_per_seq(config, seq_len) + head)


def nemotron_h_forward_flops_per_token(config, seq_len: int) -> dict:
    """Forward matmul FLOPs per token of a ``nemotron_h`` model on THIS chip,
    by part (a matmul of (m, k) x (k, n) costs 2mkn): ``ssm`` (projections and
    the chunked scan's four products at chunk Q: 2QGN + 2Q inner + 4N inner),
    ``attention`` (projections and the causal half of the two S x S products),
    ``experts`` (router, shared expert, and the routed experts by the EXPECTED
    top_k x held / all of the tokens), ``head``. Embedding lookup, norms,
    convolution, activations and the optimizer are left out."""
    h = config.hidden_size
    kinds = config.hybrid_override_pattern
    inner, conv_dim = config.mamba_inner, config.mamba_conv_dim
    q, n, g = config.chunk_size, config.ssm_state_size, config.n_groups
    ssm = (2 * h * (inner + conv_dim + config.mamba_num_heads) + 2 * inner * h
           + 2 * q * g * n + 2 * q * inner + 4 * n * inner)
    heads, kv, hd = (config.num_attention_heads, config.num_key_value_heads,
                     config.head_dim)
    attention = (4 * h * heads * hd + 4 * h * kv * hd
                 + 2 * seq_len * heads * hd)  # 2 products x half the square
    expected = (config.num_experts_per_tok * config.n_routed_experts
                / config.router_experts)
    experts = (2 * h * config.router_experts
               + 4 * h * config.moe_shared_expert_intermediate_size
               + expected * 4 * h * config.moe_intermediate_size)
    return {"ssm": 1.0 * kinds.count("M") * ssm,
            "attention": 1.0 * kinds.count("*") * attention,
            "experts": 1.0 * kinds.count("E") * experts,
            "head": 2.0 * h * config.vocab_size}


def nemotron_h_train_flops_per_seq(config, seq_len: int) -> float:
    """Training (3x forward) matmul FLOPs of one row of ``seq_len`` tokens."""
    return 3.0 * seq_len * sum(
        nemotron_h_forward_flops_per_token(config, seq_len).values())


def laguna_forward_flops_per_token(config, seq_len: int) -> dict:
    """Forward matmul FLOPs per token of a ``laguna`` model on THIS chip (the
    heads, experts and vocabulary rows it holds), by part: ``attention_proj``
    (q, k, v, the per-head gate and the output projection of every layer),
    ``attention_full`` and ``attention_window`` (the two S x S products over
    the pairs a row really sees: the causal half, or the band of
    ``sliding_window`` positions), ``dense_mlp`` (three products),
    ``experts`` (router, gated shared expert, and the gated routed experts by
    the EXPECTED top_k x held / all of the tokens), ``head``. Embedding
    lookup, norms, rotary, activations and the optimizer are left out."""
    h, hd, kv = config.hidden_size, config.head_dim, config.num_key_value_heads
    parts = dict.fromkeys(("attention_proj", "attention_full",
                           "attention_window", "dense_mlp", "experts"), 0.0)
    expected = (config.num_experts_per_tok * config.num_experts
                / config.router_experts)
    for layer, heads in enumerate(config.num_attention_heads_per_layer):
        parts["attention_proj"] += (4 * h * heads * hd + 4 * h * kv * hd
                                    + 2 * h * heads)
        window = config.window_of(layer)
        reach = min(window or seq_len, seq_len)
        seen = reach - reach * (reach - 1) / (2 * seq_len)  # keys a row sees
        parts["attention_window" if window else "attention_full"] += (
            4 * seen * heads * hd)
        if config.mlp_layer_types[layer] == "dense":
            parts["dense_mlp"] += 6 * h * config.intermediate_size
        else:
            parts["experts"] += (
                2 * h * config.router_experts
                + 6 * h * config.shared_expert_intermediate_size
                + expected * 6 * h * config.moe_intermediate_size)
    return dict(parts, head=2.0 * h * config.vocab_size)


def mellum_forward_flops_per_token(config, seq_len: int) -> dict:
    """:func:`laguna_forward_flops_per_token` of a ``mellum`` model, which
    has no per-head gate (and, by its config's derived keys, no dense layer
    and no shared expert). The experts held are the config's: the whole
    layer's under an expert axis, where the count is of all the axis's chips
    together."""
    parts = laguna_forward_flops_per_token(config, seq_len)
    parts["attention_proj"] -= sum(
        2 * config.hidden_size * heads
        for heads in config.num_attention_heads_per_layer)
    return parts


def phi_flash_forward_flops_per_token(config, seq_len: int) -> dict:
    """Forward matmul FLOPs per token of a ``phi4flash`` model on THIS chip
    (the layers, heads and vocabulary rows it holds), by part: ``mlp`` (two
    products, the first twice as wide), ``s6_proj`` (the Mamba-1 mixers' four
    projections; the scan itself is elementwise work and counts nothing
    here), ``gmu``, ``attention_proj`` (``Wqkv`` or ``Wq``, and ``out_proj``),
    ``attention_full`` and ``attention_window`` (both maps of every pair over
    the pairs a row really sees, the value product twice as wide as the
    score product: 2 d + 4 d a pair and map), ``head`` (tied: the embedding's
    rows held). Lookup, norms, convolution, activations and the optimizer are
    left out."""
    h, hd, inner = config.hidden_size, config.head_dim, config.mamba_inner
    heads, kv = config.num_attention_heads, config.num_key_value_heads
    parts = dict.fromkeys(("mlp", "s6_proj", "gmu", "attention_proj",
                           "attention_full", "attention_window"), 0.0)
    for layer, kind in enumerate(config.layer_types):
        parts["mlp"] += 6 * h * config.intermediate_size
        if kind in ("mamba", "mamba_memory"):
            rank, states = config.mamba_dt_rank, config.mamba_d_state
            parts["s6_proj"] += (4 * h * inner + 2 * inner * (rank + 2 * states)
                                 + 2 * rank * inner + 2 * inner * h)
        elif kind == "gmu":
            parts["gmu"] += 4 * h * inner
        else:
            own = 0 if kind == "cross_attention" else 2 * kv
            parts["attention_proj"] += 2 * h * hd * (heads + own) + 2 * heads * hd * h
            window = config.window_of(layer)
            reach = min(window or seq_len, seq_len)
            seen = reach - reach * (reach - 1) / (2 * seq_len)  # keys a row sees
            parts["attention_window" if window else "attention_full"] += (
                6 * hd * heads * seen)
    return dict(parts, head=2.0 * h * config.vocab_size)


def zaya_forward_flops_per_token(config, seq_len: int) -> dict:
    """Forward matmul FLOPs per token of a ``zaya`` model on THIS chip (the
    experts and vocabulary rows it holds), by part: ``cca_proj`` (q, k, v
    into the latent, the output projection back, and the second
    convolution's per-head products), ``cca_core`` (the two S x S products
    over the causal half), ``router`` (down projection, two hidden layers,
    the output), ``experts`` (three products by the EXPECTED held / router
    outputs of the tokens: one expert a token, the skip among the outputs),
    ``head`` (tied: the embedding's rows held). Lookup, norms, the depthwise
    convolution, rotary, merges and the optimizer are left out."""
    h, hd = config.hidden_size, config.head_dim
    heads, kv = config.num_attention_heads, config.num_key_value_heads
    rw = config.router_hidden_size
    layer = {
        "cca_proj": (4 * h * heads * hd + 4 * h * kv * hd
                     + 2 * config.cca_time1 * (heads + kv) * hd * hd),
        "cca_core": 4 * heads * hd * (seq_len + 1) / 2,
        "router": 2 * h * rw + 4 * rw * rw + 2 * rw * config.router_outputs,
        "experts": (config.num_experts / config.router_outputs
                    * 6 * h * config.moe_intermediate_size),
    }
    return dict({k: 1.0 * config.num_hidden_layers * v
                 for k, v in layer.items()},
                head=2.0 * h * config.vocab_size)


def qwen3_next_forward_flops_per_token(config, seq_len: int) -> dict:
    """Forward matmul FLOPs per token of a ``qwen3_next`` model on THIS chip
    (the experts and vocabulary rows it holds), by part: ``gdn_proj`` (the
    delta-rule mixers' three projections), ``delta_rule`` (the chunked rule's
    products at ``delta_chunk``: a chunk's K K^T and Q K^T, the two products
    with the inverse, the four with the state; the inverse itself is left
    out), ``attention_proj`` (q with its gate, k, v, o), ``attention_core``
    (the two S x S products over the causal half), ``experts`` (router,
    shared expert with its gate vector, and three products by the EXPECTED
    top_k x held / experts of the tokens), ``head``. Lookup, norms, the
    convolution, rotary, activations and the optimizer are left out."""
    h, hd = config.hidden_size, config.head_dim
    heads, kv = config.num_attention_heads, config.num_key_value_heads
    key_heads, value_heads = (config.linear_num_key_heads,
                              config.linear_num_value_heads)
    dk, dv, chunk = (config.linear_key_head_dim, config.linear_value_head_dim,
                     config.delta_chunk)
    key_w, value_w = key_heads * dk, value_heads * dv
    mixers = {
        "linear_attention": {
            "gdn_proj": (2 * h * (2 * key_w + 2 * value_w)
                         + 4 * h * value_heads + 2 * value_w * h),
            "delta_rule": (4 * chunk * key_w
                           + value_heads * (2 * chunk * (2 * dv + dk)
                                            + 6 * dk * dv))},
        "full_attention": {
            "attention_proj": 4 * h * heads * hd + 4 * h * kv * hd
                              + 2 * heads * hd * h,
            "attention_core": 4 * heads * hd * (seq_len + 1) / 2}}
    parts = dict.fromkeys(("gdn_proj", "delta_rule", "attention_proj",
                           "attention_core"), 0.0)
    for kind in config.layer_types:
        for name, value in mixers[kind].items():
            parts[name] += value
    parts["experts"] = config.num_hidden_layers * (
        2.0 * h * config.router_experts
        + 6 * h * config.shared_expert_intermediate_size + 2 * h
        + config.num_experts_per_tok * config.num_experts
        / config.router_experts * 6 * h * config.moe_intermediate_size)
    return dict(parts, head=2.0 * h * config.vocab_size)


def keye_vl_forward_flops_per_token(config, seq_len: int) -> dict:
    """Forward matmul FLOPs per token of a ``KeyeVL2`` model on THIS chip (the
    experts and vocabulary rows it holds), by part: ``attention_proj`` (q, k,
    v, o), ``indexer_proj`` (qI, kI, w), ``indexer_scores`` (every CAUSAL
    pair of the row, ``indexer_num_heads x indexer_head_dim`` a pair),
    ``sparse_core`` (the two products over the CHOSEN pairs, ``min(t + 1,
    topk)`` a query: the model's pairs, whatever tiles a kernel runs),
    ``experts`` (router and three products by the EXPECTED top_k x held /
    experts of the tokens; no shared expert), ``head``. Lookup, norms,
    rotary, the choice, the second scoring the indexer's KL makes,
    activations and the optimizer are left out."""
    h, hd = config.hidden_size, config.head_dim
    heads, kv = config.num_attention_heads, config.num_key_value_heads
    index_w = config.indexer_num_heads * config.indexer_head_dim
    full = min(seq_len, config.topk)
    chosen = full * (full + 1) / 2 + (seq_len - full) * config.topk
    layer = {
        "attention_proj": 4 * h * heads * hd + 4 * h * kv * hd,
        "indexer_proj": 2 * h * (index_w + config.indexer_head_dim
                                 + config.indexer_num_heads),
        "indexer_scores": 2 * index_w * (seq_len + 1) / 2,
        "sparse_core": 4 * heads * hd * chosen / seq_len,
        "experts": (2.0 * h * config.router_experts
                    + config.num_experts_per_tok * config.num_experts
                    / config.router_experts
                    * 6 * h * config.moe_intermediate_size),
    }
    return dict({k: 1.0 * config.num_hidden_layers * v
                 for k, v in layer.items()},
                head=2.0 * h * config.vocab_size)


def joyai_forward_flops_per_token(config, seq_len: int) -> dict:
    """Forward matmul FLOPs per token of a ``joyai_llm_flash`` model on THIS
    chip (the experts and vocabulary rows it holds), by part, the
    multi-token-prediction module's block counted with the layers:
    ``mla_proj`` (the queries' two products, the keys' and values' two, the
    output's), ``mla_core`` (the two S x S products over the causal half at
    192 and 128 a pair), ``dense_mlp`` (the leading dense layers),
    ``experts`` (router, shared expert and three products by the EXPECTED
    top_k x held / experts of the tokens), ``mtp_merge`` (``W_eh``), ``head``
    and ``mtp_head`` (the shared head's two passes). Lookup, norms, rotary,
    activations and the optimizer are left out."""
    h, heads = config.hidden_size, config.num_attention_heads
    qk, wide = config.qk_head_dim, config.v_head_dim
    module = config.num_nextn_predict_layers
    blocks = config.num_hidden_layers + module
    routed = blocks - config.first_k_dense_replace
    head = 2.0 * h * config.vocab_size
    return {
        "mla_proj": blocks * 2.0 * (
            h * config.q_lora_rank + config.q_lora_rank * heads * qk
            + h * (config.kv_lora_rank + config.qk_rope_head_dim)
            + config.kv_lora_rank * heads * (config.qk_nope_head_dim + wide)
            + heads * wide * h),
        "mla_core": blocks * 2.0 * heads * (qk + wide) * (seq_len + 1) / 2,
        "dense_mlp": (config.first_k_dense_replace
                      * 6.0 * h * config.intermediate_size),
        "experts": routed * (
            2.0 * h * config.router_experts + 6 * h * config.shared_width
            + config.num_experts_per_tok * config.n_routed_experts
            / config.router_experts * 6 * h * config.moe_intermediate_size),
        "mtp_merge": module * 4.0 * h * h,
        "head": head, "mtp_head": module * head}


def causal_lm_train_flops_per_seq(config, seq_len: int) -> float:
    """Training (3x forward) matmul FLOPs of one row of ``seq_len`` tokens of
    a ``causal_lm`` family's model (by the config's ``model_type``)."""
    per_token = {"nemotron_h": nemotron_h_forward_flops_per_token,
                 "laguna": laguna_forward_flops_per_token,
                 "phi4flash": phi_flash_forward_flops_per_token,
                 "zaya": zaya_forward_flops_per_token,
                 "qwen3_next": qwen3_next_forward_flops_per_token,
                 "KeyeVL2": keye_vl_forward_flops_per_token,
                 "joyai_llm_flash": joyai_forward_flops_per_token,
                 "mellum": mellum_forward_flops_per_token,
                 }[config.model_type]
    return 3.0 * seq_len * sum(per_token(config, seq_len).values())


def mfu(seq_per_sec_per_chip: float, flops_per_seq: float,
        device_kind: str) -> Optional[float]:
    """Fraction of the chip's peak used by model FLOPs; None ("not
    measured") off a TPU, where there is no peak to divide by."""
    peak = peak_tflops(device_kind)
    if peak is None:
        return None
    return seq_per_sec_per_chip * flops_per_seq / (peak * 1e12)
