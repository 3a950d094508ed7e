"""Model FLOPs of next-token pretraining of a ``phi4flash`` configuration ON
THIS CHIP, and the operations and bytes of its kernels: the yardstick's copy
(the program has its own in ``utils/flops.py``; a later PR may change that
one, not this).

A matmul of (m, k) x (k, n) costs 2mkn. Per token, forward, from the
configuration file (the layers of ``layer_types``, ``num_attention_heads``
query heads on ``num_key_value_heads`` key-value heads, ``vocab_size`` rows),
layer by layer:

* every layer's MLP: ``fc1`` 2 H 2I + ``fc2`` 2 I H = 6 H I.
* a Mamba-1 mixer: ``in_proj`` 2 H 2D, ``x_proj`` 2 D (R + 2 N), ``dt_proj``
  2 R D, ``out_proj`` 2 D H (D = expand x H). THE SCAN IS NOT MATMUL WORK and
  counts nothing here; ``selective_scan_call`` counts its elementwise
  operations for the roofline.
* a gated memory unit: 2 H D + 2 D H.
* differential attention: ``Wqkv`` 2 H (n + 2 KV) d or, on a cross layer,
  ``Wq`` 2 H n d; ``out_proj`` 2 n d H; the core: both maps of every pair, n
  maps in all, a score product 2 d and a value product over values twice as
  wide 2 (2 d) a pair, OVER THE PAIRS A ROW SEES: S (S + 1) / 2 a map on a full
  or cross layer, ``band_pairs`` on a sliding one.
* head: 2 H V (tied: the embedding's rows held).

Training is three times forward. Recomputation under remat is not counted;
embedding lookup, norms, convolution, the scan, activations and the optimizer
are left out (not matmul work).
"""

from __future__ import annotations

from benchmarks.trace.flops_laguna import band_pairs

MAMBA = ("mamba", "mamba_memory")
SCAN_KERNELS = ("selective_scan_fwd", "selective_scan_bwd")
# kernel name -> (the pass's kernel, the kind of layer that calls it)
DIFF_KERNELS = {
    f"flash_diff_{tag}{kernel}": (kernel, kind)
    for tag, kind in (("window_", "sliding_attention"), ("", "full_attention"),
                      ("cross_", "cross_attention"))
    for kernel in ("fwd", "bwd_dq", "bwd_dkv")}


def _sizes(config: dict) -> tuple:
    h = config["hidden_size"]
    heads = config["num_attention_heads"]
    hd = h // (heads * config.get("tp_size", 1))
    return h, heads, config["num_key_value_heads"], hd, (
        config.get("mamba_expand", 2) * h)


def forward_flops_per_token(config: dict, seq_len: int) -> dict:
    h, heads, kv, hd, inner = _sizes(config)
    rank = config.get("mamba_dt_rank") or -(-h // 16)
    states = config.get("mamba_d_state", 16)
    parts = dict.fromkeys(("mlp", "s6_proj", "gmu", "attention_proj",
                           "attention_full", "attention_window"), 0.0)
    for kind in config["layer_types"]:
        parts["mlp"] += 6 * h * config["intermediate_size"]
        if kind in MAMBA:
            parts["s6_proj"] += (4 * h * inner + 2 * inner * (rank + 2 * states)
                                 + 2 * rank * inner + 2 * inner * h)
        elif kind == "gmu":
            parts["gmu"] += 4 * h * inner
        else:
            own = 0 if kind == "cross_attention" else 2 * kv
            parts["attention_proj"] += 2 * h * hd * (heads + own) + 2 * heads * hd * h
            window = config["sliding_window"] if kind == "sliding_attention" else None
            parts["attention_window" if window else "attention_full"] += (
                6 * hd * heads * band_pairs(seq_len, window) / seq_len)
    return dict(parts, head=float(2 * h * config["vocab_size"]))


def train_flops_per_update(config: dict, mix: dict, chips: int) -> float:
    tokens = mix["seq_len"] * mix["global_batch_size_per_chip"] * chips
    return 3.0 * tokens * sum(
        forward_flops_per_token(config, mix["seq_len"]).values())


def selective_scan_call(config: dict, mix: dict, kernel: str) -> tuple:
    """(operations, least HBM bytes) of ONE call of the selective scan on one
    micro-batch, kernel or not. Operations, a (position, channel, state)
    triple: forward 7 (dt A, its exp, the decay's product, B's, the add, C's
    product and its sum); backward 22 (the forward's five again, and the
    cotangents of h, C, B, dt u, dt A, A and the carried dh). Bytes, float32,
    each operand and result ONCE: forward reads u, dt [S, D] and B, C [S, N]
    and writes y [S, D]; backward reads u, dt, dy, B, C and writes du, ddt
    [S, D] and dB, dC [S, N]. The states never count: they are the kernel's
    business to keep out of HBM."""
    _, _, _, _, inner = _sizes(config)
    states = config.get("mamba_d_state", 16)
    rows = mix["local_batch_size"] * mix["seq_len"]
    if kernel == "selective_scan_fwd":
        return 7.0 * rows * inner * states, 4.0 * rows * (3 * inner + 2 * states)
    return 22.0 * rows * inner * states, 4.0 * rows * (5 * inner + 4 * states)


def flash_diff_call(config: dict, mix: dict, kernel: str) -> tuple:
    """(FLOPs, HBM bytes) of ONE call of a differential flash kernel on one
    micro-batch: n maps (both of every pair), keys of d and values of 2 d, the
    pairs counted THE BAND's on a sliding layer and the triangle's on a full or
    cross one (the tiles a kernel visits round that up). Per pair and map:
    forward QK^T 2 d + PV 4 d; dq QK^T 2 d + dO V^T 4 d + dS K 2 d; dk/dv QK^T
    2 d + dO V^T 4 d + P^T dO 4 d + dS^T Q 2 d. Bytes are each operand and
    result once, bfloat16, the key-value pairs as the wrapper repeats them:
    q, k, dq, dk of d and v, out, dO, dv of 2 d a position and map."""
    _, heads, _, hd, _ = _sizes(config)
    which, kind = DIFF_KERNELS[kernel]
    s = mix["seq_len"]
    maps = mix["local_batch_size"] * heads
    window = config["sliding_window"] if kind == "sliding_attention" else None
    per_pair = {"fwd": 6, "bwd_dq": 8, "bwd_dkv": 12}[which] * hd
    widths = {"fwd": 2 + 2 * 2, "bwd_dq": 3 + 2 * 2, "bwd_dkv": 3 + 3 * 2}[which]
    return (float(per_pair) * band_pairs(s, window) * maps,
            float(widths * hd * maps * s * 2))
