"""Model FLOPs of BERT pretraining, and the table of peaks: the yardstick's
copy (the program has its own in ``utils/flops.py``; a later PR may change
that one, not this).

A matmul of (m, k) x (k, n) costs 2mkn. Per sequence of S tokens, hidden H,
L layers, FFN width F, M masked positions, padded vocabulary V, forward:
encoder L * (8 S H^2 + 4 S^2 H + 4 S H F); heads M * (2 H^2 + 2 H V) for the
MLM transform and tied decoder at the masked positions only, plus 2 H^2 + 4 H
for the pooler and the NSP classifier. Training is three times forward.
Recomputation under remat is not counted; embeddings, LayerNorm, softmax,
activations and the optimizer are left out (not matmul work).
"""

from __future__ import annotations

import json
import os


def train_flops_per_seq(config: dict, seq_len: int, max_pred: int) -> float:
    h, f = config["hidden_size"], config["intermediate_size"]
    layers = config["num_hidden_layers"]
    vocab = config["vocab_size"] + (-config["vocab_size"]) % 8
    s = seq_len
    encoder = layers * (8 * s * h * h + 4 * s * s * h + 4 * s * h * f)
    heads = max_pred * (2 * h * h + 2 * h * vocab) + 2 * h * h + 2 * h * 2
    return 3.0 * (encoder + heads)


def train_flops_per_update(config: dict, mix: dict, chips: int) -> float:
    return (train_flops_per_seq(config, mix["seq_len"],
                                mix["max_predictions_per_seq"])
            * mix["global_batch_size_per_chip"] * chips)


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json"),
              encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def peak_flops(device_kind: str) -> float:
    return float(peaks(device_kind)["bf16_flops_per_s"])


def mfu(config, mix, chips, device_kind, updates_per_s) -> float:
    """End-to-end model FLOP/s utilization, from wall time (a fraction)."""
    return (train_flops_per_update(config, mix, chips) * updates_per_s
            / (chips * peak_flops(device_kind)))
