"""Model FLOPs of next-token pretraining of a ``zaya`` configuration ON THIS
CHIP, of its flash kernels, and the least bytes of what it does between the
projections into the latent and the core: the yardstick's copy (the program
has its own in ``utils/flops.py``; a later PR may change that one, not this).

A matmul of (m, k) x (k, n) costs 2mkn. Per token, forward, from the
configuration file (what this chip holds: ``num_experts`` experts of
``num_experts * ep_size``, ``vocab_size`` rows), a layer:

* ``cca_proj``: q into the latent and the output projection back 2 H n d
  each, k and v 2 H KV d each, and the second convolution's products, ``taps``
  matrices of d x d for each of the n + KV heads: 2 taps (n + KV) d d.
* ``cca_core``: the two S x S products over the causal half: S (S + 1) / 2
  pairs a head, 4 d a pair.
* ``router``: down projection 2 H R, two hidden layers 2 R R each, the
  output 2 R (experts + 1).
* ``experts``: ONE expert a token of ``experts + 1`` outputs (the skip among
  them), so the EXPECTED held / (experts + 1) of the tokens pass three
  products here: that x 6 H F.
* head: 2 H V, the embedding's rows held.

Training is three times forward. Recomputation under remat is not counted;
embedding lookup, norms, the depthwise convolution, rotary, merges,
activations and the optimizer are left out (not matmul work).
"""

from __future__ import annotations

from benchmarks.trace.flops_lm import FLASH_MATMULS

CCA_KERNELS = {"flash_cca_fwd": "flash_fwd", "flash_cca_bwd_dq": "flash_bwd_dq",
               "flash_cca_bwd_dkv": "flash_bwd_dkv"}
MIX_PASSES = ("forward", "recompute", "backward")


def _sizes(config: dict) -> tuple:
    held = config["num_experts"]
    return (config["hidden_size"], config["head_dim"],
            config["num_attention_heads"], config["num_key_value_heads"],
            held, held * config.get("ep_size", 1) + 1)


def forward_flops_per_token(config: dict, seq_len: int) -> dict:
    h, hd, heads, kv, held, outputs = _sizes(config)
    rw, layers = config["router_hidden_size"], config["num_hidden_layers"]
    layer = {
        "cca_proj": (4 * h * heads * hd + 4 * h * kv * hd
                     + 2 * config["cca_time1"] * (heads + kv) * hd * hd),
        "cca_core": 4 * heads * hd * (seq_len + 1) / 2,
        "router": 2 * h * rw + 4 * rw * rw + 2 * rw * outputs,
        "experts": held / outputs * 6 * h * config["moe_intermediate_size"],
    }
    return dict({k: float(layers * v) for k, v in layer.items()},
                head=float(2 * h * config["vocab_size"]))


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


def flash_cca_call(config: dict, mix: dict, kernel: str) -> tuple:
    """(FLOPs, HBM bytes) of ONE call of a causal flash kernel of the latent
    attention on one micro-batch: the pairs counted are the causal half,
    S (S + 1) / 2 a query head (the tiles a skipping kernel visits round this
    up); bytes are each operand and result once, bfloat16, the key-value
    heads as the wrapper repeats them."""
    _, hd, heads, _, _, _ = _sizes(config)
    s = mix["seq_len"]
    bh = mix["local_batch_size"] * heads
    full_name = CCA_KERNELS[kernel]
    flops = FLASH_MATMULS[full_name] * 2.0 * hd * (s * (s + 1) / 2) * bh
    tensors = {"flash_fwd": 4, "flash_bwd_dq": 5, "flash_bwd_dkv": 6}[full_name]
    return flops, float(tensors * bh * s * hd * 2)


def cca_mix_call(config: dict, mix: dict, which: str) -> tuple:
    """(FLOPs, least HBM bytes) of ONE pass of one layer over one micro-batch
    through what lies between the projections and the core (both
    convolutions, the q-k mean, the norm, the value shift, the rotary turn),
    kernel or not: each operand and result ONCE, bfloat16. ``forward`` (and
    ``recompute``, the same work again) reads z = [q0, k0] and v and writes
    q, k and v; ``backward`` reads their three cotangents and z again (q and
    k before the norm are made again from it) and writes the cotangents of z
    and v. The FLOPs are the second convolution's products (twice over in the
    backward: the input's and the weights' cotangents); by peaks.json's two
    peaks the bytes bound every pass."""
    if which not in MIX_PASSES:
        raise ValueError(f"pass must be one of {MIX_PASSES}, got {which!r}")
    _, hd, heads, kv, _, _ = _sizes(config)
    tokens = mix["local_batch_size"] * mix["seq_len"]
    latent, values = (heads + kv) * hd, kv * hd
    products = 2.0 * config["cca_time1"] * latent * hd * tokens
    if which == "backward":
        return 2 * products, 2.0 * tokens * (3 * latent + 2 * values)
    return products, 2.0 * tokens * (2 * latent + 2 * values)
