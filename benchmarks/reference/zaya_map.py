"""The written mapping between ``zaya_f32``'s tensors and the program's
parameter tree (``models.ZayaForCausalLM``): names only. Both keep every
projection as an [in, out] matrix, the values' two halves side by side in one
``wv``, the depthwise convolution as [taps, channels] and the per-head one as
[taps, groups, in, out] (the last tap the current position), the experts'
gate and up side by side with the gate's columns first and stacked on a
leading axis, and the layers apart (``l<i>.`` there, ``layers_<i>/`` here),
so no tensor is reshaped on the way. The head is the embedding on both sides
(``head`` there, ``embedding`` here).
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference import zaya_f32
from benchmarks.reference.nemotron_h_map import _leaf

_MERGE = {"sx": "x_scale", "bx": "x_bias", "sy": "y_scale", "by": "y_bias"}
_LAYER = {
    "attn_norm": "attn_norm/scale", "mlp_norm": "mlp_norm/scale",
    "wq": "attn/q_proj/kernel", "wk": "attn/k_proj/kernel",
    "wv": "attn/v_proj/kernel", "wo": "attn/o_proj/kernel",
    "conv0": "attn/conv0_kernel", "conv0_b": "attn/conv0_bias",
    "conv1": "attn/conv1_kernel", "conv1_b": "attn/conv1_bias",
    "tau": "attn/k_scale",
    "wd": "router/down_proj/kernel", "bd": "router/down_proj/bias",
    "gamma": "router/eda_scale", "rnorm": "router/norm/scale",
    "w1": "router/fc1/kernel", "b1": "router/fc1/bias",
    "w2": "router/fc2/kernel", "b2": "router/fc2/bias",
    "w3": "router/out_proj/kernel", "beta": "router/router_correction_bias",
    "w_gu": "mlp/experts_up", "w_down": "mlp/experts_down",
    **{f"ma_{k}": f"attn_merge/{v}" for k, v in _MERGE.items()},
    **{f"mm_{k}": f"mlp_merge/{v}" for k, v in _MERGE.items()},
}
_GLOBAL = {"head": "embedding", "final_norm": "final_norm/scale"}


def table(c: dict) -> dict:
    """reference name -> program path, for the sizes ``c``."""
    out = {}
    for name in zaya_f32.param_table(c):
        if name in _GLOBAL:
            out[name] = _GLOBAL[name]
        else:
            layer, leaf = name.split(".")
            out[name] = f"layers_{layer[1:]}/{_LAYER[leaf]}"
    return out


def to_program(ref: dict, c: dict) -> dict:
    """The reference's tensors as the program's nested parameter tree."""
    tree: dict = {}
    for name, path in table(c).items():
        node = tree
        *parents, leaf = path.split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[leaf] = ref[name]
    return tree


def from_program(tree: dict, c: dict) -> dict:
    """A tree in the program's layout under the reference's names."""
    return {name: _leaf(tree, path) for name, path in table(c).items()}


def leaf_norms(tree: dict, c: dict) -> dict:
    """Per-tensor L2 norms of a tree in the program's layout, under the
    reference's names (one per expert for the experts' tensors). Traceable."""
    return zaya_f32.leaf_norms.__wrapped__({
        name: leaf.astype(jnp.float32)
        for name, leaf in from_program(tree, c).items()})
