"""The written mapping between ``qwen3next_f32``'s tensors and the program's
parameter tree (``models.Qwen3NextForCausalLM``): names only. Both keep every
projection as an [in, out] matrix (``W_qkvz`` as q, k, v, z blocks of columns,
``W_ba`` as b then a, ``W_q`` as a head's q then its gate), the convolution as
[taps, channels] (the last tap the current position), gate and up side by
side with the gate's columns first, the experts stacked on a leading axis and
the layers apart (``l<i>.`` there, ``layers_<i>/`` here), so no tensor is
reshaped on the way. Both kinds of mixer live under ``mixer``.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference import qwen3next_f32
from benchmarks.reference.nemotron_h_map import _leaf

_LAYER = {
    "mixer_norm": "mixer_norm/scale", "mlp_norm": "mlp_norm/scale",
    "w_qkvz": "mixer/in_proj_qkvz", "w_ba": "mixer/in_proj_ba/kernel",
    "conv": "mixer/conv_kernel", "a_log": "mixer/A_log",
    "dt_bias": "mixer/dt_bias", "gnorm": "mixer/norm_scale",
    "wq": "mixer/q_proj", "wk": "mixer/k_proj/kernel",
    "wv": "mixer/v_proj/kernel", "q_norm": "mixer/q_norm/scale",
    "k_norm": "mixer/k_norm/scale",
    "router": "mlp/router_kernel", "w_gu": "mlp/experts_up",
    "w_down": "mlp/experts_down", "shared_gu": "mlp/shared_up/kernel",
    "shared_down": "mlp/shared_down/kernel", "shared_gate": "mlp/shared_gate",
}
_GLOBAL = {"emb": "embedding", "final_norm": "final_norm/scale",
           "head": "lm_head/kernel"}


def table(c: dict) -> dict:
    """reference name -> program path, for the sizes ``c``."""
    out = {}
    for name in qwen3next_f32.param_table(c):
        if name in _GLOBAL:
            out[name] = _GLOBAL[name]
            continue
        layer, leaf = name.split(".")
        if leaf == "wo":  # the output projection of either mixer
            linear = c["kinds"][int(layer[1:])] == "linear_attention"
            path = "mixer/out_proj/kernel" if linear else "mixer/o_proj/kernel"
        else:
            path = _LAYER[leaf]
        out[name] = f"layers_{layer[1:]}/{path}"
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
    return qwen3next_f32.leaf_norms.__wrapped__({
        name: leaf.astype(jnp.float32)
        for name, leaf in from_program(tree, c).items()})
