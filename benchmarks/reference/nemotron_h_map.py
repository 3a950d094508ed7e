"""The written mapping between ``nemotron_h_f32``'s tensors and the program's
parameter tree (``models.NemotronHForCausalLM``): names only. Both keep every
projection as an [in, out] matrix, the experts stacked on a leading axis and
the layers apart (``l<i>.`` there, ``layers_<i>/`` here), so no tensor is
reshaped on the way.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference import nemotron_h_f32

_MIXER = {
    "in_proj": "in_proj/kernel", "conv_w": "conv_kernel", "conv_b": "conv_bias",
    "dt_bias": "dt_bias", "A_log": "A_log", "D": "D", "gate_norm": "norm_scale",
    "out_proj": "out_proj/kernel",
    "wq": "q_proj/kernel", "wk": "k_proj/kernel", "wv": "v_proj/kernel",
    "wo": "o_proj/kernel",
    "router": "router_kernel", "router_bias": "router_correction_bias",
    "w_up": "experts_up", "w_down": "experts_down",
    "shared_up": "shared_up/kernel", "shared_down": "shared_down/kernel",
}


def table(c: dict) -> dict:
    """reference name -> program path, for the sizes ``c``."""
    out = {}
    for name in nemotron_h_f32.param_table(c):
        if name == "emb":
            out[name] = "embedding"
        elif name == "final_norm":
            out[name] = "final_norm/scale"
        elif name == "head":
            out[name] = "lm_head/kernel"
        else:
            layer, leaf = name.split(".")
            out[name] = f"layers_{layer[1:]}/" + (
                "norm/scale" if leaf == "norm" else "mixer/" + _MIXER[leaf])
    return out


def _leaf(tree: dict, path: str):
    for part in path.split("/"):
        tree = tree[part]
    return tree


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
    return nemotron_h_f32.leaf_norms.__wrapped__({
        name: leaf.astype(jnp.float32)
        for name, leaf in from_program(tree, c).items()})
