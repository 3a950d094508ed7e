"""The written mapping between ``keye_f32``'s tensors and the program's
parameter tree (``models.KeyeVLForCausalLM``): names only. Both keep every
projection as an [in, out] matrix (the indexer's query projection a head's
``indexer_head_dim`` columns after the other), gate and up side by side with
the gate's columns first, the experts stacked on a leading axis and the layers
apart (``l<i>.`` there, ``layers_<i>/`` here), so no tensor is reshaped on the
way.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference import keye_f32
from benchmarks.reference.nemotron_h_map import _leaf

_LAYER = {
    "attn_norm": "attention_norm/scale", "mlp_norm": "mlp_norm/scale",
    "wq": "attention/q_proj/kernel", "wk": "attention/k_proj/kernel",
    "wv": "attention/v_proj/kernel", "wo": "attention/o_proj/kernel",
    "q_norm": "attention/q_norm/scale", "k_norm": "attention/k_norm/scale",
    "wqi": "attention/index_q/kernel", "wki": "attention/index_k/kernel",
    "ww": "attention/index_w/kernel",
    "router": "mlp/router_kernel", "w_gu": "mlp/experts_up",
    "w_down": "mlp/experts_down",
}
_GLOBAL = {"emb": "embedding", "final_norm": "final_norm/scale",
           "head": "lm_head/kernel"}


def table(c: dict) -> dict:
    """reference name -> program path, for the sizes ``c``."""
    out = {}
    for name in keye_f32.param_table(c):
        if name in _GLOBAL:
            out[name] = _GLOBAL[name]
            continue
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
    return keye_f32.leaf_norms.__wrapped__({
        name: leaf.astype(jnp.float32)
        for name, leaf in from_program(tree, c).items()})


def indexer_names(c: dict) -> list:
    """The reference's names of the three indexer matrices of every layer."""
    return [name for name in keye_f32.param_table(c)
            if name.split(".")[-1] in keye_f32.INDEXER]
