"""The written mapping between ``mellum_f32``'s tensors and the program's
parameter tree (``models.MellumForCausalLM``): names only. Both keep every
projection as an [in, out] matrix, gate and up side by side with the gate's
columns first, all 64 experts of a layer stacked on a leading axis and the
layers apart (``l<i>.`` there, ``layers_<i>/`` here), so no tensor is reshaped
on the way. The program's tree is the WHOLE model's whatever mesh divides it:
under an expert axis the arrays are global arrays whose shards lie on the
chips, and the names and shapes here do not change.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference import mellum_f32
from benchmarks.reference.nemotron_h_map import _leaf

_LAYER = {
    "attn_norm": "attn_norm/scale", "mlp_norm": "mlp_norm/scale",
    "wq": "attn/q_proj/kernel", "wk": "attn/k_proj/kernel",
    "wv": "attn/v_proj/kernel", "wo": "attn/o_proj/kernel",
    "router": "mlp/router_kernel", "w_gu": "mlp/experts_up",
    "w_down": "mlp/experts_down",
}
_GLOBAL = {"emb": "embedding", "final_norm": "final_norm/scale",
           "head": "lm_head/kernel"}


def table(c: dict) -> dict:
    """reference name -> program path, for the sizes ``c``."""
    out = {}
    for name in mellum_f32.param_table(c):
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


def replicated_names(c: dict) -> tuple:
    """The tensors every chip of an expert axis holds whole (attention,
    norms, routers): those whose gradient is summed over the axis."""
    return tuple(name for name in mellum_f32.param_table(c)
                 if name not in ("emb", "head")
                 and not mellum_f32._per_expert(name))


def leaf_norms(tree: dict, c: dict) -> dict:
    """Per-tensor L2 norms of a tree in the program's layout, under the
    reference's names (one per expert for the experts' tensors). Traceable."""
    return mellum_f32.leaf_norms.__wrapped__({
        name: leaf.astype(jnp.float32)
        for name, leaf in from_program(tree, c).items()})
