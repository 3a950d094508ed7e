"""The written mapping between ``joyai_f32``'s tensors and the program's
parameter tree (``models.JoyAIForCausalLM``): names only. Both keep every
projection as an [in, out] matrix (the query's up projection a head's 128 +
64 columns after the other, the key-value one a head's 128 + 128), gate and up
side by side with the gate's columns first, the experts stacked on a leading
axis, the layers apart (``l<i>.`` there, ``layers_<i>/`` here) and the
multi-token-prediction module under ``mtp.`` / ``mtp/`` with its block under
``mtp/block/``, so no tensor is reshaped on the way.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference import joyai_f32
from benchmarks.reference.nemotron_h_map import _leaf

_BLOCK = {
    "attn_norm": "attention_norm/scale", "mlp_norm": "mlp_norm/scale",
    "wqa": "attention/q_a_proj/kernel", "q_norm": "attention/q_a_norm/scale",
    "wqb": "attention/q_b_proj/kernel", "wkva": "attention/kv_a_proj/kernel",
    "kv_norm": "attention/kv_a_norm/scale",
    "wkvb": "attention/kv_b_proj/kernel", "wo": "attention/o_proj/kernel",
    "w13": "mlp/gate_up_proj/kernel", "w2": "mlp/down_proj/kernel",
    "router": "mlp/router_kernel", "router_bias": "mlp/router_correction_bias",
    "w_gu": "mlp/experts_up", "w_down": "mlp/experts_down",
    "shared_gu": "mlp/shared_up/kernel",
    "shared_down": "mlp/shared_down/kernel",
}
_MODULE = {"enorm": "enorm/scale", "hnorm": "hnorm/scale",
           "weh": "eh_proj/kernel", "final_norm": "final_norm/scale"}
_GLOBAL = {"emb": "embedding", "final_norm": "final_norm/scale",
           "head": "lm_head/kernel"}


def table(c: dict) -> dict:
    """reference name -> program path, for the sizes ``c``."""
    out = {}
    for name in joyai_f32.param_table(c):
        if name in _GLOBAL:
            out[name] = _GLOBAL[name]
            continue
        where, leaf = name.split(".")
        if where != joyai_f32.MTP:
            out[name] = f"layers_{where[1:]}/{_BLOCK[leaf]}"
        elif leaf in _MODULE:
            out[name] = f"mtp/{_MODULE[leaf]}"
        else:
            out[name] = f"mtp/block/{_BLOCK[leaf]}"
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
    return joyai_f32.leaf_norms.__wrapped__({
        name: leaf.astype(jnp.float32)
        for name, leaf in from_program(tree, c).items()})


def mtp_names(c: dict) -> list:
    """The reference's names of the tensors whose gradient is the second
    term's alone: the module's merge (two norms, ``W_eh``), its block and its
    final norm (all of ``mtp.``), less the router's bias, which has none."""
    return [name for name in joyai_f32.param_table(c)
            if name.startswith(joyai_f32.MTP + ".")
            and not name.endswith(".router_bias")]


def latent_names(c: dict) -> list:
    """The reference's names of the two down projections into the latents
    and the two latent norms of every block."""
    return [name for name in joyai_f32.param_table(c)
            if name.split(".")[-1] in joyai_f32.LATENT]
