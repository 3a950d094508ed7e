"""The written mapping between ``phi4flash_f32``'s tensors and the program's
parameter tree (``models.PhiFlashForCausalLM``): names, and ONE cut: the
program's ``Wqkv/bias`` is the reference's ``bq``, ``bk``, ``bv`` one after
the other (``_PIECES``). Both keep every
projection as an [in, out] matrix, the MLP's gate and up side by side with the
gate's columns first, ``Wqkv``'s columns as queries, keys, values, ``A_log``
as [channels, states], the convolution as [taps, channels] and the layers
apart (``l<i>.`` there, ``layers_<i>/`` here), so no tensor is reshaped on the
way. The head is the embedding on both sides.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference import phi4flash_f32
from benchmarks.reference.nemotron_h_map import _leaf

_LAYER = {
    "ln1_w": "norm1/scale", "ln1_b": "norm1/bias",
    "ln2_w": "norm2/scale", "ln2_b": "norm2/bias",
    "fc1": "mlp/fc1/kernel", "fc2": "mlp/fc2/kernel",
    "in_proj": "mixer/in_proj/kernel", "conv_w": "mixer/conv_kernel",
    "conv_b": "mixer/conv_bias", "x_proj": "mixer/x_proj/kernel",
    "dt_proj": "mixer/dt_proj/kernel", "dt_bias": "mixer/dt_bias",
    "A_log": "mixer/A_log", "D": "mixer/D",
    "out_proj": "mixer/out_proj/kernel",
    "gmu_in": "mixer/in_proj/kernel", "gmu_out": "mixer/out_proj/kernel",
    "wqkv": "mixer/Wqkv/kernel", "wq": "mixer/Wq/kernel",
    "bq": "mixer/Wq/bias",  # a cross layer's; else a piece of Wqkv/bias
    "wo": "mixer/out_proj/kernel", "bo": "mixer/out_proj/bias",
    "subln": "mixer/subln/scale",
    "lq1": "mixer/lambda_q1", "lk1": "mixer/lambda_k1",
    "lq2": "mixer/lambda_q2", "lk2": "mixer/lambda_k2",
}
_GLOBAL = {"emb": "embedding", "final_norm_w": "final_norm/scale",
           "final_norm_b": "final_norm/bias"}


_PIECES = ("bq", "bk", "bv")  # of Wqkv/bias, in this order


def _pieces(c: dict, layer: int) -> dict:
    """leaf -> (start, stop) within layer ``layer``'s ``Wqkv/bias``; empty
    where the layer has no ``Wqkv``."""
    if c["kinds"][layer] not in ("sliding_attention", "full_attention"):
        return {}
    wide, kv = c["heads"] * c["hd"], c["KV"] * c["hd"]
    return {"bq": (0, wide), "bk": (wide, wide + kv),
            "bv": (wide + kv, wide + 2 * kv)}


def table(c: dict) -> dict:
    """reference name -> program path, for the sizes ``c`` (the three pieces
    of a ``Wqkv/bias`` all name that leaf)."""
    out = {}
    for name in phi4flash_f32.param_table(c):
        if name in _GLOBAL:
            out[name] = _GLOBAL[name]
        else:
            layer, leaf = name.split(".")
            path = ("mixer/Wqkv/bias" if leaf in _pieces(c, int(layer[1:]))
                    else _LAYER[leaf])
            out[name] = f"layers_{layer[1:]}/{path}"
    return out


def to_program(ref: dict, c: dict) -> dict:
    """The reference's tensors as the program's nested parameter tree."""
    tree: dict = {}
    for name, path in table(c).items():
        layer, _, leaf_name = name.partition(".")
        if leaf_name in _PIECES[1:] or (
                leaf_name == "bq" and _pieces(c, int(layer[1:]))):
            if leaf_name != "bq":
                continue  # joined below, with the layer's bq
            value = jnp.concatenate([ref[f"{layer}.{n}"] for n in _PIECES])
        else:
            value = ref[name]
        node = tree
        *parents, leaf = path.split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[leaf] = value
    return tree


def from_program(tree: dict, c: dict) -> dict:
    """A tree in the program's layout under the reference's names."""
    out = {}
    for name, path in table(c).items():
        layer, _, leaf_name = name.partition(".")
        cut = _pieces(c, int(layer[1:])).get(leaf_name) if leaf_name else None
        value = _leaf(tree, path)
        out[name] = value if cut is None else value[cut[0]:cut[1]]
    return out


def leaf_norms(tree: dict, c: dict) -> dict:
    """Per-tensor L2 norms of a tree in the program's layout, under the
    reference's names. Traceable."""
    return phi4flash_f32.leaf_norms.__wrapped__({
        name: leaf.astype(jnp.float32)
        for name, leaf in from_program(tree, c).items()})
