"""The written mapping between the reference's tensors and the program's
parameter tree (``models.BertForPreTraining``): names and shapes only.

The reference stores every projection as a [in, out] matrix; the program
splits attention projections into [hidden, heads, head] and stacks the
encoder's layers on a leading axis under ``nn.scan`` (so does the reference).
"""

from __future__ import annotations

import jax.numpy as jnp

_ATT = "bert/encoder/layers/attention/"
_ENC = "bert/encoder/layers/"
_EMB = "bert/embeddings/"
_HEAD = "predictions/transform/"

# reference name -> (program path, how the program shapes it)
TABLE = {
    "word_emb": (_EMB + "word_embeddings/embedding", None),
    "pos_emb": (_EMB + "position_embeddings/embedding", None),
    "type_emb": (_EMB + "token_type_embeddings/embedding", None),
    "emb_ln_g": (_EMB + "layer_norm/scale", None),
    "emb_ln_b": (_EMB + "layer_norm/bias", None),
    "layer.wq": (_ATT + "query/kernel", "in_heads"),
    "layer.bq": (_ATT + "query/bias", "heads"),
    "layer.wk": (_ATT + "key/kernel", "in_heads"),
    "layer.bk": (_ATT + "key/bias", "heads"),
    "layer.wv": (_ATT + "value/kernel", "in_heads"),
    "layer.bv": (_ATT + "value/bias", "heads"),
    "layer.wo": (_ATT + "output/kernel", "heads_out"),
    "layer.bo": (_ATT + "output/bias", None),
    "layer.ln1_g": (_ATT + "output_layer_norm/scale", None),
    "layer.ln1_b": (_ATT + "output_layer_norm/bias", None),
    "layer.wi": (_ENC + "intermediate/dense/kernel", None),
    "layer.bi": (_ENC + "intermediate/dense/bias", None),
    "layer.wf": (_ENC + "output/kernel", None),
    "layer.bf": (_ENC + "output/bias", None),
    "layer.ln2_g": (_ENC + "output_layer_norm/scale", None),
    "layer.ln2_b": (_ENC + "output_layer_norm/bias", None),
    "pool_w": ("bert/pooler/dense_act/dense/kernel", None),
    "pool_b": ("bert/pooler/dense_act/dense/bias", None),
    "mlm_w": (_HEAD + "dense_act/dense/kernel", None),
    "mlm_b": (_HEAD + "dense_act/dense/bias", None),
    "mlm_ln_g": (_HEAD + "layer_norm/scale", None),
    "mlm_ln_b": (_HEAD + "layer_norm/bias", None),
    "mlm_bias": ("predictions/bias", None),
    "nsp_w": ("seq_relationship/kernel", None),
    "nsp_b": ("seq_relationship/bias", None),
}


def to_program(ref: dict, heads: int) -> dict:
    """The reference's tensors as the program's nested parameter tree."""
    tree: dict = {}
    for name, (path, shaping) in TABLE.items():
        value = ref[name]
        if shaping == "in_heads":       # [L, H, H] -> [L, H, A, hd]
            value = value.reshape(value.shape[:2] + (heads, -1))
        elif shaping == "heads":        # [L, H] -> [L, A, hd]
            value = value.reshape(value.shape[:1] + (heads, -1))
        elif shaping == "heads_out":    # [L, H, H] -> [L, A, hd, H]
            value = value.reshape(
                value.shape[:1] + (heads, -1) + value.shape[2:])
        node = tree
        *parents, leaf = path.split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[leaf] = value
    return tree


def leaf_norms(tree: dict) -> dict:
    """Per-tensor L2 norms of a tree in the program's layout, under the
    reference's names (one norm per layer for a stacked tensor). Traceable."""
    out = {}
    for name, (path, _) in TABLE.items():
        node = tree
        for part in path.split("/"):
            node = node[part]
        axes = tuple(range(1 if name.startswith("layer.") else 0, node.ndim))
        out[name] = jnp.sqrt(jnp.sum(jnp.square(node.astype(jnp.float32)), axis=axes))
    return out


def from_program(tree: dict) -> dict:
    """A (host) tree in the program's layout as the reference's tensors."""
    out = {}
    for name, (path, shaping) in TABLE.items():
        node = tree
        for part in path.split("/"):
            node = node[part]
        if shaping in ("in_heads", "heads"):      # fold [A, hd] back into H
            node = node.reshape(node.shape[:-2] + (-1,))
        elif shaping == "heads_out":              # [L, A, hd, H] -> [L, H, H]
            node = node.reshape(node.shape[:1] + (-1,) + node.shape[3:])
        out[name] = node
    return out
