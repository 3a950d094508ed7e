"""Sharding helpers: logical-annotation → NamedSharding plumbing.

The glue between model code (logical axis names on params, bert.py) and the
mesh (mesh.py). This is where the reference's "DDP wraps the model"
(run_pretraining.py:270) becomes "every param/batch array gets a
NamedSharding and jit inserts the collectives".
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bert_pytorch_tpu.parallel.mesh import AXIS_SEQ, BATCH_AXES


def mesh_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def batch_sharding(mesh: Mesh, seq_sharded: bool = False) -> NamedSharding:
    """Sharding for [B, S] / [B] host batches: batch over the data, fsdp and
    expert axes (``BATCH_AXES``), sequence over seq axis when context
    parallelism is on."""
    if seq_sharded:
        return NamedSharding(mesh, P(BATCH_AXES, AXIS_SEQ))
    return NamedSharding(mesh, P(BATCH_AXES))


def params_shardings(mesh: Mesh, abstract_variables: Any, rules) -> Any:
    """Map a tree of ``nn.Partitioned``-boxed abstract params (from
    ``jax.eval_shape(model.init, ...)``) to a tree of NamedShardings."""
    logical_specs = nn.get_partition_spec(abstract_variables)
    return nn.logical_to_mesh_sharding(logical_specs, mesh, rules)


def shard_params(params: Any, shardings: Any) -> Any:
    """Device-put a host param tree onto the mesh per the sharding tree."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), params, shardings
    )


def unbox(tree: Any) -> Any:
    """Strip ``nn.Partitioned`` metadata boxes, returning raw arrays."""
    return nn.unbox(tree)
