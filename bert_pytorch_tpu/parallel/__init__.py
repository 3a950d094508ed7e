"""Parallelism layer: device mesh, sharding rules, collectives, launcher.

The TPU-native replacement for the reference's NCCL/DDP/torchrun stack
(SURVEY.md §2.2/§5.8): instead of wrapping the model in DDP and letting NCCL
allreduce gradients (run_pretraining.py:185,270), we lay the pod out as a
`jax.sharding.Mesh` with axes ``('data', 'fsdp', 'pipe', 'seq', 'model',
'expert')``, annotate
parameters/activations with logical axis names, and let XLA insert the
collectives (psum / all-gather / reduce-scatter) over ICI.

Strategies (rule sets):
  - ``dp``    — pure data parallelism: params replicated, batch sharded.
                The reference's only strategy (DDP), here with zero
                allreduce code — XLA emits the gradient psum.
  - ``fsdp``  — params sharded over the fsdp axis (ZeRO-3 analog); XLA
                all-gathers weights per layer and reduce-scatters grads.
  - ``tp``    — Megatron-style tensor parallelism over the model axis
                (heads/mlp/vocab sharded).
  - ``sp``    — sequence/context parallelism over the seq axis for
                long-context (ring attention lives in ops/pallas).
  - ``pp``    — pipeline parallelism over the pipe axis: the encoder's
                stacked layers shard into contiguous stage blocks and
                microbatches rotate through them on a GPipe schedule
                (parallel/pipeline.py).
  - ``ep``    — expert parallelism over the expert axis (the decoder
                families that name their axes: a layer's experts and the
                vocabulary's rows divided, the slots exchanged between
                chips, ops/moe.py; the causal_lm step runs this axis
                manually, pretrain.make_train_step).
These compose: a mesh may use several axes at once. The composition is
first-class via ``MeshSpec`` (``--mesh dp=4,fsdp=2,pipe=2``, the one way
the command line names a mesh): any axis product's rules derive from one
template, and the names above are the rule sets ``logical_axis_rules``
also accepts by name (docs/parallelism.md).
"""

from bert_pytorch_tpu.parallel.mesh import (
    MeshConfig,
    MeshSpec,
    MeshSpecError,
    create_mesh,
    current_mesh,
    derive_rules,
    logical_axis_rules,
    parse_mesh_spec,
)
from bert_pytorch_tpu.parallel.pipeline import gpipe, stage_layer_count
from bert_pytorch_tpu.parallel.sharding import (
    batch_sharding,
    mesh_sharding,
    params_shardings,
    shard_params,
)

__all__ = [
    "MeshConfig",
    "MeshSpec",
    "MeshSpecError",
    "create_mesh",
    "current_mesh",
    "derive_rules",
    "logical_axis_rules",
    "parse_mesh_spec",
    "gpipe",
    "stage_layer_count",
    "batch_sharding",
    "mesh_sharding",
    "params_shardings",
    "shard_params",
]
