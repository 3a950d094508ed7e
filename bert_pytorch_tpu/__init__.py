"""bert_pytorch_tpu — a TPU-native (JAX/XLA/Pallas/pjit) BERT pretraining and
finetuning framework with the capability set of gpauloski/BERT-PyTorch.

Built TPU-first: SPMD over a `jax.sharding.Mesh`, one jitted train step with
microbatch `lax.scan` accumulation, bf16 compute / fp32 params, Pallas kernels
for the fused ops the reference delegated to NVIDIA Apex, and a C++ tokenizer
core replacing the HuggingFace Rust tokenizers.

Layout (mirrors SURVEY.md §2's component inventory):
  config      — BertConfig + CLI > JSON > defaults config system
  models/     — BERT encoder + every task head of the reference model library
  ops/        — Pallas/XLA kernels: LayerNorm, bias-GELU, attention, global-norm
  optim/      — LAMB, AdamW, BertAdam, warmup schedules, K-FAC preconditioner
  parallel/   — device mesh, sharding rules, collectives, multi-host launcher
  data/       — HDF5 sharded dataset, dynamic masking, samplers, tokenization
  utils/      — logging (stream/file/CSV/TB), checkpointing, dist helpers
  tools/      — offline pipeline: download / format / shard / vocab / encode
"""

import time

# When the program's first import began, on ``time.perf_counter_ns``: the
# ``startup`` record's ``package_imported_s`` (telemetry/profiler.py).
IMPORT_BEGAN_NS = time.perf_counter_ns()

__version__ = "0.1.0"

from bert_pytorch_tpu.config import BertConfig  # noqa: F401
