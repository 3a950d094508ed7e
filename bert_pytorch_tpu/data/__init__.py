"""Data runtime: sharded HDF5 streaming, dynamic masking, samplers, loaders.

The TPU-host analog of the reference's data stack (SURVEY.md §2.1):
ShardedPretrainingDataset + contiguous DistributedSampler + a torch-free
prefetching DataLoader, plus sequence packing (data/packing.py,
docs/packing.md) for padding-free pretraining batches.
"""

from bert_pytorch_tpu.data.dataset import (
    LEGACY_FORMAT_KEYS,
    NEW_FORMAT_KEYS,
    ShardedPretrainingDataset,
    TokenRowsDataset,
)
from bert_pytorch_tpu.data.device_prefetch import DevicePrefetcher
from bert_pytorch_tpu.data.loader import (
    BATCH_KEYS,
    PACKED_EXTRA_KEYS,
    DataLoader,
)
from bert_pytorch_tpu.data.packing import (
    PACKED_FORMAT_KEYS,
    PackedPretrainingDataset,
    first_fit_decreasing,
    pack_features,
    write_packed_shard,
)
from bert_pytorch_tpu.data.sampler import DistributedSampler

__all__ = [
    "BATCH_KEYS",
    "DataLoader",
    "DevicePrefetcher",
    "DistributedSampler",
    "LEGACY_FORMAT_KEYS",
    "NEW_FORMAT_KEYS",
    "PACKED_EXTRA_KEYS",
    "PACKED_FORMAT_KEYS",
    "PackedPretrainingDataset",
    "ShardedPretrainingDataset",
    "TokenRowsDataset",
    "first_fit_decreasing",
    "pack_features",
    "write_packed_shard",
]
