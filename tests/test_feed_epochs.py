"""The pretraining feed is built once and runs on across epochs (ISSUE 33;
``data/loader.py epoch_chain``, ``pretrain.device_prefetch``, the loop of
``run_pretraining.py``).

Counts and bytes only, no host-clock assertion (ROADMAP D14). The first half
drives the feed alone over a small shard; the second half drives the
trainer's loop at a tiny size over 128 rows, 32 an update (4 updates an
epoch), with a recorder round ``pretrain.device_prefetch`` in the way the
benchmark's feed probe stands there.
"""

import hashlib
import json
import os
import shutil
import time

import jax
import numpy as np
import pytest

from bert_pytorch_tpu import pretrain
from bert_pytorch_tpu.data import DataLoader, DevicePrefetcher
from bert_pytorch_tpu.data.dataset import ShardedPretrainingDataset
from bert_pytorch_tpu.data.loader import epoch_chain
from bert_pytorch_tpu.data.sampler import DistributedSampler
from bert_pytorch_tpu.tools.make_synthetic_data import make_shard
from bert_pytorch_tpu.utils import checkpoint as ckpt

ROWS, BATCH, ACCUM = 48, 16, 2          # the feed alone: 3 batches an epoch


@pytest.fixture(scope="module")
def shard_paths(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("feed_shards")
    paths = [str(tmp / f"shard_{i}.hdf5") for i in range(2)]
    for i, path in enumerate(paths):
        make_shard(path, ROWS // 2, 32, 100, seed=i)
    return paths


def _loader(paths, batch=BATCH, num_workers=0):
    dataset = ShardedPretrainingDataset(
        paths, 4, max_pred_per_seq=20, masked_lm_prob=0.15, vocab_size=100,
        seed=0)
    sampler = DistributedSampler(dataset, num_replicas=1, rank=0)
    return DataLoader(dataset, sampler, batch, num_workers=num_workers)


def _shardings(batch):
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    return {key: one for key in batch}


def _per_epoch_batches(paths, epochs, **kw):
    """What the loop of PR 31 fed: one pass of the loader an epoch, the
    sampler's epoch set by the loop before each."""
    loader = _loader(paths, **kw)
    out = []
    for epoch in epochs:
        loader.sampler.set_epoch(epoch)
        out += [(epoch, pretrain.stack_microbatches(batch, ACCUM))
                for batch in loader]
    return out


def _same_bytes(got, want):
    assert got.keys() == want.keys()
    for key in want:
        a, b = np.asarray(got[key]), np.asarray(want[key])
        assert a.dtype == b.dtype and a.shape == b.shape, key
        assert a.tobytes() == b.tobytes(), key


def _until(condition, what, seconds=30.0):
    """Wait for another thread's progress (bounded; nothing is timed)."""
    deadline = time.monotonic() + seconds
    while not condition():
        assert time.monotonic() < deadline, f"never saw: {what}"
        time.sleep(0.002)


# -- (a) the same bytes under the same epochs ------------------------------

@pytest.mark.parametrize("num_workers", [0, 1])
@pytest.mark.parametrize("depth", [2, 0])
def test_the_chained_feed_delivers_the_bytes_of_per_epoch_feeds(
        shard_paths, depth, num_workers):
    if num_workers and not depth:
        pytest.skip("the worker processes are one case, under the thread")
    epochs = [0, 1] if num_workers else [0, 1, 2]
    want = _per_epoch_batches(shard_paths, epochs, num_workers=num_workers)
    assert len(want) == len(epochs) * (ROWS // BATCH)
    loader = _loader(shard_paths, num_workers=num_workers)
    feed = pretrain.device_prefetch(loader, ACCUM, _shardings(want[0][1]),
                                    depth=depth)
    try:
        for (epoch, batch), (got_epoch, got) in zip(want, feed):
            assert got_epoch == epoch
            assert all(isinstance(v, jax.Array) for v in got.values())
            _same_bytes(got, batch)
    finally:
        feed.close()
    # the epoch is what the masks are drawn under: the same rows, other masks
    first, again = want[0][1], want[ROWS // BATCH][1]
    assert not np.array_equal(first["masked_lm_labels"],
                              again["masked_lm_labels"])
    assert np.array_equal(first["next_sentence_labels"],
                          again["next_sentence_labels"])


def test_the_masks_of_an_epoch_are_drawn_under_its_number(shard_paths):
    """The producer sets epoch e + 1 only after the loader of epoch e has
    drawn its last row: a batch tagged e equals the batch a dataset held at
    epoch e gives, for a chain started at any epoch."""
    want = _per_epoch_batches(shard_paths, [5, 6])
    chain = epoch_chain(_loader(shard_paths), start_epoch=5)
    for (epoch, batch), (got_epoch, got) in zip(want, chain):
        assert got_epoch == epoch
        _same_bytes(pretrain.stack_microbatches(got, ACCUM), batch)
    chain.close()


# -- (b) a staged batch waits at the boundary ------------------------------

def test_at_a_boundary_a_staged_batch_is_waiting(shard_paths):
    per_epoch = ROWS // BATCH
    feed = DevicePrefetcher(epoch_chain(_loader(shard_paths)),
                            stage=lambda item: item, depth=2)
    try:
        for _ in range(per_epoch):        # epoch 0, the consumer the slower
            _until(lambda: feed._queue.qsize() >= 1 or feed._thread is None,
                   "a staged batch")
            epoch, _ = next(feed)
            assert epoch == 0
        _until(lambda: feed._queue.qsize() >= 1, "the next epoch staged")
        feed.snapshot()
        epoch, _ = next(feed)
        assert epoch == 1
        assert feed.snapshot()["depth_max"] >= 1
    finally:
        feed.close()

    # the per-epoch construction: the new epoch's feed is built when the old
    # one has ended, and a feed just built has staged nothing (its producer
    # starts with the first ``next``): at the boundary the queue is empty.
    # (What the first ``next`` then reads of the queue's size is the
    # scheduler's to decide, and is not asserted.)
    loader = _loader(shard_paths)
    for epoch in (0, 1):
        loader.sampler.set_epoch(epoch)
        feed = DevicePrefetcher(iter(loader), stage=lambda item: item,
                                depth=2)
        try:
            assert feed._thread is None and feed._queue.qsize() == 0
            assert sum(1 for _ in feed) == per_epoch
        finally:
            feed.close()


# -- (d) a chain that starts mid-epoch, (e) an epoch with no batch ---------

@pytest.mark.parametrize("index, first_epochs", [
    (BATCH, [3, 3, 4, 4, 4, 5]),          # one batch trained: two are left
    (ROWS, [4, 4, 4, 5]),                 # the epoch was trained to its end
    (ROWS - 3, [4, 4, 4, 5]),             # less than a batch is left
])
def test_a_chain_over_a_restored_sampler_starts_there(
        shard_paths, index, first_epochs):
    loader = _loader(shard_paths)
    loader.sampler.load_state_dict(
        dict(loader.sampler.state_dict(), epoch=3, index=index))
    chain = epoch_chain(loader, start_epoch=3)
    got = [next(chain) for _ in first_epochs]
    chain.close()
    assert [epoch for epoch, _ in got] == first_epochs
    whole = _per_epoch_batches(shard_paths, [3, 4, 5])
    skipped = -(-index // BATCH)            # batches begun count as gone
    for (epoch, batch), (want_epoch, want) in zip(got, whole[skipped:]):
        assert epoch == want_epoch
        _same_bytes(pretrain.stack_microbatches(batch, ACCUM), want)


def test_an_epoch_with_no_batch_raises_by_name(shard_paths):
    loader = _loader(shard_paths, batch=ROWS + 16)
    with pytest.raises(RuntimeError, match="epoch 0 .* holds no batch: "
                                           f"{ROWS} rows a rank, {ROWS + 16}"):
        next(epoch_chain(loader))
    # and at the consumer of the feed, not on a thread nobody reads
    feed = pretrain.device_prefetch(loader, ACCUM, {}, depth=2)
    with pytest.raises(RuntimeError, match="holds no batch"):
        next(feed)


# -- the trainer's loop: (c) checkpoints at a boundary, (d), (f) -----------

UPDATES = 11                               # boundaries before updates 5 and 9
EPOCHS = [0] * 4 + [1] * 4 + [2] * 3


class _Recorder:
    """Stands where the benchmark's feed probe stands: round what
    ``pretrain.device_prefetch`` returns, passing every item through."""

    def __init__(self, inner, seen):
        self._inner, self._seen = inner, seen

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __iter__(self):
        for epoch, batch in self._inner:
            digest = hashlib.sha256()
            for key in sorted(batch):
                digest.update(np.asarray(batch[key]).tobytes())
            self._seen.append((epoch, digest.hexdigest()))
            yield epoch, batch


@pytest.fixture(scope="module")
def trainer(tmp_path_factory):
    """One uninterrupted run of 11 updates that writes a checkpoint after
    every one, and a function that resumes a copy of one of them."""
    import run_pretraining

    tmp = tmp_path_factory.mktemp("feed_trainer")
    data = tmp / "data"
    data.mkdir()
    for i in range(2):
        make_shard(str(data / f"shard_{i}.hdf5"), 64, 32, 1000, seed=i)
    (tmp / "model.json").write_text(json.dumps({
        "vocab_size": 1000, "hidden_size": 32, "num_hidden_layers": 1,
        "num_attention_heads": 4, "intermediate_size": 64,
        "max_position_embeddings": 32, "type_vocab_size": 2,
        "next_sentence": True, "mask_token_id": 4}))

    def run(out, steps):
        seen = []
        real = pretrain.device_prefetch
        pretrain.device_prefetch = (
            lambda *a, **k: _Recorder(real(*a, **k), seen))
        try:
            result = run_pretraining.main(run_pretraining.parse_arguments([
                "--input_dir", str(data), "--output_dir", str(out),
                "--model_config_file", str(tmp / "model.json"),
                "--global_batch_size", "32", "--local_batch_size", "2",
                "--max_steps", "40", "--steps", str(steps),
                "--num_steps_per_checkpoint", "1", "--keep_checkpoints", "20",
                "--checkpoint_write", "sync", "--skip_final_checkpoint",
                "--dtype", "float32", "--seed", "7", "--disable_tensorboard"]))
        finally:
            pretrain.device_prefetch = real
        return seen, result

    whole, _ = run(tmp / "whole", UPDATES)

    def resumed_from(step, steps):
        out = tmp / f"from_{step}"
        os.makedirs(out / "pretrain_ckpts")
        for name in os.listdir(tmp / "whole" / "pretrain_ckpts"):
            if name.startswith(f"ckpt_{step}."):
                shutil.copy(tmp / "whole" / "pretrain_ckpts" / name,
                            out / "pretrain_ckpts" / name)
        return run(out, steps)

    return {"whole": whole, "out": str(tmp / "whole"),
            "resumed_from": resumed_from}


def test_the_loop_trains_every_batch_under_its_epoch(trainer):
    assert [epoch for epoch, _ in trainer["whole"]] == EPOCHS
    digests = [d for _, d in trainer["whole"]]
    assert len(set(digests)) == UPDATES    # no batch twice, masks redrawn


@pytest.mark.parametrize("step, epoch, index", [
    (3, 0, 96), (4, 0, 128),   # before the boundary: the producer is past it
    (5, 1, 32), (6, 1, 64),    # after it: the producer is further still
    (8, 1, 128), (9, 2, 32),
])
def test_a_checkpoint_records_the_trained_epoch_and_index(
        trainer, step, epoch, index):
    saved = ckpt.load_checkpoint(ckpt.checkpoint_path(
        os.path.join(trainer["out"], "pretrain_ckpts"), step))
    assert int(saved["epoch"]) == epoch
    assert int(saved["sampler"]["epoch"]) == epoch
    assert int(saved["sampler"]["index"]) == index


@pytest.mark.parametrize("step, steps", [
    (4, 3),    # written just before the boundary, the producer across it
    (5, 6),    # just after it; the resumed run crosses the next one (9)
    (2, 4),    # mid-epoch: the chain starts there and crosses at 5
])
def test_a_resumed_run_is_fed_what_the_uninterrupted_run_was(
        trainer, step, steps):
    seen, result = trainer["resumed_from"](step, steps)
    assert result["global_step"] == step + steps
    assert seen == trainer["whole"][step:step + steps]


def test_the_run_summary_counts_the_boundaries(trainer):
    path = os.path.join(trainer["out"], "pretraining_telemetry.jsonl")
    with open(path) as f:
        [summary] = [rec for rec in map(json.loads, f)
                     if rec.get("kind") == "run_summary"]
    assert summary["feed_epoch_boundaries"] == 2
    assert summary["feed_boundary_wait_s"] >= 0.0
    assert "dropout_draw_shards" in summary
