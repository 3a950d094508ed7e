"""Torch-free batching loader with background prefetch.

Replaces the reference's ``torch.utils.data.DataLoader(num_workers=4,
pin_memory=True)`` (run_pretraining.py:394-395): a producer walks the
sampler, pulls samples from the dataset (whose own background thread streams
shard files), collates numpy batches, and keeps a small queue ahead of the
training loop so host-side dynamic masking overlaps device compute — the
TPU-feeding strategy called out in SURVEY.md §7 "hard parts".

``num_workers=0`` (default) produces on one background THREAD. With the
vectorized masking path this measures 13.1k seq/s at the phase-1 shape
(seq 128, batch 64) and 11.1k seq/s at phase-2 (seq 512) on this image —
32x / 132x one v5e chip's consumption, i.e. enough for a full 8-chip
host (tools/bench_loader.py reproduces the numbers).
``num_workers=N`` matches the reference's multi-worker process scaling:
N spawned PROCESSES each produce every Nth batch (torch's round-robin
batch assignment), and the parent interleaves their queues back into
exact sampler order — sample-to-step assignment and the dataset's
forward-moving access pattern (strictly increasing indices per worker;
forward skips allowed) match the thread path, and the live sampler.index
tracks DELIVERED batches exactly (the thread path's runs ahead by the
prefetch queue; resume goes through the runner's trained_index either
way). ``epoch_chain`` strings the passes of one loader together, epoch
after epoch, as the source of the pretraining run's one feed. Masking
draws derive from (seed base, epoch, sample index) inside
the dataset (data/dataset.py, PR 5) — workers need no per-worker reseed
to decorrelate, epochs still re-draw, and thread and process paths
produce byte-identical features (the resume-exactness invariant,
docs/fault_tolerance.md). NB: each strided
worker re-reads every shard file, so with the cheap vectorized masking
the thread path is FASTER at BERT shapes; processes pay off only if
per-sample featurization grows to dominate file IO.

``drop_last`` defaults to True: XLA-jitted steps want static batch shapes, so
ragged tail batches (which the reference tolerates, SURVEY §2.1) would force
a recompile for one step.
"""

from __future__ import annotations

import itertools
import multiprocessing as mp
import queue
import threading
import time
from typing import Iterator, Optional

import numpy as np

from bert_pytorch_tpu.telemetry.profiler import span

BATCH_KEYS = (
    "input_ids",
    "segment_ids",
    "input_mask",
    "masked_lm_labels",
    "next_sentence_labels",
)
# Packed samples (data/packing.py) append the per-token sequence-id vector
# and per-pack [CLS] offsets; next_sentence_labels is then [K] per row.
PACKED_EXTRA_KEYS = ("sequence_ids", "cls_positions")


def _bounded_put(q, item, stop_event) -> bool:
    """Put that aborts when the consumer is gone — a plain q.put() blocks
    forever once the consumer stops draining with the queue full (the
    abandoning side's stop_event.set() can't unblock a producer already
    inside q.put). Shared by the thread producer and the worker processes;
    both queue flavors raise queue.Full on timeout."""
    while True:
        try:
            q.put(item, timeout=0.1)
            return True
        except queue.Full:
            if stop_event.is_set():
                return False


def _worker_main(dataset, index_batches, out_queue, stop_event, worker_id):
    """Producer process: featurize+collate its assigned batches in order.

    ``index_batches`` is the ordered list of (batch_number, [dataset indices])
    this worker owns. Results go out as (batch_number, batch_dict); errors as
    (batch_number, RuntimeError) so the parent re-raises at the right step.
    """
    # No reseed: masking draws derive from (seed base, epoch, sample
    # index) inside the dataset (data/dataset.py), and the seed BASE rides
    # in the pickled dataset state — so workers decorrelate per index with
    # no per-worker fold, epochs re-draw via the pickled set_epoch state,
    # and the process path produces BYTE-IDENTICAL features to the thread
    # path (also for seed=None, whose random base is drawn once in the
    # parent). That worker-topology independence is what keeps checkpoint
    # resume exact under any worker count (docs/fault_tolerance.md).
    for bno, idxs in index_batches:
        if stop_event.is_set():
            return
        try:
            batch = DataLoader._collate([dataset[i] for i in idxs])
        except BaseException as e:
            _bounded_put(out_queue, (bno, RuntimeError(
                f"DataLoader worker {worker_id} failed on batch {bno}: "
                f"{type(e).__name__}: {e}")), stop_event)
            return
        if not _bounded_put(out_queue, (bno, batch), stop_event):
            return
    _bounded_put(out_queue, (None, None), stop_event)


def epoch_chain(loader, start_epoch: int = 0) -> Iterator[tuple]:
    """``(epoch, batch)`` for every batch of ``loader``, epoch after epoch
    without end: the source of a run's one feed (pretrain.device_prefetch).

    When the loader's iterator for epoch *e* is exhausted, whoever is
    pulling (the device-prefetch thread; the loop under
    ``--device_prefetch 0``) calls ``sampler.set_epoch(e + 1)`` — which
    sets the dataset's epoch for the masks' (seed, epoch, index) draw —
    and goes on with ``iter(loader)``, inside a ``prefetch:epoch_start``
    span that lasts until the new epoch's first host batch is there. The
    loader thread of epoch *e* has ended by then (the ``None`` that ended
    the iterator was the last thing it put), so the dataset is never read
    under two epochs at once. A sampler restored mid-epoch (index not 0)
    starts the chain there, and may have no whole batch left; an epoch
    taken from its start that yields no batch is an error (fewer rows than
    one batch under ``drop_last``: the chain would spin for ever).
    """
    sampler = loader.sampler
    resumed = sampler.index != 0
    for epoch in itertools.count(start_epoch):
        with span("prefetch:epoch_start", epoch=epoch):
            sampler.set_epoch(epoch)
            batches = iter(loader)
            batch = next(batches, None)
        if batch is None and not resumed:
            raise RuntimeError(
                f"epoch {epoch} of the training data holds no batch: "
                f"{len(sampler)} rows a rank, {loader.batch_size} a batch "
                f"(drop_last={loader.drop_last})")
        while batch is not None:
            yield epoch, batch
            batch = next(batches, None)
        resumed = False


class DataLoader:
    def __init__(
        self,
        dataset,
        sampler,
        batch_size: int,
        drop_last: bool = True,
        prefetch_batches: int = 2,
        num_workers: int = 0,
    ):
        self.dataset = dataset
        self.sampler = sampler
        self.batch_size = int(batch_size)
        self.drop_last = drop_last
        self.prefetch_batches = prefetch_batches
        self.num_workers = int(num_workers)
        self._reset_stats()

    # -- telemetry gauges (docs/telemetry.md) ---------------------------
    #
    # Consumer-side instrumentation of the prefetch queue: how long the
    # training loop blocked waiting for a batch (wait), how often it found
    # the queue EMPTY (a stall — the producer is the bottleneck), and the
    # queue depth observed at each get (depth ~= prefetch_batches means the
    # producer keeps up; ~0 means it doesn't). snapshot() returns the deltas
    # since the last snapshot, so the runner can fold them into each
    # telemetry step-window record.

    def _reset_stats(self) -> None:
        self._stats = {"batches": 0, "wait_s_total": 0.0, "wait_s_max": 0.0,
                       "stalls": 0, "depth_sum": 0, "depth_max": 0}

    def _observe_get(self, wait_s: float, depth: int) -> None:
        s = self._stats
        s["batches"] += 1
        s["wait_s_total"] += wait_s
        s["wait_s_max"] = max(s["wait_s_max"], wait_s)
        if depth == 0:
            s["stalls"] += 1
        s["depth_sum"] += depth
        s["depth_max"] = max(s["depth_max"], depth)

    def snapshot(self) -> Optional[dict]:
        """Gauges accumulated since the previous snapshot (None if no
        batches were delivered in the interval)."""
        s = self._stats
        if s["batches"] == 0:
            return None
        out = {
            "batches": s["batches"],
            "wait_s_total": round(s["wait_s_total"], 6),
            "wait_s_max": round(s["wait_s_max"], 6),
            "stalls": s["stalls"],
            "depth_mean": round(s["depth_sum"] / s["batches"], 2),
            "depth_max": s["depth_max"],
        }
        self._reset_stats()
        return out

    def __len__(self) -> int:
        n = len(self.sampler)
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    def __iter__(self) -> Iterator[dict]:
        if self.num_workers > 0:
            return self._iter_multiprocess()
        return self._iter_thread()

    def _iter_multiprocess(self) -> Iterator[dict]:
        """Spawned workers, round-robin over batches, in-order delivery.

        The sampler is consumed up front (it is a cheap index mapping), and
        its live ``index`` is advanced per DELIVERED batch below — exact,
        unlike the thread path whose live index runs AHEAD of training by
        the prefetch queue (the skew run_pretraining.py works around with
        its trained_index counter; both paths resume correctly through
        that counter). Spawn — not fork — because the parent has a live
        JAX runtime.
        """
        start = self.sampler.index  # nonzero on mid-epoch resume
        positions = list(self.sampler)  # drains; resets sampler.index to 0
        n_batches = len(positions) // self.batch_size
        tail = positions[n_batches * self.batch_size:]
        batches = [
            (b, positions[b * self.batch_size:(b + 1) * self.batch_size])
            for b in range(n_batches)
        ]
        if tail and not self.drop_last:
            batches.append((n_batches, tail))
        ctx = mp.get_context("spawn")
        stop = ctx.Event()
        n_workers = max(1, min(self.num_workers, max(1, len(batches))))
        out_queues = [
            ctx.Queue(maxsize=max(2, self.prefetch_batches))
            for _ in range(n_workers)
        ]
        procs = [
            ctx.Process(
                target=_worker_main,
                args=(self.dataset, batches[w::n_workers], out_queues[w],
                      stop, w),
                daemon=True)
            for w in range(n_workers)
        ]
        for p in procs:
            p.start()
        try:
            for b in range(len(batches)):
                q = out_queues[b % n_workers]
                try:
                    depth = q.qsize()
                except NotImplementedError:  # macOS mp.Queue
                    depth = 0
                t_wait0 = time.perf_counter()
                while True:
                    try:
                        bno, item = q.get(timeout=5.0)
                        break
                    except queue.Empty:
                        dead = procs[b % n_workers]
                        if not dead.is_alive():
                            raise RuntimeError(
                                f"DataLoader worker {b % n_workers} died "
                                f"(exit code {dead.exitcode}) before "
                                f"producing batch {b}")
                if isinstance(item, BaseException):
                    raise item
                assert bno == b, (bno, b)
                self._observe_get(time.perf_counter() - t_wait0, depth)
                self.sampler.index = min(
                    len(self.sampler), start + (b + 1) * self.batch_size)
                yield item
            self.sampler.index = 0  # epoch complete, like __next__'s reset
        finally:
            stop.set()
            for p in procs:
                p.join(timeout=5.0)
                if p.is_alive():
                    p.terminate()
            for q in out_queues:
                q.close()
                q.cancel_join_thread()

    def _iter_thread(self) -> Iterator[dict]:
        q: queue.Queue = queue.Queue(maxsize=self.prefetch_batches)
        stop = threading.Event()

        def put(item) -> bool:
            # Without the abort, an abandoned iteration (e.g. every
            # early-stopped validation pass) leaks one producer thread and
            # its buffered batches.
            return _bounded_put(q, item, stop)

        def produce():
            samples = []
            try:
                for idx in self.sampler:
                    if stop.is_set():
                        return
                    samples.append(self.dataset[idx])
                    if len(samples) == self.batch_size:
                        if not put(self._collate(samples)):
                            return
                        samples = []
                if samples and not self.drop_last:
                    if not put(self._collate(samples)):
                        return
            except BaseException as e:  # surface worker errors to the consumer
                put(e)
                return
            put(None)

        worker = threading.Thread(target=produce, daemon=True)
        worker.start()
        try:
            while True:
                depth = q.qsize()
                t_wait0 = time.perf_counter()
                item = q.get()
                if item is None:
                    return
                if isinstance(item, BaseException):
                    raise item
                self._observe_get(time.perf_counter() - t_wait0, depth)
                yield item
        finally:
            stop.set()

    @staticmethod
    def _collate(samples) -> dict:
        if isinstance(samples[0], dict):  # data/dataset.py TokenRowsDataset
            with span("data:collate"):
                return {key: np.stack([s[key] for s in samples])
                        for key in samples[0]}
        keys = BATCH_KEYS + PACKED_EXTRA_KEYS[:len(samples[0]) - len(BATCH_KEYS)]
        with span("data:collate"):
            arrays = [np.stack([s[i] for s in samples])
                      for i in range(len(keys))]
        return dict(zip(keys, arrays))
