"""Double-buffered device prefetch: the stage between the host loader
queue and the training loop.

``data/loader.py`` keeps host batches ahead of the loop; this module keeps
them ahead ON DEVICE. A background thread pulls host items, runs the
caller's ``stage`` function (typically ``put_batch`` with the step's input
shardings — an async dispatch, so on real accelerators the H2D transfer
overlaps device compute), and keeps up to ``depth`` staged batches in a
queue. The training loop's ``data_wait`` then measures only true producer
stalls: with a fast producer the queue is never empty and data_wait p50
drops to ~0; with a slow producer the stall still lands in data_wait,
correctly attributed.

Telemetry attribution (docs/telemetry.md): the thread records, per batch,
the time it blocked on the HOST producer and the time it spent in the
staging call. When the consumer blocks on an empty queue, the delivered
batch's staging time bounds how much of that wait was H2D work:
``pop_h2d_wait_s`` returns ``min(consumer_wait, stage_time)`` — by
construction never more than the step's data_wait, which is what lets the
schema lint pin ``h2d_wait <= data_wait``. (On a synchronous backend like
CPU the staging call IS the copy; on TPU it is the dispatch, and a staged
batch that has not finished transferring simply parks the wait inside the
next step's device phase, where overlap hides it.)

``depth <= 0`` degrades to inline staging on the consumer thread — same
iterator contract and gauges, no background thread — so one code path
serves ``--device_prefetch 0`` everywhere.

A prefetcher lives as long as its source. The fine-tuning runners hand it
one pass of their loader and build the next when that has ended (the new
epoch then starts from an empty queue). ``run_pretraining.py`` hands it
``data/loader.py epoch_chain`` through ``pretrain.device_prefetch``: ONE
feed a run whose source never ends, whose items are ``(epoch, batch)``,
and whose producer thread, not the loop, steps the sampler into the next
epoch — so at a boundary the consumer finds the new epoch's first batches
staged. The sampler's live position is then the producer's; a checkpoint
records the epoch of the batch last trained and the rows trained in it
(docs/fault_tolerance.md).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterable, Iterator

from bert_pytorch_tpu.data.loader import _bounded_put
from bert_pytorch_tpu.telemetry.profiler import span


def add_cli_args(parser, default: int = 2) -> None:
    """Register the one shared device-prefetch flag (every runner)."""
    parser.add_argument(
        "--device_prefetch", type=int, default=default,
        help="batches staged ahead ON DEVICE (double-buffered host->device "
             "transfer, data/device_prefetch.py): the H2D copy overlaps "
             "device compute and telemetry's data_wait measures only true "
             "producer stalls (an h2d_wait sub-phase reports the staging "
             "share). 0 stages inline on the loop thread (no overlap)")


class DevicePrefetcher:
    """One-shot iterator of device-resident items staged ``depth`` ahead.

    ``source`` yields host items; ``stage(item)`` moves one to device
    (e.g. ``pretrain.put_batch`` with the step's input shardings). Errors
    from either surface at the consumer's ``next()``. Call ``close()``
    when abandoning the iterator before its source ends (the runners do,
    in their ``finally``): it sets the stop event — which aborts a thread
    parked in its blocked put — and briefly joins; a thread stuck inside an
    uninterruptible ``next(source)`` is left to daemon teardown but will
    not touch the staging fn again (see :meth:`close`).
    """

    def __init__(self, source: Iterable, stage: Callable, depth: int = 2,
                 clock: Callable[[], float] = time.perf_counter):
        self._source = iter(source)
        self._stage = stage
        self.depth = int(depth)
        self._clock = clock
        self._queue: queue.Queue = queue.Queue(maxsize=max(1, self.depth))
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._done = False
        self._last_h2d_wait_s = 0.0
        self._reset_stats()

    # -- gauges (window "prefetch" sub-object, telemetry/runner.py) -----

    def _reset_stats(self) -> None:
        self._stats = {"batches": 0, "wait_s_total": 0.0,
                       "h2d_wait_s_total": 0.0, "h2d_s_total": 0.0,
                       "producer_wait_s_total": 0.0, "depth_max": 0}

    def snapshot(self):
        """Gauges accumulated since the previous snapshot (None when no
        batches were delivered in the interval)."""
        s = self._stats
        if s["batches"] == 0:
            return None
        out = {"batches": s["batches"],
               "wait_s_total": round(s["wait_s_total"], 6),
               "h2d_wait_s_total": round(s["h2d_wait_s_total"], 6),
               "h2d_s_total": round(s["h2d_s_total"], 6),
               "producer_wait_s_total": round(s["producer_wait_s_total"], 6),
               "depth_max": s["depth_max"]}
        self._reset_stats()
        return out

    def pop_h2d_wait_s(self) -> float:
        """H2D-attributable share of the wait for the batch just
        delivered (consumed by TrainTelemetry.timed -> note_h2d)."""
        value, self._last_h2d_wait_s = self._last_h2d_wait_s, 0.0
        return value

    # -- producer thread ------------------------------------------------

    def _produce(self) -> None:
        while not self._stop.is_set():
            t0 = self._clock()
            try:
                with span("prefetch:source_wait"):
                    item = next(self._source)
            except StopIteration:
                break
            except BaseException as e:  # surfaced at the consumer's next()
                _bounded_put(self._queue, (e, 0.0, 0.0), self._stop)
                return
            if self._stop.is_set():
                # close() raced the blocking pull above: never call the
                # staging fn (a device dispatch) on an abandoned
                # prefetcher — the consumer may be tearing the runtime
                # down.
                return
            t1 = self._clock()
            try:
                with span("prefetch:h2d"):
                    staged = self._stage(item)
            except BaseException as e:
                _bounded_put(self._queue, (e, 0.0, 0.0), self._stop)
                return
            t2 = self._clock()
            if not _bounded_put(self._queue, (staged, t1 - t0, t2 - t1),
                                self._stop):
                return
        _bounded_put(self._queue, (None, 0.0, 0.0), self._stop)

    # -- consumer protocol ----------------------------------------------

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        if self.depth <= 0:
            return self._next_inline()
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._produce, name="device-prefetch", daemon=True)
            self._thread.start()
        t0 = self._clock()
        depth = self._queue.qsize()
        item, producer_wait_s, h2d_s = self._queue.get()
        wait_s = self._clock() - t0
        if item is None:
            self.close()
            raise StopIteration
        if isinstance(item, BaseException):
            self.close()
            raise item
        self._observe(wait_s, min(wait_s, h2d_s), h2d_s,
                      producer_wait_s, depth)
        return item

    def _next_inline(self):
        """depth<=0: pull + stage on the consumer thread. The whole
        staging call is consumer wait, so the attribution is exact; a
        producer or staging error closes the iterator exactly like the
        threaded path (no silent skip-and-resume past a failed item)."""
        t0 = self._clock()
        try:
            with span("prefetch:source_wait"):
                item = next(self._source)
        except StopIteration:
            self._done = True
            raise
        except BaseException:
            self._done = True
            raise
        t1 = self._clock()
        try:
            with span("prefetch:h2d"):
                staged = self._stage(item)
        except BaseException:
            self._done = True
            raise
        t2 = self._clock()
        self._observe(t2 - t0, t2 - t1, t2 - t1, t1 - t0, 0)
        return staged

    def _observe(self, wait_s, h2d_wait_s, h2d_s, producer_wait_s,
                 depth) -> None:
        self._last_h2d_wait_s = h2d_wait_s
        s = self._stats
        s["batches"] += 1
        s["wait_s_total"] += wait_s
        s["h2d_wait_s_total"] += h2d_wait_s
        s["h2d_s_total"] += h2d_s
        s["producer_wait_s_total"] += producer_wait_s
        s["depth_max"] = max(s["depth_max"], depth)

    def close(self) -> None:
        """Stop the producer. The short join covers the common case (the
        thread is parked in the queue put, which aborts on the stop
        event); a thread blocked inside ``next(source)`` — an
        uninterruptible pull from the host loader — is abandoned to
        daemon-thread teardown instead of burning a preemption grace
        budget on a long join (it exits at the stop check before ever
        touching the staging fn again)."""
        self._done = True
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=1.0)
            self._thread = None
