"""TrainTelemetry — the facade every runner threads its training loop
through (run_pretraining, run_squad, run_glue, run_ner, run_swag).

One object owns the telemetry pieces and their lifecycle:

* a JSONL sink (``utils/logging.py JSONLHandler``) — registered with the
  global logger by the runner so ordinary train records land there too,
  while telemetry records go ONLY there (the CSV/stream sinks stay clean);
* a :class:`~bert_pytorch_tpu.telemetry.step_timer.StepTimer` for the
  data-wait / host-dispatch / device-compute decomposition + MFU windows;
* a :class:`~bert_pytorch_tpu.telemetry.profiler.ProfilerWindow` for
  bounded ``jax.profiler`` traces with per-step annotations;
* a :class:`~bert_pytorch_tpu.telemetry.sampler.CaptureController` — the
  on-demand profiling plane: ``POST /profilez`` on the introspection hub
  arms it from an HTTP thread; :meth:`TrainTelemetry.step_done` ticks it
  at each step boundary, starting/collecting the bounded host-sampler +
  trace capture and emitting the ``profile_window`` record;
* a :class:`~bert_pytorch_tpu.telemetry.compile_events.CompileMonitor`
  (``instrument()``) attributing every XLA compile / cache hit to the
  jitted entry point and shapes digest that triggered it;
* a :class:`~bert_pytorch_tpu.telemetry.sentinels.FailureSentinel` and
  rank-0 :class:`~bert_pytorch_tpu.telemetry.sentinels.Heartbeat`;
* a :class:`~bert_pytorch_tpu.telemetry.memory.MemorySampler` reading
  ``device.memory_stats()`` watermarks on the sync cadence (one record
  per window; a single ``memory_supported: false`` note on CPU);
* a :class:`~bert_pytorch_tpu.telemetry.model_stats.DivergenceMonitor`
  consuming the in-jit grad-health block the train steps splice into
  ``metrics["grad_health"]`` (popped here, emitted as ``grad_health``
  records, checked for grad-norm spikes / update-ratio drift).

Minimal loop integration::

    tele = TrainTelemetry(jsonl_path=..., heartbeat_path=..., ...)
    train_step = tele.instrument(train_step, "train_step")
    for batch in tele.timed(iter(loader), first_step=step + 1):
        # ^ measures data_wait; opens the profiler's step annotation
        with telemetry.span("train:dispatch"):
            state, metrics = train_step(state, batch)
        tele.dispatch_done()                      # measures host dispatch
        tele.step_done(step, metrics)             # sync + window + sentinel
                                                  # + heartbeat
    tele.finish(step)                             # flush partial window
"""

from __future__ import annotations

import contextlib
import itertools
import math
import sys
import time
from typing import Callable, Iterator, Optional

from bert_pytorch_tpu.telemetry.compile_events import CompileMonitor
from bert_pytorch_tpu.telemetry.memory import MemorySampler
from bert_pytorch_tpu.telemetry.model_stats import (DivergenceMonitor,
                                                    health_record)
from bert_pytorch_tpu.telemetry.profiler import (ProfilerWindow,
                                                 imported_between, span,
                                                 startup_record)
from bert_pytorch_tpu.telemetry.sampler import CaptureController
from bert_pytorch_tpu.telemetry.sentinels import (FailureSentinel, Heartbeat,
                                                  HeartbeatWatchdog)
from bert_pytorch_tpu.telemetry.step_timer import StepTimer
from bert_pytorch_tpu.utils import logging as logging_util


class TrainTelemetry:
    def __init__(
        self,
        jsonl_path: Optional[str] = None,
        sink=None,
        is_primary: bool = True,
        window: int = 20,
        sync_every: int = 1,
        seq_per_step: Optional[int] = None,
        flops_per_seq: Optional[float] = None,
        tokens_per_step: Optional[int] = None,
        device_kind: str = "",
        n_devices: int = 1,
        profile_steps=None,
        profile_dir: Optional[str] = None,
        sentinel_policy: str = "continue",
        sentinel_patience: int = 3,
        heartbeat_path: Optional[str] = None,
        heartbeat_every: int = 1,
        watchdog_timeout_s: float = 0.0,
        grad_spike_factor: float = 10.0,
        update_ratio_max: float = 1.0,
        grad_warmup: int = 10,
        cost_analysis: str = "auto",
        introspect=None,
        flight_recorder=None,
        clock: Callable[[], float] = time.perf_counter,
    ):
        self.is_primary = is_primary
        self._clock = clock
        # Rank-0 writes the artifacts; other ranks keep a disabled sink so
        # the loop code is rank-agnostic. An already-open handler can be
        # shared in via ``sink`` (the runners register the same handler
        # with the global logger so train records land in the JSONL too).
        if sink is not None:
            self.sink = sink
        else:
            self.sink = logging_util.JSONLHandler(
                jsonl_path, is_primary=is_primary) if jsonl_path else None
        self.timer = StepTimer(
            window=window, sync_every=sync_every, clock=clock,
            seq_per_step=seq_per_step, flops_per_seq=flops_per_seq,
            device_kind=device_kind, n_devices=n_devices,
            tokens_per_step=tokens_per_step)
        self.profiler = ProfilerWindow(
            profile_steps, profile_dir, enabled=is_primary)
        self.compile_monitor = CompileMonitor(
            emit=self.emit, cost_analysis=cost_analysis)
        self.sentinel = FailureSentinel(
            policy=sentinel_policy, patience=sentinel_patience,
            emit=self.emit)
        # Grad-health early-warning shares the sentinel's policy/patience:
        # a sustained divergence warning is the same class of failure as a
        # sustained NaN, just caught earlier (model_stats.py).
        self.divergence = DivergenceMonitor(
            emit=self.emit, policy=sentinel_policy,
            patience=sentinel_patience, spike_factor=grad_spike_factor,
            ratio_max=update_ratio_max, warmup=grad_warmup)
        # Device-memory watermarks, sampled where the host already blocks
        # (the sync cadence) and emitted one record per window. Rank-0
        # only: every process sees the same allocator story under SPMD,
        # and per-rank duplicates would just bloat the artifact.
        self.memory = MemorySampler(emit=self.emit, enabled=is_primary)
        self.heartbeat = Heartbeat(heartbeat_path, is_primary=is_primary)
        self.heartbeat_every = max(1, int(heartbeat_every))
        # Hung-step watchdog (docs/fault_tolerance.md): fed a liveness
        # note per completed step; flags (fault record + warning, never a
        # kill) when none lands within the timeout. Rank-0 only — one
        # flag per job, and the collective hangs it exists to catch stall
        # every rank anyway. Started lazily at the first step so runner
        # setup (data/featurization, sometimes minutes) doesn't count.
        self.watchdog = (HeartbeatWatchdog(watchdog_timeout_s, emit=self.emit)
                        if watchdog_timeout_s and is_primary else None)
        # Live introspection hub (telemetry/introspect.py) and crash
        # flight recorder (telemetry/flightrec.py): both fed from emit()
        # — which background threads (watchdog) also call — so the
        # bindings are frozen after __init__ (concurrency registry);
        # each object does its own locking.
        self.introspect = introspect
        self.flight_recorder = flight_recorder
        # On-demand capture plane (telemetry/sampler.py): armed over
        # HTTP (POST /profilez on the hub), started/collected at the
        # step boundary in step_done. It shares the startup window's
        # ProfilerWindow — the process-wide trace latch (profiler.py
        # _TRACE_ACTIVE) is what keeps the two from stacking traces.
        # Frozen binding after __init__ like the hub itself.
        self.capture = CaptureController(
            source="trainer", covered_unit="steps", window=self.profiler,
            trace_dir=profile_dir, emit=self.emit)
        if self.introspect is not None:
            self.introspect.capture = self.capture
        # The debug HTTP server serving the hub, attached by
        # telemetry/cli.from_args (or tests); finish()/close() shut it
        # down so a runner that opened --debug_port never leaks the port.
        self.debug_server = None
        self._loader_stats: Optional[Callable[[], Optional[dict]]] = None
        self._prefetcher = None
        self._last_sync_target = None
        self.last_step_synced = False
        # sys.modules' keys as the first step call is entered, and what it
        # gained by the call's end (the startup record's
        # imported_in_first_call): two snapshots, once a run
        self._modules_before = None
        self._imported = None

    # -- wiring ---------------------------------------------------------

    def emit(self, record=None, **kwargs) -> None:
        """Write one telemetry record to the JSONL sink — teeing it into
        the live introspection hub and the flight-recorder ring first
        (both no-ops when not attached; an incident record — fault /
        divergence / sentinel — makes the recorder flush its
        postmortem)."""
        rec = dict(record or {})
        rec.update(kwargs)
        if self.introspect is not None:
            self.introspect.observe_record(rec)
        if self.flight_recorder is not None:
            self.flight_recorder.note_record(rec)
        if self.sink is not None:
            self.sink.write_record(rec)

    def instrument(self, fn, name: str):
        """Wrap a jitted callable for compile-event attribution."""
        return self.compile_monitor.instrument(fn, name)

    def attach_loader(self, loader) -> None:
        """Use ``loader.snapshot()`` gauges in each window record."""
        snapshot = getattr(loader, "snapshot", None)
        if callable(snapshot):
            self._loader_stats = snapshot

    def attach_prefetcher(self, prefetcher) -> None:
        """Attribute the H2D share of each step's data wait to the
        ``h2d_wait`` sub-phase (data/device_prefetch.py DevicePrefetcher),
        and fold the prefetcher's gauges into window records."""
        self._prefetcher = prefetcher

    def first_update_done(self, startup) -> None:
        """The runner's own barrier on its first update has returned (the
        ``block_until_ready`` before its throughput clock starts): close
        ``startup`` (the ``profiler.StartupSpans`` it opened on entry) and
        emit the run's one ``startup`` record, the update's three parts
        from the step timer's marks and this moment."""
        startup.close()
        self.emit(startup_record(
            startup, *self.timer.marks(), self._clock(),
            [e for e in self.compile_monitor.events
             if e.get("kind") == "compile"], self._imported))

    @contextlib.contextmanager
    def checkpoint_stall(self):
        """Context manager timing a checkpoint save's host stall; the
        measured block lands on the step it rode on as a ``ckpt_step``
        sample (step_timer.py note_ckpt_stall). Wrap every IN-LOOP
        ``save_checkpoint`` call with it — async saves then show up as
        checkpoint-step p95 collapsing toward steady-state p95. Only
        meaningful before :meth:`finish` (the flush there is what emits a
        stall noted after the last full window)."""
        t0 = self._clock()
        try:
            with span("train:checkpoint"):
                yield
        finally:
            self.timer.note_ckpt_stall(self._clock() - t0)

    # -- per-step protocol ----------------------------------------------

    def timed(self, iterator: Iterator, first_step: int = 1) -> Iterator:
        """Wrap the batch iterator so host time blocked on the input
        pipeline is measured as data_wait (and traced as ``train:feed``).

        Each turn of the consumer's loop — the wait for its batch and the
        whole loop body — runs inside the profiler's step annotation, and
        the startup trace window opens before the step's feed: the
        annotation is entered here and left when the loop comes back for
        the next batch or ends. ``first_step`` is the number, in
        ``--profile_steps`` terms, of the step the first batch feeds."""
        for step in itertools.count(first_step):
            self.profiler.maybe_start(step)
            with self.profiler.annotation(step):
                self.timer.data_start()
                try:
                    with span("train:feed"):
                        item = next(iterator)
                except StopIteration:
                    return
                self.timer.data_end()
                if self._prefetcher is not None:
                    # The batch just delivered came through the device
                    # prefetcher; record how much of the wait was H2D
                    # staging (0.0 when the batch was already resident).
                    self.timer.note_h2d(self._prefetcher.pop_h2d_wait_s())
                if step == first_step:
                    self._modules_before = set(sys.modules)
                yield item
            # Startup trace window's auto-stop, once its last step's
            # annotation has closed (so the trace holds that step whole).
            self.profiler.maybe_stop(step, sync_target=self._last_sync_target)

    def dispatch_done(self) -> None:
        self.timer.dispatch_end()
        before, self._modules_before = self._modules_before, None
        if before is not None:
            self._imported = imported_between(before, set(sys.modules))

    def step_done(self, step: int, metrics: Optional[dict] = None,
                  sync_target=None,
                  force_sync: bool = False) -> Optional[dict]:
        """Close out one step: device sync (per the cadence, traced as
        ``train:sync``), then — traced as ``train:telemetry`` — sentinel
        check, heartbeat, memory sample, capture tick, window emission.

        ``metrics`` is the step's device metrics dict (used as the sync
        target and the source of the ``finite``/``loss`` scalars);
        ``sync_target`` overrides it. Returns the window record when one
        was emitted.
        """
        # The in-jit grad-health block rides in metrics but is telemetry's,
        # not the runner's: pop it unconditionally so runner-side
        # float(metrics[...]) loops never trip over the nested dict, and
        # read it only on synced steps (fetching it otherwise would BE a
        # sync and defeat the cadence). The real-token count
        # (padding-aware accounting, step_timer.py) follows the same
        # contract: popped always, fetched only when this step syncs.
        health = metrics.pop("grad_health", None) \
            if isinstance(metrics, dict) else None
        real_tokens = metrics.pop("real_tokens", None) \
            if isinstance(metrics, dict) else None
        target = sync_target if sync_target is not None else metrics
        self._last_sync_target = target
        synced = False
        if target is not None and (self.timer.should_sync() or force_sync):
            self.timer.device_sync(target)
            synced = True
        self.last_step_synced = synced
        with span("train:telemetry"):
            return self._close_step(step, metrics, target, synced, health,
                                    real_tokens)

    def _close_step(self, step, metrics, target, synced, health,
                    real_tokens) -> Optional[dict]:
        """Everything of :meth:`step_done` after the device sync."""
        if synced:
            if real_tokens is not None:
                self.timer.note_tokens(float(real_tokens))
            self.memory.sample(step)
            if health is not None and float(health.get("due", 0.0)):
                record = health_record(step, health)
                self.emit(record)
                # DivergenceError propagates under policy="abort", same
                # surface as the sentinel's NonFiniteError.
                self.divergence.observe(
                    step, record["grad_norm"], record["update_ratio"])
        if metrics is not None and synced:
            loss = metrics.get("loss")
            loss = None if loss is None else float(loss)
            finite = metrics.get("finite")
            if finite is not None:
                finite = float(finite)
            else:
                # No in-jit sentinel (the finetune runners): fall back to a
                # host-side isfinite on the fetched loss.
                finite = 1.0 if (loss is None or math.isfinite(loss)) else 0.0
            self.sentinel.observe(step, finite, loss)
            if self.timer._step_index % self.heartbeat_every == 0:
                self.heartbeat.beat(step, last_loss=loss)
        if self.introspect is not None:
            # Every step, synced or not: /healthz liveness must not
            # depend on the sync cadence (the loss rides only when this
            # step fetched it — reading it off-cadence would BE a sync).
            hub_loss = None
            if metrics is not None and synced and \
                    metrics.get("loss") is not None:
                hub_loss = float(metrics["loss"])
            self.introspect.note_step(step, loss=hub_loss)
        if self.watchdog is not None:
            self.watchdog.start().note(step)
        # On-demand capture boundary: starts an armed capture, collects
        # an expired one (the finished profile_window record rides the
        # normal emit tee into hub/recorder/sink).
        self.capture.tick(step, sync_target=target)
        window = self.timer.step_done(step)
        if window is not None:
            if self._loader_stats is not None:
                gauges = self._loader_stats()
                if gauges:
                    window["loader"] = gauges
            if self._prefetcher is not None:
                gauges = self._prefetcher.snapshot()
                if gauges:
                    window["prefetch"] = gauges
            self.emit(window)
            self.memory.flush(step)  # one memory record per window
        return window

    # -- teardown -------------------------------------------------------

    def finish(self, step: int, summary: Optional[dict] = None) -> None:
        """End of run: stop a still-open trace, flush the partial window,
        final heartbeat, optional run summary record."""
        if self.watchdog is not None:
            self.watchdog.stop()
        self.profiler.stop(sync_target=self._last_sync_target)
        window = self.timer.flush(step)
        if window is not None:
            self.emit(window)
        self.memory.flush(step)  # partial-window memory samples
        if summary is not None:
            rec = {"kind": "run_summary", "tag": "telemetry", "step": step,
                   "steps": step}
            rec.update(summary)
            self.emit(rec)
        self.heartbeat.beat(step)
        self._shutdown_observability()

    def _shutdown_observability(self) -> None:
        """Stop the debug server and mark the flight recorder's clean
        exit (a fault/divergence flush earlier in the run keeps its
        postmortem; a clean run removes it)."""
        server, self.debug_server = self.debug_server, None
        if server is not None:
            try:
                server.shutdown()
                server.server_close()
            except Exception:
                pass
        if self.flight_recorder is not None:
            self.flight_recorder.close(clean=True)

    def close(self) -> None:
        if self.watchdog is not None:
            self.watchdog.stop()
        self._shutdown_observability()
        if self.sink is not None:
            self.sink.close()
