"""Bounded ``jax.profiler`` trace windows for the training loop — and
the generalized ``begin``/``end`` facility the on-demand profiling plane
drives (telemetry/sampler.py, ``POST /profilez``).

``--profile_steps`` accepts either ``"N"`` (legacy: N steady-state steps
starting after the compile step, i.e. the window ``[2, 2+N)`` in
step-in-run terms) or ``"N:M"`` (explicit half-open step range). The window
auto-stops: when the range's last step completes — or the run ends inside
the window — the trace is synced (``block_until_ready`` on the step's
outputs, so the trace holds the full device work) and written.

Every step is wrapped in ``jax.profiler.StepTraceAnnotation("train",
step_num=...)`` and the loop's phases in spans (:func:`span`, ``SPANS``), whoever
opened the trace session (``--profile_steps``, ``POST /profilez``, a
benchmark, ``jax.profiler.start_trace`` by hand): they land in the same
``.xplane.pb`` as the device's ops, on the same clock, and cost a flag test
while no session is open. Inside the loop the session is the only store:
no ``train:*``, ``prefetch:*`` or ``data:*`` span is kept anywhere else.

The ``startup:*`` spans, and they alone, are also kept in memory, because
no session can be open while JAX is being imported and a restarted job's
time to train again is what they measure: ``run_pretraining.main`` opens a
:class:`StartupSpans` on entry, :func:`span` appends (name, parent, start,
end) to it for each ``startup:*`` span, some ten in all, and the first
update closes it for good (``TrainTelemetry`` turns it into the one
``kind="startup"`` record, :func:`startup_record`). After that a
``startup:*`` span is a ``TraceAnnotation`` like any other.

The startup window used to be this module's ONLY contract — one window
per process lifetime, latched by ``done``. :meth:`ProfilerWindow.begin`
and :meth:`ProfilerWindow.end` generalize past it: an on-demand capture
(``POST /profilez``) re-uses the same instance for any number of bounded
windows after the startup one, each to its own trace directory. What
does NOT generalize is concurrency — ``jax.profiler.start_trace`` is a
process-wide singleton and a second start while one is active raises —
so every start goes through the module-level exclusivity latch
(``_TRACE_ACTIVE``, concurrency registry): ``begin`` REFUSES (returns
False) instead of stacking traces, which is what lets two HTTP planes
and a startup window coexist on one process without coordinating.

On TPU the trace contains device (XLA op) timelines; on CPU it degrades to
host tracing only — both are readable with TensorBoard's profile plugin or
xprof. See docs/telemetry.md for the workflow.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Optional, Tuple

import jax

import bert_pytorch_tpu

# When the package began to be imported: the record's ``package_imported_s``,
# and process_origin()'s fallback.
_IMPORTED_NS = bert_pytorch_tpu.IMPORT_BEGAN_NS

# Every span the program writes, on the thread that writes it. The
# ``train:*`` spans nest in the step's ``train`` annotation on the loop's
# thread (telemetry/runner.py, telemetry/step_timer.py, run_pretraining.py);
# ``prefetch:*`` are per batch on the device-prefetch thread
# (data/device_prefetch.py; on the loop's thread under --device_prefetch 0),
# ``prefetch:epoch_start`` per epoch inside its ``prefetch:source_wait``
# (data/loader.py epoch_chain);
# ``data:*`` are per batch / per shard on the loader's threads
# (data/loader.py, data/dataset.py). docs/telemetry.md names what each
# covers; benchmarks/trace/scopes.py reads them. The ``startup:*`` spans are
# ``run_pretraining.main``'s, on the main thread, before the loop:
# ``startup:backend`` lies in ``startup:setup`` and ``startup:restore`` in
# ``startup:state_init``; benchmarks/trace/startup.py reads them from the
# ``startup`` record.
STARTUP = "startup:"
SPANS = ("train:feed", "train:dispatch", "train:sync", "train:fetch_metrics",
         "train:log", "train:telemetry", "train:checkpoint", "train:eval",
         "prefetch:source_wait", "prefetch:h2d", "prefetch:epoch_start",
         "data:shard_load", "data:collate",
         "startup:setup", "startup:backend", "startup:model",
         "startup:optimizer", "startup:data", "startup:state_init",
         "startup:restore", "startup:step_build")


def span(name: str, **stats):
    """One host span in the profiler's trace: a ``TraceAnnotation`` (keyword
    arguments become the event's stats). Nothing is recorded, and nothing
    but a flag is read, while no trace session is open; a ``startup:*`` span
    is kept in memory too while a :class:`StartupSpans` is open."""
    annotation = jax.profiler.TraceAnnotation(name, **stats)
    if _startup is not None and name.startswith(STARTUP):
        return _startup.keep(name, annotation)
    return annotation


# -- start-up: the spans kept in memory until the first update ---------------

_startup = None  # the open StartupSpans, if any (main thread only)


class StartupSpans:
    """The ``startup:*`` spans of one ``main``, as (name, parent, start_ns,
    end_ns) on ``time.perf_counter_ns``, in the order they closed."""

    def __init__(self):
        self.entered_ns = time.perf_counter_ns()
        self.spans: list = []
        self._open: list = []  # names of the spans entered and not left

    @contextlib.contextmanager
    def keep(self, name: str, annotation):
        """``annotation`` entered and left, and the span kept if the store
        is still the open one when it ends."""
        with annotation:
            parent = self._open[-1] if self._open else None
            self._open.append(name)
            start = time.perf_counter_ns()
            try:
                yield
            finally:
                end = time.perf_counter_ns()
                self._open.pop()
                if _startup is self:
                    self.spans.append((name, parent, start, end))

    def close(self) -> None:
        """No span is kept after this (the first update, or a later
        ``main`` in the same process)."""
        global _startup
        if _startup is self:
            _startup = None


def startup_open() -> StartupSpans:
    """Called by ``main`` on entry: from here every ``startup:*`` span is
    kept, until the store's ``close``."""
    global _startup
    _startup = StartupSpans()
    return _startup


_origin = None


def process_origin() -> Tuple[int, str]:
    """(``perf_counter_ns`` at which this process was created, where that
    comes from). On Linux the kernel's own stamp (``/proc/self/stat`` field
    22, in clock ticks since boot: 10 ms steps) against ``CLOCK_BOOTTIME``
    now, so interpreter start and imports are inside; elsewhere the moment
    the package began to be imported, which leaves out what came before."""
    global _origin
    if _origin is None:
        _origin = (_IMPORTED_NS, "package_import")
        try:
            with open("/proc/self/stat", encoding="ascii") as f:
                ticks = int(f.read().rpartition(")")[2].split()[19])
            age_ns = time.clock_gettime_ns(time.CLOCK_BOOTTIME) - (
                ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK"))
            created = time.perf_counter_ns() - age_ns
            if 0 <= age_ns and created <= _IMPORTED_NS:
                _origin = (created, "proc_stat")
        except (OSError, ValueError, IndexError, AttributeError):
            pass
    return _origin


IMPORTS_NAMED = 20


def imported_between(before: set, after: set) -> dict:
    """The record's ``imported_in_first_call`` from two snapshots of
    ``sys.modules``' keys: how many entries came between them, and the first
    ``IMPORTS_NAMED`` new top-level package names."""
    new = after - before
    return {"modules": len(new),
            "packages": sorted({name.partition(".")[0]
                                for name in new})[:IMPORTS_NAMED]}


def startup_record(store: StartupSpans, feed_start_s: float,
                   feed_end_s: float, call_end_s: float, sync_end_s: float,
                   compiles: list, imported: Optional[dict] = None) -> dict:
    """The one ``kind="startup"`` record of a run (schema.py; the sibling of
    serving's ``serve_cold_start``). The four ``*_s`` arguments are the
    step timer's ``perf_counter`` marks of the first update (its feed
    entered and left, its step call returned) and the end of the runner's
    barrier on that update; ``compiles`` are the ``compile`` records so far;
    ``imported`` is :func:`imported_between` over the first step call.
    Every stamp in the record is in seconds since the process was created."""
    origin_ns, origin = process_origin()

    def since(ns: float) -> float:
        return round((ns - origin_ns) * 1e-9, 6)

    entered = since(store.entered_ns)
    phases = [{"name": name, "parent": parent, "start_s": since(start),
               "end_s": since(end)}
              for name, parent, start, end in
              sorted(store.spans, key=lambda s: s[2])]
    wait = round(feed_end_s - feed_start_s, 6)
    call = round(call_end_s - feed_end_s, 6)
    sync = round(sync_end_s - call_end_s, 6)
    total = since(sync_end_s * 1e9)
    named = sum(p["end_s"] - p["start_s"] for p in phases
                if p["parent"] is None)
    pair = (time.perf_counter_ns(), time.time_ns())
    return {
        "kind": "startup", "tag": "telemetry", "origin": origin,
        # main_entered_s in two: up to the program's first import
        # (interpreter, the caller's own start), and from there
        "package_imported_s": since(_IMPORTED_NS),
        "main_entered_s": entered,
        "phases": phases,
        "first_batch_wait_s": wait,
        "first_call_s": call,
        "first_sync_s": sync,
        "time_to_first_update_s": total,
        # what lies between the named parts: main's lines between two
        # spans, the loop's preamble and its lines before the barrier
        "unattributed_s": round(
            total - entered - named - wait - call - sync, 6),
        "compiles": len(compiles),
        "compiles_cold": sum(1 for c in compiles
                             if c.get("cache") in ("miss", "uncached")),
        "compiles_warm": sum(1 for c in compiles if c.get("cache") == "hit"),
        # what sys.modules gained inside the first step call: an import
        # that runs there is inside first_call_s, and inside trace_s
        "imported_in_first_call": imported or {"modules": 0, "packages": []},
        # perf_counter_ns and time_ns read together: a stamp s of this
        # record is unix time time_ns + (origin + s * 1e9 - perf_counter_ns),
        # the clock of a trace's events (docs/telemetry.md "Start-up")
        "clock": {"perf_counter_ns": pair[0], "time_ns": pair[1],
                  "process_created_perf_counter_ns": origin_ns},
    }

# Process-wide trace exclusivity (concurrency registry): jax.profiler
# allows one active trace per process; flipped by whichever thread's
# begin/end wins, checked by every other would-be starter.
_TRACE_LOCK = threading.Lock()
_TRACE_ACTIVE = False


def _acquire_trace() -> bool:
    global _TRACE_ACTIVE
    with _TRACE_LOCK:
        if _TRACE_ACTIVE:
            return False
        _TRACE_ACTIVE = True
        return True


def _release_trace() -> None:
    global _TRACE_ACTIVE
    with _TRACE_LOCK:
        _TRACE_ACTIVE = False


def trace_active() -> bool:
    """Whether ANY trace window is live in this process (status surface)."""
    with _TRACE_LOCK:
        return _TRACE_ACTIVE


def parse_profile_spec(spec) -> Optional[Tuple[int, int]]:
    """``"N"``/``N`` -> (2, 2+N) steady-state window; ``"N:M"`` -> (N, M);
    falsy / "0" -> None (disabled). Raises ValueError on malformed specs."""
    if spec is None:
        return None
    if isinstance(spec, int):
        return (2, 2 + spec) if spec > 0 else None
    text = str(spec).strip()
    if not text:
        return None
    if ":" in text:
        start_s, stop_s = text.split(":", 1)
        start, stop = int(start_s), int(stop_s)
        if start < 1 or stop <= start:
            raise ValueError(
                f"--profile_steps range must satisfy 1 <= N < M, got {text!r}")
        return (start, stop)
    n = int(text)
    return (2, 2 + n) if n > 0 else None


class ProfilerWindow:
    """Drives bounded trace windows from per-step calls.

    ``enabled`` gates everything (non-primary processes pass False: traces
    are per-host artifacts and rank 0's is the one the tooling reads).
    The spec-driven startup window remains one-shot (``done`` latches
    after it); ``begin``/``end`` windows are unlimited.
    """

    def __init__(self, spec, trace_dir: Optional[str],
                 enabled: bool = True):
        self.range = parse_profile_spec(spec) if enabled else None
        self.trace_dir = trace_dir
        self.enabled = bool(enabled)
        self.active = False
        self.done = False
        # True only while the SPEC-driven startup window is tracing:
        # maybe_stop's auto-stop rule applies to it alone — an on-demand
        # begin() window at step 50 must not be killed by the startup
        # range having ended at step 4.
        self._startup_active = False

    def begin(self, trace_dir: Optional[str] = None) -> bool:
        """Start a trace window outside the startup contract (on-demand
        captures). Returns False — never raises, never stacks — when
        this window is disabled, already tracing, or ANY other trace is
        active in the process (the startup window of this or another
        ProfilerWindow included)."""
        if not self.enabled or self.active:
            return False
        if not _acquire_trace():
            return False
        try:
            jax.profiler.start_trace(trace_dir or self.trace_dir)
        except Exception:
            # A refused/failed start must release the latch or no trace
            # could ever start again in this process.
            _release_trace()
            return False
        self.active = True
        return True

    def end(self, sync_target=None) -> bool:
        """Stop the active trace window (on-demand counterpart of
        ``begin``; does NOT latch ``done`` — the startup contract's
        one-shot marker belongs to ``stop``)."""
        if not self.active:
            return False
        if sync_target is not None:
            # The trace must hold the device work of every step in the
            # window, not just their dispatches.
            jax.block_until_ready(sync_target)
        try:
            jax.profiler.stop_trace()
        finally:
            self.active = False
            self._startup_active = False
            _release_trace()
        return True

    def maybe_start(self, step_in_run: int) -> bool:
        """Start the startup trace when ``step_in_run`` enters the
        spec's window (one-shot: ``done`` latches after it)."""
        if (self.range is None or self.active or self.done
                or step_in_run < self.range[0]
                or step_in_run >= self.range[1]):
            return False
        if not self.begin():
            return False
        self._startup_active = True
        return True

    def annotation(self, step_in_run: int):
        """Context manager wrapping one step of the loop: written whoever
        opened the trace session, so any capture groups its events by
        step and every ``train:*`` span has its step number."""
        return jax.profiler.StepTraceAnnotation("train", step_num=step_in_run)

    def maybe_stop(self, step_in_run: int, sync_target=None) -> bool:
        """Stop when the STARTUP window's last step completed
        (auto-stop; on-demand ``begin`` windows are bounded by their
        controller, not the spec range)."""
        if not self._startup_active or step_in_run < self.range[1] - 1:
            return False
        return self.stop(sync_target)

    def stop(self, sync_target=None) -> bool:
        """Unconditional stop (end of run inside the window); latches
        the startup one-shot ``done`` marker only when the startup
        window was the one tracing."""
        was_startup = self._startup_active
        if not self.end(sync_target=sync_target):
            return False
        if was_startup:
            self.done = True
        return True
