"""Compile/cache observability: every XLA compile becomes a telemetry record.

A cold BERT-large compile is indistinguishable from a hang in a flat seq/s
log, and a warm start from a cold one. This module makes compilation
explicit: a :class:`CompileMonitor`
wraps each jitted entry point, and JAX's ``jax.monitoring`` events — which
``utils/compile_cache.py`` taps via :func:`install_compile_listeners` —
attribute every backend compile and persistent-cache hit/miss to the
wrapped function and the argument-shapes digest that triggered it.

Emitted record (``kind="compile"``, schema.py)::

    {"kind": "compile", "fn": "train_step", "shapes_digest": "ab12…",
     "compile_s": 12.31, "trace_s": 0.21, "lower_s": 0.18,
     "backend_compile_s": 11.90, "cache_load_s": 0.0, "cache": "miss",
     "trace_parts": {"modules": {"BertLayer": {"calls": 2, "self_s": 0.04}},
                     "kernels": {"flash_fwd": {"builds": 2, "build_s": 0.05}},
                     "optimizer_s": 0.03, "other_s": 0.09,
                     "outside_trace_s": 0.0}}

``compile_s`` is host time round the wrapped call, whatever the call does
(under a wrapper that blocks or copies inside it, that too). The other four
are measured where the work happens, from ``jax.monitoring``: ``trace_s``
(the function traced to a jaxpr) and ``lower_s`` (the jaxpr turned into an
MLIR module) are the time covered by those events, an event inside another
counted once; ``backend_compile_s`` is the time in JAX's compile-or-load
call, which on a persistent-cache hit is mostly ``cache_load_s`` (the read
and deserialization of the entry, fired on hits only).

``trace_parts`` says what ``trace_s`` was spent on. While the wrapped call
is on the stack the hooks of ``utils/trace_parts.py`` book host intervals
into it where the work happens (pretrain.py round the model's ``apply`` and
the optimizer's update, each ``pallas_call`` site of ops/pallas/ and the
megablox calls of ops/moe.py): ``modules`` is the SELF time of the flax
module methods by class (a method's duration less the intervals entered
inside it; the ``KEPT_CLASSES`` largest, the rest summed under
``OTHER_CLASSES``), ``kernels`` the kernel builds by the kernel's name (a
build inside a module's method is taken out of that module's self time, so
no interval is booked twice), ``optimizer_s`` the optimizer's update, and
``other_s`` the remainder of ``trace_s``: JAX's own passes (linearize,
transpose, remat's partial evaluation) and the step's Python outside model
and optimizer. The four add up to ``trace_s``. JAX tells of a trace when it
ends, so a hook cannot know whether it runs inside one: the outermost
intervals are kept and cut against the call's trace spans when the record
is made, and one that no span holds goes whole to ``outside_trace_s``
instead. ``builds`` against the calls the device trace holds says which
kernels share a trace: a site behind a jitted entry point is built once a
distinct shape; any other at every call, in the primal body, in the forward
rule and in the backward rule.

``cache`` is one of:

* ``"hit"``  — served from the persistent compile cache (warm start);
* ``"miss"`` — a real XLA compile ran and the executable was persisted to
  the cache;
* ``"uncached"`` — a real compile that was NOT persisted: the persistent
  cache is disabled, or the compile was cheaper than the
  min-compile-time/min-entry-size persistence bars (jax fires the miss
  counter only when it writes the entry, so a below-the-bar compile is
  indistinguishable from a disabled cache — both mean "next run recompiles
  this");
* ``"jit"``  — no compile activity at all for a first-seen shapes digest
  (served by JAX's in-process executable cache, e.g. a re-jit of an
  identical program).

Attribution uses a per-thread current-call context: jit tracing and
compilation run synchronously on the calling thread, so events fired while
the wrapper is on-stack belong to it. Listener registration is global and
permanent (jax.monitoring has no unregister), so listeners are installed
once and route through a module-level active-monitor registry.

With ``cost_analysis`` != "off" every first-seen (fn, shapes_digest) pair
additionally emits one ``kind="compile_cost"`` record — the executable's
static FLOPs / bytes-accessed / argument-output-temp bytes
(telemetry/memory.py :func:`analyze_executable`) — so each compile event
in the stream carries the cost of what it compiled.
"""

from __future__ import annotations

import hashlib
import threading
import time
from typing import Callable, Optional

from bert_pytorch_tpu.utils import compile_cache as compile_cache_util
from bert_pytorch_tpu.utils import trace_parts as trace_parts_util

_BACKEND_COMPILE_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    # older/newer spellings kept for forward compatibility
    "/jax/backend_compile_duration",
)
# Fired on the compiling thread as durations and as (start, end) spans; one
# trace or lowering can lie inside another (a jitted function called while
# another is traced), so these two are kept as spans and merged.
_NESTING_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
}
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
# trace_parts keeps the classes with the most self time, the rest as one row
KEPT_CLASSES = 12
OTHER_CLASSES = "(other classes)"

# The call on this thread's stack; the booking hooks of utils/trace_parts.py
# read the same slot.
_tls = trace_parts_util.tls


def _current_call():
    return getattr(_tls, "call", None)


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    call = _current_call()
    if call is None:
        return
    if event in _BACKEND_COMPILE_EVENTS:
        call["backend_compile_s"] += float(duration_secs)
        call["compiled"] = True
    elif event == _CACHE_LOAD_EVENT:
        call["cache_load_s"] += float(duration_secs)


def _on_span(event: str, start: float, end: float, **_kw) -> None:
    call = _current_call()
    kind = _NESTING_EVENTS.get(event)
    if call is None or kind is None:
        return
    # Events arrive as they END, so the ones inside this one are at the
    # tail: they give way to it.
    spans = call[kind]
    while spans and spans[-1][0] >= start:
        spans.pop()
    spans.append((start, end))


def _on_event(event: str, **_kw) -> None:
    call = _current_call()
    if call is None:
        return
    if event == _CACHE_HIT_EVENT:
        call["cache_hits"] += 1
    elif event == _CACHE_MISS_EVENT:
        call["cache_misses"] += 1


_install_lock = threading.Lock()
_installed = False


def _ensure_listeners() -> None:
    global _installed
    with _install_lock:
        if _installed:
            return
        compile_cache_util.install_compile_listeners(
            _on_event, _on_duration, _on_span)
        _installed = True


def _new_call(clock=None) -> dict:
    # "book" is made by the first hook of utils/trace_parts.py that the call
    # reaches, on "clock"
    return {"backend_compile_s": 0.0, "compiled": False, "cache_load_s": 0.0,
            "cache_hits": 0, "cache_misses": 0, "trace": [], "lower": [],
            "clock": clock, "book": None}


def _split(call: dict) -> dict:
    """The record's fields for where a call's compile time went."""
    trace_s = round(sum((e - s for s, e in call["trace"]), 0.0), 4)
    return {
        "trace_s": trace_s,
        "lower_s": round(sum((e - s for s, e in call["lower"]), 0.0), 4),
        "backend_compile_s": round(call["backend_compile_s"], 4),
        "cache_load_s": round(call["cache_load_s"], 4),
        "trace_parts": _trace_parts(call, trace_s),
    }


def _trace_parts(call: dict, trace_s: float) -> dict:
    """What ``trace_s`` was spent on, from the call's book (module
    docstring): ``modules`` ({class: calls, self_s}), ``kernels`` ({name:
    builds, build_s}), ``optimizer_s`` and, as the remainder, ``other_s``:
    the four add up to ``trace_s``. An outermost booked interval that lies
    outside every trace span is taken whole to ``outside_trace_s`` and to
    none of the four."""
    book = call["book"]
    modules, kernels = {}, {}
    optimizer_s = outside_s = 0.0
    done = 0
    for start, end, upto in (book.tops if book else ()):
        middle = (start + end) / 2 + book.spans_ahead_s
        traced = any(s <= middle <= e for s, e in call["trace"])
        for kind, name, own_s in book.booked[done:upto]:
            if not traced:
                outside_s += own_s
            elif kind == trace_parts_util.OPTIMIZER:
                optimizer_s += own_s
            else:
                row = (modules if kind == trace_parts_util.MODULE
                       else kernels).setdefault(name, [0, 0.0])
                row[0] += 1
                row[1] += own_s
        done = upto
    ranked = sorted(modules.items(), key=lambda kv: -kv[1][1])
    rest = ranked[KEPT_CLASSES:]
    if rest:
        ranked = ranked[:KEPT_CLASSES] + [(OTHER_CLASSES, [
            sum(r[0] for _, r in rest), sum(r[1] for _, r in rest)])]
    parts = {
        "modules": {name: {"calls": n, "self_s": round(s, 4)}
                    for name, (n, s) in ranked},
        "kernels": {name: {"builds": n, "build_s": round(s, 4)}
                    for name, (n, s) in sorted(kernels.items())},
        "optimizer_s": round(optimizer_s, 4),
    }
    parts["other_s"] = round(
        trace_s - sum(m["self_s"] for m in parts["modules"].values())
        - sum(k["build_s"] for k in parts["kernels"].values())
        - parts["optimizer_s"], 4)
    parts["outside_trace_s"] = round(outside_s, 4)
    return parts


def shapes_digest(tree) -> str:
    """Stable digest of the arg tree's structure + shapes/dtypes — the
    compile-relevant signature of a call (values don't recompile; shapes,
    dtypes, and tree structure do)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    parts = [str(treedef)]
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None:
            parts.append(f"py:{type(leaf).__name__}:{leaf!r}")
        else:
            parts.append(f"{dtype}{tuple(shape)}")
    return hashlib.sha1("|".join(parts).encode()).hexdigest()[:12]


class CompileMonitor:
    """Wrap jitted callables; emit one record per observed compile/lookup.

    ``emit`` receives the record dict; a ``clock`` is injectable for tests.
    """

    def __init__(self, emit: Callable[[dict], None],
                 clock: Callable[[], float] = time.perf_counter,
                 cost_analysis: str = "off"):
        _ensure_listeners()
        self._emit = emit
        self._clock = clock
        self.events: list = []  # everything emitted, for programmatic access
        # Static cost/memory attribution (telemetry/memory.py): one
        # kind="compile_cost" record per (fn, shapes_digest), emitted
        # right after that signature's first compile event so every
        # compile in the stream carries its cost. Mode semantics —
        # auto/off/full — are analyze_executable's; validate HERE so a
        # bad mode fails at construction, not mid-run after the first
        # (expensive) compile already happened.
        from bert_pytorch_tpu.telemetry.memory import COST_MODES

        if cost_analysis not in COST_MODES:
            raise ValueError(
                f"cost_analysis must be one of {COST_MODES}, got "
                f"{cost_analysis!r}")
        self.cost_analysis = cost_analysis
        self._cost_done: set = set()

    def note(self, record: dict) -> None:
        """Append + emit one caller-built record through this monitor's
        sink — the side channel for kernel-layer events that belong in
        the same stream as the compile records they explain (the serve
        engine's ``kind="autotune"`` geometry records ride here, next to
        the compile events whose fn names carry the winner digest)."""
        self.events.append(record)
        self._emit(record)

    def instrument(self, fn, name: str):
        """Return ``fn`` wrapped so first-seen shape signatures (and any
        call during which compile activity fires) emit a compile record.

        The digest walks the FULL arg tree (params + optimizer state for a
        train step — hundreds of leaves), so it is computed only when a
        record might be emitted: on the wrapper's first call, or when
        compile/cache activity actually fired during the call (a new shape
        signature always triggers a real trace+compile, so it can't slip
        by). Steady-state calls — the ones inside a benchmark's measured
        window and the StepTimer's host-dispatch segment — add only a
        thread-local set/restore and two clock reads.
        """
        seen: set = set()

        def wrapper(*args, **kwargs):
            prev = _current_call()
            call = _new_call(self._clock)
            _tls.call = call
            t0 = self._clock()
            try:
                out = fn(*args, **kwargs)
            finally:
                _tls.call = prev
            elapsed = self._clock() - t0
            activity = (call["compiled"] or call["cache_hits"]
                        or call["cache_misses"])
            if activity or not seen:
                # Donated args are deleted by now, but aval metadata
                # (shape/dtype) stays readable — only data access raises.
                digest = shapes_digest((args, kwargs))
                first = digest not in seen
                seen.add(digest)
                if first or activity:
                    self._record(name, digest, elapsed, call)
                    self._attribute_cost(fn, name, digest, args, kwargs)
            return out

        wrapper.__name__ = f"{name}_monitored"
        return wrapper

    def _record(self, name, digest, elapsed, call) -> None:
        # The persistent-cache counter events are authoritative: every
        # lookup fires exactly one hit or miss for the MAIN program, while
        # backend_compile_duration also fires for tiny auxiliary modules
        # (constant conversions) even on a cache-hit call — so `compiled`
        # alone cannot distinguish warm from cold.
        if call["cache_misses"]:
            cache = "miss"
        elif call["cache_hits"]:
            cache = "hit"
        elif call["compiled"]:
            cache = "uncached"
        else:
            cache = "jit"
        record = {
            "kind": "compile",
            "tag": "telemetry",
            "fn": name,
            "shapes_digest": digest,
            # host time round the call that compiled: trace + lower +
            # backend compile + the enqueue, and whatever else the wrapped
            # callable does before it returns (a probe that blocks on the
            # result or copies it to the host: benchmarks/kinds/*.py)
            "compile_s": round(elapsed, 4),
            **_split(call),
            "cache": cache,
        }
        self.events.append(record)
        self._emit(record)

    def _attribute_cost(self, fn, name, digest, args, kwargs) -> None:
        if self.cost_analysis == "off":
            return
        key = (name, digest)
        if key in self._cost_done:
            return
        self._cost_done.add(key)
        from bert_pytorch_tpu.telemetry import memory as memory_util

        # The analysis lowers the function again and asks for its
        # executable (memory.py): what that costs, and where, is start-up
        # time like the first call's.
        prev, call = _current_call(), _new_call(self._clock)
        _tls.call = call
        t0 = self._clock()
        try:
            fields = memory_util.analyze_executable(
                fn, args, kwargs, mode=self.cost_analysis)
        finally:
            _tls.call = prev
        if fields is None:
            return
        record = {"kind": "compile_cost", "tag": "telemetry", "fn": name,
                  "shapes_digest": digest, **fields,
                  "analysis_s": round(self._clock() - t0, 4), **_split(call)}
        self.events.append(record)
        self._emit(record)
