"""Persistent XLA compilation cache setup shared by every entry point.

A restarted or resumed job reuses the cached executables instead of
recompiling — minutes for BERT-large. One resolver decides where the cache
lives (:func:`resolve_cache_dir`):

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX's own handling of the variable
  stands and this module sets no directory at all, so whoever launched the
  process places the cache;
* otherwise an explicit ``--compile_cache_dir`` (tests; the supervisor
  handing one directory to all its replicas);
* otherwise ``<checkout>/.jax_cache`` — a FIXED path, because the path is
  part of what makes a later process find the entries; a temporary or
  time-stamped directory never hits twice.

A directory that cannot be created or written is an error, not a silent
uncached run: every cold process would pay the whole compile again and
nothing would say why.

This module is also the tap point for compile OBSERVABILITY
(:mod:`bert_pytorch_tpu.telemetry.compile_events`):
:func:`install_compile_listeners` registers ``jax.monitoring`` listeners so
every backend compile duration and persistent-cache hit/miss event reaches
the telemetry layer, which attributes them to the jitted function and shape
signature that triggered them — cold-vs-warm is always distinguishable in
the artifacts.
"""

from __future__ import annotations

import os
import tempfile
from typing import Optional

# Compiles cheaper than this are faster to redo than to round-trip through
# the cache; only the big train-step executables are worth persisting.
MIN_COMPILE_TIME_SECS = 10.0

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def resolve_cache_dir(explicit: str = "") -> Optional[str]:
    """The directory this process should point JAX's cache at, or None
    when ``JAX_COMPILATION_CACHE_DIR`` already placed it (module docstring)."""
    if os.environ.get(CACHE_DIR_ENV):
        return None
    return explicit or DEFAULT_CACHE_DIR


def enable_compile_cache(cache_dir: str = "",
                         min_compile_secs: Optional[float] = None) -> str:
    """Turn on JAX's persistent compilation cache; returns the directory
    in effect. ``cache_dir`` is the entry point's ``--compile_cache_dir``
    ("" = the resolver's default). Raises ``OSError`` when the directory
    cannot be created or written.

    ``min_compile_secs`` sets the persistence bar. Training keeps the
    default, ``MIN_COMPILE_TIME_SECS`` (only the multi-minute train-step
    executables are worth the round trip); SERVING passes 0.0 — a replica's per-(task, bucket)
    forwards each compile in seconds, but a fresh replica compiles dozens
    of them, and the cold-start acceptance ("second start performs zero
    cold compiles", docs/serving.md) needs every one persisted. Below-bar
    compiles fire no cache-miss counter (they are never written), so they
    would read as "uncached" forever and the warm-start proof could never
    hold.
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    target = resolve_cache_dir(cache_dir)
    if target is not None:
        os.makedirs(target, exist_ok=True)
        with tempfile.NamedTemporaryFile(dir=target):
            pass
        jax.config.update("jax_compilation_cache_dir", target)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(MIN_COMPILE_TIME_SECS if min_compile_secs is None
              else min_compile_secs))
    # An executable keeps the op names it was compiled with (module path,
    # named scopes, source lines) and a profiler trace shows them. JAX leaves
    # them out of the cache key by default, so a hit could hand this process
    # the names of whichever checkout compiled the same arithmetic first.
    # With them in the key a restart of the same checkout still hits; another
    # checkout, or an edited file on the traced path, compiles its own (a
    # program with a Pallas kernel always did). Locations are cut to the line
    # that wrote the op: with the callers' frames in them the key would turn
    # on the line a jitted function is first called from, and the second
    # lowering of the step for its cost record (telemetry/memory.py) would
    # be a second compile.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    # jax latches cache-enablement at the first compile of the process: if
    # anything compiled before this call — an eager op that triggered jit —
    # the directory would be silently ignored for the rest of the process.
    # Reset the latch so it re-reads the config.
    compilation_cache.reset_cache()
    return target or os.environ[CACHE_DIR_ENV]


def install_compile_listeners(event_cb, duration_cb, span_cb) -> None:
    """Register ``jax.monitoring`` listeners for compile observability.

    ``event_cb(event, **kw)`` receives counter events (persistent-cache
    hits/misses: ``/jax/compilation_cache/cache_hits`` / ``cache_misses``);
    ``duration_cb(event, duration_secs, **kw)`` receives durations (the
    compile-or-load call: ``/jax/core/compile/backend_compile_duration``;
    a cache entry read: ``/jax/compilation_cache/cache_retrieval_time_sec``);
    ``span_cb(event, start, end, **kw)`` receives the events JAX times with
    a start and an end (``/jax/core/compile/jaxpr_trace_duration``,
    ``/jax/core/compile/jaxpr_to_mlir_module_duration``), which is what
    tells one inside another from two in a row. All fire on the compiling
    thread. Registration is permanent, so callers install
    once and route internally (telemetry/compile_events.py does)."""
    import jax.monitoring as monitoring

    monitoring.register_event_listener(event_cb)
    monitoring.register_event_duration_secs_listener(duration_cb)
    monitoring.register_event_time_span_listener(span_cb)
