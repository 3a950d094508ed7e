"""Device-memory observability: live/peak watermark sampling + one-shot
static cost attribution per jitted executable.

Two halves, both feeding the JSONL stream:

* :class:`MemorySampler` — samples ``device.memory_stats()`` on the
  existing sync cadence (the host is already blocked there, so the
  PJRT stats call adds no extra round trip) and emits one
  ``kind="memory"`` record per telemetry window with the live-bytes
  last/max and the peak watermark across devices. Backends without
  allocator stats (CPU returns ``None``; some runtimes raise) get ONE
  ``memory_supported: false`` note and the sampler disables itself —
  never a per-step warning storm.

* :func:`analyze_executable` — static attribution for one jitted
  function: HLO ``cost_analysis`` (FLOPs, bytes accessed) and — when a
  compile is affordable — ``compiled.memory_analysis()``
  (argument/output/temp bytes). The CompileMonitor calls
  it once per (fn, shapes-digest) and joins the result to the compile
  event's digest, so every compile in the stream carries its cost.

The compile-affordability rule matters: JAX's AOT ``lower().compile()``
does NOT share the executable the call path compiled, so asking for
``memory_analysis`` costs one extra backend compile per digest. That is
noise on CPU (and exactly once per shape), and a persistent-cache
deserialize when the persistent compile cache is on (every entry point
turns it on, utils/compile_cache.py) — but a second whole BERT-large
compile on the accelerator when it is off. ``mode="auto"``
therefore compiles only on CPU or with the persistent cache enabled and
falls back to the (cheap, compile-free) lowered-HLO cost analysis
elsewhere; ``"full"`` always compiles; ``"off"`` disables the whole
attribution.
"""

from __future__ import annotations

from typing import Callable, Optional

COST_MODES = ("auto", "off", "full")


class MemorySampler:
    """Window-aggregated ``device.memory_stats()`` watermarks."""

    def __init__(self, emit: Callable[[dict], None], enabled: bool = True):
        self._emit = emit
        self.enabled = enabled
        self.supported: Optional[bool] = None  # unknown until first sample
        self._reset()

    def _reset(self):
        self._samples = 0
        self._live_last = 0
        self._live_max = 0
        self._peak_max = 0
        self._limit = 0
        self._n_devices = 0

    def _read(self):
        """(live_bytes_total, peak_bytes_max, limit_total, n_devices) or
        None when no local device exposes allocator stats."""
        import jax

        live = peak = limit = 0
        n = 0
        for dev in jax.local_devices():
            try:
                stats = dev.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            n += 1
            live += int(stats.get("bytes_in_use", 0))
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
            limit += int(stats.get("bytes_limit", 0))
        return (live, peak, limit, n) if n else None

    def sample(self, step: int) -> None:
        """Take one watermark sample (call on synced steps only — the
        device is quiesced there, so 'live' means post-step residency)."""
        if not self.enabled or self.supported is False:
            return
        reading = self._read()
        if reading is None:
            self.supported = False
            # One note, then silence: the absence of memory records is
            # explained in-stream instead of by a log storm.
            self._emit({"kind": "memory", "tag": "telemetry",
                        "step": int(step), "memory_supported": False})
            return
        self.supported = True
        live, peak, limit, n = reading
        self._samples += 1
        self._live_last = live
        self._live_max = max(self._live_max, live)
        self._peak_max = max(self._peak_max, peak)
        self._limit = limit
        self._n_devices = n

    def flush(self, step: int) -> Optional[dict]:
        """Emit the window's aggregate record (None when no samples)."""
        if not self.enabled or not self._samples:
            return None
        record = {
            "kind": "memory",
            "tag": "telemetry",
            "step": int(step),
            "memory_supported": True,
            "samples": self._samples,
            "n_devices": self._n_devices,
            "bytes_in_use": self._live_last,
            "bytes_in_use_max": self._live_max,
            "peak_bytes_in_use": self._peak_max,
            "bytes_limit": self._limit,
        }
        self._reset()
        self._emit(record)
        return record


def _compile_affordable() -> bool:
    """One extra AOT compile is cheap: CPU backend, or the persistent
    compile cache will serve (or at worst persist) it."""
    import jax

    if jax.default_backend() == "cpu":
        return True
    return bool(jax.config.jax_compilation_cache_dir)


def analyze_executable(fn, args, kwargs, mode: str = "auto"):
    """Static cost/memory attribution for one jitted call signature.

    Returns a dict of record fields (``analysis`` says which path ran:
    ``"compiled"`` with memory_analysis bytes, or ``"lowered"`` with
    HLO cost analysis only) — or None when the function exposes no AOT
    surface or the backend supports neither analysis. Never raises:
    attribution is telemetry, not control flow.

    Works after the call even with donated arguments: lowering needs
    only aval metadata (shape/dtype), which deleted arrays retain.
    """
    if mode not in COST_MODES:
        raise ValueError(f"cost-analysis mode must be one of {COST_MODES}, "
                         f"got {mode!r}")
    if mode == "off":
        return None
    lower = getattr(fn, "lower", None)
    if lower is None:
        return None
    try:
        lowered = lower(*args, **kwargs)
    except Exception:
        return None
    fields: dict = {}
    if mode == "full" or _compile_affordable():
        try:
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            for name, key in (
                    ("argument_bytes", "argument_size_in_bytes"),
                    ("output_bytes", "output_size_in_bytes"),
                    ("temp_bytes", "temp_size_in_bytes")):
                value = getattr(mem, key, None)
                if value is not None:
                    fields[name] = int(value)
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            if cost.get("flops") is not None:
                fields["flops"] = float(cost["flops"])
            if cost.get("bytes accessed") is not None:
                fields["bytes_accessed"] = float(cost["bytes accessed"])
            # Mosaic (Pallas) kernels in the compiled program: the proof
            # that a fused kernel is what runs, not the XLA path or the
            # interpreter, neither of which leaves a custom call
            fields["tpu_custom_calls"] = compiled.as_text().count(
                "tpu_custom_call")
            fields["analysis"] = "compiled"
            return fields
        except Exception:
            fields = {}  # discard any partial compiled fields: a record
            # labeled analysis="lowered" must not carry memory_analysis
            # bytes from the compiled path that then failed mid-way
    try:
        cost = lowered.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        if cost.get("flops") is not None:
            fields["flops"] = float(cost["flops"])
        if cost.get("bytes accessed") is not None:
            fields["bytes_accessed"] = float(cost["bytes accessed"])
        fields["analysis"] = "lowered"
        return fields
    except Exception:
        return None
