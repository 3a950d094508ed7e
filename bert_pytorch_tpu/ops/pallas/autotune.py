"""Measured block-geometry autotune for the Pallas inference kernels.

``_pick_blocks`` / ``_pick_bh_block`` (ops/pallas/attention.py) are
hand-written heuristics: good defaults, but FlashAttention's own result
(Dao 2022, arXiv:2205.14135) is that the IO-aware tiling choice is
worth measuring, not guessing — the best (block_q, block_k, bh_block)
triple shifts with sequence length, batch*heads, and backend. This
module lets serving PAY FOR THE MEASUREMENT ONCE and remember it:

* :func:`measure` times every candidate geometry of one inference
  kernel at one ``(seq, bh, depth)`` shape (median of ``repeats`` timed
  calls after an untimed compile call) and records the winner;
* winners persist as a small JSON file (:func:`save_winners` /
  :func:`load_winners`) next to the persisted AOT compile cache, with
  the SAME keying discipline: the registry key covers (kernel, seq, bh)
  and the file stamps the backend platform + interpret mode, and the
  serve engine folds the winner digest into its stable jitted-forward
  names (serve/engine.py) — the compile-cache key derives from the
  fn-name-derived HLO module name, so a warm restart that loads the
  winners file compiles the SAME programs under the SAME names and
  warms entirely from the persistent cache (``compiles_cold == 0``
  still holds, the PR-8 acceptance);
* :func:`lookup` is the kernels' consult point: a cached winner wins,
  otherwise the caller falls back to the heuristic. Winners are read at
  TRACE time: load them BEFORE the first forward traces — the serve
  engine loads in ``__init__``, before warmup — because already-compiled
  shapes never re-read the registry.

The registry is PROCESS-GLOBAL, not per-engine: an engine built with
``autotune="off"`` in a process where another engine (or a test)
already loaded winners for the same (kernel, seq, bh) will trace with
those winners too. That is safe — the serve engine folds the winner
digest into its forward names regardless of its own autotune mode, so
names always describe the geometry actually compiled and the compile
cache never aliases — but it means heuristic-vs-winner A/B comparisons
must isolate processes or :func:`clear_winners` between legs
(tests/test_kernels_fastpath.py clears them in its ``clean_registry``
fixture, before and after a test).

On CPU the kernels run in interpret mode, so measured timings rank
pure-Python emulation, not MXU behavior — the mechanism (measure,
persist, reload, warm-restart) is what CPU proves; real geometry wins
ride the on-chip capture harness. The registry and file format are
platform-stamped so CPU winners never leak into a TPU process.

Module-level imports stay jax-free on purpose: the winners-file FORMAT
validator below is shared with the jax-free lint gate
(``bert_pytorch_tpu/analysis/check_all.py`` loads this module by file
path, the telemetry/schema.py technique).
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Dict, List, Optional, Tuple

WINNERS_VERSION = 1

# Inference kernel variants the registry keys on (ops/pallas/attention.py).
KERNELS = ("infer", "infer_int8")

# (kernel, seq, bh) -> {"block_q": int, "block_k": int, "bh_block": int,
#                       "measured_ms": float}
_winners: Dict[Tuple[str, int, int], dict] = {}
_lock = threading.Lock()


def _key(kernel: str, seq: int, bh: int) -> str:
    """The file spelling of a registry key — one flat string so the
    winners JSON stays grep-able and diff-able."""
    return f"{kernel}:s{int(seq)}:bh{int(bh)}"


def _parse_key(key: str) -> Optional[Tuple[str, int, int]]:
    parts = key.split(":")
    if len(parts) != 3 or not parts[1].startswith("s") \
            or not parts[2].startswith("bh"):
        return None
    try:
        return parts[0], int(parts[1][1:]), int(parts[2][2:])
    except ValueError:
        return None


def lookup(kernel: str, seq: int, bh: int) -> Optional[Tuple[int, int, int]]:
    """Cached winner ``(block_q, block_k, bh_block)`` or None (caller
    falls back to the heuristic). Read at trace time by the kernels."""
    with _lock:
        entry = _winners.get((kernel, int(seq), int(bh)))
    if entry is None:
        return None
    return entry["block_q"], entry["block_k"], entry["bh_block"]


def record_winner(kernel: str, seq: int, bh: int, block_q: int,
                  block_k: int, bh_block: int,
                  measured_ms: Optional[float] = None) -> None:
    entry = {"block_q": int(block_q), "block_k": int(block_k),
             "bh_block": int(bh_block)}
    if measured_ms is not None:
        entry["measured_ms"] = round(float(measured_ms), 4)
    with _lock:
        _winners[(kernel, int(seq), int(bh))] = entry


def clear_winners() -> None:
    """Reset the process-global registry (tests)."""
    with _lock:
        _winners.clear()


def name_digest(kernel: str, seq: int, bh: int) -> str:
    """Short digest of the cached winner geometry, or "" when none.

    The serve engine appends this to its stable jitted-forward names
    (``serve_<task>_b<bucket>..._g<digest>``) so a GEOMETRY change
    invalidates exactly its own persistent-compile-cache entry — the
    cache keys on the fn-name-derived HLO module name, and without the
    suffix a new winner would recompile under the old name, silently
    aliasing two different programs to one cache identity. No winner →
    no suffix: the heuristic is deterministic per (seq, bh), so the
    plain name already names one program.
    """
    geom = lookup(kernel, seq, bh)
    if geom is None:
        return ""
    text = f"{kernel}:{seq}:{bh}:{geom[0]}x{geom[1]}g{geom[2]}"
    return hashlib.sha1(text.encode()).hexdigest()[:6]


# -- persistence ------------------------------------------------------------


def _platform() -> Tuple[str, bool]:
    import jax

    from bert_pytorch_tpu.ops.pallas.common import interpret_mode

    return jax.default_backend(), interpret_mode()


def save_winners(path: str) -> int:
    """Write the registry to ``path`` (atomic rename); returns the entry
    count. Stamps the backend platform + interpret mode so a loader on
    a different backend ignores the file instead of importing timings
    measured under a different execution model."""
    platform, interpret = _platform()
    with _lock:
        body = {_key(k, s, b): dict(entry)
                for (k, s, b), entry in sorted(_winners.items())}
    payload = {"version": WINNERS_VERSION, "platform": platform,
               "interpret": interpret, "winners": body}
    tmp = f"{path}.tmp.{os.getpid()}"
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return len(body)


def load_winners(path: str) -> int:
    """Merge a winners file into the registry; returns how many entries
    loaded. A missing file loads zero (fresh start); a file from another
    platform loads zero (its timings rank a different execution model);
    a malformed file raises ValueError — a corrupt cache must fail loud,
    not silently detune."""
    if not os.path.exists(path):
        return 0
    with open(path, "r", encoding="utf-8") as f:
        payload = json.load(f)
    errors = validate_winners(payload)
    if errors:
        raise ValueError(
            f"autotune winners file {path} is malformed: {errors[0]}")
    platform, interpret = _platform()
    if payload["platform"] != platform or \
            bool(payload.get("interpret")) != interpret:
        return 0
    loaded = 0
    with _lock:
        for key, entry in payload["winners"].items():
            parsed = _parse_key(key)
            if parsed is None:
                continue
            _winners[parsed] = {
                k: entry[k] for k in
                ("block_q", "block_k", "bh_block", "measured_ms")
                if k in entry}
            loaded += 1
    return loaded


def validate_winners(payload) -> List[str]:
    """Format errors for a decoded winners file (empty list = valid).

    jax-free on purpose: ``analysis/check_all.py`` loads this module by
    file path and runs this over every winners JSON it is given, the
    same lint-at-the-source discipline as the telemetry record schema.
    """
    if not isinstance(payload, dict):
        return [f"winners file is {type(payload).__name__}, not an object"]
    errors = []
    if payload.get("version") != WINNERS_VERSION:
        errors.append(f"unknown version {payload.get('version')!r}")
    if not isinstance(payload.get("platform"), str) \
            or not payload.get("platform"):
        errors.append("platform must be a non-empty string")
    if not isinstance(payload.get("interpret"), bool):
        errors.append("interpret must be a boolean")
    winners = payload.get("winners")
    if not isinstance(winners, dict):
        return errors + ["winners must be an object"]
    for key, entry in winners.items():
        parsed = _parse_key(key)
        if parsed is None:
            errors.append(f"winner key {key!r} is not "
                          "<kernel>:s<seq>:bh<bh>")
            continue
        kernel, seq, bh = parsed
        if kernel not in KERNELS:
            errors.append(f"winner key {key!r}: unknown kernel "
                          f"{kernel!r} (known: {KERNELS})")
        if not isinstance(entry, dict):
            errors.append(f"winner {key!r} must be an object")
            continue
        for field in ("block_q", "block_k", "bh_block"):
            v = entry.get(field)
            if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                errors.append(
                    f"winner {key!r}.{field} must be a positive integer, "
                    f"got {v!r}")
                continue
            if field.startswith("block") and seq % v != 0:
                errors.append(
                    f"winner {key!r}.{field}={v} does not divide "
                    f"seq {seq} — the kernel grid would be ragged")
            if field == "bh_block" and bh % v != 0:
                errors.append(
                    f"winner {key!r}.bh_block={v} does not divide "
                    f"bh {bh} — the kernel grid would be ragged")
        ms = entry.get("measured_ms")
        if ms is not None and (not isinstance(ms, (int, float))
                               or isinstance(ms, bool) or ms < 0):
            errors.append(
                f"winner {key!r}.measured_ms must be a non-negative "
                f"number, got {ms!r}")
    return errors


def validate_winners_file(path: str) -> List[str]:
    """File-level wrapper for the lint gate: parse + validate."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            payload = json.load(f)
    except OSError as exc:
        return [f"unreadable: {exc}"]
    except ValueError as exc:
        return [f"not valid JSON: {exc}"]
    return validate_winners(payload)


# -- measurement ------------------------------------------------------------


def candidates(seq: int, bh: int, max_bh_block: int = 16
               ) -> List[Tuple[int, int, int]]:
    """The candidate ``(block_q, block_k, bh_block)`` grid for one shape:
    square q/k tiles over the hardware-friendly divisor ladder (the same
    ladder ``pick_block`` walks, inlined here to keep this module's
    import surface jax-free — forward-only kernels have no fwd/bwd
    block-agreement constraint, but square tiles keep the grid small and
    match the measured-best training geometry), crossed with every
    power-of-two bh grouping that divides ``bh``."""
    blocks = [c for c in (512, 256, 128, 64, 32, 16, 8)
              if c <= seq and seq % c == 0]
    if not blocks:
        blocks = [seq]
    groups = []
    g = 1
    while g <= min(bh, max_bh_block):
        if bh % g == 0:
            groups.append(g)
        g *= 2
    return [(b, b, g) for b in blocks for g in groups]


def measure(kernel: str, seq: int, bh: int, depth: int,
            dtype=None, repeats: int = 3,
            clock=None) -> dict:
    """Time every candidate geometry of one inference kernel at one
    shape; records (and returns) the winner.

    Each candidate compiles once (untimed) then runs ``repeats`` timed
    calls; the median wall time ranks it. Runs OUTSIDE any
    CompileMonitor-instrumented wrapper, so the candidate compiles never
    pollute the serve engine's warm/cold startup split.
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bert_pytorch_tpu.ops.pallas import attention as pallas_attention

    if kernel not in KERNELS:
        raise ValueError(f"kernel must be one of {KERNELS}, got {kernel!r}")
    clock = clock or _time.perf_counter
    dtype = dtype or jnp.float32
    kernel_fn = (pallas_attention.flash_attention_infer_int8
                 if kernel == "infer_int8"
                 else pallas_attention.flash_attention_infer)
    # One bh-sized batch of single-head rows keeps the measured grid
    # identical to the serve forward's [B*H, S, D] kernel view.
    rng = np.random.default_rng(0)
    shape = (bh, seq, 1, depth)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), dtype)
               for _ in range(3))
    results = []
    last_exc: Optional[Exception] = None
    for geom in candidates(seq, bh):
        fn = jax.jit(lambda q, k, v, _g=geom: kernel_fn(q, k, v,
                                                        geometry=_g))
        try:
            jax.block_until_ready(fn(q, k, v))  # compile, untimed
            times = []
            for _ in range(repeats):
                t0 = clock()
                jax.block_until_ready(fn(q, k, v))
                times.append(clock() - t0)
            results.append((sorted(times)[len(times) // 2], geom))
        except Exception as exc:
            # A geometry the backend rejects is not a winner; keep the
            # cause so an all-candidates failure is debuggable below.
            last_exc = exc
            continue
    if not results:
        raise RuntimeError(
            f"autotune: no candidate geometry for {kernel} seq={seq} "
            f"bh={bh} survived measurement") from last_exc
    best_ms, best = min(results, key=lambda r: r[0])
    best_ms *= 1000.0
    record_winner(kernel, seq, bh, *best, measured_ms=best_ms)
    return {"kernel": kernel, "seq": int(seq), "bh": int(bh),
            "winner": {"block_q": best[0], "block_k": best[1],
                       "bh_block": best[2]},
            "candidates": len(results), "measured_ms": round(best_ms, 4)}
