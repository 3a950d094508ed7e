"""Bucket-compiled inference engine for the BERT task heads
(docs/serving.md).

The :class:`InferenceEngine` owns the device side of serving:

* **params-only checkpoint load** — each task head restores just the model
  subtree via :func:`bert_pytorch_tpu.utils.checkpoint.load_params_only`
  (a K-FAC pretraining checkpoint's preconditioner/optimizer pytrees never
  touch serving host memory); a missing checkpoint falls back to seeded
  random init (demo/smoke mode, loudly noted by run_server.py);
* **AOT bucket compilation** — one jitted forward per (task head,
  length-bucket, packedness), each with a STABLE function name per
  (task, bucket, packed, quant) so the persistent compile cache
  (whose key covers the fn-name-derived HLO module name) makes a
  restarted replica's warmup pure cache hits — cold start in seconds,
  ``startup["compiles_cold"] == 0``, proven by the cache counter
  events rather than wall clock. Compiles are attributed by the shared
  :class:`~bert_pytorch_tpu.telemetry.compile_events.CompileMonitor`,
  so the serve telemetry can assert "zero compiles after warmup"
  instead of hoping;
* **inference weight quantization** (``quantize="bf16"|"int8"``,
  ops/quant.py) — applied tensor-by-tensor inside the streaming
  params-only checkpoint decode; int8 serves ~4x smaller matmul
  weights through int8 GEMMs. ``attention_backend="pallas_infer"``
  selects the forward-only fused attention kernel
  (ops/pallas/attention.py);
* **batch planning** — :meth:`plan_batch` picks the SMALLEST bucket whose
  budget fits the flushed group (and, with packing on, the first-fit-
  decreasing row assignment over ``data/packing.py``'s packer), returning
  requests that do not fit for the batcher to requeue;
* **execution + demultiplexing** — split into three composable steps so
  the pipelined dispatch plane (serve/service.py, docs/serving.md
  "Continuous batching") can run them on different stages:
  :meth:`stage` pads/packs the group into the fixed
  (max_batch_size, bucket) compile shape (host-only — the assembler
  stage), :meth:`execute_staged` runs the jitted forward (the ONLY
  device call — the executor stage), and :meth:`demux` slices each
  request's own output back out (row, or (row, segment-span) /
  (row, pack-slot) when packed; host conversion — the completion
  stage). :meth:`execute` composes the three for the serial dispatch
  mode, offline scoring, and tests.

Batch shapes are FIXED at (max_batch_size, bucket): a partially full
group pads with all-zero rows (attention mask 0 — rows are independent
under the padding/block-diagonal mask, so parity with a direct
single-request forward holds to fp32 exactness; tests/test_serve.py).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from bert_pytorch_tpu.config import BertConfig
from bert_pytorch_tpu.data.packing import first_fit_decreasing
from bert_pytorch_tpu.serve import tasks as tasks_lib
from bert_pytorch_tpu.serve.batcher import Request
from bert_pytorch_tpu.telemetry.compile_events import CompileMonitor
from bert_pytorch_tpu.testing import faults
from bert_pytorch_tpu.utils import checkpoint as ckpt_util


class SwapBusy(RuntimeError):
    """A second hot-swap was requested while one is already in flight
    (loads cannot overlap — serve/http.py maps this to HTTP 409)."""


class TaskSpec:
    """One served head: its flax model, restored (possibly quantized)
    params, handler, and the jitted (instrumented) forwards — ONE per
    (bucket, packedness, fused-epilogue), each with a stable per-spec
    function name (see :meth:`InferenceEngine._build_forwards`)."""

    def __init__(self, name: str, model, params, handler):
        self.name = name
        self.model = model
        self.params = params
        self.handler = handler
        self.forwards: Dict[Tuple[int, bool, bool], Callable] = {}


class BatchPlan:
    """Output of :meth:`InferenceEngine.plan_batch`."""

    def __init__(self, bucket: int, rows: List[List[Request]],
                 leftover: List[Request], packed: bool):
        self.bucket = bucket
        self.rows = rows          # per dispatched row, its member requests
        self.leftover = leftover  # did not fit; requeue at queue front
        self.packed = packed

    @property
    def requests(self) -> List[Request]:
        return [r for row in self.rows for r in row]


class StagedBatch:
    """A plan staged into its fixed compile-shape arrays, ready for the
    device (output of :meth:`InferenceEngine.stage`).

    ``args`` is the positional argument tuple the plan's jitted forward
    takes (after params); ``offsets`` maps request id -> (row, token
    offset, pack slot) for :meth:`InferenceEngine.demux`; ``pack_s`` is
    the host seconds spent filling the arrays — the engine's share of
    the trace's ``assembly`` span. ``staged_at`` is stamped by the
    dispatch plane (the assembler) when staging completes, so the
    executor's pickup delay (``staged_wait``) is attributable.

    ``fused`` selects the fused-epilogue forward variant (docs/
    serving.md "Raw-speed kernels"); for a ``"gather"`` epilogue,
    ``gather_slots`` maps request id -> (row, first slot, slot count)
    into the [B, epilogue_slots, V] gathered output."""

    def __init__(self, task: str, plan: BatchPlan, args: tuple,
                 offsets: Dict[int, Tuple[int, int, int]], pack_s: float,
                 fused: bool = False,
                 gather_slots: Optional[Dict[int, Tuple[int, int, int]]]
                 = None):
        self.task = task
        self.plan = plan
        self.args = args
        self.offsets = offsets
        self.pack_s = pack_s
        self.fused = fused
        self.gather_slots = gather_slots or {}
        self.staged_at: Optional[float] = None


class InferenceEngine:
    def __init__(
        self,
        config: BertConfig,
        tokenizer,
        tasks: Dict[str, dict],
        buckets: Sequence[int] = (64, 128),
        max_batch_size: int = 8,
        max_requests_per_pack: int = 1,
        dtype=None,
        seed: int = 0,
        monitor: Optional[CompileMonitor] = None,
        clock: Callable[[], float] = time.perf_counter,
        quantize: Optional[str] = None,
        attention_backend: str = "xla",
        fuse_epilogues: bool = False,
        epilogue_slots: int = 8,
        autotune: str = "off",
        autotune_cache: Optional[str] = None,
        version: str = "v0",
    ):
        """``quantize`` selects the inference weight format
        (ops/quant.py): None serves the checkpoint's fp32 params,
        ``"bf16"`` halves weight bytes, ``"int8"`` quarters the matmul
        weights and runs int8 GEMMs (per-token dynamic activation
        scales). ``attention_backend`` routes the encoder's attention
        (ops/attention.py); ``"pallas_infer"`` is the forward-only fused
        kernel for serving on TPU (interpret-mode on CPU) and
        ``"pallas_infer_int8"`` its int8-QK^T variant (per-head
        symmetric scales).

        ``fuse_epilogues`` folds each head's output extraction into the
        forward's epilogue (docs/serving.md "Raw-speed kernels"):
        fill_mask gathers its [MASK] slots before the vocab projection
        ([B, epilogue_slots, V] out instead of [B, S, V]); squad stacks
        start/end into one output. ``epilogue_slots`` is the per-row
        gather quota; a batch whose rows need more falls back to that
        spec's unfused forward (both are AOT-warmed).

        ``autotune`` drives the measured Pallas block-geometry pass
        (ops/pallas/autotune.py) for the ``pallas_infer*`` backends:
        ``"load"`` reads persisted winners from ``autotune_cache``,
        ``"measure"`` additionally times candidates for any
        (bucket, batch*heads) shape without one and persists the
        result. Runs in ``__init__`` — BEFORE the forwards are built —
        because geometry is read at trace time and the winner digest is
        folded into the stable forward names (a warm restart that loads
        the same winners file compiles the same programs under the same
        names, keeping ``compiles_cold == 0``)."""
        import jax.numpy as jnp

        from bert_pytorch_tpu.ops import quant as quant_ops

        self.quantize = quant_ops.check_mode(
            None if quantize in (None, "none") else quantize)
        self.attention_backend = attention_backend
        self.fuse_epilogues = bool(fuse_epilogues)
        self.epilogue_slots = int(epilogue_slots)
        if self.fuse_epilogues and self.epilogue_slots < 1:
            raise ValueError(
                f"epilogue_slots must be >= 1, got {epilogue_slots}")
        if autotune not in ("off", "load", "measure"):
            raise ValueError(
                f"autotune must be off|load|measure, got {autotune!r}")
        if autotune != "off" and not autotune_cache:
            # Silently degrading to the heuristic would defeat the one
            # guarantee the flag exists for (winners persisted -> warm
            # restart compiles nothing new); a forgotten --autotune_cache
            # must fail at construction, not at the next restart.
            raise ValueError(
                f"autotune={autotune!r} requires autotune_cache (the "
                "winners JSON path next to the AOT compile cache)")
        if autotune != "off" and attention_backend not in (
                "pallas_infer", "pallas_infer_int8"):
            # Same fail-loud policy for the backend pairing: only the
            # Pallas inference kernels have geometry to tune — silently
            # no-opping under xla would let an operator believe measured
            # autotune is active when nothing was tuned.
            raise ValueError(
                f"autotune={autotune!r} tunes the Pallas inference "
                f"kernels; attention_backend={attention_backend!r} has "
                "no geometry to tune (use pallas_infer or "
                "pallas_infer_int8)")
        self.autotune = autotune
        self.autotune_cache = autotune_cache
        self.startup: Optional[dict] = None
        self.config = config
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        if not self.buckets or self.buckets[0] < 8:
            raise ValueError(f"buckets must be >= 8, got {buckets}")
        if max(self.buckets) > config.max_position_embeddings:
            raise ValueError(
                f"largest bucket {max(self.buckets)} exceeds "
                f"max_position_embeddings {config.max_position_embeddings}")
        self.max_batch_size = int(max_batch_size)
        self.max_requests_per_pack = max(1, int(max_requests_per_pack))
        self.pack = self.max_requests_per_pack > 1
        self.dtype = dtype if dtype is not None else jnp.float32
        self._clock = clock
        self.monitor = monitor or CompileMonitor(emit=lambda rec: None)
        self._setup_autotune()
        # Hot-swap state (docs/serving.md "Model registry & canary
        # rollouts"): _swap_lock makes (spec.params, serving_version,
        # _swap_epoch) flip as ONE atomic unit — the executor captures
        # all three in a single acquisition, so an in-flight batch
        # always runs against exactly one consistent version, and the
        # epoch check counts any params change that bypassed the flip
        # into _torn_serves (the zero-tolerance report gate).
        self._swap_lock = threading.Lock()
        self.serving_version = str(version)
        self._swap_epoch = 0
        self._swaps = 0
        self._torn_serves = 0
        self._swap_inflight = False
        handlers = tasks_lib.build_handlers(tokenizer, tasks)
        self.tasks: Dict[str, TaskSpec] = {}
        # Per-task (options, seed) as __init__ built them: swap_params
        # re-creates the SAME fp32 init template (the streaming-decode
        # load target) for the incoming checkpoint.
        self._task_build: Dict[str, Tuple[dict, int]] = {}
        for name, options in tasks.items():
            options = options or {}
            task_seed = seed + len(self.tasks)
            model, params = self._build_task(name, options, seed=task_seed)
            spec = TaskSpec(name, model, params, handlers[name])
            self._build_forwards(spec)
            self.tasks[name] = spec
            self._task_build[name] = (dict(options), task_seed)
        self.warmed = False

    # -- construction ----------------------------------------------------

    def _autotune_kernel(self) -> Optional[str]:
        """The autotune registry kernel this engine's forwards trace, or
        None when the backend has no Pallas geometry to tune."""
        return {"pallas_infer": "infer",
                "pallas_infer_int8": "infer_int8"}.get(
                    self.attention_backend)

    def _setup_autotune(self) -> None:
        """Load (and, in ``"measure"`` mode, fill) the Pallas geometry
        winners BEFORE any forward is built: geometry is read at trace
        time, and the winner digest rides the stable forward names.
        One ``kind="autotune"`` record per (bucket, bh) says where that
        shape's geometry came from — measured now, loaded from the
        cache, or the heuristic fallback."""
        if self.autotune == "off":
            return
        from bert_pytorch_tpu.ops.pallas import autotune as autotune_lib

        kernel = self._autotune_kernel()
        if kernel is None:
            return  # xla/pallas backends have no infer geometry to tune
        autotune_lib.load_winners(self.autotune_cache)
        bh = self.max_batch_size * self.config.num_attention_heads
        measured = 0
        for bucket in self.buckets:
            geom = autotune_lib.lookup(kernel, bucket, bh)
            record = {"kind": "autotune", "tag": "telemetry",
                      "kernel": kernel, "seq": bucket, "bh": bh}
            if geom is not None:
                record["source"] = "cached"
                record["winner"] = {"block_q": geom[0], "block_k": geom[1],
                                    "bh_block": geom[2]}
            elif self.autotune == "measure":
                t0 = self._clock()
                result = autotune_lib.measure(
                    kernel, bucket, bh, self.config.head_dim,
                    dtype=self.dtype)
                measured += 1
                record.update(source="measured", winner=result["winner"],
                              candidates=result["candidates"],
                              measure_s=round(self._clock() - t0, 3))
            else:
                record["source"] = "heuristic"
            self.monitor.note(record)
        if measured:
            autotune_lib.save_winners(self.autotune_cache)

    def _build_task(self, name: str, options: dict, seed: int):
        import flax.linen as nn
        import jax
        import jax.numpy as jnp

        from bert_pytorch_tpu import models
        from bert_pytorch_tpu.ops import quant as quant_ops

        cfg = self.config

        def build(quant):
            kwargs = dict(dtype=self.dtype, quant=quant,
                          attention_backend=self.attention_backend)
            if name == "fill_mask":
                return models.BertForMaskedLM(cfg, **kwargs)
            if name == "classify":
                labels = options.get("labels") or ["0", "1"]
                return models.BertForSequenceClassification(
                    cfg, num_labels=len(labels), **kwargs)
            if name == "squad":
                return models.BertForQuestionAnswering(cfg, **kwargs)
            if name == "ner":
                labels = options.get("labels") or ["O"]
                # +1: label ids start at 1, id 0 is reserved (run_ner.py).
                return models.BertForTokenClassification(
                    cfg, num_labels=len(labels) + 1, **kwargs)
            raise ValueError(f"unknown serve task {name!r}")

        # The fp32-layout model is always built: its init provides the
        # load TARGET (and demo-mode weights); the quant model reuses the
        # module tree with quantized param storage for apply().
        model = build(None)
        sample = (jnp.zeros((1, self.buckets[0]), jnp.int32),) * 3
        params = nn.unbox(
            model.init(jax.random.PRNGKey(seed), *sample))["params"]
        checkpoint = options.get("checkpoint")
        if checkpoint:
            # Quantization happens INSIDE the streaming decode — each
            # tensor converts as its bytes arrive; the fp32 tree never
            # materializes on the serving host (utils/checkpoint.py).
            params = ckpt_util.load_params_only(
                checkpoint, params, quantize=self.quantize)
        elif self.quantize:
            params = quant_ops.quantize_params(params, self.quantize)
        if self.quantize:
            model = build(self.quantize)
        return model, params

    def _name_suffix(self, bucket: int) -> str:
        """The autotune-winner digest suffix for this bucket's forward
        names (``_g<digest>``), or "" when no winner is cached.

        The persistent compile cache keys on the fn-name-derived HLO
        module name, so WITHOUT the suffix a new measured geometry
        would compile a different program under the SAME name —
        aliasing two executables to one cache identity, where a stale
        entry for the old geometry could be served against the new
        one's name. With it, a geometry change invalidates exactly its
        own entry; no winner means the deterministic heuristic, whose
        program the plain name already identifies."""
        kernel = self._autotune_kernel()
        if kernel is None:
            return ""
        from bert_pytorch_tpu.ops.pallas import autotune as autotune_lib

        digest = autotune_lib.name_digest(
            kernel, bucket,
            self.max_batch_size * self.config.num_attention_heads)
        return f"_g{digest}" if digest else ""

    def _build_forwards(self, spec: TaskSpec) -> None:
        """One jitted forward per (bucket, packedness, fused-epilogue),
        each named ``serve_<task>_b<bucket>[_packed][_fused]_<quant>``
        (+ the autotune-winner digest, :meth:`_name_suffix`).

        The name is load-bearing twice over: the persistent compile
        cache keys on the HLO module name, which jax derives from the
        Python function name — the old closures were ALL literally named
        ``forward``, so a restarted replica's cache keys depended on
        nothing but shapes (collision-prone across specs) and every
        CompileMonitor event attributed to one ambiguous ``fn``. Stable
        per-spec names make the warm-start cache hit deterministic
        across process restarts (the cold-start acceptance:
        second start => zero cold compiles) and compile telemetry
        attributable per (task, bucket, packed, quant, epilogue,
        geometry).

        Fused-epilogue engines (docs/serving.md "Raw-speed kernels"):
        a ``"gather"`` head (fill_mask) gets BOTH variants per
        (bucket, packed) — the fused forward takes a [B, epilogue_slots]
        positions argument and emits the gathered [B, P, V] logits; the
        unfused twin stays as the slot-overflow fallback. A
        ``"stack_span"`` head (squad) gets only the fused variant (the
        stack always applies). Heads with nothing to fuse compile the
        exact same program (and name) as an unfused engine, so they
        share its persistent-cache entries.
        """
        import jax

        model = spec.model
        pooled = spec.handler.output_kind == "pooled"
        epilogue = spec.handler.epilogue if self.fuse_epilogues else None
        qtag = self.quantize or "fp32"
        for bucket in self.buckets:
            for packed in ((False, True) if self.pack else (False,)):
                variants = []  # (fused, closure)
                if not packed:
                    def base(params, input_ids, segment_ids, input_mask):
                        return model.apply(
                            {"params": params}, input_ids, segment_ids,
                            input_mask)
                elif pooled:
                    def base(params, input_ids, segment_ids, input_mask,
                             sequence_ids, cls_positions):
                        return model.apply(
                            {"params": params}, input_ids, segment_ids,
                            input_mask, True, sequence_ids, cls_positions)
                else:
                    def base(params, input_ids, segment_ids, input_mask,
                             sequence_ids):
                        return model.apply(
                            {"params": params}, input_ids, segment_ids,
                            input_mask, True, sequence_ids)
                if epilogue == "gather":
                    if not packed:
                        def fused(params, input_ids, segment_ids,
                                  input_mask, positions):
                            return model.apply(
                                {"params": params}, input_ids,
                                segment_ids, input_mask, True, None,
                                positions)
                    else:
                        def fused(params, input_ids, segment_ids,
                                  input_mask, sequence_ids, positions):
                            return model.apply(
                                {"params": params}, input_ids,
                                segment_ids, input_mask, True,
                                sequence_ids, positions)
                    variants = [(False, base), (True, fused)]
                elif epilogue == "stack_span":
                    import jax.numpy as jnp

                    def fused(*args, _base=base):
                        start, end = _base(*args)
                        # One [B, 2, S] output: a single D2H transfer
                        # (and one host conversion in demux) instead of
                        # two — XLA fuses the stack into the epilogue.
                        return jnp.stack([start, end], axis=1)
                    variants = [(True, fused)]
                else:
                    variants = [(False, base)]
                for is_fused, fwd in variants:
                    name = (f"serve_{spec.name}_b{bucket}"
                            f"{'_packed' if packed else ''}"
                            f"{'_fused' if is_fused else ''}_{qtag}"
                            f"{self._name_suffix(bucket)}")
                    fwd.__name__ = name
                    fwd.__qualname__ = name
                    spec.forwards[(bucket, packed, is_fused)] = \
                        self.monitor.instrument(jax.jit(fwd), name)

    def warmup(self) -> int:
        """AOT-compile every (task, bucket[, packed]) forward the serving
        loop can dispatch; returns the number of compile events observed.
        After this, steady-state traffic never compiles — the acceptance
        the smoke test asserts via the CompileMonitor.

        Also records :attr:`startup` — ``cold_start_s`` plus compile
        counts split warm/cold from the persistent-cache COUNTER events
        (``cache`` = hit vs miss/uncached; the authority per
        telemetry/compile_events.py — wall clock proves nothing), so a
        restarted replica can assert it recompiled nothing.
        """
        import jax

        from bert_pytorch_tpu.ops import quant as quant_ops
        from bert_pytorch_tpu.ops.pallas.common import device_report

        t0 = self._clock()
        before = len(self.monitor.events)
        zeros = {}
        pos_zeros = np.zeros((self.max_batch_size, self.epilogue_slots),
                             np.int32)
        for bucket in self.buckets:
            B, S, K = (self.max_batch_size, bucket,
                       self.max_requests_per_pack)
            zeros[bucket] = (
                np.zeros((B, S), np.int32), np.zeros((B, S), np.int32),
                np.zeros((B, S), np.int32), np.zeros((B, S), np.int32),
                np.zeros((B, K), np.int32))
        for spec in self.tasks.values():
            pooled = spec.handler.output_kind == "pooled"
            gathered = spec.handler.epilogue == "gather"
            for (bucket, packed, fused), fwd in spec.forwards.items():
                ids, seg, mask, sids, cpos = zeros[bucket]
                if fused and gathered:
                    args = ((ids, seg, mask, pos_zeros) if not packed
                            else (ids, seg, mask, sids, pos_zeros))
                elif not packed:
                    args = (ids, seg, mask)
                elif pooled:
                    args = (ids, seg, mask, sids, cpos)
                else:
                    args = (ids, seg, mask, sids)
                out = fwd(spec.params, *args)
                jax.block_until_ready(out)
        compile_events = [e for e in self.monitor.events[before:]
                          if e.get("kind") == "compile"]
        self.startup = {
            "cold_start_s": round(self._clock() - t0, 3),
            "compiles": len(compile_events),
            "compiles_cold": sum(1 for e in compile_events
                                 if e.get("cache") in ("miss", "uncached")),
            "compiles_warm": sum(1 for e in compile_events
                                 if e.get("cache") == "hit"),
            "quantize": self.quantize or "none",
            "attention_backend": self.attention_backend,
            "fuse_epilogues": self.fuse_epilogues,
            "autotune": self.autotune,
            "weight_bytes": sum(quant_ops.weight_bytes(s.params)
                                for s in self.tasks.values()),
            # platform / device_kind / device_count / kernels
            # compiled|interpreted: what these forwards were compiled for
            **device_report(),
        }
        self.warmed = True
        return len(self.monitor.events) - before

    # -- hot swap (docs/serving.md "Model registry & canary rollouts") ---

    def version(self) -> str:
        """The serving model version (stamped atomically with the params
        flip — what /healthz, /statsz, and /metricsz report)."""
        with self._swap_lock:
            return self.serving_version

    def swap_stats(self) -> dict:
        """Swap counters for /statsz: the serving version, completed
        swaps, and torn serves (forwards whose params reference changed
        without the epoch-bumping flip — structurally 0; the
        zero-tolerance "rollout torn-model serves" gate reads it)."""
        with self._swap_lock:
            return {"version": self.serving_version,
                    "swaps": self._swaps,
                    "torn_serves": self._torn_serves}

    def swap_params(self, task: str, checkpoint: str, version: str,
                    emit: Optional[Callable[[dict], None]] = None) -> dict:
        """Hot-swap one task's params to ``checkpoint``, stamping the
        engine as serving ``version``. Raises :class:`SwapBusy` when a
        swap is already in flight (serve/http.py maps it to 409).

        The load runs OFF the dispatch path: the new params stream
        through the same quantize-at-decode path as startup (the fp32
        tree never materializes), built against a fresh init template
        from the task's original (options, seed) — so geometry, dtype,
        and quant layout match the forwards exactly. Because the jitted
        forwards key the persistent compile cache on their STABLE names
        and the staged shapes are unchanged, a same-geometry swap hits
        the already-compiled executables: zero compiles, cold or warm
        (the info dict proves it from the CompileMonitor's counter
        events, never wall clock).

        The flip itself is one lock acquisition that replaces the params
        reference, the version stamp, and the swap epoch together; an
        in-flight batch that captured the old reference keeps executing
        the old version to completion — there is no intermediate state
        to serve from."""
        spec = self.tasks.get(task)
        if spec is None:
            raise ValueError(
                f"unknown task {task!r} (serving: {sorted(self.tasks)})")
        if not checkpoint or not os.path.isfile(checkpoint):
            raise FileNotFoundError(f"swap checkpoint missing: "
                                    f"{checkpoint!r}")
        with self._swap_lock:
            if self._swap_inflight:
                raise SwapBusy(
                    "a hot-swap is already in flight; retry after it "
                    "completes")
            self._swap_inflight = True
            swap_attempt = self._swaps + 1
        try:
            options, seed = self._task_build[task]
            compiles_before = len(self.monitor.events)
            t0 = self._clock()
            _, new_params = self._build_task(
                task, dict(options, checkpoint=checkpoint), seed=seed)
            load_s = self._clock() - t0
            # Chaos hook: hold the swap window open between load and
            # flip (testing/faults.py swap_hold) — a SIGKILL landing
            # here proves in-flight batches only ever saw the OLD
            # consistent version.
            faults.get_plan().serve_swap_check(swap_attempt, emit=emit)
            with self._swap_lock:
                from_version = self.serving_version
                spec.params = new_params
                self.serving_version = str(version)
                self._swap_epoch += 1
                self._swaps += 1
        finally:
            with self._swap_lock:
                self._swap_inflight = False
        compile_events = [e for e in self.monitor.events[compiles_before:]
                          if e.get("kind") == "compile"]
        return {
            "task": task,
            "version": str(version),
            "from_version": from_version,
            "checkpoint": checkpoint,
            "load_s": round(load_s, 3),
            "compiles": len(compile_events),
            "compiles_cold": sum(1 for e in compile_events
                                 if e.get("cache") in ("miss", "uncached")),
            "compiles_warm": sum(1 for e in compile_events
                                 if e.get("cache") == "hit"),
        }

    # -- planning --------------------------------------------------------

    def select_bucket(self, length: int) -> int:
        """Smallest bucket that fits ``length``; the largest bucket for
        over-long requests (prepare() already truncated to it)."""
        for bucket in self.buckets:
            if length <= bucket:
                return bucket
        return self.buckets[-1]

    def max_len(self) -> int:
        return self.buckets[-1]

    def plan_batch(self, requests: List[Request],
                   packed: Optional[bool] = None) -> BatchPlan:
        """Assign a flushed request group to rows of the smallest workable
        bucket. Unpacked: one request per row, first ``max_batch_size``
        requests, bucket = smallest fitting the longest. Packed: the
        smallest bucket whose FFD packing needs <= ``max_batch_size``
        rows; requests falling outside the first ``max_batch_size`` rows
        are leftover for the batcher to requeue."""
        if packed is None:
            packed = self.pack
        if not requests:
            raise ValueError("plan_batch needs at least one request")
        if not packed:
            take = requests[: self.max_batch_size]
            leftover = requests[self.max_batch_size:]
            bucket = self.select_bucket(max(r.length for r in take))
            return BatchPlan(bucket, [[r] for r in take], leftover, False)

        lengths = [r.length for r in requests]
        # Budget-greedy bucket choice: every dispatch costs a FULL
        # (max_batch_size x bucket) token budget regardless of fill, so
        # the right bucket minimizes total dispatched budget INCLUDING
        # the extra dispatches a smaller bucket forces (ties -> smaller
        # bucket, which also means lower per-dispatch latency). A
        # smallest-that-fits-one-dispatch rule would pick a half-empty
        # large bucket over two dense small ones.
        chosen_bucket, chosen_packs, best_budget = None, None, None
        for bucket in self.buckets:
            if max(lengths) > bucket:
                continue
            packs = first_fit_decreasing(
                lengths, bucket, self.max_requests_per_pack)
            dispatches = -(-len(packs) // self.max_batch_size)
            budget = dispatches * self.max_batch_size * bucket
            if best_budget is None or budget < best_budget:
                chosen_bucket, chosen_packs, best_budget = (
                    bucket, packs, budget)
        if chosen_packs is None:  # nothing fits: largest bucket, truncate
            chosen_bucket = self.buckets[-1]
            chosen_packs = first_fit_decreasing(
                lengths, chosen_bucket, self.max_requests_per_pack)
        rows = [[requests[i] for i in pack]
                for pack in chosen_packs[: self.max_batch_size]]
        leftover_idx = sorted(
            i for pack in chosen_packs[self.max_batch_size:] for i in pack)
        return BatchPlan(chosen_bucket, rows,
                         [requests[i] for i in leftover_idx], True)

    # -- execution -------------------------------------------------------

    def stage(self, task: str, plan: BatchPlan) -> StagedBatch:
        """Pack/pad one planned batch into its fixed compile-shape
        arrays. HOST-ONLY — never touches the device, so the pipelined
        dispatch plane's assembler stage can run it concurrently with
        the executor's jitted forward (the one-device-thread
        invariant).

        Fused-epilogue engines additionally stage the per-row gather
        positions for ``"gather"`` heads ([B, epilogue_slots] absolute
        row positions, zero-padded — slot 0 gathers position 0
        harmlessly for unused slots); a batch whose rows overflow the
        slot quota stages for the unfused fallback forward instead."""
        spec = self.tasks[task]
        t_host0 = self._clock()
        B, S = self.max_batch_size, plan.bucket
        ids = np.zeros((B, S), np.int32)
        seg = np.zeros((B, S), np.int32)
        mask = np.zeros((B, S), np.int32)
        offsets: Dict[int, Tuple[int, int, int]] = {}  # req id -> (row, off, slot)
        epilogue = spec.handler.epilogue if self.fuse_epilogues else None
        fused = epilogue == "stack_span"
        gather_slots: Dict[int, Tuple[int, int, int]] = {}
        row_positions: List[List[int]] = []
        if epilogue == "gather":
            # First pass (features only): do the rows fit the quota?
            fused = True
            for row in plan.rows:
                positions: List[int] = []
                offset = 0
                for req in row:
                    pts = spec.handler.gather_positions(req.features)
                    gather_slots[req.id] = (len(row_positions),
                                            len(positions), len(pts))
                    positions.extend(offset + p for p in pts)
                    offset += req.length if plan.packed else 0
                if len(positions) > self.epilogue_slots:
                    fused = False
                    gather_slots = {}
                    break
                row_positions.append(positions)
        if plan.packed:
            K = self.max_requests_per_pack
            sids = np.zeros((B, S), np.int32)
            cpos = np.zeros((B, K), np.int32)
            for r, row in enumerate(plan.rows):
                offset = 0
                for k, req in enumerate(row):
                    n = req.length
                    ids[r, offset:offset + n] = req.features["input_ids"]
                    seg[r, offset:offset + n] = req.features["segment_ids"]
                    mask[r, offset:offset + n] = 1
                    sids[r, offset:offset + n] = k + 1
                    cpos[r, k] = offset
                    offsets[req.id] = (r, offset, k)
                    offset += n
            if spec.handler.output_kind == "pooled":
                args = (ids, seg, mask, sids, cpos)
            else:
                args = (ids, seg, mask, sids)
        else:
            for r, row in enumerate(plan.rows):
                (req,) = row
                n = req.length
                ids[r, :n] = req.features["input_ids"]
                seg[r, :n] = req.features["segment_ids"]
                mask[r, :n] = 1
                offsets[req.id] = (r, 0, 0)
            args = (ids, seg, mask)
        if fused and epilogue == "gather":
            pos = np.zeros((B, self.epilogue_slots), np.int32)
            for r, positions in enumerate(row_positions):
                pos[r, :len(positions)] = positions
            args = args + (pos,)
        return StagedBatch(task, plan, args, offsets,
                           pack_s=self._clock() - t_host0,
                           fused=fused, gather_slots=gather_slots)

    def execute_staged(self, staged: StagedBatch
                       ) -> Tuple[object, dict]:
        """Run one staged batch's jitted forward (incl. the device
        sync); returns (device output, info dict). The ONLY method on
        the serving path that touches the device — in pipelined
        dispatch, only the executor stage calls it."""
        import jax

        spec = self.tasks[staged.task]
        plan = staged.plan
        compiles_before = len(self.monitor.events)
        t0 = self._clock()
        fwd = spec.forwards[(plan.bucket, plan.packed, staged.fused)]
        # Capture the params reference, its swap epoch, and the version
        # stamp in ONE lock acquisition: the whole forward runs against
        # this single consistent tree no matter when a hot-swap flips
        # the spec (docs/serving.md "Model registry & canary rollouts").
        with self._swap_lock:
            params = spec.params
            epoch = self._swap_epoch
            version = self.serving_version
        out = fwd(params, *staged.args)
        out = jax.block_until_ready(out)
        # Flip-atomicity audit: the params reference may only change
        # through the epoch-bumping swap. A changed reference at an
        # UNCHANGED epoch means something mutated params outside the
        # flip while this batch ran — counted as a torn serve (the
        # zero-tolerance "rollout torn-model serves" gate).
        with self._swap_lock:
            if spec.params is not params and self._swap_epoch == epoch:
                self._torn_serves += 1
        device_s = self._clock() - t0
        compiles = sum(
            1 for e in self.monitor.events[compiles_before:]
            if e.get("kind") == "compile")
        info = {
            "bucket": plan.bucket,
            "rows": self.max_batch_size,
            "real_tokens": sum(r.length for r in plan.requests),
            "device_s": device_s,
            "pack_s": staged.pack_s,
            "compiles": compiles,
            "packed": plan.packed,
            "fused": staged.fused,
            "version": version,
        }
        return out, info

    def demux(self, staged: StagedBatch, out) -> List[object]:
        """Slice each request's own output back out of the batch output
        (host conversion + per-request views, in ``plan.requests``
        order). Host-only — the completion stage runs it, so client
        decode never blocks the next device step.

        Fused-epilogue batches consume the ALREADY-EXTRACTED outputs
        (docs/serving.md "Raw-speed kernels"): a ``"gather"`` head's
        [B, epilogue_slots, V] plane slices to each request's own slot
        run (handed to postprocess as a
        :class:`~bert_pytorch_tpu.serve.tasks.GatheredTokens`), and a
        ``"stack_span"`` head's single [B, 2, S] output re-splits into
        the usual (start, end) tuple — one host conversion instead of
        two."""
        spec = self.tasks[staged.task]
        plan = staged.plan
        kind = spec.handler.output_kind
        if kind == "span":
            if staged.fused:
                both = np.asarray(out, np.float32)  # [B, 2, S]
                start, end = both[:, 0], both[:, 1]
            else:
                start = np.asarray(out[0], np.float32)
                end = np.asarray(out[1], np.float32)
        else:
            host = np.asarray(out, np.float32)
        gathered = staged.fused and spec.handler.epilogue == "gather"
        results: List[object] = []
        for req in plan.requests:
            r, off, slot = staged.offsets[req.id]
            n = req.length
            if kind == "pooled":
                results.append(host[r, slot] if plan.packed else host[r])
            elif kind == "span":
                results.append((start[r, off:off + n], end[r, off:off + n]))
            elif gathered:
                gr, s0, count = staged.gather_slots[req.id]
                results.append(
                    tasks_lib.GatheredTokens(host[gr, s0:s0 + count]))
            else:
                results.append(host[r, off:off + n])
        return results

    def execute(self, task: str, plan: BatchPlan
                ) -> Tuple[List[object], dict]:
        """Run one planned batch end to end (stage -> execute_staged ->
        demux on the calling thread); returns (per-request output slices
        in ``plan.requests`` order, info dict with bucket/rows/
        real_tokens/device_s/compiles, plus ``pack_s`` — the host time
        spent packing the group into the fixed compile shape, the
        engine's share of the trace's ``assembly`` span). The serial
        dispatch mode, offline scoring, and parity tests use this
        composition; pipelined dispatch calls the three steps from
        their own stages."""
        staged = self.stage(task, plan)
        out, info = self.execute_staged(staged)
        return self.demux(staged, out), info

    def run_direct(self, task: str, payload: dict) -> dict:
        """One request end to end through the SAME batched path (a batch
        of one) — the offline/batch-scoring and parity-test entry point."""
        spec = self.tasks[task]
        features = spec.handler.prepare(payload, self.max_len())
        req = Request(task, features, payload)
        plan = self.plan_batch([req], packed=False)
        outputs, _ = self.execute(task, plan)
        return spec.handler.postprocess(features, outputs[0], payload)
