"""Shared CLI surface for the inference fast path (docs/serving.md).

One flag helper next to the engine options so every entry point that
builds an :class:`~bert_pytorch_tpu.serve.engine.InferenceEngine` —
``run_server.py`` online, ``tools/batch_infer.py`` offline, bench legs —
exposes the SAME quantization/kernel knobs with the same spellings, and
``/statsz`` reports the mode a replica is actually serving (the router
work reads it to tell a cheap int8 replica from an fp32 one).
"""

from __future__ import annotations

import argparse

QUANTIZE_CHOICES = ("none", "bf16", "int8")
ATTENTION_BACKENDS = ("xla", "pallas", "pallas_infer", "pallas_infer_int8",
                      "auto")
DISPATCH_MODES = ("pipelined", "serial")
AUTOTUNE_MODES = ("off", "load", "measure")


def add_dispatch_args(parser: argparse.ArgumentParser) -> None:
    """The dispatch-plane knob (serve/service.py, docs/serving.md
    "Continuous batching"), shared by run_server.py and
    tools/batch_infer.py so an A/B comparison uses one spelling."""
    parser.add_argument(
        "--dispatch_mode", type=str, default="pipelined",
        choices=DISPATCH_MODES,
        help="pipelined (default) runs the three-stage continuous-"
             "batching plane: an assembler admits late arrivals into "
             "the forming batch while the executor keeps the device "
             "hot and a completion stage decodes off the device "
             "thread; serial is the flush-then-wait loop, kept for "
             "A/B measurement")


def add_fast_path_args(parser: argparse.ArgumentParser) -> None:
    """The inference-fast-path engine options (ops/quant.py,
    ops/pallas/attention.py ``flash_attention_infer``)."""
    parser.add_argument(
        "--quantize", type=str, default="none", choices=QUANTIZE_CHOICES,
        help="inference weight format: bf16 halves weight bytes, int8 "
             "quarters the matmul weights and serves int8 GEMMs "
             "(per-tensor symmetric scales applied while the checkpoint "
             "streams in; embeddings/LayerNorm stay fp32). Parity bounds "
             "per level: docs/serving.md")
    parser.add_argument(
        "--attention_backend", type=str, default="xla",
        choices=ATTENTION_BACKENDS,
        help="encoder attention kernel for the serve forwards; "
             "pallas_infer is the forward-only fused kernel (TPU; "
             "interpret-mode on CPU) and pallas_infer_int8 its "
             "int8-QK^T variant (per-head symmetric scales; "
             "docs/serving.md 'Raw-speed kernels' for parity bounds)")
    parser.add_argument(
        "--fuse_epilogues", action="store_true",
        help="fold each head's output extraction into the forward's "
             "epilogue (fill_mask gathers its [MASK] slots before the "
             "vocab projection, squad stacks start/end into one "
             "output) — same results, fewer device->host bytes "
             "(docs/serving.md 'Raw-speed kernels')")
    parser.add_argument(
        "--epilogue_slots", type=int, default=8,
        help="per-row gather quota for fused epilogues; a batch whose "
             "rows carry more positions of interest falls back to the "
             "unfused forward")
    parser.add_argument(
        "--autotune", type=str, default="off", choices=AUTOTUNE_MODES,
        help="measured Pallas block-geometry pass for the "
             "pallas_infer* backends (ops/pallas/autotune.py): 'load' "
             "reads persisted winners from --autotune_cache, 'measure' "
             "additionally times candidates for unseen shapes at "
             "startup and persists the winners")
    parser.add_argument(
        "--autotune_cache", type=str, default="",
        help="autotune winners JSON, kept next to the persisted AOT "
             "compile cache with the same keying discipline (a warm "
             "restart that loads the same winners compiles the same "
             "programs under the same names — compiles_cold stays 0)")
# The engine itself normalizes the "none" spelling to None
# (InferenceEngine.__init__) — entry points pass args.quantize verbatim.


def add_tracing_args(parser: argparse.ArgumentParser) -> None:
    """The request-tracing / metrics-plane knobs (serve/tracing.py),
    shared by run_server.py and tools/batch_infer.py (its engine flags
    flow through run_server.parse_arguments)."""
    parser.add_argument(
        "--trace_sample_rate", type=float, default=0.01,
        help="fraction of requests exported as serve_trace span trees "
             "(deterministic head sampling on the request id; requests "
             "over the SLO are ALWAYS traced). 0 disables trace export "
             "while the serve_phase aggregates and /metricsz keep "
             "working")
    parser.add_argument(
        "--slo_p99_ms", type=float, default=500.0,
        help="per-request latency SLO target (ms): drives the "
             "always-sample-slow rule, the over-SLO counters on "
             "/metricsz, and telemetry-report's SLO verdict. 0 disables "
             "SLO accounting")
    parser.add_argument(
        "--slo_error_budget", type=float, default=0.01,
        help="fraction of requests allowed over the SLO target before "
             "the error budget is burned (telemetry-report's "
             "budget-burn verdict)")


def build_tracer(args, emit=None, window: int = 64):
    """One TraceCollector from the add_tracing_args flags (the single
    construction point run_server/bench share)."""
    from bert_pytorch_tpu.serve.tracing import TraceCollector

    return TraceCollector(
        emit=emit,
        sample_rate=args.trace_sample_rate,
        slo_p99_ms=args.slo_p99_ms or None,
        error_budget=args.slo_error_budget,
        window=window)
