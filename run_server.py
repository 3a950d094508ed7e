"""Online inference server — the serving entry point (docs/serving.md).

Loads a checkpoint PARAMS-ONLY (utils/checkpoint.py ``load_params_only``
— the optimizer/K-FAC pytrees never touch serving memory), AOT-compiles
one jitted forward per (task head, length bucket) at startup, and serves
a stdlib JSON-over-HTTP API with dynamic micro-batching and optional
request packing::

    python run_server.py --model_config_file configs/bert_base_config.json \
        --vocab_file vocab.txt --tasks fill_mask,classify \
        --classify_labels neg,pos --fill_mask_checkpoint out/ \
        --buckets 32,64,128 --max_batch_size 8 --max_wait_ms 5 --port 8000

    curl -s localhost:8000/v1/fill_mask \
        -d '{"text": "the capital of [MASK] is paris"}'
    curl -s localhost:8000/healthz
    curl -s localhost:8000/statsz
    curl -s localhost:8000/metricsz   # Prometheus text format

Per-task ``--<task>_checkpoint`` accepts either a ``ckpt_*.msgpack`` file
or a directory (the newest checkpoint is picked via
``latest_checkpoint``); a task without one serves RANDOMLY-INITIALIZED
weights (smoke/demo mode) and says so loudly. Serve telemetry
(``serve_window``/``serve_summary`` records, schema v1) lands in the
JSONL sink next to training telemetry and is summarized by
``telemetry-report``.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys

from bert_pytorch_tpu.utils import logging as logger


def parse_arguments(argv=None):
    parser = argparse.ArgumentParser(description="TPU BERT inference server")
    parser.add_argument("--model_config_file", type=str, required=True)
    parser.add_argument("--vocab_file", type=str, default=None)
    parser.add_argument("--tokenizer", type=str, default=None,
                        choices=["wordpiece", "bpe"])
    parser.add_argument("--uppercase", action="store_true")
    parser.add_argument("--tasks", type=str,
                        default="fill_mask,classify,squad,ner",
                        help="comma-separated task heads to serve")
    for task in ("fill_mask", "classify", "squad", "ner"):
        parser.add_argument(f"--{task}_checkpoint", type=str, default=None,
                            help=f"params checkpoint for the {task} head "
                                 "(file or run output dir); omitted = "
                                 "random init (demo mode)")
    parser.add_argument("--classify_labels", type=str, default="0,1",
                        help="comma-separated labels for classify")
    parser.add_argument("--ner_labels", type=str,
                        default="O,B-PER,I-PER,B-LOC,I-LOC,B-ORG,I-ORG,"
                                "B-MISC,I-MISC",
                        help="comma-separated NER tag set (ids 1-based)")
    parser.add_argument("--buckets", type=str, default="32,64,128",
                        help="length buckets; one forward is AOT-compiled "
                             "per (task, bucket) at startup")
    parser.add_argument("--max_batch_size", type=int, default=8)
    parser.add_argument("--max_wait_ms", type=float, default=5.0,
                        help="micro-batch deadline: a partial batch "
                             "dispatches when its oldest request has "
                             "waited this long")
    # Inference fast path (docs/serving.md): --quantize/--attention_backend,
    # shared with tools/batch_infer.py via one helper. Tracing/SLO knobs
    # (docs/serving.md "Request tracing & metrics") and the dispatch-plane
    # mode (docs/serving.md "Continuous batching") ride the same way.
    from bert_pytorch_tpu.serve.cli import (add_dispatch_args,
                                            add_fast_path_args,
                                            add_tracing_args)

    add_dispatch_args(parser)
    add_fast_path_args(parser)
    add_tracing_args(parser)
    parser.add_argument("--pack_requests", action="store_true",
                        help="pack several short requests per row with "
                             "block-diagonal attention (data/packing.py)")
    parser.add_argument("--max_requests_per_pack", type=int, default=4)
    parser.add_argument("--max_pending", type=int, default=1024,
                        help="pending-queue cap; submissions beyond it "
                             "shed with HTTP 503 instead of growing "
                             "memory/latency without bound")
    parser.add_argument("--host", type=str, default="127.0.0.1")
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--request_timeout_s", type=float, default=30.0)
    parser.add_argument("--dtype", type=str, default="bfloat16",
                        choices=["bfloat16", "float32"])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--output_dir", type=str, default=None,
                        help="telemetry/heartbeat anchor dir")
    parser.add_argument("--telemetry_jsonl", type=str, default="",
                        help="serve telemetry JSONL sink; default "
                             "<output_dir>/serve_telemetry.jsonl")
    parser.add_argument("--heartbeat_file", type=str, default="",
                        help="resumable liveness file the dispatch loop "
                             "maintains (telemetry/sentinels.py Heartbeat "
                             "— the same file the training runners write, "
                             "read by the capture harness); default "
                             "<output_dir>/heartbeat.json, disabled "
                             "without an output_dir")
    parser.add_argument("--telemetry_window", type=int, default=64,
                        help="requests per serve_window record")
    parser.add_argument("--postmortem_file", type=str, default="",
                        help="crash flight recorder flush target "
                             "(telemetry/flightrec.py): the bounded ring "
                             "of this replica's last telemetry records + "
                             "log lines, written atomically on fault/"
                             "crash and periodically (so a SIGKILLed "
                             "replica leaves forensics for the "
                             "supervisor's postmortem harvest); default "
                             "<output_dir>/postmortem.json, disabled "
                             "without an output_dir")
    parser.add_argument("--compile_cache_dir", type=str, default="",
                        help="persistent XLA compile cache; default "
                             "<checkout>/.jax_cache, and "
                             "JAX_COMPILATION_CACHE_DIR wins when set "
                             "(utils/compile_cache.py)")
    parser.add_argument("--serving_version", type=str, default="v0",
                        help="model version this replica starts on "
                             "(serve/registry.py names; reported on "
                             "/healthz, /statsz and the "
                             "bert_serve_serving_version gauge — the "
                             "router's canary split routes on it)")
    parser.add_argument("--save_init_checkpoint", type=str, default="",
                        help="write the first task's (possibly random-"
                             "init) params as ckpt_0.msgpack + integrity "
                             "manifest under this dir before serving — "
                             "gives a jax-free parent (tools/"
                             "chaos_serve.py) a real checkpoint to "
                             "publish into a model registry")
    args = parser.parse_args(argv)

    with open(args.model_config_file) as f:
        configs = json.load(f)
    if args.vocab_file is None:
        args.vocab_file = configs.get("vocab_file")
        if args.vocab_file is None:
            raise ValueError("vocab_file must be in model config or CLI")
    if args.tokenizer is None:
        args.tokenizer = configs.get("tokenizer", "wordpiece")
    return args


def build_service(args):
    """(service, telemetry_sink) — separated from main() so batch_infer
    and tests can build the serving stack without binding a socket."""
    import jax.numpy as jnp

    from bert_pytorch_tpu.config import BertConfig
    from bert_pytorch_tpu.data.tokenization import (get_bpe_tokenizer,
                                                    get_wordpiece_tokenizer)
    from bert_pytorch_tpu.serve import (Batcher, InferenceEngine,
                                        ServeTelemetry, ServingService)
    from bert_pytorch_tpu.telemetry.compile_events import CompileMonitor
    from bert_pytorch_tpu.utils import checkpoint as ckpt_util

    from bert_pytorch_tpu.utils.compile_cache import enable_compile_cache

    # min_compile_secs=0: persist EVERY per-(task, bucket) forward —
    # the warm-restart acceptance is "second start performs zero cold
    # compiles", and the training-oriented default bar would filter
    # the seconds-scale serve executables out of the cache.
    cache_dir = enable_compile_cache(
        args.compile_cache_dir, min_compile_secs=0.0)
    logger.info(f"compile cache {cache_dir}")

    config = BertConfig.from_json_file(args.model_config_file)
    if config.vocab_size % 8 != 0:
        config.vocab_size += 8 - (config.vocab_size % 8)
    if args.tokenizer == "wordpiece":
        tokenizer = get_wordpiece_tokenizer(
            args.vocab_file, uppercase=args.uppercase)
    else:
        tokenizer = get_bpe_tokenizer(
            args.vocab_file, uppercase=args.uppercase)

    logger.info(f"tokenizer back end {type(tokenizer).__module__}."
                f"{type(tokenizer).__name__}")

    def resolve_ckpt(path):
        if not path:
            return None
        if os.path.isdir(path):
            found = ckpt_util.latest_checkpoint(path)
            if found is None:
                raise FileNotFoundError(f"no ckpt_*.msgpack under {path}")
            return found
        return path

    tasks = {}
    for task in args.tasks.split(","):
        task = task.strip()
        if not task:
            continue
        options = {"checkpoint":
                   resolve_ckpt(getattr(args, f"{task}_checkpoint", None))}
        if task == "classify":
            options["labels"] = args.classify_labels.split(",")
        elif task == "ner":
            options["labels"] = args.ner_labels.split(",")
        elif task == "squad":
            options["do_lower_case"] = not args.uppercase
        tasks[task] = options
        if options["checkpoint"] is None:
            logger.info(f"task {task}: NO checkpoint — serving randomly "
                        "initialized weights (demo mode)")

    telemetry_jsonl = args.telemetry_jsonl or (
        os.path.join(args.output_dir, "serve_telemetry.jsonl")
        if args.output_dir else None)
    sink = (logger.JSONLHandler(telemetry_jsonl, overwrite=False)
            if telemetry_jsonl else None)
    # Crash flight recorder (telemetry/flightrec.py, docs/
    # observability.md): every telemetry record tees into a bounded
    # ring, flushed to postmortem.json on fault/crash and periodically —
    # the file the supervisor harvests when this replica dies.
    from bert_pytorch_tpu.telemetry.flightrec import FlightRecorder

    postmortem = getattr(args, "postmortem_file", "") or (
        os.path.join(args.output_dir, "postmortem.json")
        if args.output_dir else None)
    recorder = (FlightRecorder(postmortem, process="serve")
                .install_exit_hooks() if postmortem else None)
    emit = sink.write_record if sink else None
    if recorder is not None:
        emit = recorder.tee(emit)
    serve_tele = ServeTelemetry(
        emit=emit,
        window=args.telemetry_window)
    monitor = CompileMonitor(
        emit=emit if emit is not None else (lambda rec: None))
    # Request tracing + /metricsz (docs/serving.md "Request tracing &
    # metrics"): spans for the head-sampled fraction (and EVERY over-SLO
    # request), serve_phase decomposition windows, Prometheus export.
    from bert_pytorch_tpu.serve.cli import build_tracer

    tracer = build_tracer(args, emit=emit,
                          window=args.telemetry_window)
    # Serve heartbeat: the same resumable liveness file the five training
    # runners maintain, so the capture harness covers serving processes.
    from bert_pytorch_tpu.telemetry.sentinels import Heartbeat

    heartbeat_path = args.heartbeat_file or (
        os.path.join(args.output_dir, "heartbeat.json")
        if args.output_dir else None)
    heartbeat = Heartbeat(heartbeat_path) if heartbeat_path else None
    # On-demand profiling plane (telemetry/sampler.py, docs/
    # observability.md): POST /profilez arms a bounded host-sampler +
    # jax trace capture; the dispatch plane ticks it per boundary with
    # position = requests served. The ProfilerWindow here exists only
    # for the on-demand begin/end facility (no startup spec).
    from bert_pytorch_tpu.telemetry.profiler import ProfilerWindow
    from bert_pytorch_tpu.telemetry.sampler import CaptureController

    profile_dir = (os.path.join(args.output_dir, "profile")
                   if args.output_dir else None)
    capture = CaptureController(
        source="replica", covered_unit="requests",
        window=ProfilerWindow(None, profile_dir, enabled=bool(profile_dir)),
        trace_dir=profile_dir, emit=emit)

    engine = InferenceEngine(
        config,
        tokenizer,
        tasks,
        buckets=[int(b) for b in args.buckets.split(",")],
        max_batch_size=args.max_batch_size,
        max_requests_per_pack=(args.max_requests_per_pack
                               if args.pack_requests else 1),
        dtype=jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32,
        seed=args.seed,
        monitor=monitor,
        quantize=args.quantize,  # "none" normalizes to None in the engine
        attention_backend=args.attention_backend,
        fuse_epilogues=args.fuse_epilogues,
        epilogue_slots=args.epilogue_slots,
        autotune=args.autotune,
        autotune_cache=args.autotune_cache or None,
        version=getattr(args, "serving_version", "v0"),
    )
    batcher = Batcher(
        max_batch_size=args.max_batch_size,
        max_wait_ms=args.max_wait_ms,
        max_requests_per_pack=engine.max_requests_per_pack,
        max_pending=args.max_pending)
    service = ServingService(engine, batcher, serve_tele, tracer=tracer,
                             heartbeat=heartbeat, capture=capture,
                             dispatch_mode=getattr(args, "dispatch_mode",
                                                   "pipelined"))
    # Rides the service so main()/tests reach it without widening the
    # (service, sink) signature batch_infer/bench already consume.
    service.flight_recorder = recorder
    return service, sink


def main(args) -> int:
    """Serve until interrupted; returns the process exit code.

    A SIGTERM-initiated drain exits with ``preemption.EXIT_PREEMPTED``
    (75) — the SAME contract the five training runners hold
    (utils/preemption.py): the supervisor (serve/supervisor.py) and any
    scheduler can distinguish "drained cleanly, every accepted request
    answered" from success (0, an operator Ctrl-C) and from crashes
    (anything else). A crashed replica is restarted with backoff; a
    drained one was ASKED to stop.
    """
    from bert_pytorch_tpu.serve import make_server

    logger.init(handlers=[logger.StreamHandler()])
    service, sink = build_service(args)
    save_dir = getattr(args, "save_init_checkpoint", "")
    if save_dir:
        # Materialize the first task's params as a real, manifested
        # checkpoint BEFORE serving: the jax-free chaos/rollout parent
        # publishes this file into a model registry and swaps it back in
        # as a new version (same geometry, so the swap compiles nothing).
        from bert_pytorch_tpu.utils import checkpoint as ckpt_util

        first_task = sorted(service.engine.tasks)[0]
        ckpt_path = ckpt_util.save_checkpoint(
            save_dir, 0,
            {"model": service.engine.tasks[first_task].params, "epoch": 0})
        logger.info(f"init checkpoint for task {first_task}: {ckpt_path}")
    if service.flight_recorder is not None:
        # Log lines tee into the flight-recorder ring too: a postmortem
        # carries the replica's last words, not just its last records.
        logger.add_handler(service.flight_recorder.log_handler())
    logger.info(
        f"warming {len(service.engine.tasks)} task heads over buckets "
        f"{service.engine.buckets} "
        f"(pack={service.engine.max_requests_per_pack}, "
        f"quantize={service.engine.quantize or 'none'}, "
        f"attention={service.engine.attention_backend})")
    service.engine.warmup()
    startup = service.engine.startup or {}
    logger.info(
        f"running on {startup.get('platform')} "
        f"({startup.get('device_kind')} x {startup.get('device_count')}), "
        f"Pallas kernels {startup.get('kernels')}")
    logger.info(
        f"warmup done in {startup.get('cold_start_s')}s: "
        f"{startup.get('compiles_cold')} cold compiles / "
        f"{startup.get('compiles_warm')} persistent-cache hits "
        f"({startup.get('weight_bytes', 0) / (1 << 20):.1f} MiB weights); "
        "steady-state serving recompiles nothing")
    service.start()
    server = make_server(service, host=args.host, port=args.port,
                         request_timeout_s=args.request_timeout_s)
    host, port = server.server_address[:2]
    logger.info(f"serving {sorted(service.engine.tasks)} on "
                f"http://{host}:{port} (POST /v1/<task>, GET /healthz, "
                "GET /statsz, GET /metricsz) — dispatch "
                f"{service.dispatch_mode}, tracing "
                f"{args.trace_sample_rate:.0%} head-sampled, "
                f"SLO p99 {args.slo_p99_ms:g}ms (over-SLO always traced)")

    preempted = {"signaled": False}

    def shutdown(signum, frame):
        # Graceful drain (docs/fault_tolerance.md): flip /healthz to 503
        # FIRST — load balancers stop routing on their next probe while
        # the listener is still up — then unwind through the finally
        # below, which flushes in-flight requests before stopping. The
        # flag is what turns the exit code into EXIT_PREEMPTED: only a
        # SIGTERM-initiated drain is a preemption (Ctrl-C stays 0).
        preempted["signaled"] = True
        service.begin_drain()
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, shutdown)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        logger.info("draining: rejecting new requests (healthz 503), "
                    "flushing in-flight batches, then shutting down")
        if preempted["signaled"] and service.telemetry.emit is not None:
            # The training runners' preemption fault record, serve
            # flavor: the artifact says WHY this run ended (schema v1
            # `fault` kind; step = requests served at the signal).
            # Emitted through the teed path so the flight recorder sees
            # the incident and flushes its postmortem alongside.
            service.telemetry.emit({
                "kind": "fault", "tag": "serve", "fault": "preemption",
                "signal": "SIGTERM", "injected": False,
                "step": service.telemetry.request_count(),
            })
        server.shutdown()
        service.stop()  # drain + dispatch-thread join + telemetry summary
        if sink is not None:
            sink.close()
        if service.flight_recorder is not None:
            exc = sys.exc_info()[1]
            if exc is not None and not isinstance(exc, KeyboardInterrupt):
                # An exception is escaping the serve loop: flush the
                # forensics WITH the traceback instead of deleting them
                # (a clean close would also disarm the excepthook).
                service.flight_recorder.flush("crash", exc=exc)
            else:
                # Clean close removes the postmortem; the preemption
                # fault above counts as an incident, so a drained
                # replica keeps its forensics on disk.
                service.flight_recorder.close(clean=True)
        logger.close()
    from bert_pytorch_tpu.utils import preemption

    return preemption.EXIT_PREEMPTED if preempted["signaled"] else 0


if __name__ == "__main__":
    sys.exit(main(parse_arguments()))
