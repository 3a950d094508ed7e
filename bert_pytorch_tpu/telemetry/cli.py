"""Shared telemetry CLI surface for the runners.

Every runner exposes the same canonical flag set via :func:`add_cli_args`
and builds its :class:`~bert_pytorch_tpu.telemetry.runner.TrainTelemetry`
via :func:`from_args` — one copy of the flags, help text, and
default-path fallbacks instead of five drifting ones. Per-runner knobs are
constructor arguments (``window_default``: pretraining logs denser windows
than the short finetune runs; ``sync_every_default``: the small-model
finetune runners keep the full per-step decomposition — a per-step sync
is cheap there and buys step-exact sentinels — while the pretraining hot
loop samples it; since PR 7 no loop fetches the loss outside the sync
cadence, jaxlint HS101 enforces it).
"""

from __future__ import annotations

import os
from typing import Optional


def add_cli_args(parser, window_default: int = 50,
                 sync_every_default: int = 4) -> None:
    """Register the canonical telemetry flags (docs/telemetry.md)."""
    parser.add_argument("--profile_steps", type=str, default="0",
                        help="capture a JAX profiler trace: 'N' traces N "
                             "steady-state steps (after the compile step), "
                             "'N:M' traces the explicit step range [N, M). "
                             "Auto-stops at the range end (or end of run). "
                             "'0' disables (docs/telemetry.md)")
    parser.add_argument("--profile_dir", type=str, default="",
                        help="profiler trace output directory; default "
                             "<output_dir>/profile")
    parser.add_argument("--telemetry_jsonl", type=str, default="",
                        help="JSONL telemetry sink path; default "
                             "<output_dir>/<prefix>_telemetry.jsonl (no "
                             "sink without an output dir)")
    parser.add_argument("--telemetry_window", type=int,
                        default=window_default,
                        help="steps per telemetry window record "
                             "(step-time percentiles + MFU)")
    parser.add_argument("--telemetry_sync_every", type=int,
                        default=sync_every_default,
                        help="device-sync cadence for the step-time "
                             "decomposer: 1 = block on every step's metrics "
                             "(full data/host/device split, step-exact "
                             "sentinel), N = sample every Nth step (each "
                             "sync is a host<->device round trip and "
                             "stops the host from running ahead of the "
                             "device), 0 = never sync (data/host only)")
    parser.add_argument("--sentinel_policy", type=str, default="continue",
                        choices=["continue", "abort"],
                        help="non-finite loss/grad-norm policy: 'continue' "
                             "logs a sentinel record per observed bad step; "
                             "'abort' raises after --sentinel_patience "
                             "consecutive observed bad steps")
    parser.add_argument("--sentinel_patience", type=int, default=3,
                        help="consecutive OBSERVED non-finite steps before "
                             "'abort' raises (one scaler-recovered fp16 "
                             "overflow step should not kill a run). The "
                             "sentinel observes on the sync/log cadence, so "
                             "detection lag scales with "
                             "--telemetry_sync_every; pass 1 there for "
                             "step-exact abort")
    parser.add_argument("--heartbeat_file", type=str, default="",
                        help="rank-0 liveness file (step/wallclock/"
                             "last_loss/counter, atomically replaced); "
                             "default <output_dir>/heartbeat.json. The "
                             "capture harness reads it instead of guessing "
                             "liveness from checkpoint mtimes")
    parser.add_argument("--debug_port", type=int, default=0,
                        help="live training introspection plane "
                             "(telemetry/introspect.py, docs/"
                             "observability.md): serve /healthz "
                             "(heartbeat-backed step liveness), /statsz "
                             "(live window/grad-health/compile snapshot) "
                             "and /metricsz (Prometheus text, consistent "
                             "with the JSONL windows per metric name) on "
                             "127.0.0.1:<port>. 0 (default) disables")
    parser.add_argument("--debug_stale_after_s", type=float, default=0.0,
                        help="debug-plane /healthz staleness bound: 503 "
                             "once no step completed for this many "
                             "seconds. 0 (default) follows "
                             "--watchdog_timeout_s when set, else 60 — "
                             "size it above the worst healthy step time")
    parser.add_argument("--postmortem_file", type=str, default="",
                        help="crash flight recorder (telemetry/"
                             "flightrec.py): bounded ring of the last "
                             "telemetry records + log lines, flushed "
                             "atomically here on fault/divergence/crash "
                             "(and periodically, so even a SIGKILLed "
                             "process leaves forensics); default "
                             "<output_dir>/postmortem.json, disabled "
                             "without an output dir. A clean run removes "
                             "the file")
    parser.add_argument("--grad_stats_every", type=int, default=-1,
                        help="in-jit grad-health cadence (per-layer-group "
                             "grad/param norms + update:weight ratios, "
                             "telemetry/model_stats.py): N computes every "
                             "Nth optimizer step, 0 disables, -1 (default) "
                             "follows --telemetry_sync_every so the host "
                             "reads every computed block for free on its "
                             "existing sync")
    parser.add_argument("--grad_spike_factor", type=float, default=10.0,
                        help="divergence early-warning: warn when the "
                             "global grad norm exceeds this factor x its "
                             "own EMA (0 disables). Warnings follow "
                             "--sentinel_policy/--sentinel_patience")
    parser.add_argument("--update_ratio_max", type=float, default=1.0,
                        help="divergence early-warning: warn when the "
                             "global update:weight ratio exceeds this "
                             "absolute bound (0 disables) — a per-step "
                             "relative weight change near 1 is a blown "
                             "learning rate, caught before the loss NaNs")
    parser.add_argument("--watchdog_timeout_s", type=float, default=0.0,
                        help="hung-step watchdog (docs/fault_tolerance.md): "
                             "flag (one fault record + warning; never a "
                             "kill) when no step completes for this many "
                             "seconds. Arms at the FIRST completed step, so "
                             "the step-0 compile never counts — size it "
                             "well above the worst healthy step time. "
                             "0 (default) disables")
    parser.add_argument("--telemetry_cost_analysis", type=str,
                        default="auto", choices=["auto", "off", "full"],
                        help="static per-executable cost attribution "
                             "(compile_cost records: FLOPs, bytes "
                             "accessed, argument/output/temp bytes). "
                             "'auto' compiles for memory_analysis only "
                             "when that is cheap (CPU, or persistent "
                             "compile cache on) and falls back to the "
                             "compile-free HLO cost analysis elsewhere; "
                             "'full' always compiles (one extra backend "
                             "compile per shapes digest)")


def stats_every(args) -> int:
    """Resolve --grad_stats_every: -1 follows the sync cadence (the host
    can only READ the block on synced steps, so computing it off-cadence
    would burn device FLOPs on values nobody fetches)."""
    every = getattr(args, "grad_stats_every", 0)
    if every is None or every < 0:
        return max(0, int(getattr(args, "telemetry_sync_every", 0)))
    return int(every)


def default_jsonl_path(args, output_dir: Optional[str],
                       prefix: str) -> Optional[str]:
    """Resolve the JSONL sink path (None = no sink)."""
    if args.telemetry_jsonl:
        return args.telemetry_jsonl
    if output_dir:
        return os.path.join(output_dir, f"{prefix}_telemetry.jsonl")
    return None


def from_args(args, sink=None, is_primary: bool = True,
              seq_per_step: Optional[int] = None,
              flops_per_seq: Optional[float] = None,
              tokens_per_step: Optional[int] = None,
              output_dir: Optional[str] = None,
              process: str = "train"):
    """Build a TrainTelemetry from the :func:`add_cli_args` namespace.

    ``output_dir`` anchors the profile-dir / heartbeat / postmortem
    fallbacks; without one, traces go to ``./profile`` and the heartbeat
    and flight recorder are disabled unless the flags name paths
    explicitly. ``process`` labels the runner in the debug plane's
    exports and the postmortem payload ("pretrain", "glue", ...), so a
    fleet timeline can attribute trainer samples by name.

    Rank-0 only for the observability plane: non-primary ranks get
    neither a debug server (one port per JOB, like the artifacts) nor a
    flight recorder (their sink is disabled; an empty ring would flush
    empty postmortems over rank 0's).
    """
    import jax

    from bert_pytorch_tpu.telemetry.runner import TrainTelemetry

    profile_dir = args.profile_dir or (
        os.path.join(output_dir, "profile") if output_dir else "profile")
    heartbeat = args.heartbeat_file or (
        os.path.join(output_dir, "heartbeat.json") if output_dir else None)
    introspect = None
    recorder = None
    if is_primary:
        postmortem = getattr(args, "postmortem_file", "") or (
            os.path.join(output_dir, "postmortem.json")
            if output_dir else None)
        if postmortem:
            from bert_pytorch_tpu.telemetry.flightrec import FlightRecorder
            from bert_pytorch_tpu.utils import logging as logging_util

            recorder = FlightRecorder(
                postmortem, process=process).install_exit_hooks()
            # Log lines tee into the ring too (the runners initialized
            # their handlers before building telemetry, so append).
            logging_util.add_handler(recorder.log_handler())
        if getattr(args, "debug_port", 0):
            from bert_pytorch_tpu.telemetry.introspect import \
                IntrospectionHub

            stale_after = getattr(args, "debug_stale_after_s", 0.0) or \
                getattr(args, "watchdog_timeout_s", 0.0) or 60.0
            introspect = IntrospectionHub(
                process=process, stale_after_s=stale_after)
    tele = TrainTelemetry(
        sink=sink,
        is_primary=is_primary,
        window=args.telemetry_window,
        sync_every=args.telemetry_sync_every,
        seq_per_step=seq_per_step,
        flops_per_seq=flops_per_seq,
        tokens_per_step=tokens_per_step,
        device_kind=jax.devices()[0].device_kind,
        n_devices=jax.device_count(),
        profile_steps=args.profile_steps,
        profile_dir=profile_dir,
        sentinel_policy=args.sentinel_policy,
        sentinel_patience=args.sentinel_patience,
        heartbeat_path=heartbeat,
        watchdog_timeout_s=getattr(args, "watchdog_timeout_s", 0.0),
        grad_spike_factor=args.grad_spike_factor,
        update_ratio_max=args.update_ratio_max,
        cost_analysis=args.telemetry_cost_analysis,
        introspect=introspect,
        flight_recorder=recorder)
    if introspect is not None:
        from bert_pytorch_tpu.telemetry.introspect import start_debug_server
        from bert_pytorch_tpu.utils import logging as logging_util

        try:
            tele.debug_server = start_debug_server(
                introspect, port=int(args.debug_port))
        except OSError as exc:
            # Observability must never take the run down: a port
            # already held (a second runner on the host, a stale
            # process) costs the debug plane, not the training job.
            logging_util.info(
                f"telemetry: debug plane DISABLED — could not bind "
                f"port {args.debug_port}: {exc}")
        else:
            host, port = tele.debug_server.server_address[:2]
            logging_util.info(
                f"telemetry: debug plane on http://{host}:{port} "
                "(/healthz /statsz /metricsz)")
    return tele
