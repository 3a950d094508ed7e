#!/usr/bin/env python
"""Lint JSONL metric artifacts against the telemetry record schema.

Invoked from the tier-1 suite (tests/test_telemetry.py) over EVERY
committed ``*.jsonl`` artifact in the repo root — bench artifacts,
telemetry captures, sweep logs — so a future round cannot commit
malformed metrics (invalid JSON lines, NaN/Infinity spellings, records
claiming a schema version whose required keys are missing).
``chip_smoke.py`` runs it over the serve telemetry its on-chip server
wrote. Legacy artifacts written before the schema
existed carry no ``schema`` key and are held to the universal rules only
(bert_pytorch_tpu/telemetry/schema.py). The ``serve`` record family
(``serve_window``/``serve_summary``, serve/stats.py) is linted with its
consistency rules — latency percentiles ordered p50 <= p95 <= p99,
``batch_occupancy`` in (0, 1]. The request-tracing kinds
(``serve_trace``/``serve_phase``, serve/tracing.py) are held to their
decomposition invariants: span durations non-negative and summing to no
more than ``total_ms``, ``queue_wait_ms <= total_ms``, a boolean
``sampled`` flag, ``queue_wait_share`` in [0, 1], ordered total
percentiles, and ``over_slo`` bounded by the window with a positive
``slo_target_ms`` — and the fault-tolerance family
(``fault``/``resume``, docs/fault_tolerance.md) with its own: a real
boolean ``injected`` marker, and every ``resume.skipped`` entry naming
step/path/reason. The async-hot-path step_window fields are held to
their invariants too: ``h2d_wait_*`` must be numeric and never exceed
the ``data_wait_*`` it is a sub-phase of, and ``ckpt_step_*``
percentiles require a positive ``ckpt_steps`` checkpoint-step flag
(docs/telemetry.md "Checkpoint-step p95"). The fleet-tier kinds
(``fleet_event``/``router_window``/``router_summary``,
serve/supervisor.py + serve/router.py) carry their own rules: the
ok/shed/error triple must decompose the window exactly, hedge wins are
bounded by hedges fired, healthy replicas by the fleet size, and the
latency/failover percentiles must be ordered. The fleet-observatory
kinds (``obs_scrape``/``obs_fleet_window``, telemetry/collector.py —
the fleet-timeline JSONLs ``tools/obs_collect.py`` writes and self-
lints by default) carry theirs: a non-empty target of a known kind
(trainer/replica/router), a boolean ``ok``, non-negative staleness/
latency/rate aggregates, and healthy counts bounded by totals. The
cross-tier tracing kinds (docs/observability.md "Trace propagation")
have the strictest rules of all: a ``router_trace`` must carry a
non-empty trace id, a span list restricted to the router taxonomy
(admission/attempt/backoff) where every span fits inside ``total_ms``
(spans may OVERLAP — hedged attempts race — so the serve_trace
sum-of-durations rule does NOT apply), every attempt span names its
1-based attempt index, target replica, and outcome, the ``attempts``
counter equals the attempt-span count, ``winning_attempt`` is bounded
by it, and ``hedge_wasted_ms`` needs at least one hedge fired; a
``trace_stitch`` must mark itself ``orphan`` when it has no router
parent, and when it carries the full decomposition,
``router_overhead_ms + network_gap_ms + replica_ms`` must equal
``client_total_ms`` within epsilon with a ``consistent`` verdict that
may only be true when the gap is non-negative (minus clock-noise
epsilon). The profiling-plane kinds (docs/observability.md "Profiling
plane") carry theirs: a ``profile_window`` must name its source, a
known trigger (startup/ondemand/fleet) and covered unit
(steps/requests), carry non-negative covered/samples/duration/
trace-byte counts and a string ``trace_path`` (empty = trace skipped),
and its host-frame table must be internally consistent — every frame a
positive sample count bounded by the capture's total, shares in (0, 1]
summing to no more than 1 (a frame over the total would mean two
captures folded together — the double-arm race the 409 guard
prevents). The deployment-plane
kinds (docs/serving.md "Model registry & canary rollouts") carry
theirs: a ``registry_event`` must name its version, a non-empty event,
and a legal lifecycle state (staged/canary/live/retired), with
``state_change`` events restricted to the registry's legal edges and
every canary -> staged rollback carrying a ``reason``; a
``rollout_window`` must carry a ``canary_share`` in (0, 1], a
non-negative stage, an ok/errors pair bounded by ``window_requests``,
an action from the rollout vocabulary (hold/advance/promote/rollback)
— where a rollback names its ``reason`` — ordered latency percentiles
when present, and a non-negative ``torn_serves``; and across records
in one artifact, each (task, version) rollout's share sequence must be
monotone non-decreasing unless a rollback resets it. The
elasticity-plane kind (``scale_event``, serve/autoscaler.py —
docs/serving.md "Elastic fleet") carries its own: a decision from the
scale vocabulary (scale_up/scale_down/hold), a non-empty ``reason``,
non-negative integer ``replicas_before``/``replicas_after`` whose delta
matches the decision (+1 for scale_up, -1 for scale_down, 0 for hold),
an integer ``exogenous`` drift declaration, non-negative
window/streak/health counters and signal shares (``queue_wait_share``
in [0, 1]) when present — and across records per tag, the fleet's
membership must be RECONSTRUCTIBLE from the stream: each event's
``replicas_before`` must equal the previous event's ``replicas_after``
plus its declared ``exogenous`` drift. The chaos harnesses
(tools/chaos_run.py, tools/chaos_serve.py) lint their artifacts
through this same module.

Usage::

    python tools/check_telemetry_schema.py [paths...]

With no paths, lints ``<repo_root>/*.jsonl``. Exit 0 = all valid,
1 = violations (one ``path:line: error`` per finding), 2 = a named path
is missing. Imports only the schema module — no jax — so it runs
anywhere, including pre-commit hooks on machines without the accelerator
stack.
"""

from __future__ import annotations

import glob
import os
import sys

from _bootstrap import REPO_ROOT, load_by_path

validate_file = load_by_path(
    "_telemetry_schema", "bert_pytorch_tpu", "telemetry", "schema.py"
).validate_file


def main(argv=None) -> int:
    paths = list(argv if argv is not None else sys.argv[1:])
    if not paths:
        paths = sorted(glob.glob(os.path.join(REPO_ROOT, "*.jsonl")))
        if not paths:
            print("check_telemetry_schema: no *.jsonl artifacts found")
            return 0
    failed = False
    for path in paths:
        if not os.path.exists(path):
            print(f"check_telemetry_schema: {path}: no such file")
            return 2
        errors = validate_file(path)
        rel = os.path.relpath(path, REPO_ROOT)
        if errors:
            failed = True
            for lineno, err in errors:
                print(f"{rel}:{lineno}: {err}")
        else:
            print(f"{rel}: ok")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
