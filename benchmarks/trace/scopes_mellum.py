"""The ``train_mellum`` kind's reading of a traced window: ``scopes.py``'s
reduction under the rules of ``scopes_mellum.json`` (the ``mellum`` family's
scopes and kernels under an expert axis), how often each flash kernel ran,
and the exchange's own times. As ``scopes_laguna.py`` for its kind: a reader
gets ``trace_dir`` in its context, and a trace without the family's scopes (a
program that lacks them) gives ``None``: the readers then report nothing and
do not raise.

**The exchange's times** (``exchange_times``). The reduction's parts are self
times of the ops on a device's ``XLA Ops`` line; a collective the compiler
made asynchronous spends its time in flight on the ``Async XLA Ops`` line,
where no part sees it. So the exchange is read apart, whatever form its
collectives take: on each device, the union of the intervals of every op
under one of the exchange's scopes (``scopes_mellum.json`` ``exchange``), on
either line, is ``exchange_s``; of it, the part during which no OTHER leaf op
runs on that device is ``exposed_s`` (``reduce.py`` reads
``collective_exposed_s`` the same way, over every collective of the step).
Both are means over the devices.
"""

from __future__ import annotations

import json
import os

from benchmarks.trace import flops, reduce, scopes, scopes_lm

HERE = os.path.dirname(os.path.abspath(__file__))
# parts only the family's step under an expert axis produces
FAMILY_PARTS = ("moe_exchange_out", "moe_exchange_back", "lm_head_gather",
                "embed_exchange", "grad_sync")

_reductions = {}  # path of a trace -> its reduction (one parse per process)


def rules() -> dict:
    with open(os.path.join(HERE, "scopes_mellum.json"), encoding="utf-8") as f:
        return json.load(f)


def exchange_times(planes: list, names) -> dict:
    """{"exchange_s", "exposed_s"}: seconds, mean over the device planes, of
    the ops whose ``op_name`` holds one of ``names``."""
    total = exposed = 0.0
    used = 0
    for plane in planes:
        if not plane["name"].startswith("/device:TPU:"):
            continue
        events = sorted((e for line in plane["lines"]
                         if line["name"] == reduce.OPS_LINE
                         for e in line["events"]), key=lambda e: (e[1], -e[2]))
        if not events:
            continue
        used += 1
        mine = lambda e: any(name in (e[3] or "") for name in names)
        in_flight = [e for line in plane["lines"]
                     if line["name"] == reduce.ASYNC_LINE
                     for e in line["events"] if mine(e)]
        timed = list(zip(events, reduce.self_times([e[:3] for e in events])))
        leaves = [(event, row) for event, row in timed if row[4]]
        exchange = reduce.union(
            [[row[1], row[2]] for event, row in leaves if mine(event)]
            + [[e[1], e[1] + e[2]] for e in in_flight])
        others = reduce.union([[row[1], row[2]] for event, row in leaves
                               if not mine(event)])
        total += reduce._length(exchange)
        exposed += reduce._length(reduce._minus(exchange, others))
    if not used:
        return {}
    return {"exchange_s": total / used * 1e-9, "exposed_s": exposed / used * 1e-9}


def for_run(ctx: dict) -> dict | None:
    """The reduction of the run's traced window under this kind's rules (with
    ``kernel_calls`` and ``exchange`` beside ``kernels``), or None where there
    is nothing to read."""
    if not ctx.get("summary") or not ctx.get("updates"):
        return None
    path = scopes.newest_trace(ctx.get("trace_dir"))
    if path is None:
        return None
    if path not in _reductions:
        planes, table = scopes.read_xspace(path), rules()
        found = scopes.reduce_scopes(planes, table=table)
        found["kernel_calls"] = scopes_lm.kernel_calls(planes, table["kernels"])
        found["exchange"] = exchange_times(planes, table["exchange"])
        found["has_family"] = any(
            part in found["by_part"] for part in FAMILY_PARTS)
        _reductions[path] = found
        print("scopes_mellum: " + json.dumps({
            k: found[k] for k in ("busy_s", "by_pass", "by_part",
                                  "unattributed_s", "kernels", "kernel_calls",
                                  "exchange")}))
    found = _reductions[path]
    return found if found["has_family"] else None


def kernels_roofline_pct(ctx: dict, kernels, call) -> float | None:
    """The share of their roofline of those of ``kernels`` the trace holds:
    for each the least time a chip could take for its calls (``call(config,
    mix, kernel)`` -> (FLOPs, HBM bytes) of one; the larger of FLOPs over the
    bf16 peak and bytes over the HBM peak of ``trace/peaks.json``), summed,
    over their device time. Calls and time are both a chip's mean; every
    call counts, the forward's second run under remat too."""
    found = for_run(ctx)
    if not found or not ctx.get("device_kind"):
        return None
    calls = {k: n for k, n in found["kernel_calls"].items() if k in kernels}
    spent = sum(found["kernels"].get(k, 0.0) for k in calls)
    if not spent:
        return None
    peaks = flops.peaks(ctx["device_kind"])
    least = 0.0
    for kernel, n in calls.items():
        work, traffic = call(ctx["config"], ctx["mix"], kernel)
        least += n * max(work / peaks["bf16_flops_per_s"],
                         traffic / peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent


def device_ms(ctx: dict, *parts: str) -> float | None:
    """Per update, the device time (all passes) of the parts named."""
    found = for_run(ctx)
    if not found:
        return None
    return 1e3 * sum(found["by_part"].get(p, 0.0) for p in parts) / ctx["updates"]
