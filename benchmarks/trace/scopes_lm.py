"""The ``train_lm`` kind's reading of a traced window: ``scopes.py``'s
reduction under the rules of ``scopes_lm.json`` (the ``nemotron_h`` family's
scopes), and how often each flash kernel ran.

A reader of this kind gets ``trace_dir`` in its context (``kinds/train_lm.py``
passes it); without it the newest trace of a ``train`` kind's working
directory is read, as ``scopes.for_run`` does. A trace without the family's
scopes (a program that lacks them) gives ``None``: the readers then report
nothing and do not raise.
"""

from __future__ import annotations

import json
import os

from benchmarks.trace import reduce, scopes

HERE = os.path.dirname(os.path.abspath(__file__))
# parts only the family's scopes produce: a trace without them is not this kind's
FAMILY_PARTS = ("ssd_scan", "ssm_other", "moe_experts", "moe_other", "lm_head")

_reductions = {}  # path of a trace -> its reduction (one parse per process)


def rules() -> dict:
    with open(os.path.join(HERE, "scopes_lm.json"), encoding="utf-8") as f:
        return json.load(f)


def kernel_calls(planes: list, kernels) -> dict:
    """How many times each kernel ran, mean over the device planes."""
    counts, used = {}, 0
    for plane in planes:
        if not plane["name"].startswith("/device:TPU:"):
            continue
        events = [e for line in plane["lines"] if line["name"] == reduce.OPS_LINE
                  for e in line["events"]]
        if not events:
            continue
        used += 1
        for event in events:
            kind = scopes.kind_of(event[0]).lstrip("%")
            if kind in kernels:
                counts[kind] = counts.get(kind, 0) + 1
    return {k: v / used for k, v in counts.items()} if used else {}


def for_run(ctx: dict) -> dict | None:
    """The reduction of the run's traced window under this kind's rules (with
    ``kernel_calls`` beside ``kernels``), or None where there is nothing to
    read."""
    if not ctx.get("summary") or not ctx.get("updates"):
        return None
    path = scopes.newest_trace(ctx.get("trace_dir"))
    if path is None:
        return None
    if path not in _reductions:
        planes, table = scopes.read_xspace(path), rules()
        found = scopes.reduce_scopes(planes, table=table)
        found["kernel_calls"] = kernel_calls(planes, table["kernels"])
        found["has_family"] = any(
            part in found["by_part"] for part in FAMILY_PARTS)
        _reductions[path] = found
        print("scopes_lm: " + json.dumps({
            k: found[k] for k in ("busy_s", "by_pass", "by_part",
                                  "unattributed_s", "kernels", "kernel_calls")}))
    found = _reductions[path]
    return found if found["has_family"] else None


def device_ms(ctx: dict, *parts: str) -> float | None:
    """Per update, the device time (all passes) of the parts named."""
    found = for_run(ctx)
    if not found:
        return None
    return 1e3 * sum(found["by_part"].get(p, 0.0) for p in parts) / ctx["updates"]
