"""The ``train_phi4flash`` kind's reading of a traced window: ``scopes.py``'s
reduction under the rules of ``scopes_phi4flash.json`` (the ``phi4flash``
family's scopes and kernels), and how often each kernel ran (``scopes_lm``'s
count). As ``scopes_lm.py`` and ``scopes_laguna.py`` for their kinds: a
reader gets ``trace_dir`` in its context, and a trace without the family's
scopes (a program that lacks them) gives ``None``: the readers then report
nothing and do not raise.
"""

from __future__ import annotations

import json
import os

from benchmarks.trace import scopes, scopes_lm

HERE = os.path.dirname(os.path.abspath(__file__))
# parts only the family's scopes produce: a trace without them is not this kind's
FAMILY_PARTS = ("selective_scan", "s6_in_proj", "s6_other", "gmu", "attn_diff",
                "diff_window_attention", "diff_full_attention")

_reductions = {}  # path of a trace -> its reduction (one parse per process)


def rules() -> dict:
    with open(os.path.join(HERE, "scopes_phi4flash.json"), encoding="utf-8") as f:
        return json.load(f)


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
        found["kernel_calls"] = scopes_lm.kernel_calls(planes, table["kernels"])
        found["has_family"] = any(
            part in found["by_part"] for part in FAMILY_PARTS)
        _reductions[path] = found
        print("scopes_phi4flash: " + json.dumps({
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


def roofline_pct(ctx: dict, kernels, per_call, spent_s=None) -> float | None:
    """The share of their roofline of the calls of ``kernels`` that the trace
    holds: for each kernel the least time the chip could take for one call
    (the larger of operations over the bf16 peak and bytes over the HBM peak of
    trace/peaks.json; ``per_call(kernel)`` gives one call's operations and
    bytes), times its calls, summed, over ``spent_s`` (default: those
    kernels' own device time)."""
    from benchmarks.trace import flops

    found = for_run(ctx)
    if not found or not ctx.get("device_kind"):
        return None
    calls = {k: n for k, n in found["kernel_calls"].items() if k in kernels}
    spent = (sum(found["kernels"].get(k, 0.0) for k in calls)
             if spent_s is None else spent_s(found))
    if not calls or not spent:
        return None
    peaks = flops.peaks(ctx["device_kind"])
    least = 0.0
    for kernel, n in calls.items():
        work, traffic = per_call(kernel)
        least += n * max(work / peaks["bf16_flops_per_s"],
                         traffic / peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent
