"""The step's share of the chip's bf16 matmul peak: model FLOPs of one update
(trace/flops.py; recomputation not counted) over device-busy time per update,
chips and peak (trace/peaks.json). From device time, not wall time."""


def read(ctx):
    summary, updates = ctx.get("summary"), ctx.get("updates")
    if not summary or not updates or not summary["busy_s"]:
        return None
    per_update_s = summary["busy_s"] / updates
    return 100.0 * ctx["flops_per_update"] / (
        per_update_s * ctx["chips"] * ctx["peak_flops"])
