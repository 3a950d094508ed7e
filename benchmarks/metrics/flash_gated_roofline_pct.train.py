"""The gated attention layer's flash kernels' share of their roofline over the
traced window: for each of ``flash_gated_fwd``, ``flash_gated_bwd_dq``,
``flash_gated_bwd_dkv`` the least time the chip could take for the calls the
trace holds (the larger of FLOPs over the bf16 peak and bytes over the HBM peak
of trace/peaks.json; FLOPs and bytes of one call from trace/flops_qwen3next.py
``flash_gated_call``: the CAUSAL HALF of the square, 16 query heads of 256),
summed, over those kernels' device time. Every call counts, the forward's
second run under remat too: it is work the kernel did."""
from benchmarks.trace import flops_qwen3next, scopes_qwen3next


def read(ctx):
    found = scopes_qwen3next.for_run(ctx)
    if not found or not ctx.get("device_kind") or not ctx.get("config"):
        return None
    calls = {k: n for k, n in found["kernel_calls"].items()
             if k in flops_qwen3next.GATED_KERNELS}
    spent = sum(found["kernels"].get(k, 0.0) for k in calls)
    if not calls or not spent:
        return None
    least = sum(n * scopes_qwen3next.least_seconds(
        ctx, *flops_qwen3next.flash_gated_call(ctx["config"], ctx["mix"], kernel))
        for kernel, n in calls.items())
    return 100.0 * least / spent
