"""The latent attention's flash kernels' share of their roofline over the
traced window: for each of ``flash_cca_fwd``, ``flash_cca_bwd_dq``,
``flash_cca_bwd_dkv`` the least time the chip could take for the calls the
trace holds (the larger of FLOPs over the bf16 peak and bytes over the HBM peak
of trace/peaks.json; FLOPs and bytes of one call from trace/flops_zaya.py
``flash_cca_call``: the CAUSAL HALF of the square, 8 query heads of 128),
summed, over those kernels' device time. Every call counts, the forward's
second run under remat too: it is work the kernel did."""
from benchmarks.trace import flops_zaya, scopes_zaya


def read(ctx):
    found = scopes_zaya.for_run(ctx)
    if not found or not ctx.get("device_kind") or not ctx.get("config"):
        return None
    calls = {k: n for k, n in found["kernel_calls"].items()
             if k in flops_zaya.CCA_KERNELS}
    spent = sum(found["kernels"].get(k, 0.0) for k in calls)
    if not calls or not spent:
        return None
    least = sum(n * scopes_zaya.least_seconds(
        ctx, *flops_zaya.flash_cca_call(ctx["config"], ctx["mix"], kernel))
        for kernel, n in calls.items())
    return 100.0 * least / spent
