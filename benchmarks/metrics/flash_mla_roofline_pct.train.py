"""The latent attention core's kernels' share of their roofline over the
traced window: for each of ``flash_mla_fwd``, ``flash_mla_bwd_dq``,
``flash_mla_bwd_dkv`` the least time the chip could take for the calls the
trace holds (the larger of FLOPs over the bf16 peak and bytes over the HBM
peak of trace/peaks.json; one call's FLOPs and bytes from trace/flops_joyai.py
``mla_core_call``: the CAUSAL pairs of every head at 192 + 128 a pair, q,
k_nope, v, the output and the shared turned key ONCE: the same work whatever
form implements it), summed, over the kernels' device time. Every call the
trace holds counts, the forward's second run under remat too (work the kernel
did); a pass the program does not run is in neither."""
from benchmarks.trace import flops_joyai, scopes_joyai


def read(ctx):
    found = scopes_joyai.for_run(ctx)
    if not found or not found["kernels"] or not ctx.get("device_kind"):
        return None
    least = sum(calls * scopes_joyai.least_seconds(
        ctx, *flops_joyai.mla_core_call(ctx["config"], ctx["mix"], kernel))
        for kernel, calls in found["kernel_calls"].items())
    spent = sum(found["kernels"].get(k, 0.0) for k in found["kernel_calls"])
    return 100.0 * least / spent if spent else None
