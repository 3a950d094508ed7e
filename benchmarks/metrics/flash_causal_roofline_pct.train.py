"""The causal flash kernels' share of their roofline over the traced window:
for each of ``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv`` the least time
the chip could take for the calls the trace holds (the larger of FLOPs over the
bf16 peak and bytes over the HBM peak of trace/peaks.json; FLOPs and bytes of
one call from trace/flops_lm.py: the CAUSAL HALF of the square, S (S + 1) / 2
pairs a head, which the tiles a skipping kernel visits round up to), summed,
over the kernels' device time. Every call counts, the forward's second run
under remat too: it is work the kernel did."""
from benchmarks.trace import flops, flops_lm, scopes_lm


def read(ctx):
    found = scopes_lm.for_run(ctx)
    if not found or not found["kernels"] or not ctx.get("device_kind"):
        return None
    peaks = flops.peaks(ctx["device_kind"])
    least = 0.0
    for kernel, calls in found["kernel_calls"].items():
        work, traffic = flops_lm.flash_causal_call(ctx["config"], ctx["mix"], kernel)
        least += calls * max(work / peaks["bf16_flops_per_s"],
                             traffic / peaks["hbm_bytes_per_s"])
    spent = sum(found["kernels"].get(k, 0.0) for k in found["kernel_calls"])
    return 100.0 * least / spent if spent else None
